"""JoyAI-LLM-Flash (models/joyai.py: latent attention, sigmoid top-k
routing with a balance bias, a shared expert, an MTP module, and the
held share of ops/moe.py) against the plain float32 reference the
benchmark keeps (benchmark/reference/joyai_f32.py), at a small size on
the CPU: d 64, 4 heads of 16 + 8 / 16, 8 routed experts of width 32 of
which 4 are held, top 2, a dense and an expert layer and the MTP module,
S 64, seeded random weights. And the family through the one step maker,
the one optimizer and the fault-tolerant loop."""

import dataclasses
import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import family_kit as kit

from benchmark.reference import joyai_f32
from torchft_tpu.models import (
    common,
    joyai,
    make_grad_step,
    make_train_step,
    olmoe,
)
from torchft_tpu.ops import moe
from torchft_tpu.optim import balance_bias_rule, with_balance_bias

CFG = joyai.JOYAI_CONFIGS["joyai_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
_params = functools.partial(kit.seeded_params, joyai)
_batch = kit.batch


def _ref_kw(cfg):
    return dict(
        n_layer=cfg.n_layers, n_dense=cfg.n_dense_layers, n_head=cfg.n_heads,
        nope=cfg.qk_nope_dim, rope=cfg.qk_rope_dim, v_dim=cfg.v_head_dim,
        kv_rank=cfg.kv_lora_rank, top_k=cfg.top_k,
        first_expert=cfg.first_expert, routed_scale=cfg.routed_scale,
        mtp_coef=cfg.mtp_coef, eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
    )


def _chosen(experts, n_routed):
    return jnp.any(jax.nn.one_hot(experts, n_routed, dtype=bool), axis=-2)


# -- against the reference ---------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_compute_equals_the_reference(seed) -> None:
    params, (tokens, targets) = _params(CFG32, seed), _batch(seed)
    got = jax.jit(functools.partial(joyai.loss_terms, CFG32))(
        params, tokens, targets)
    want = jax.jit(functools.partial(joyai_f32.terms, **_ref_kw(CFG32)))(
        params, tokens, targets)
    assert np.array_equal(_chosen(got["experts"], CFG.n_routed_experts),
                          want["chosen"])
    for name in ("loss", "ce", "mtp_ce"):
        assert float(got[name]) == pytest.approx(float(want[name]), abs=2e-5)
    assert float(got["loss"]) == pytest.approx(
        float(got["ce"]) + CFG.mtp_coef * float(got["mtp_ce"]), abs=1e-6)
    for name in ("hidden", "mtp_hidden"):
        np.testing.assert_allclose(got[name], want[name], atol=5e-5)
    # what the check line prints: rows on this share's experts, and the
    # busiest expert over the mean
    loads = np.asarray(got["loads"])
    assert loads.sum(axis=-1).tolist() == [2 * 64 * CFG.top_k] * 2
    assert np.array_equal(got["rows_held"], loads[:, :4].sum(axis=-1))
    assert np.allclose(got["load_max_over_mean"],
                       loads.max(axis=-1) / loads.mean(axis=-1))


def test_f32_gradients_equal_the_reference() -> None:
    """One leaf of each kind, and every other one too; the balance bias
    has no gradient of the loss: its place carries the loads."""
    params, (tokens, targets) = _params(CFG32, 3), _batch(3)
    got = jax.jit(jax.grad(functools.partial(joyai.loss_fn, CFG32)))(
        params, tokens, targets)
    want = jax.jit(jax.grad(functools.partial(
        joyai_f32.loss, **_ref_kw(CFG32))))(params, tokens, targets)
    terms = joyai.loss_terms(CFG32, params, tokens, targets)
    layer, mtp = got["layers_1"]["moe"], got["mtp"]["block"]["moe"]
    assert np.array_equal(layer[joyai.BALANCE_BIAS], terms["loads"][0])
    assert np.array_equal(mtp[joyai.BALANCE_BIAS], terms["loads"][1])
    assert not np.any(kit.bias_leaves(want))
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    seen = set()
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        if path[-1].key == joyai.BALANCE_BIAS:
            continue
        w = flat_want[path]
        err = float(jnp.linalg.norm((g - w).ravel())
                    / (jnp.linalg.norm(w.ravel()) + 1e-30))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)
        seen.add(jax.tree_util.keystr(path[-2:]))
    for kind in ("q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm",
                 "kv_b_proj", "o_proj", "router", "eh_proj", "enorm", "hnorm",
                 "wte", "lm_head", "gate_proj", "shared"):
        assert any(kind in s for s in seen) or kind == "shared", kind


@pytest.mark.parametrize("seed", [0, 1])
def test_bf16_compute_agrees_with_the_reference(seed) -> None:
    """bf16 compute, 128 tokens: tokens whose top-2 set flips are left
    out, as the cell's check leaves them out."""
    params, (tokens, targets) = _params(CFG, seed), _batch(seed)
    got = jax.jit(functools.partial(joyai.loss_terms, CFG))(
        params, tokens, targets)
    want = jax.jit(functools.partial(joyai_f32.terms, **_ref_kw(CFG)))(
        params, tokens, targets)
    flipped = np.any(np.any(
        np.asarray(_chosen(got["experts"], CFG.n_routed_experts))
        != np.asarray(want["chosen"]), axis=-1), axis=0)
    assert flipped.mean() < 0.15
    assert abs(float(got["loss"]) - float(want["loss"])) < 2e-2
    for name in ("hidden", "mtp_hidden"):
        h = np.asarray(got[name].astype(jnp.float32)).reshape(-1, 64)
        h_ref = np.asarray(want[name]).reshape(-1, 64)
        err = (np.linalg.norm(h - h_ref, axis=-1)
               / np.linalg.norm(h_ref, axis=-1))[~flipped]
        assert np.sqrt(np.mean(err ** 2)) < 0.03, name


FAULTS = ("no_renormalise", "no_scale", "bias_ignored", "rotate_half",
          "score_scale_128", "v_truncated", "expert_dropped", "mtp_left_out")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_comparison(monkeypatch, fault) -> None:
    """Each listed fault moves what the cell's check compares by far more
    than f32 rounding: the test of the reference's teeth at this size."""
    from torchft_tpu.models import llama
    from torchft_tpu.ops.attention import reference_attention

    cfg, params, attn_fn = CFG32, _params(CFG32, 5), None
    tokens, targets = _batch(5)
    # the sound side: the plain reference, which no patch reaches, once
    want = kit.sound(("joyai", "reference", 5), lambda: joyai_f32.terms(
        params, tokens, targets, **_ref_kw(CFG32)))
    if fault == "no_renormalise":
        real = moe.top_k_routing
        monkeypatch.setattr(moe, "top_k_routing", lambda s, k, **kw: real(
            s, k, **dict(kw, renormalise=False)))
    elif fault == "no_scale":
        cfg = dataclasses.replace(cfg, routed_scale=1.0)
    elif fault == "bias_ignored":
        params = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.zeros_like(x)
            if p[-1].key == joyai.BALANCE_BIAS else x, params)
    elif fault == "rotate_half":
        monkeypatch.setattr(joyai, "_rope_pairs", llama._rope)
    elif fault == "score_scale_128":
        attn_fn = functools.partial(reference_attention,
                                    scale=CFG.qk_nope_dim ** -0.5)
    elif fault == "v_truncated":
        attn_fn = lambda q, k, v: reference_attention(  # noqa: E731
            q, k, v.at[..., CFG.v_head_dim // 2:].set(0))
    elif fault == "expert_dropped":
        params["layers_1"]["moe"]["down_proj"]["kernel"] = params[
            "layers_1"]["moe"]["down_proj"]["kernel"].at[1].set(0)
    elif fault == "mtp_left_out":
        cfg = dataclasses.replace(cfg, mtp_coef=0.0)
    got = joyai.loss_terms(cfg, params, tokens, targets, attn_fn)
    moved = max(
        abs(float(got["loss"]) - float(want["loss"])),
        float(jnp.max(jnp.abs(got["hidden"] - want["hidden"]))),
        float(jnp.max(jnp.abs(got["mtp_hidden"] - want["mtp_hidden"]))),
    )
    assert moved > 1e-2, (fault, moved)


def test_interleaved_rope_is_not_rotate_half_and_turns_pairs() -> None:
    from torchft_tpu.models import llama

    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 8))
    got = joyai._rope_pairs(x, 10000.0)
    assert not np.allclose(got, llama._rope(x, 10000.0), atol=1e-3)
    np.testing.assert_allclose(got, joyai_f32._rope_interleaved(x, 10000.0),
                               atol=1e-6)
    # a rotation: every pair keeps its length; position 0 is not turned
    pairs = lambda a: np.asarray(a).reshape(1, 8, 2, 4, 2)  # noqa: E731
    np.testing.assert_allclose(np.linalg.norm(pairs(got), axis=-1),
                               np.linalg.norm(pairs(x), axis=-1), atol=1e-5)
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)


# -- the held share ----------------------------------------------------------


def _layer_and_stream(seed):
    params = _params(CFG32, seed)
    x = jax.random.normal(jax.random.key(50 + seed), (2, 64, 64), jnp.float32)
    return params["mtp"]["block"], x


def _full_layer(layer, seed):
    """The same layer with all 8 routed experts: the held 4 and 4 more."""
    extra = joyai.init_params(
        dataclasses.replace(CFG32, first_expert=4), jax.random.key(900 + seed)
    )["mtp"]["block"]["moe"]
    full = jax.tree_util.tree_map(lambda a: a, layer)
    for name in ("gate_proj", "up_proj", "down_proj"):
        full["moe"][name] = {"kernel": jnp.concatenate(
            [layer["moe"][name]["kernel"], extra[name]["kernel"]])}
    return full


@pytest.mark.parametrize("split", [(2, 2, 2, 2), (4, 4), (3, 5), (1, 6, 1),
                                   (8,)])
def test_the_shares_add_up_to_the_uncut_layer(split) -> None:
    """The routed parts that all the shares give, with the shared expert
    counted once, are the reference's layer with every expert held."""
    layer, x = _layer_and_stream(7)
    full = _full_layer(layer, 7)
    h = joyai_f32._rms(x, full["ln_2"]["scale"], CFG.rms_eps).reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        want, _ = joyai_f32._moe(h, full["moe"], top_k=CFG.top_k,
                                 first_expert=0,
                                 routed_scale=CFG.routed_scale)
        shared = common.swiglu(h, full["moe"]["shared"], jnp.float32)
        total, first = jnp.zeros_like(want), 0
        for held in split:
            cfg = dataclasses.replace(CFG32, first_expert=first,
                                      n_experts_held=held)
            share = jax.tree_util.tree_map(lambda a: a, full)
            for name in ("gate_proj", "up_proj", "down_proj"):
                share["moe"][name] = {"kernel": full["moe"][name]["kernel"][
                    first:first + held]}
            y, rec = joyai._moe_sublayer(cfg, share, x)
            total = total + (y - x).reshape(-1, 64) - shared
            first += held
        assert first == CFG.n_routed_experts
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 0.1   # the routed part


def test_every_assignment_held_and_none_held_run_one_program() -> None:
    """Dropless for every routing: a bias that sends every assignment to
    the held experts, and one that sends none there, are ordinary inputs
    of the one compiled program, and both agree with the reference."""
    layer, x = _layer_and_stream(9)
    run = jax.jit(functools.partial(joyai._moe_sublayer, CFG32))
    seen = []
    for sign in (+1.0, -1.0, 0.0):
        bias = sign * 10.0 * (jnp.arange(8) < 4)
        layer["moe"][joyai.BALANCE_BIAS] = bias.astype(jnp.float32)
        y, rec = run(layer, x)
        seen.append(int(jnp.sum(rec["loads"][:4])))
        h = joyai_f32._rms(x, layer["ln_2"]["scale"], CFG.rms_eps)
        with jax.default_matmul_precision("highest"):
            want, _ = joyai_f32._moe(
                h.reshape(-1, 64), layer["moe"], top_k=CFG.top_k,
                first_expert=0, routed_scale=CFG.routed_scale)
        np.testing.assert_allclose((y - x).reshape(-1, 64), want, atol=2e-5)
        assert bool(jnp.all(jnp.isfinite(y)))
    assert seen[0] == 2 * 64 * CFG.top_k and seen[1] == 0
    assert 0 < seen[2] < seen[0]
    assert run._cache_size() == 1
    # and the gradient of a share that holds nothing of a batch is that
    # of the shared expert alone: finite, and zero for the routed weights
    layer["moe"][joyai.BALANCE_BIAS] = -10.0 * (jnp.arange(8) < 4).astype(
        jnp.float32)
    grads = jax.grad(lambda l: jnp.sum(
        joyai._moe_sublayer(CFG32, l, x)[0] ** 2))(layer)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))
    assert not np.any(grads["moe"]["gate_proj"]["kernel"])
    assert np.any(grads["moe"]["shared"]["gate_proj"]["kernel"])


def test_sigmoid_routing_selects_by_bias_and_weights_by_score() -> None:
    scores = jnp.array([[0.9, 0.8, 0.1, 0.2]])
    bias = jnp.array([0.0, -1.0, 1.0, 0.0])
    weights, experts = moe.top_k_routing(
        scores, 2, bias=bias, renormalise=True, scale=2.5)
    assert sorted(experts[0].tolist()) == [0, 2]          # 0.9 and 0.1 + 1
    by_expert = dict(zip(experts[0].tolist(), weights[0].tolist()))
    assert by_expert[0] == pytest.approx(2.5 * 0.9 / 1.0)
    assert by_expert[2] == pytest.approx(2.5 * 0.1 / 1.0)
    # no gradient reaches the bias; the scores' is the renormalised one
    g_scores, g_bias = jax.grad(lambda s, b: moe.top_k_routing(
        s, 2, bias=b, renormalise=True)[0][0, 0], argnums=(0, 1))(scores, bias)
    assert not np.any(g_bias) and np.any(g_scores)


def test_olmoe_routing_and_program_are_what_they_were() -> None:
    """``top_k_routing`` without a bias is ``lax.top_k``, and the whole
    gradient program of OLMoE traces to the jaxpr the commit before the
    held share (a4592dd) traced: same instructions, same order."""
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(0), (64, 8)))
    weights, experts = moe.top_k_routing(probs, 2)
    want_w, want_e = jax.lax.top_k(probs, 2)
    assert np.array_equal(weights, want_w) and np.array_equal(experts, want_e)
    cfg = olmoe.OLMOE_CONFIGS["olmoe_tiny"]
    params = jax.eval_shape(lambda: olmoe.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, a, b: olmoe.loss_fn(cfg, p, a, b)))(params, tokens, tokens))
    text = re.sub(r"/[^ ]*?\.py:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "3c40614299f885d4c7d1230c9b2a6a7a68c7e055e6686e838d81f49e710ed6b8")


# -- the balance bias through the one optimizer ------------------------------


def test_the_bias_rule_moves_towards_balance_on_a_skewed_router() -> None:
    """A selection skewed so far that every token takes experts 0 and 1
    (their bias starts at 1, above any sigmoid score): the rule lowers
    the two and raises the six, step by step, until the loads level."""
    cfg = dataclasses.replace(CFG32, n_mtp=0)
    params = joyai.init_params(cfg, jax.random.key(0))
    router = params["layers_1"]["moe"]["router"]["kernel"]
    params["layers_1"]["moe"][joyai.BALANCE_BIAS] = (
        jnp.arange(8) < 2).astype(jnp.float32)
    tx = with_balance_bias(optax.sgd(0.0), 0.05, joyai.is_balance_bias)
    step = make_train_step(cfg, tx, donate=False, loss=joyai.loss_fn)
    terms = jax.jit(functools.partial(joyai.loss_terms, cfg))
    opt = tx.init(params)
    tokens, targets = _batch(0, rows=4)
    before = float(terms(params, tokens, targets)["load_max_over_mean"][0])
    for i in range(20):
        loads = terms(params, tokens, targets)["loads"][0]
        new, opt, _ = step(params, opt, tokens, targets)
        moved = (new["layers_1"]["moe"][joyai.BALANCE_BIAS]
                 - params["layers_1"]["moe"][joyai.BALANCE_BIAS])
        np.testing.assert_allclose(
            moved, 0.05 * np.sign(float(jnp.mean(loads)) - loads), atol=1e-6)
        if i == 0:      # the two overloaded down, every starved one up
            np.testing.assert_allclose(moved, [-0.05] * 2 + [0.05] * 6,
                                       atol=1e-6)
        params = new
    after = float(terms(params, tokens, targets)["load_max_over_mean"][0])
    assert before == 4.0 and after < 2.0, (before, after)
    # sgd(0): nothing else moved, so only the rule did this
    assert np.array_equal(params["layers_1"]["moe"]["router"]["kernel"],
                          router)


def test_the_rule_has_no_moments_and_leaves_the_other_leaves_to_tx() -> None:
    """The bias rule never reads its state; what it keeps there since
    PR 38 is the loads it last saw (``optim.BalanceBiasState``), for
    ``OptimizerWrapper``'s routing gauges."""
    params = {"a": {"kernel": jnp.ones((3,))},
              "moe": {joyai.BALANCE_BIAS: jnp.zeros((4,))}}
    tx = with_balance_bias(optax.adamw(0.1, weight_decay=0.5), 0.001,
                           joyai.is_balance_bias)
    state = tx.init(params)
    grads = {"a": {"kernel": jnp.ones((3,))},
             "moe": {joyai.BALANCE_BIAS: jnp.array([4.0, 0.0, 2.0, 2.0])}}
    updates, state = tx.update(grads, state, params)
    np.testing.assert_allclose(updates["moe"][joyai.BALANCE_BIAS],
                               [-0.001, 0.001, 0.0, 0.0])
    assert float(updates["a"]["kernel"][0]) < -0.05       # adamw's, decayed
    # twice the loads (two groups' sum, not their mean) is the same update
    doubled, kept = balance_bias_rule(0.001).update(
        {"b": 2 * grads["moe"][joyai.BALANCE_BIAS]}, optax.EmptyState())
    np.testing.assert_allclose(doubled["b"],
                               updates["moe"][joyai.BALANCE_BIAS])
    np.testing.assert_array_equal(kept.loads["b"], [8.0, 0.0, 4.0, 4.0])


def test_microbatched_grad_step_carries_the_mean_loads() -> None:
    params, (tokens, targets) = _params(CFG, 2), _batch(2, rows=4)
    _, whole = make_grad_step(CFG, loss=joyai.loss_fn)(params, tokens, targets)
    _, halves = make_grad_step(CFG, microbatches=2, loss=joyai.loss_fn)(
        params, tokens, targets)
    for a, b in zip(kit.bias_leaves(whole), kit.bias_leaves(halves)):
        assert float(jnp.sum(a)) == 4 * 64 * CFG.top_k
        assert float(jnp.sum(b)) == 2 * 64 * CFG.top_k    # a slice's mean


# -- the family through the step maker and the fault-tolerant loop -----------


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    from benchmark import mla_flops
    from benchmark.families import joyai as family

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "joyai-llm-flash-ep16.json")) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_experts_held) == (
        256, 0, 16)
    assert (cfg.qk_head_dim, cfg.v_head_dim, cfg.n_heads) == (192, 128, 32)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_mtp) == (5, 1, 1)
    shapes = jax.eval_shape(lambda: joyai.init_params(cfg, jax.random.key(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(680.4e6, rel=1e-3)          # the issue's count
    assert mla_flops.mla_params(2048, 32, 1536, 512, 128, 64, 128) == \
        pytest.approx(26.35e6, rel=1e-3)
    parts = mla_flops.train_flops_per_token(**mla_flops.config_dims(config))
    assert parts["total"] == pytest.approx(3.40e9, rel=2e-3)
    assert (parts["mla_proj"] + parts["mla_core"]) / parts["total"] == \
        pytest.approx(0.72, abs=0.01)
    assert model.flops_per_token == parts["total"]
    for key, value in (("n_group", 8), ("rope_scaling", {"factor": 4}),
                       ("n_shared_experts", 2), ("scoring_func", "softmax"),
                       ("rope_interleave", False)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size, and the bias
    rule on the fused path: behind the commit gate the bias moves exactly
    as in the plain step."""
    with kit.ft_steps(kit.tiny("joyai")) as run:
        assert all(np.any(b) for b in kit.bias_leaves(run.params))


def test_two_groups_on_other_batches_hold_one_bias_and_a_healed_one_gets_it():
    """grad -> average_gradients -> step across two replica groups that
    see different batches: the loads ride the gradient buckets, so both
    apply the same bias update behind the commit gate. The second group
    starts from other weights, behind, and gets the first's bias (moved
    by then) only by the heal. At rest on one step the sha256 of
    parameters and optimizer state are equal, and so is every bias."""
    with kit.two_groups_one_healed(kit.tiny("joyai")) as run:
        biases = [kit.bias_leaves(jax.device_get(g.state["params"]))
                  for g in run.groups]
        for a, b in zip(*biases):
            assert np.any(a) and np.array_equal(a, b)
            # whole multiples of the rate: only the sign rule touched it
            assert np.allclose(a / 0.01, np.round(a / 0.01), atol=1e-4)


def test_the_cells_own_comparison_at_the_small_size() -> None:
    """``families/joyai.py``'s ``per_token_errors`` + ``judge``: sound
    weights pass the structure of the check (f32 compute, no flips), a
    dropped expert and an ignored bias are seen by it."""
    from benchmark.families import joyai as family

    params, (tokens, targets) = _params(CFG32, 4), _batch(4)
    seen = family.per_token_errors(CFG32, params, params, tokens, targets)
    verdict = family.judge(seen)
    assert verdict["ok"] and verdict["top8_disagreement"] == 0.0
    assert verdict["tokens_compared"] == verdict["tokens"] == 128
    assert max(verdict["hidden_rel_l2_max"], verdict["mtp_rel_l2_max"]) < 1e-4
    assert len(verdict["rows_held"]) == len(verdict["load_max_over_mean"]) == 2
    unbiased = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x)
        if p[-1].key == joyai.BALANCE_BIAS else x, params)
    assert not family.judge(family.per_token_errors(
        CFG32, unbiased, params, tokens, targets))["ok"]
    scaled = dataclasses.replace(CFG32, routed_scale=1.0)
    assert not family.judge(family.per_token_errors(
        CFG32, params, params, tokens, targets, system_cfg=scaled))["ok"]
    # the check's own seeding of the bias: other leaves untouched
    seeded = family.seed_balance_bias(params, 3)
    assert seeded["wte"]["embedding"] is params["wte"]["embedding"]
    assert all(np.any(b) for b in kit.bias_leaves(seeded))
    again = family.seed_balance_bias(params, 3)
    for a, b in zip(kit.bias_leaves(seeded), kit.bias_leaves(again)):
        assert np.array_equal(a, b)


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("joyai")
