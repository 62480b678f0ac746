"""LFM2-MoE (models/lfm2.py: a gated short-convolution mixer over
ops/ssm_pointwise.py::gated_conv, a rotary GQA mixer with QK-norm, a
dense SwiGLU or sigmoid top-k SwiGLU experts with a balance bias, ONE
table used as embedding and head; the mixer of a layer is
``layer_types[i]``, its MLP ``i < n_dense_layers``) against the plain
float32 reference the benchmark keeps (benchmark/reference/lfm2_f32.py),
at a small size on the CPU: d 64, layers ``C A C A`` with one dense MLP,
4 query heads on 2 key/value heads of 16, 8 routed experts of width 32 of
which 4 are held, top 2, S 64, seeded random weights. Then the family
(benchmark/families/lfm2.py) through the one step maker, the one
optimizer and the fault-tolerant loop, and the two routing gauges of the
optimizer wrapper's sink."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import family_kit as kit

from benchmark import lfm2_flops
from benchmark.families import lfm2 as family
from benchmark.reference import lfm2_f32
from benchmark.tests import lfm2_faults
from benchmark.tests.lfm2_faults import FAULTS, with_leaf
from torchft_tpu import optim
from torchft_tpu.models import common, lfm2
from torchft_tpu.ops import moe

CFG = lfm2.LFM2_CONFIGS["lfm2_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
BIAS = lfm2.BALANCE_BIAS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_params = functools.partial(kit.seeded_params, lfm2)
_batch = kit.batch


def _reference(cfg):
    return functools.partial(lfm2_f32.terms, **family.reference_dims(cfg))


# -- against the reference ---------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_compute_equals_the_reference(seed) -> None:
    params, (tokens, targets) = _params(CFG32, seed), _batch(seed)
    with jax.default_matmul_precision("highest"):
        got = lfm2.loss_terms(CFG32, params, tokens, targets)
    want = _reference(CFG32)(params, tokens, targets)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), abs=2e-5)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5)
    chosen = jnp.any(jax.nn.one_hot(got["experts"], 8, dtype=bool), axis=-2)
    assert np.array_equal(chosen, want["chosen"])
    assert got["loads"].shape == (3, 8)
    assert float(jnp.sum(got["loads"])) == 3 * 128 * CFG.top_k


def test_f32_gradients_equal_the_reference() -> None:
    """Every leaf but the balance bias (whose place carries the loads):
    the convolution's kernels' backward, QK-norm and RoPE, the held
    experts, and the ONE table through both of its uses."""
    params, (tokens, targets) = _params(CFG32, 2), _batch(2)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(
            lambda p: lfm2.loss_fn(CFG32, p, tokens, targets))(params)
    want = jax.grad(lambda p: lfm2_f32.loss(
        p, tokens, targets, **family.reference_dims(CFG32)))(params)
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(want)) == 46
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if path[-1].key == BIAS:
            assert float(jnp.sum(g)) == 128 * CFG.top_k, name   # the loads
            continue
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * scale, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1])
def test_bf16_compute_agrees_with_the_reference(seed) -> None:
    """bf16 compute, 128 tokens, the cell's own comparison: the reference
    is computed on the top-2 sets the system took, its own choice is
    counted beside it, and every token is compared."""
    params, (tokens, targets) = _params(CFG, seed), _batch(seed)
    seen = family.per_token_errors(CFG, params, params, tokens, targets)
    assert seen["error"].shape == (128,)
    assert float(seen["disagreement"]) < 0.1
    assert abs(float(seen["loss"]) - float(seen["reference_loss"])) < 2e-2
    assert np.sqrt(np.mean(seen["error"] ** 2)) < 0.03
    assert seen["error"].max() < 0.08


def test_the_reference_follows_a_selection_and_still_says_its_own() -> None:
    params, (tokens, targets) = _params(CFG32, 6), _batch(6)
    ref = _reference(CFG32)
    own = ref(params, tokens, targets)
    again = ref(params, tokens, targets, selection=own["chosen"])
    assert np.array_equal(again["hidden"], own["hidden"])
    assert np.array_equal(again["chosen"], own["chosen"])
    other = jnp.roll(own["chosen"], 1, axis=-1)       # every set moved on
    moved = ref(params, tokens, targets, selection=other)
    assert float(jnp.max(jnp.abs(moved["hidden"] - own["hidden"]))) > 1e-2
    assert np.array_equal(moved["chosen"][0], own["chosen"][0])
    assert not np.array_equal(moved["chosen"][1], own["chosen"][1])


@pytest.mark.parametrize("kinds,n_dense", [
    (("conv",), 1), (("full_attention",), 0),
    (("full_attention", "conv", "conv"), 2), (("conv", "conv"), 0)])
def test_mixer_and_mlp_are_two_axes_of_the_config(kinds, n_dense) -> None:
    """``layer_types`` chooses a layer's mixer and ``n_dense_layers`` its
    MLP, independently: every combination has its leaves and agrees with
    the reference."""
    cfg = dataclasses.replace(CFG32, layer_types=kinds, n_dense_layers=n_dense)
    params = lfm2.init_params(cfg, jax.random.key(0))
    for i, kind in enumerate(kinds):
        assert set(params[f"layers_{i}"]) == {
            "norm_1", "norm_2", lfm2.MIXERS[kind],
            "mlp" if i < n_dense else "moe"}
    tokens, targets = _batch(0)
    with jax.default_matmul_precision("highest"):
        got = lfm2.loss_terms(cfg, params, tokens, targets)
    want = _reference(cfg)(params, tokens, targets)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), abs=2e-5)
    assert ("loads" in got) == (n_dense < len(kinds))
    hash(cfg)       # the step-program store keys on it
    with pytest.raises(AssertionError):
        dataclasses.replace(CFG32, layer_types=("conv", "mamba"))


# -- the mixers, each alone --------------------------------------------------


def test_a_key_value_head_serves_consecutive_query_heads() -> None:
    """``common.repeat_kv`` (what Nemotron-H's and this model's attention
    is held to; since PR 55 it reads the heads by index and no program
    holds the copy): query heads 0-1 read key/value head 0 and 2-3 head
    1. That Nemotron-H's whole gradient program is the one it traced
    before the helper and the shared kernel body is a case of
    ``tests/test_nemotron_h.py::
    test_the_gated_expert_paths_are_what_they_were``, beside this
    model's."""
    kv = jnp.arange(2 * 3 * 2 * 4, dtype=jnp.float32).reshape(2, 3, 2, 4)
    out = common.repeat_kv(kv, 4)
    assert out.shape == (2, 3, 4, 4)
    for head in range(4):
        assert np.array_equal(out[:, :, head], kv[:, :, head // 2])


def test_the_attention_mixer_norms_turns_and_groups() -> None:
    """Zeroing key/value head 1's value projection silences query heads
    2-3 and leaves 0-1; the QK-norm's weight and the rotary embedding are
    in the path (another weight, another theta: another result)."""
    params = _params(CFG32, 3)
    layer = params["layers_1"]
    x = jax.random.normal(jax.random.key(1), (2, 64, 64), jnp.float32)
    def out(cfg, lay):
        return lfm2._attn_mixer(
            cfg, lay, x, attn_fn=lfm2._local_causal_attention) - x

    base = out(CFG32, layer)
    half = with_leaf({"l": layer}, "l", ("attn", "v_proj", "kernel"),
                     lambda w: w.at[:, 16:].set(0))["l"]
    o_rows = layer["attn"]["o_proj"]["kernel"]
    only_01 = with_leaf({"l": layer}, "l", ("attn", "o_proj", "kernel"),
                        lambda w: w.at[32:].set(0))["l"]
    np.testing.assert_allclose(out(CFG32, half), out(CFG32, only_01),
                               atol=1e-5)
    assert o_rows.shape == (64, 64)
    scaled = with_leaf({"l": layer}, "l", ("attn", "q_norm", "scale"),
                       lambda s: s.at[:8].multiply(3.0))["l"]
    assert float(jnp.max(jnp.abs(out(CFG32, scaled) - base))) > 1e-3
    other = dataclasses.replace(CFG32, rope_theta=1e2)
    assert float(jnp.max(jnp.abs(out(other, layer) - base))) > 1e-3


def test_the_conv_mixer_runs_the_two_fused_kernels() -> None:
    """One ``sconv_fwd`` forward, and ``sconv_bwd`` (with the forward
    nowhere: the residuals are the inputs) in the gradient; no position
    reads a later one."""
    params = _params(CFG, 4)
    x = jax.random.normal(jax.random.key(2), (2, 64, 64), jnp.bfloat16)
    run = functools.partial(lfm2._conv_mixer, CFG, params["layers_0"])
    text = str(jax.make_jaxpr(run)(x))
    assert text.count("sconv_fwd") == 1 and "sconv_bwd" not in text
    grad = str(jax.make_jaxpr(jax.grad(
        lambda a: jnp.sum(run(a).astype(jnp.float32))))(x))
    assert grad.count("sconv_bwd") == 1
    y = run(x)
    later = x.at[:, 40:].set(0)
    assert np.array_equal(run(later)[:, :40], y[:, :40])
    assert not np.array_equal(run(later)[:, 40:], y[:, 40:])


# -- the tied table ----------------------------------------------------------


def test_the_table_is_one_leaf_and_its_gradient_the_sum_of_both_uses():
    """No ``lm_head`` leaf: parameters, gradients and Adam's moments hold
    ONE ``[V, d]`` array; its gradient is the embedding's (rows of the
    tokens seen) plus the head's (every row), which an untied pair of
    copies shows apart."""
    params, (tokens, targets) = _params(CFG32, 5), _batch(5)
    assert "lm_head" not in params
    tables = [x for x in jax.tree_util.tree_leaves(params)
              if x.shape == (512, 64)]
    assert len(tables) == 1
    opt = optax.adam(1e-3).init(params)
    assert sum(x.shape == (512, 64)
               for x in jax.tree_util.tree_leaves(opt)) == 2   # mu and nu

    def untied(embed, head):
        h, rec = lfm2.forward_hidden(
            CFG32, dict(params, wte={"embedding": embed}), tokens)
        return lfm2.ce_from_hidden(h, head.T, targets, 0) + rec["carrier"]

    table = params["wte"]["embedding"]
    with jax.default_matmul_precision("highest"):
        g_embed, g_head = jax.grad(untied, argnums=(0, 1))(table, table)
        tied = jax.grad(lambda p: lfm2.loss_fn(CFG32, p, tokens, targets))(
            params)["wte"]["embedding"]
    np.testing.assert_allclose(tied, g_embed + g_head, atol=1e-6)
    unseen = np.setdiff1d(np.arange(512), np.asarray(tokens).ravel())
    assert unseen.size and not np.any(g_embed[unseen])
    assert np.all(np.any(np.asarray(g_head) != 0, axis=1))
    assert float(jnp.max(jnp.abs(g_embed))) > 1e-4


# -- the held share ----------------------------------------------------------


def _layer_and_stream(seed):
    params = _params(CFG32, seed)
    x = jax.random.normal(jax.random.key(50 + seed), (2, 64, 64), jnp.float32)
    return params["layers_3"], x


def _full_layer(layer, seed):
    """The same layer with all 8 routed experts: the held 4 and 4 more."""
    extra = lfm2.init_params(
        dataclasses.replace(CFG32, first_expert=4), jax.random.key(900 + seed)
    )["layers_3"]["moe"]
    full = jax.tree_util.tree_map(lambda a: a, layer)
    for name in ("gate_proj", "up_proj", "down_proj"):
        full["moe"][name] = {"kernel": jnp.concatenate(
            [layer["moe"][name]["kernel"], extra[name]["kernel"]])}
    return full


@pytest.mark.parametrize("split", [(2, 2, 2, 2), (1,) * 8, (4, 4), (3, 5),
                                   (1, 6, 1), (8,)])
def test_the_shares_add_up_to_the_uncut_layer(split) -> None:
    """The routed parts that all the shares give (4 chips of the
    deployment hold 8 each of 32 at ``first_expert`` 0 / 8 / 16 / 24; here
    4 shares of 2, and uneven ones), with everything every chip computes
    alike — the residual stream, the norm, the router — counted once, are
    the reference's expert MLP with every expert held."""
    layer, x = _layer_and_stream(7)
    full = _full_layer(layer, 7)
    n = lfm2_f32._rms(x, full["norm_2"]["scale"], CFG.rms_eps).reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        want, _ = lfm2_f32._experts(
            n, full["moe"], top_k=CFG.top_k, first_expert=0,
            routed_scale=CFG.routed_scale)
        total, first = jnp.zeros_like(want), 0
        for held in split:
            cfg = dataclasses.replace(CFG32, first_expert=first,
                                      n_experts_held=held)
            share = jax.tree_util.tree_map(lambda a: a, full)
            for name in ("gate_proj", "up_proj", "down_proj"):
                share["moe"][name] = {"kernel": full["moe"][name]["kernel"][
                    first:first + held]}
            y, rec = lfm2._moe_mlp(cfg, share, x)
            total = total + (y - x).reshape(-1, 64)
            first += held
        assert first == CFG.n_routed_experts
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_every_assignment_held_and_none_held_run_one_program() -> None:
    layer, x = _layer_and_stream(9)
    run = jax.jit(functools.partial(lfm2._moe_mlp, CFG32))
    seen = []
    for sign in (+1.0, -1.0, 0.0):
        layer["moe"][BIAS] = (
            sign * 10.0 * (jnp.arange(8) < 4)).astype(jnp.float32)
        y, rec = run(layer, x)
        seen.append(int(jnp.sum(rec["loads"][:4])))
        n = lfm2_f32._rms(x, layer["norm_2"]["scale"], CFG.rms_eps)
        with jax.default_matmul_precision("highest"):
            want, _ = lfm2_f32._experts(
                n.reshape(-1, 64), layer["moe"], top_k=CFG.top_k,
                first_expert=0, routed_scale=CFG.routed_scale)
        np.testing.assert_allclose((y - x).reshape(-1, 64), want, atol=2e-5)
    assert seen[0] == 2 * 64 * CFG.top_k and seen[1] == 0
    assert 0 < seen[2] < seen[0]
    assert run._cache_size() == 1


def test_the_renormalisation_epsilon_is_the_callers() -> None:
    """``top_k_routing``'s default stays 1e-20 (the other families'
    programs are pinned by their jaxpr); LFM2 passes its published
    1e-6, which the reference uses too."""
    scores = jnp.array([[1e-7, 2e-7, 0.0, 0.0]], jnp.float32)
    bias = jnp.zeros((4,), jnp.float32)
    default, _ = moe.top_k_routing(scores, 2, bias=bias, renormalise=True)
    assert float(jnp.sum(default)) == pytest.approx(1.0)
    ours, _ = moe.top_k_routing(scores, 2, bias=bias, renormalise=True,
                                eps=1e-6)
    assert float(jnp.sum(ours)) == pytest.approx(3e-7 / (3e-7 + 1e-6))
    assert CFG.renorm_eps == lfm2_f32.RENORM_EPS == 1e-6


# -- the faults of the cell's check ------------------------------------------


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_comparison(monkeypatch, fault) -> None:
    """Each listed fault moves what the cell's checks compare by far more
    than f32 rounding: the test of the reference's teeth at this size.
    A fault inside the convolution also moves the convolution's own
    comparison."""
    params = _params(CFG32, 5)
    tokens, targets = _batch(5)
    # the sound side: the plain reference, which no patch reaches, once
    want = kit.sound(("lfm2", "reference", 5), lambda: _reference(CFG32)(
        params, tokens, targets))
    patches, weights, cfg, conv_fn = lfm2_faults.fault(fault, CFG32, params)
    for patch in patches:
        monkeypatch.setattr(*patch)
    got = lfm2.loss_terms(cfg or CFG32, weights or params, tokens, targets)
    chosen = jnp.any(jax.nn.one_hot(got["experts"], 8, dtype=bool), axis=-2)
    own = lfm2.loss_terms(cfg or CFG32, weights or params, tokens, tokens)
    moved = max(
        abs(float(got["loss"]) - float(want["loss"])),
        # the tokens as their own targets: the tied table's witness
        abs(float(own["loss"]) - float(lfm2_f32.cross_entropy(
            want["hidden"], params["wte"]["embedding"], tokens))),
        float(jnp.max(jnp.abs(got["hidden"] - want["hidden"]))),
        float(jnp.mean(jnp.any(chosen != want["chosen"], axis=-1))),
    )
    # rounding to 8 (bf16) or 4 (e4m3) bits in one place of a tiny model
    floor = 5e-4 if fault in lfm2_faults.ROUNDING else 1e-2
    assert moved > floor, (fault, moved)
    if conv_fn is not None:
        alone = jax.jit(family.conv_comparison(conv_fn))(
            *family.conv_inputs(CFG32, 5, 64))
        assert not family.judge_conv(alone)["ok"], alone


def test_the_faults_stand_in_is_sound_without_its_fault(monkeypatch):
    """The jnp convolution that stands in for the kernels under four of
    the faults, with nothing changed, is the kernels' result."""
    params, (tokens, targets) = _params(CFG32, 5), _batch(5)
    want = lfm2.loss_terms(CFG32, params, tokens, targets)
    monkeypatch.setattr(lfm2, "_gated_conv", lambda m, bcx, dt: (
        lfm2_faults.jnp_conv(bcx, m["conv"]["kernel"]).astype(dt)))
    got = lfm2.loss_terms(CFG32, params, tokens, targets)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=1e-5)
    sound = jax.jit(family.conv_comparison(lfm2_faults.jnp_conv))(
        *family.conv_inputs(CFG, 5, 64))
    assert family.judge_conv(sound)["ok"], sound


def test_the_cells_own_check_of_the_convolution() -> None:
    """``conv_comparison`` + ``judge_conv`` at the small size: the sound
    kernels pass leaf by leaf (bf16 operands: the one rounding of each
    result), every leaf has a limit that judges it alone."""
    sound = jax.device_get(jax.jit(family.conv_comparison())(
        *family.conv_inputs(CFG, 3, 64)))
    assert set(sound) == set(family.CONV_LEAVES)
    verdict = family.judge_conv(sound)
    assert verdict["ok"] and verdict["conv_over"] == []
    for name in family.CONV_LEAVES:
        over = dict(sound, **{name: 1.5 * family.CONV_REL_L2_MAX[name]})
        assert family.judge_conv(over)["conv_over"] == [name]


def test_check_reference_is_both_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict and the convolution's, and is
    ``ok`` only where both are (the tiny configuration, bf16 compute;
    the whole model's limits are set for the cell's size)."""
    monkeypatch.setattr(family, "CONV_SEQ", 64)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_RMS_MAX", 0.03)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_MAX", 0.08)
    monkeypatch.setattr(family, "TOP_K_DISAGREEMENT_MAX", 0.1)
    monkeypatch.setattr(family, "REFERENCE_LOSS_ATOL", 2e-2)
    monkeypatch.setattr(family, "OWN_LOSS_ATOL", 2e-2)
    model, device = kit.tiny("lfm2"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"hidden_rel_l2_rms", "top4_disagreement", "held_share",
            "conv_rel_l2"} <= set(seen)
    assert seen["conv_over"] == []
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 580
    monkeypatch.setattr(family, "CONV_REL_L2_MAX",
                        dict(family.CONV_REL_L2_MAX, dX=0.0))
    again = family.check_reference(model, params, 5, device)
    assert not again["ok"] and again["conv_over"] == ["dX"]
    assert again["hidden_rel_l2_rms"] == seen["hidden_rel_l2_rms"]


def test_the_cells_own_comparison_at_the_small_size() -> None:
    params, (tokens, targets) = _params(CFG32, 4), _batch(4)
    seen = family.per_token_errors(CFG32, params, params, tokens, targets)
    verdict = family.judge(seen)
    assert verdict["ok"] and verdict["top4_disagreement"] == 0.0
    assert verdict["tokens"] == 128
    # the tied table: a token's own logit stands out
    assert verdict["own_abs_diff"] < 1e-4
    assert float(seen["own_loss"]) < float(seen["loss"]) - 0.5
    assert verdict["hidden_rel_l2_max"] < 1e-4
    assert len(verdict["rows_held"]) == len(verdict["held_share"]) == 3
    assert all(0 < s < 1 for s in verdict["held_share"])
    unbiased = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x) if p[-1].key == BIAS else x, params)
    assert not family.judge(family.per_token_errors(
        CFG32, unbiased, params, tokens, targets))["ok"]
    no_norm = dataclasses.replace(CFG32, rope_theta=1e4)
    assert not family.judge(family.per_token_errors(
        CFG32, params, params, tokens, targets, system_cfg=no_norm))["ok"]
    seeded = family.seed_balance_bias(params, 3)
    assert seeded["wte"]["embedding"] is params["wte"]["embedding"]
    assert all(np.any(b) for b in kit.bias_leaves(seeded))


# -- the family, the optimizer and the fault-tolerant loop --------------------


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-ep4.json")) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_experts_held) == (
        32, 0, 8)
    assert cfg.layer_types == (
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv")
    # the model's own layers 1 - 7
    assert list(cfg.layer_types) == config["published"]["layer_types"][1:8]
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.init_depth) == (7, 1, 24)
    assert (cfg.d_model, cfg.conv_kernel, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.rope_theta) == (2048, 3, 32, 8, 64, 1e6)
    assert (cfg.d_ff, cfg.d_expert, cfg.top_k, cfg.routed_scale,
            cfg.rms_eps, cfg.vocab_size) == (7168, 1792, 4, 1.0, 1e-5, 16384)
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "num_experts", "vocab_size"]
    assert model.tx.held_experts == (0, 8)
    shapes = jax.eval_shape(
        lambda: lfm2.init_params(cfg, jax.random.key(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(711.4e6, rel=1e-4)          # the issue's count

    def size(layer):
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes[layer]))

    assert size("layers_0") == pytest.approx(60.8e6, rel=1e-3)
    assert size("layers_1") == pytest.approx(98.6e6, rel=1e-3)
    assert size("layers_2") == pytest.approx(104.9e6, rel=1e-3)
    assert shapes["wte"]["embedding"].size == 16384 * 2048
    # benchmark/lfm2_flops.py against ISSUE 38's hand count: 1.70 GFLOP a
    # token; conv mixers 30 %, dense MLP 16 %, held experts 23 %,
    # attention 19 %, the head 12 %
    parts = lfm2_flops.train_flops_per_token(**lfm2_flops.config_dims(config))
    assert parts["total"] == pytest.approx(1.70e9, rel=5e-3)
    assert parts["sconv_proj"] == 6 * 5 * 4 * 2048 * 2048
    assert parts["gqa_core"] == 3 * 2 * 32 * 128 * 8193
    assert parts["routed_held"] == 6 * 6 * 1.0 * 3 * 2048 * 1792
    for part, share in (("sconv_proj", 0.30), ("dense_mlp", 0.16),
                        ("routed_held", 0.23), ("head", 0.12)):
        assert parts[part] / parts["total"] == pytest.approx(share, abs=0.01)
    assert (parts["gqa_proj"] + parts["gqa_core"]) / parts["total"] == \
        pytest.approx(0.19, abs=0.01)
    assert lfm2_flops.sconv_bytes_per_token("sconv_fwd", channels=2048) == 16384
    assert lfm2_flops.sconv_bytes_per_token("sconv_bwd", channels=2048) == 28672
    assert model.flops_per_token == parts["total"]
    for key, value in (("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False), ("num_hidden_layers", 8),
                       ("layer_types", ["conv"] * 6 + ["mamba"]),
                       ("num_attention_heads", 24)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))


def test_the_warm_up_is_a_schedule_and_the_rule_keeps_the_loads() -> None:
    """Step ``c`` runs at ``peak·(c + 1)/warm`` and the count is a leaf
    of the optimizer state; matrices (the taps among them) take weight
    decay, norms none; the bias rule's state is the loads it last saw —
    no moments — and ``routing_gauges`` reads the held share and the skew
    from it."""
    model = kit.tiny("lfm2")
    params = lfm2.init_params(model.cfg, jax.random.key(0))
    opt = model.tx.init(params)
    counts = [x for x in jax.tree_util.tree_leaves(opt)
              if x.shape == () and jnp.issubdtype(x.dtype, jnp.integer)]
    assert counts and all(int(c) == 0 for c in counts)
    held_loads = jnp.array([4.0, 2, 1, 1, 0, 0, 0, 0])      # all on 0 - 3
    grads = jax.tree_util.tree_map_with_path(
        lambda p, x: held_loads if p[-1].key == BIAS else jnp.ones_like(x),
        params)
    sizes = []
    for _ in range(6):
        updates, opt = model.tx.update(grads, opt, params)
        sizes.append(float(jnp.max(jnp.abs(
            updates["layers_0"]["conv"]["conv"]["kernel"]))))
    ratios = [s / sizes[3] for s in sizes]
    assert ratios[0] == pytest.approx(0.25, rel=0.05)
    assert ratios[4] == pytest.approx(1.0, rel=0.02)
    states = [s for s in jax.tree_util.tree_leaves(
        opt, is_leaf=lambda x: isinstance(x, optim.BalanceBiasState))
        if isinstance(s, optim.BalanceBiasState)]
    assert len(states) == 1
    kept = jax.tree_util.tree_leaves(states[0].loads)
    assert len(kept) == 3 and all(np.array_equal(k, held_loads) for k in kept)
    skew, share, fits = optim.routing_gauges(opt, model.tx.held_experts)
    assert float(skew) == pytest.approx(4.0) and float(share) == 1.0
    assert float(fits) == 1.0       # 8 assignments: the buffer is all of them
    assert np.isnan(float(optim.routing_gauges(opt)[1]))
    assert optim.routing_gauges(optax.adam(1e-3).init(params)) is None
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = model.tx.update(zero, model.tx.init(params), params)
    assert np.any(updates["layers_0"]["conv"]["conv"]["kernel"])
    assert np.any(updates["wte"]["embedding"])
    assert not np.any(updates["layers_0"]["norm_1"]["scale"])
    assert not np.any(updates["layers_1"]["attn"]["q_norm"]["scale"])


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size; and the
    optimizer wrapper's two routing gauges arrive on its sink without a
    wait (read at a later commit than the one that asked)."""
    with kit.ft_steps(kit.tiny("lfm2")) as run:
        assert all(np.any(b) for b in kit.bias_leaves(run.params))
        seen = kit.routing_gauges(run)
        assert 0.0 < seen["moe_held_share"] < 1.0
        assert seen["moe_load_max_over_mean"] >= 1.0
        assert seen["moe_row_buffer_share"] == 1.0


def test_two_groups_hold_one_state_and_a_healed_one_gets_the_one_table():
    """grad -> average_gradients -> step across two replica groups that
    see different batches; the second starts from other weights, behind,
    and gets the first's parameters — the ONE table among them —, bias,
    loads and count only by the heal. At rest on one step the sha256 of
    parameters and optimizer state are equal."""
    with kit.two_groups_one_healed(kit.tiny("lfm2")) as run:
        for g in run.groups:
            state = jax.device_get(g.state)
            assert "lm_head" not in state["params"]
            assert sum(x.shape == (512, 64) for x in
                       jax.tree_util.tree_leaves(state["params"])) == 1
            assert sum(x.shape == (512, 64) for x in
                       jax.tree_util.tree_leaves(state["opt"])) == 2
        tables = [np.asarray(g.state["params"]["wte"]["embedding"])
                  for g in run.groups]
        assert np.array_equal(*tables)
        biases = [kit.bias_leaves(jax.device_get(g.state["params"]))
                  for g in run.groups]
        for a, b in zip(*biases):
            assert np.any(a) and np.array_equal(a, b)
        # the classic path reports the gauges too
        assert "moe_load_max_over_mean" in run.first.opt.metrics.snapshot()


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("lfm2")
