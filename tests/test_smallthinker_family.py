"""The SmallThinker family (benchmark/families/smallthinker.py) at the
small size of tests/test_smallthinker.py, which holds the model to its
reference: the cell's own two comparisons and their verdicts, the
configuration the family builds, and the model through the one step
maker, the one optimizer and the fault-tolerant loop, with the routing
gauges of the optimizer wrapper's sink. A file of its own so that the
two run on two of tier-1's workers."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark import smallthinker_flops
from benchmark.families import smallthinker as family
from benchmark.reference import smallthinker_f32
from torchft_tpu import optim
from torchft_tpu.models import smallthinker
from torchft_tpu.ops.attention import causal_attention

CFG = smallthinker.SMALLTHINKER_CONFIGS["smallthinker_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
BIAS = smallthinker.BALANCE_BIAS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, S, E = CFG.d_model, 64, CFG.n_routed_experts
_params = functools.partial(kit.seeded_params, smallthinker)
_batch = kit.batch


def _reference(cfg):
    return functools.partial(smallthinker_f32.terms,
                             **family.reference_dims(cfg))


def test_the_cells_own_check_of_the_windowed_call() -> None:
    """``swa_comparison`` + ``judge_swa`` at the small size: the sound
    call passes leaf by leaf (bf16 operands: the one rounding of each
    result), every leaf has a limit that judges it alone, and it is the
    WORST head that is judged."""
    sound = jax.device_get(jax.jit(family.swa_comparison(CFG))(
        *family.swa_inputs(CFG, 3, S)))
    assert set(sound) == set(family.SWA_LEAVES)
    verdict = family.judge_swa(sound)
    assert verdict["ok"] and verdict["swa_over"] == []
    for name in family.SWA_LEAVES:
        over = dict(sound, **{name: 1.5 * family.SWA_REL_L2_MAX[name]})
        assert family.judge_swa(over)["swa_over"] == [name]

    def one_head_off(q, k, v, window=None):
        o = causal_attention(q, k, v, window=window)
        return o.at[:, :, 9].set(causal_attention(
            q[:, :, 9:10], k[:, :, 9:10], v[:, :, 9:10], window=window - 1
        )[:, :, 0])

    off = jax.device_get(jax.jit(family.swa_comparison(CFG32, one_head_off))(
        *family.swa_inputs(CFG32, 3, S)))
    assert not family.judge_swa(off)["ok"]


def test_check_reference_is_both_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict and the windowed call's, and is
    ``ok`` only where both are (the tiny configuration, bf16 compute;
    the whole model's limits are set for the cell's size)."""
    monkeypatch.setattr(family, "HIDDEN_REL_L2_RMS_MAX", 0.03)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_MAX", 0.08)
    monkeypatch.setattr(family, "TOP_K_DISAGREEMENT_MAX", 0.1)
    monkeypatch.setattr(family, "REFERENCE_LOSS_ATOL", 2e-2)
    model, device = kit.tiny("smallthinker"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"hidden_rel_l2_rms", "top6_disagreement", "held_share",
            "swa_rel_l2"} <= set(seen)
    assert seen["swa_over"] == []
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 600
    monkeypatch.setattr(family, "SWA_REL_L2_MAX",
                        dict(family.SWA_REL_L2_MAX, dk=0.0))
    again = family.check_reference(model, params, 5, device)
    assert not again["ok"] and again["swa_over"] == ["dk"]
    assert again["hidden_rel_l2_rms"] == seen["hidden_rel_l2_rms"]


def test_the_cells_own_comparison_at_the_small_size() -> None:
    params, (tokens, targets) = _params(CFG32, 4), _batch(4)
    seen = family.per_token_errors(CFG32, params, params, tokens, targets)
    verdict = family.judge(seen)
    assert verdict["ok"] and verdict["top6_disagreement"] == 0.0
    assert verdict["tokens"] == 128
    assert verdict["hidden_rel_l2_max"] < 1e-4
    assert len(verdict["rows_held"]) == len(verdict["held_share"]) == 8
    assert all(0 < s < 1 for s in verdict["held_share"])
    unbiased = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x) if p[-1].key == BIAS else x, params)
    assert not family.judge(family.per_token_errors(
        CFG32, unbiased, params, tokens, targets))["ok"]
    other = dataclasses.replace(CFG32, rope_theta=1e4)
    assert not family.judge(family.per_token_errors(
        CFG32, params, params, tokens, targets, system_cfg=other))["ok"]
    seeded = family.seed_balance_bias(params, 3)
    assert seeded["wte"]["embedding"] is params["wte"]["embedding"]
    assert all(np.any(b) for b in kit.bias_leaves(seeded))


# -- the family, the optimizer and the fault-tolerant loop --------------------


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b-ep4.json")) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_experts_held) == (
        64, 0, 16)
    # the model's own layers 0 - 3: one whole period of both lists
    assert cfg.windowed == cfg.rotated == (0, 1, 1, 1)
    assert list(cfg.windowed) == config["published"][
        "sliding_window_layout"][:4]
    assert list(cfg.rotated) == config["published"]["rope_layout"][:4]
    assert (cfg.n_layers, cfg.init_depth) == (4, 52)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.window, cfg.rope_theta) == (2560, 28, 4, 128, 4096, 1.5e6)
    assert (cfg.d_expert, cfg.top_k, cfg.routed_scale, cfg.rms_eps,
            cfg.vocab_size) == (768, 6, 1.0, 1e-6, 37984)
    assert config["reduced"] == [
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "moe_num_primary_experts", "vocab_size"]
    assert (model.seq_len, cfg.remat, cfg.xent_chunks) == (16384, True, 8)
    assert model.tx.held_experts == (0, 16)
    # every width equals the catalog row's (model-configs guide)
    for key, value in (
            ("head_dim", 128), ("hidden_size", 2560),
            ("max_position_embeddings", 16384), ("moe_ffn_hidden_size", 768),
            ("moe_num_active_primary_experts", 6),
            ("num_attention_heads", 28), ("num_key_value_heads", 4),
            ("rms_norm_eps", 1e-6), ("rope_theta", 1500000),
            ("sliding_window_size", 4096)):
        assert config[key] == value, key
    assert config["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936, "sliding_window_layout": [0, 1, 1, 1] * 13,
        "rope_layout": [0, 1, 1, 1] * 13}
    shapes = jax.eval_shape(
        lambda: smallthinker.init_params(cfg, jax.random.key(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(656.5e6, rel=1e-3)          # the issue's count

    def size(layer):
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes[layer]))

    assert size("layers_0") == pytest.approx(115.5e6, rel=1e-3)
    assert shapes["wte"]["embedding"].size == 37984 * 2560
    assert shapes["lm_head"]["kernel"].shape == (2560, 37984)
    # benchmark/smallthinker_flops.py against ISSUE 50's hand count: 2.12
    # GFLOP a token; projections 24 %, the full core 17 %, the three
    # windowed cores 22 %, the head 28 %, the held experts 10 %
    parts = smallthinker_flops.train_flops_per_token(
        **smallthinker_flops.config_dims(config))
    assert parts["total"] == pytest.approx(2.12e9, rel=5e-3)
    assert parts["gqa_proj"] == 6 * 4 * 2560 * 128 * 64
    assert parts["full_core"] == 3 * 28 * 256 * 16385
    assert parts["routed_held"] == 6 * 4 * 1.5 * 3 * 2560 * 768
    for part, share in (("gqa_proj", 0.24), ("full_core", 0.17),
                        ("swa_core", 0.22), ("head", 0.28),
                        ("routed_held", 0.10)):
        assert parts[part] / parts["total"] == pytest.approx(share, abs=0.01)
    assert smallthinker_flops.live_pairs(16384, 4096) == (
        16384 * 4096 - 4096 * 4095 / 2)
    assert smallthinker_flops.live_pairs(16384) == 16384 * 16385 / 2
    assert model.flops_per_token == parts["total"]
    for key, value in (("moe_primary_router_apply_softmax", False),
                       ("norm_topk_prob", False), ("num_hidden_layers", 5),
                       ("tie_word_embeddings", True),
                       ("rope_scaling", {"type": "yarn"}),
                       ("rope_layout", [0, 1, 2, 1])):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))


def test_the_tables_scale_is_the_configurations_where_it_gives_one() -> None:
    """``embed_std`` scales the table alone (the stream's scale beside
    what the layers add to it); without it the table is drawn at
    ``init_std`` like every matrix, bit for bit what it was."""
    base = smallthinker.init_params(CFG32, jax.random.key(3))
    wide = smallthinker.init_params(
        dataclasses.replace(CFG32, embed_std=5 * CFG.init_std),
        jax.random.key(3))
    np.testing.assert_allclose(wide["wte"]["embedding"],
                               5 * base["wte"]["embedding"], rtol=1e-6)
    same = smallthinker.init_params(
        dataclasses.replace(CFG32, embed_std=CFG.init_std), jax.random.key(3))
    for (path, a), b, c in zip(
            jax.tree_util.tree_flatten_with_path(base)[0],
            jax.tree_util.tree_leaves(wide), jax.tree_util.tree_leaves(same)):
        assert np.array_equal(a, c), path
        assert np.array_equal(a, b) == (path[0].key != "wte"), path
    assert float(jnp.std(base["wte"]["embedding"])) == pytest.approx(
        CFG.init_std, rel=0.02)


def test_the_warm_up_is_a_schedule_and_the_rule_keeps_the_loads() -> None:
    """Step ``c`` runs at ``peak·(c + 1)/warm`` and the count is a leaf
    of the optimizer state; matrices take weight decay, norms none; the
    bias rule's state is the loads it last saw — no moments — and
    ``routing_gauges`` reads the held share and the skew from it."""
    model = kit.tiny("smallthinker")
    params = smallthinker.init_params(model.cfg, jax.random.key(0))
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
            updates["layers_0"]["attn"]["q_proj"]["kernel"]))))
    ratios = [s / sizes[3] for s in sizes]
    assert ratios[0] == pytest.approx(0.25, rel=0.05)
    assert ratios[4] == pytest.approx(1.0, rel=0.02)
    states = [s for s in jax.tree_util.tree_leaves(
        opt, is_leaf=lambda x: isinstance(x, optim.BalanceBiasState))
        if isinstance(s, optim.BalanceBiasState)]
    assert len(states) == 1
    kept = jax.tree_util.tree_leaves(states[0].loads)
    assert len(kept) == 4 and all(np.array_equal(k, held_loads) for k in kept)
    skew, share, fits = optim.routing_gauges(opt, model.tx.held_experts)
    assert float(skew) == pytest.approx(4.0) and float(share) == 1.0
    assert float(fits) == 1.0       # 8 assignments: the buffer is all of them
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = model.tx.update(zero, model.tx.init(params), params)
    assert np.any(updates["lm_head"]["kernel"])
    assert np.any(updates["wte"]["embedding"])
    assert not np.any(updates["layers_0"]["norm_1"]["scale"])


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size; and the
    optimizer wrapper's routing gauges arrive on its sink without a wait
    (read at a later commit than the one that asked)."""
    with kit.ft_steps(kit.tiny("smallthinker")) as run:
        assert all(np.any(b) for b in kit.bias_leaves(run.params))
        seen = kit.routing_gauges(run)
        assert 0.0 < seen["moe_held_share"] < 1.0
        assert seen["moe_load_max_over_mean"] >= 1.0
        assert seen["moe_row_buffer_share"] == 1.0


def test_a_healed_groups_digest_equals_its_donors() -> None:
    """grad -> average_gradients -> step across two replica groups that
    see different batches; the second starts from other weights, behind,
    and gets the first's parameters, bias, loads and count only by the
    heal. At rest on one step the sha256 of parameters and optimizer
    state are equal."""
    with kit.two_groups_one_healed(kit.tiny("smallthinker")) as run:
        biases = [kit.bias_leaves(jax.device_get(g.state["params"]))
                  for g in run.groups]
        for a, b in zip(*biases):
            assert np.any(a) and np.array_equal(a, b)
        # the classic path reports the gauges too
        assert "moe_load_max_over_mean" in run.first.opt.metrics.snapshot()


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("smallthinker")
