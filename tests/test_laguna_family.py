"""The Laguna family (benchmark/families/laguna.py) at the small size of
tests/test_laguna.py, which holds the model to its reference: the cell's
own comparisons and their verdicts, faults that must fail them, the
configuration the family builds, and the model through the one step
maker, the one optimizer and the fault-tolerant loop, with the routing
gauges of the optimizer wrapper's sink. A file of its own so that the two
run on two of tier-1's workers."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark import laguna_flops
from benchmark.families import laguna as family
from benchmark.tests import laguna_faults
from torchft_tpu.models import laguna
from torchft_tpu.ops.attention import causal_attention

CFG = laguna.LAGUNA_CONFIGS["laguna_tiny"]
# three layers that hold every part once, in float32: full and dense,
# sliding and sparse, full and sparse (tests/test_laguna.py's ``CFG3``)
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32, windowed=(0, 1, 0),
                            heads=(4, 6, 4), sparse=(0, 1, 1))
BIAS = laguna.BALANCE_BIAS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 64
_batch = kit.batch


def _params(cfg, seed):
    """Seeded weights, the balance biases seeded as the cell's check
    seeds them."""
    return family.seed_balance_bias(
        laguna.init_params(cfg, jax.random.key(seed)), seed)


@pytest.mark.parametrize("call", family.FLASH_CALLS)
def test_the_cells_own_check_of_a_flash_call(call) -> None:
    """``flash_comparison`` + ``judge_flash`` at the small size, at the
    call's own head count on 2 key/value heads: the sound call passes leaf
    by leaf (bf16 operands: the one rounding of each result), every leaf
    has a limit that judges it alone, and it is the WORST head that is
    judged."""
    assert family.flash_shape(CFG, call) == {
        "swa": {"heads": 6, "window": 20},
        "full": {"heads": 4, "window": None}}[call]
    (q, k, v), do = family.flash_inputs(CFG, call, np.uint32(3), 2, S)
    assert q.shape == do.shape == (2, S, family.flash_shape(CFG, call)[
        "heads"], 32) and k.shape == v.shape == (2, S, 2, 32)
    sound = family.flash_errors(CFG, call, 3, 2, S)
    assert set(sound) == set(family.FLASH_LEAVES)
    verdict = family.judge_flash(call, sound)
    assert verdict["ok"] and verdict[f"{call}_over"] == []
    for name in family.FLASH_LEAVES:
        over = dict(sound, **{
            name: 1.5 * family.FLASH_REL_L2_MAX[call][name]})
        assert family.judge_flash(call, over)[f"{call}_over"] == [name]

    def one_head_off(q, k, v, window=None):
        # the last query head alone looks one key less far (or, without
        # a window, at its own key/value head's neighbour)
        o = causal_attention(q, k, v, window=window)
        other = (causal_attention(q[:, :, -1:], k[:, :, -1:], v[:, :, -1:],
                                  window=window - 1) if window else
                 causal_attention(q[:, :, -1:], k[:, :, :1], v[:, :, :1]))
        return o.at[:, :, -1].set(other[:, :, 0])

    cfg32 = dataclasses.replace(CFG, dtype=jnp.float32)
    off = family.flash_errors(cfg32, call, 3, 2, S, one_head_off)
    assert not family.judge_flash(call, off)["ok"]


def test_check_reference_is_all_three_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict, both flash calls' and the gauges,
    and is ``ok`` only where all are (the tiny configuration, bf16
    compute; the whole model's limits are set for the cell's size)."""
    monkeypatch.setattr(family, "HIDDEN_REL_L2_RMS_MAX", 0.03)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_MAX", 0.08)
    monkeypatch.setattr(family, "TOP_K_DISAGREEMENT_MAX", 0.1)
    monkeypatch.setattr(family, "REFERENCE_LOSS_ATOL", 2e-2)
    model, device = kit.tiny("laguna", layers=3), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    # the second verdict judges the first's readings again: nothing is
    # compiled twice
    kept = {}

    def once(name):
        real = getattr(family, name)

        def cached(cfg, *args):
            key = (name, args[0] if name == "flash_errors" else None)
            if key not in kept:
                kept[key] = real(cfg, *args)
            return kept[key]
        return cached

    for name in ("per_token_errors", "flash_errors"):
        monkeypatch.setattr(family, name, once(name))
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"rms", "worst", "top8_disagreement", "held_share",
            "load_max_over_mean", "gate_range", "yarn_lo", "yarn_hi",
            "yarn_moved", "swa_rel_l2", "full_rel_l2"} <= set(seen)
    assert seen["swa_over"] == seen["full_over"] == []
    assert len(seen["held_share"]) == 2           # the sparse layers
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 600
    monkeypatch.setattr(family, "FLASH_REL_L2_MAX", dict(
        family.FLASH_REL_L2_MAX,
        full=dict(family.FLASH_REL_L2_MAX["full"], dk=0.0)))
    again = family.check_reference(model, params, 5, device)
    assert len(kept) == 3
    assert not again["ok"]
    assert again["full_over"] == ["dk"] and again["swa_over"] == []
    assert again["rms"] == seen["rms"]


def test_the_cells_own_comparison_at_the_small_size() -> None:
    params, (tokens, targets) = _params(CFG32, 4), _batch(4)
    compare = jax.jit(family.comparison(CFG32))
    verdict = family.judge(jax.device_get(
        compare(params, params, tokens, targets)))
    assert verdict["ok"] and verdict["top8_disagreement"] == 0.0
    assert verdict["tokens"] == 128 and verdict["worst"] < 1e-4
    assert len(verdict["held_share"]) == 2
    assert all(0 < s < 1 for s in verdict["held_share"])
    unbiased = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x) if p[-1].key == BIAS else x, params)
    assert not family.judge(jax.device_get(
        compare(unbiased, params, tokens, targets)))["ok"]
    assert all(np.any(x) for p, x in
               jax.tree_util.tree_flatten_with_path(params)[0]
               if p[-1].key == BIAS)


@pytest.mark.parametrize("fault", ["window_513", "groups_swapped",
                                   "yarn_ramp_dropped", "weight_on_input"])
def test_a_fault_fails_the_comparison(fault) -> None:
    """Faults of ``benchmark/tests/laguna_faults.py`` — a window one key
    too long, the query heads grouped as the other kind of layer groups
    them, plain frequencies where YaRN's belong, the router's weight on
    the expert's input — against the sound reference under the cell's own
    limits, in float32 so that nothing but the fault is seen; one that
    strikes a flash call fails that call's own comparison too."""
    params, (tokens, targets) = _params(CFG32, 6), _batch(6)
    patches, system_cfg, attn_fn, flash_fns = laguna_faults.fault(
        fault, CFG32)
    with laguna_faults.patched(patches):
        seen = family.per_token_errors(
            CFG32, params, params, tokens, targets, system_cfg=system_cfg,
            attn_fn=attn_fn)
    assert not family.judge(seen)["ok"]
    assert fault in laguna_faults.FAULTS and len(laguna_faults.FAULTS) == 15
    for call, fn in flash_fns.items():
        alone = family.flash_errors(CFG32, call, 6, 2, S, fn)
        assert not family.judge_flash(call, alone)["ok"], call


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs2-ep8.json")) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_experts_held) == (
        256, 0, 32)
    # the model's own layers 0 - 4: the dense layer and one whole period
    assert cfg.windowed == (0, 1, 1, 1, 0) and cfg.sparse == (0, 1, 1, 1, 1)
    assert cfg.heads == (48, 64, 64, 64, 48)
    published = config["published"]
    assert config["layer_types"] == published["layer_types"][:5]
    assert config["mlp_layer_types"] == published["mlp_layer_types"][:5]
    assert list(cfg.heads) == published["num_attention_heads_per_layer"][:5]
    assert published["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention"] * 10
    assert published["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (40, 256, 100352)
    assert (cfg.n_layers, cfg.init_depth) == (5, 40)
    assert (cfg.d_model, cfg.n_kv_heads, cfg.head_dim, cfg.window,
            cfg.d_ff, cfg.d_expert, cfg.d_shared) == (
                2048, 8, 128, 512, 8192, 512, 512)
    assert (cfg.top_k, cfg.routed_scale, cfg.rms_eps, cfg.vocab_size) == (
        8, 2.5, 1e-6, 12544)
    assert cfg.rope_swa == laguna.Rotation(theta=1e4)
    assert cfg.rope_full == laguna.LagunaConfig().rope_full
    assert (cfg.rope_full.theta, cfg.rope_full.partial,
            cfg.rope_full.yarn_factor, cfg.rope_full.original_positions,
            cfg.rope_full.beta_fast, cfg.rope_full.beta_slow) == (
                5e5, 0.5, 64, 4096, 64, 1)
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    assert (model.rows, model.seq_len, cfg.remat, cfg.xent_chunks) == (
        4, 8192, True, 4)
    assert model.tx.held_experts == (0, 32)
    assert family.yarn_gauges(cfg) == {
        "yarn_lo": 5, "yarn_hi": 16, "yarn_moved": 26}
    # every number of the catalog row's config stands under its own key
    for key, value in (
            ("hidden_size", 2048), ("intermediate_size", 8192),
            ("num_attention_heads", 48), ("num_key_value_heads", 8),
            ("head_dim", 128), ("max_position_embeddings", 262144),
            ("rms_norm_eps", 1e-6), ("num_experts_per_tok", 8),
            ("moe_intermediate_size", 512),
            ("shared_expert_intermediate_size", 512),
            ("sliding_window", 512), ("partial_rotary_factor", 0.5),
            ("moe_routed_scaling_factor", 2.5)):
        assert config[key] == value, key
    for name in ("published", "share", "deployment", "sizing", "assumed",
                 "departures"):
        assert config[name], name
    for reading in ("gating", "router_score", "qk_norm"):
        assert "other reading" in config["assumed"][reading], reading
    shapes = jax.eval_shape(
        lambda: laguna.init_params(cfg, jax.random.key(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(691.6e6, rel=1e-4)          # the issue's count

    def size(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    assert size(shapes["layers_0"]) == pytest.approx(79.8e6, rel=1e-3)
    assert size(shapes["layers_1"]) == pytest.approx(142.2e6, rel=1e-3)
    assert size(shapes["layers_4"]) == pytest.approx(133.8e6, rel=1e-3)
    assert size(shapes["layers_0"]["attn"]) == pytest.approx(29.46e6, rel=1e-3)
    assert size(shapes["layers_1"]["attn"]) == pytest.approx(37.88e6, rel=1e-3)
    assert shapes["layers_1"]["moe"]["up_proj"]["kernel"].shape == (
        32, 2048, 512)
    assert shapes["wte"]["embedding"].shape == (12544, 2048)
    assert shapes["lm_head"]["kernel"].shape == (2048, 12544)
    # benchmark/laguna_flops.py against ISSUE 59's hand count: 2.4 GFLOP a
    # token; projections 43 %, the two full cores 25 %, the three windowed
    # cores 6 %, the dense MLP 13 %, the head 6 %, the sparse sublayers 7 %
    parts = laguna_flops.train_flops_per_token(
        **laguna_flops.config_dims(config))
    assert parts["total"] == pytest.approx(2.405e9, rel=1e-3)
    assert parts["full_core"] == 2 * 3 * 2 * 48 * 256 * 8193 / 2
    assert parts["routed_held"] == 6 * 4 * 1.0 * 3 * 2048 * 512
    sparse = parts["router"] + parts["routed_held"] + parts["shared"]
    for part, share in ((parts["gqa_proj"], 0.43), (parts["full_core"], 0.25),
                        (parts["swa_core"], 0.06), (parts["dense_mlp"], 0.13),
                        (parts["head"], 0.06), (sparse, 0.07)):
        assert part / parts["total"] == pytest.approx(share, abs=0.006)
    assert laguna_flops.live_pairs(8192, 512) == 8192 * 512 - 512 * 511 / 2
    assert laguna_flops.live_pairs(8192) == 8192 * 8193 / 2
    assert model.flops_per_token == parts["total"]
    # k and v cross HBM at their own 8 heads, q and o at 64
    assert laguna_flops.flash_bytes_per_call(
        "flash_fwd", 4, 64, 8, 8192, 128, 128) == (
            4 * 8192 * (2 * 64 * 128 * 2 + 2 * 8 * 128 * 2 + 64 * 4))
    for key, value in (("gating", False), ("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("moe_apply_router_weight_on_input", True),
                       ("num_hidden_layers", 4),
                       ("layer_types", ["linear_attention"] * 5),
                       ("mlp_layer_types", ["dense"] * 4),
                       ("num_attention_heads_per_layer", [48] * 4)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    ropes = json.loads(json.dumps(config["rope_parameters"]))
    ropes["full_attention"]["rope_type"] = "llama3"
    with pytest.raises(ValueError, match="llama3"):
        family.build(dict(config, rope_parameters=ropes))


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size; and the
    optimizer wrapper's routing gauges arrive on its sink without a wait
    (read at a later commit than the one that asked)."""
    with kit.ft_steps(kit.tiny("laguna")) as run:
        biases = kit.bias_leaves(run.params)
        assert len(biases) == 1 and all(np.any(b) for b in biases)
        seen = kit.routing_gauges(run)
        assert 0.0 < seen["moe_held_share"] < 1.0
        assert seen["moe_load_max_over_mean"] >= 1.0
        assert seen["moe_row_buffer_share"] == 1.0


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("laguna")
