"""Nemotron-H's family (``benchmark/families/nemotron_h.py``) at the small
size: the faults of ``benchmark/tests/nemotron_faults.py`` each move what
the cell's check compares, the stand-ins are sound without their fault,
the cell's own check of the scan — forward and six gradients against the
recurrence — holds the sound kernels and fails the two lower-precision
stand-ins; then the family through the one step maker, the one optimizer
and the fault-tolerant loop. A file of its own: pytest-xdist hands whole
files to its workers, and two files of two minutes end sooner than one
of four."""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark.families import nemotron_h as family
from benchmark.tests import nemotron_faults
from benchmark.tests.nemotron_faults import FAULTS, with_leaf
from test_nemotron_h import (
    BIAS,
    CFG,
    CFG32,
    ROOT,
    _batch,
    _params,
    _reference,
)
from torchft_tpu.models import nemotron_h


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_comparison(monkeypatch, fault) -> None:
    """Each listed fault moves what the cell's check compares by far more
    than f32 rounding: the test of the reference's teeth at this size.
    (The sound system agrees to 5e-5: the first test of this file.)"""
    params = _params(CFG32, 5)
    tokens, targets = _batch(5)
    # the sound side: the plain reference, which no patch reaches, once
    want = kit.sound(("nemotron_h", "reference", 5), lambda: _reference(
        CFG32)(params, tokens, targets))
    patches, weights, cfg, attn_fn = nemotron_faults.fault(
        fault, CFG32, params)
    for patch in patches:
        monkeypatch.setattr(*patch)
    got = nemotron_h.loss_terms(cfg or CFG32, weights or params, tokens,
                                targets, attn_fn)
    moved = max(
        abs(float(got["loss"]) - float(want["loss"])),
        float(jnp.max(jnp.abs(got["hidden"] - want["hidden"]))),
    )
    # rounding to 8 (bf16) or 4 (e4m3) bits in one place of a tiny model
    floor = 5e-4 if fault in nemotron_faults.ROUNDING else 1e-2
    assert moved > floor, (fault, moved)


def test_the_faults_stand_ins_are_sound_without_their_fault(monkeypatch):
    """The loop that stands in for the kernels under the two rounding
    faults is the scan when it rounds nothing, and the regrouped call is
    the scan when the groups are one: so what the faults read is the
    fault."""
    params, (tokens, targets) = _params(CFG32, 5), _batch(5)
    want = nemotron_h.loss_terms(CFG32, params, tokens, targets)["hidden"]
    monkeypatch.setattr(nemotron_h, "ssd_scan",
                        nemotron_faults.position_by_position)
    got = nemotron_h.loss_terms(CFG32, params, tokens, targets)["hidden"]
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize(
    "variant", ["sound", "scan_state_bf16", "scan_decays_bf16"])
def test_the_cells_own_check_of_the_scan(variant) -> None:
    """``family.scan_comparison``: ``ssd_scan`` and its six gradients
    against the reference's recurrence and ``jax.vjp`` of it, leaf by
    leaf. In f32 on the CPU the sound scan agrees to rounding in every
    leaf, and the two lower-precision stand-ins move ``dΔ`` and ``dA`` —
    the leaves the decays reach — a hundred times that."""
    args, dy = family.scan_inputs(CFG32, 11, 96)
    assert args[0].shape == (1, 96, CFG.ssm_heads, CFG.ssm_head_dim)
    seen = jax.jit(family.scan_comparison(
        nemotron_faults.SCAN_VARIANTS[variant]))(args, dy)
    assert set(seen) == set(family.SCAN_LEAVES)
    worst = max(float(seen[name]) for name in ("ddt", "dA"))
    if variant == "sound":
        assert all(float(v) < 1e-4 for v in seen.values()), seen
    else:
        assert worst > 1e-3, (variant, seen)


def test_the_scans_limits_judge_leaf_by_leaf() -> None:
    sound = {name: 0.5 * limit
             for name, limit in family.SCAN_REL_L2_MAX.items()}
    assert family.judge_scan(sound)["ok"]
    for name in family.SCAN_LEAVES:
        over = dict(sound, **{name: 1.5 * family.SCAN_REL_L2_MAX[name]})
        assert not family.judge_scan(over)["ok"], name


def test_check_reference_is_both_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict and the scan's, and is ``ok`` only
    where both are (the tiny configuration, bf16 compute)."""
    monkeypatch.setattr(family, "SCAN_SEQ", 128)
    model, device = kit.tiny("nemotron_h"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"hidden_rel_l2_rms", "top6_disagreement", "held_share",
            "scan_rel_l2"} <= set(seen)
    assert set(seen["scan_rel_l2"]) == set(family.SCAN_LEAVES)
    assert seen["scan_over"] == []
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 560
    monkeypatch.setattr(family, "SCAN_REL_L2_MAX",
                        dict(family.SCAN_REL_L2_MAX, ddt=0.0))
    again = family.check_reference(model, params, 5, device)
    assert not again["ok"] and again["scan_over"] == ["ddt"]
    assert again["hidden_rel_l2_rms"] == seen["hidden_rel_l2_rms"]


# -- through the step maker and the fault-tolerant loop -----------------------


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    from benchmark import ssd_flops

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_experts_held) == (
        128, 0, 8)
    assert (cfg.pattern, cfg.n_layers, cfg.init_depth) == ("MEMEM*EME", 9, 52)
    assert (cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_kernel) == (2688, 64, 64, 8, 128, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.d_expert, cfg.d_shared, cfg.top_k, cfg.routed_scale) == (
        1856, 3712, 6, 2.5)
    shapes = jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.key(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(667.0e6, rel=1e-4)          # the issue's count

    def size(layer):
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes[layer]))

    assert size("layers_0") == pytest.approx(38.74e6, rel=1e-3)
    assert size("layers_1") == pytest.approx(100.13e6, rel=1e-3)
    assert size("layers_5") == pytest.approx(23.40e6, rel=1e-3)
    # benchmark/ssd_flops.py against a hand count (ISSUE 33: 715 MFLOP a
    # token forward, x 3; the four Mamba mixers 45 %)
    parts = ssd_flops.train_flops_per_token(**ssd_flops.config_dims(config))
    assert parts["total"] == pytest.approx(3 * 715e6, rel=2e-3)
    assert (parts["ssm_proj"] + parts["ssm_scan"]) / parts["total"] == \
        pytest.approx(0.45, abs=0.01)
    assert parts["ssm_proj"] == 6 * 4 * 2688 * (10304 + 4096)
    assert parts["gqa_core"] == 3 * 32 * 256 * 8193
    assert parts["routed_held"] == pytest.approx(
        6 * 4 * (6 * 8 / 128) * 2 * 2688 * 1856)
    dims = dict(heads=64, head_dim=64, groups=8, state=128)
    assert ssd_flops.ssd_flops_per_token("ssd_fwd", chunk=128, **dims) == \
        129 * (1024 + 4096) + 4 * 64 * 64 * 128
    assert ssd_flops.ssd_flops_per_token("ssd_bwd", chunk=128, **dims) == \
        2 * 129 * (1024 + 4096) + 8 * 64 * 64 * 128
    # bytes: x and y (and dy, dx) 8 KB, B + C 4 KB, delta 256 B a token
    assert ssd_flops.ssd_bytes_per_token("ssd_fwd", **dims) == 20736
    assert ssd_flops.ssd_bytes_per_token("ssd_bwd", **dims) == 33280
    assert model.flops_per_token == parts["total"]
    for key, value in (("n_group", 8), ("n_shared_experts", 2),
                       ("mamba_proj_bias", True), ("sliding_window", 4096),
                       ("hybrid_override_pattern", "MEMEM-EME"),
                       ("mlp_hidden_act", "silu"), ("num_hidden_layers", 8),
                       ("use_conv_bias", False)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))


def test_the_warm_up_is_a_schedule_in_the_optimizer_state() -> None:
    """Step ``c`` runs at ``peak·(c + 1)/warm``; the count is a leaf of
    the optimizer state (checkpointed, healed, hashed); vectors and the
    balance bias take no weight decay."""
    model = kit.tiny("nemotron_h")
    params = nemotron_h.init_params(model.cfg, jax.random.key(0))
    opt = model.tx.init(params)
    counts = [x for x in jax.tree_util.tree_leaves(opt)
              if x.shape == () and jnp.issubdtype(x.dtype, jnp.integer)]
    assert counts and all(int(c) == 0 for c in counts)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    sizes = []
    for _ in range(6):
        updates, opt = model.tx.update(grads, opt, params)
        # Adam on a constant gradient moves a leaf by the rate itself;
        # D takes no decay
        sizes.append(float(jnp.max(jnp.abs(
            updates["layers_0"]["mamba"]["D"]))))
    counts = [int(x) for x in jax.tree_util.tree_leaves(opt)
              if x.shape == () and jnp.issubdtype(x.dtype, jnp.integer)]
    assert 6 in counts
    # warm-up over 4 steps to 1e-3 (tiny-nemotron.json), then flat
    ratios = [s / sizes[3] for s in sizes]
    assert ratios[0] == pytest.approx(0.25, rel=0.05)
    assert ratios[1] == pytest.approx(0.5, rel=0.05)
    assert ratios[4] == pytest.approx(1.0, rel=0.02)
    # with a zero gradient only the decay moves a leaf: matrices only
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = model.tx.update(zero, model.tx.init(params), params)
    mamba = updates["layers_0"]["mamba"]
    assert np.any(mamba["in_proj"]["kernel"])
    for vector in ("A_log", "D", "dt_bias"):
        assert not np.any(mamba[vector]), vector
    assert not np.any(updates["layers_0"]["norm"]["scale"])


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size, and the bias
    rule on the fused path: behind the commit gate the bias moves exactly
    as in the plain step."""
    with kit.ft_steps(kit.tiny("nemotron_h")) as run:
        assert all(np.any(b) for b in kit.bias_leaves(run.params))


def test_two_groups_on_other_batches_hold_one_state_and_a_healed_one_gets_it():
    """grad -> average_gradients -> step across two replica groups that
    see different batches: the loads ride the gradient buckets, so both
    apply the same bias update behind the commit gate, and the warm-up's
    count steps with the commits. The second group starts from other
    weights, behind (its count at 0), and gets the first's parameters,
    bias and count only by the heal. At rest on one step the sha256 of
    parameters and optimizer state are equal."""
    with kit.two_groups_one_healed(kit.tiny("nemotron_h")) as run:
        def counts(group):
            return sorted({int(x) for x in jax.tree_util.tree_leaves(
                jax.device_get(group.state["opt"]))
                if x.shape == () and np.issubdtype(x.dtype, np.integer)})

        # the schedule's count came over with the heal: the joiner made
        # fewer steps than it counts
        assert counts(run.first) == counts(run.second)
        assert max(counts(run.second)) > sum(
            1 for r in run.second.records if r["committed"])
        biases = [kit.bias_leaves(jax.device_get(g.state["params"]))
                  for g in run.groups]
        for a, b in zip(*biases):
            assert np.any(a) and np.array_equal(a, b)
            # whole multiples of the rate: only the sign rule touched it
            assert np.allclose(a / 0.01, np.round(a / 0.01), atol=1e-4)


def test_the_cells_own_comparison_at_the_small_size() -> None:
    """``families/nemotron_h.py``'s ``per_token_errors`` + ``judge``:
    sound weights pass the structure of the check (f32 compute, no
    flips), a dropped expert and an ignored bias are seen by it."""
    params, (tokens, targets) = _params(CFG32, 4), _batch(4)
    seen = family.per_token_errors(CFG32, params, params, tokens, targets)
    verdict = family.judge(seen)
    assert verdict["ok"] and verdict["top6_disagreement"] == 0.0
    assert verdict["tokens"] == 128
    assert verdict["hidden_rel_l2_max"] < 1e-4
    assert len(verdict["rows_held"]) == len(verdict["held_share"]) == 2
    assert all(0 < s < 1 for s in verdict["held_share"])
    unbiased = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x) if p[-1].key == BIAS else x, params)
    assert not family.judge(family.per_token_errors(
        CFG32, unbiased, params, tokens, targets))["ok"]
    scaled = dataclasses.replace(CFG32, routed_scale=1.0)
    assert not family.judge(family.per_token_errors(
        CFG32, params, params, tokens, targets, system_cfg=scaled))["ok"]
    no_d = with_leaf(params, "layers_0", ("mamba", "D"), jnp.zeros_like)
    assert not family.judge(family.per_token_errors(
        CFG32, no_d, params, tokens, targets))["ok"]
    # the check's own seeding of the bias: other leaves untouched
    seeded = family.seed_balance_bias(params, 3)
    assert seeded["wte"]["embedding"] is params["wte"]["embedding"]
    assert all(np.any(b) for b in kit.bias_leaves(seeded))
    again = family.seed_balance_bias(params, 3)
    for a, b in zip(kit.bias_leaves(seeded), kit.bias_leaves(again)):
        assert np.array_equal(a, b)


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("nemotron_h")
