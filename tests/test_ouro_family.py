"""The Ouro family (benchmark/families/ouro.py) at the small size of
tests/test_ouro.py, which holds the model to its reference: the
configuration the family builds, the optimizer's mask and the statistics'
rule, the cell's own comparison and its verdict, every fault of
``benchmark/tests/ouro_faults.py`` under it, and the model through the one
step maker, the one optimizer and the fault-tolerant loop with the exit
distribution's three gauges. A file of its own so that the two run on two
of tier-1's workers; the cell file's cases that need no run ride along."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark import ouro_flops
from benchmark.families import ouro as family
from benchmark.reference import ouro_f32
from benchmark.tests import ouro_faults as faults
from benchmark.tests.lfm2_faults import patched
from benchmark.tests.test_ouro_cell import (  # noqa: F401
    test_flash_calls_counts_a_call_a_layer_a_pass,
    test_the_cells_shapes_come_from_the_configurations_own_keys,
    test_the_manifest_holds_the_cell_where_it_reads_something,
    test_the_notes_sum_the_loops_scopes_over_whole_step_programs,
)
from torchft_tpu import optim
from torchft_tpu.models import ouro

CFG = ouro.OURO_CONFIGS["ouro_tiny"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b-l8.json")
# the limits are set for the cell's size; at the tiny one (bf16 compute, 64
# channels a token, two sequences of 40) the sound system reads loss 7e-4 -
# 1.2e-3, a pass's 1.8e-3 - 5.5e-3, a token's 0.014 - 0.015 rms / 0.05 -
# 0.06 at the worst, p 0.008 - 0.009, and the mildest faults: the four
# losses unweighted 0.018 in the loss, the fp8 stream 0.06 rms / 0.25 at the
# worst token / p 0.025, the gate on the un-normed stream p 0.30
TINY_LIMITS = {"loss_abs_diff": 0.005, "pass_loss_abs_diff": 0.02,
               "nll_abs_rms": 0.03, "nll_abs_max": 0.15, "p_abs_max": 0.018}


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    with open(PUBLISHED) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.vocab_size, cfg.d_model, cfg.n_layers, cfg.n_heads,
            cfg.head_dim, cfg.d_ff, cfg.ut_steps) == (49152, 2048, 8, 16, 128,
                                                      5632, 4)
    assert (cfg.rope_theta, cfg.rms_eps, cfg.exit_entropy_weight,
            cfg.init_std) == (1e6, 1e-6, 0.05, 0.02)
    assert cfg.remat and cfg.xent_chunks == 8
    assert (model.rows, model.seq_len, model.vocab_draw) == (1, 8192, 49152)
    assert model.flops_per_token == ouro_flops.train_flops_per_token(
        **ouro_flops.config_dims(config))["total"]
    assert family.flops_per_token(model) == pytest.approx(15.50e9, rel=1e-3)
    assert model.tx.publish_step_stats is ouro.publish_exit_gauges
    for key, value in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("use_sliding_window", True),
                       ("num_key_value_heads", 4), ("num_hidden_layers", 9),
                       ("layer_types", ["sliding_attention"] * 8)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    # the tiny configuration is the same family at other numbers
    tiny = kit.tiny("ouro")
    assert tiny.cfg == dataclasses.replace(CFG, remat=True, xent_chunks=2)
    assert (tiny.rows, tiny.seq_len, tiny.vocab_draw) == (2, 32, 256)


def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone() -> None:
    """Every published number under its own key; ``reduced`` names exactly
    the two keys that differ; four passes; the whole vocabulary; the
    parameter count is the tree's."""
    with open(PUBLISHED) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    published = dict(config, **config["published"])
    assert published["num_hidden_layers"] == 48 == len(
        published["layer_types"]) == config["max_window_layers"]
    assert set(published["layer_types"]) == {"full_attention"}
    assert config["layer_types"] == published["layer_types"][:8]
    row = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5632, "max_position_embeddings": 65536,
           "model_type": "ouro", "num_attention_heads": 16,
           "num_key_value_heads": 16, "rms_norm_eps": 1e-6,
           "rope_scaling": None, "rope_theta": 1000000,
           "sliding_window": None, "tie_word_embeddings": False,
           "total_ut_steps": 4, "early_exit_threshold": 1,
           "use_sliding_window": False, "vocab_size": 49152}
    assert {k: config[k] for k in row} == row
    for key in ("deployment", "sizing", "assumed", "departures"):
        assert config[key], key
    for key in ("norms", "final_norm", "gate", "attention_bias",
                "exit_entropy_weight", "seq_len", "optimizer",
                "initializer_range", "check_weights"):
        assert key in config["assumed"], key
    params = jax.eval_shape(lambda: ouro.init_params(
        family.build(config).cfg, jax.random.key(0)))
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert count == (8 * layer + 2 * 49152 * 2048 + 2048 + 2049 + 3
                     ) == 612_438_020
    assert str(count) in config["sizing"].replace(" ", "")


def test_the_optimizer_decays_matrices_alone_and_keeps_the_statistics():
    model = kit.tiny("ouro")
    params = ouro.init_params(model.cfg, jax.random.key(0))
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    grads[ouro.EXIT_STATS] = jnp.asarray([2.5, 1.25, 6.0])
    state = model.tx.init(params)
    assert not np.any(optim.step_stats(state))
    updates, state = model.tx.update(grads, state, params)
    # a zero gradient moves what the weight decay reaches, and nothing else
    for moved in (updates["wte"]["embedding"], updates["lm_head"]["kernel"],
                  updates["exit_gate"]["kernel"],
                  updates["layers_0"]["attn"]["k_proj"]["kernel"],
                  updates["layers_1"]["mlp"]["down_proj"]["kernel"]):
        assert np.any(moved)
    for still in (updates["exit_gate"]["bias"], updates["ln_f"]["scale"],
                  updates[ouro.EXIT_STATS],
                  *(updates["layers_1"][n]["scale"] for n in (
                      "attn_norm", "attn_out_norm", "mlp_norm",
                      "mlp_out_norm"))):
        assert not np.any(still)
    # what arrived at the statistics' leaf is kept, not applied
    np.testing.assert_array_equal(optim.step_stats(state), [2.5, 1.25, 6.0])
    # step c runs at peak x (c + 1) / warm-up: the first at 3e-4 / 2000
    w = params["wte"]["embedding"]
    np.testing.assert_allclose(
        updates["wte"]["embedding"], -(3e-4 / 2000) * 0.1 * w, rtol=1e-5)


def test_the_check_seeds_what_one_or_zero_would_hide() -> None:
    """The norms' weights and the gate's bias, and nothing else: the
    matrices, ``W_q``, ``W_k`` and ``w_g`` among them, are the same arrays
    (families/ouro.py says what a sharpened softmax did on the chip)."""
    params = ouro.init_params(CFG, jax.random.key(2))
    seeded = family.seed_check_weights(params, 2)
    for path in (("wte", "embedding"), ("lm_head", "kernel"),
                 ("exit_gate", "kernel"),
                 ("layers_1", "attn", "q_proj", "kernel"),
                 ("layers_1", "attn", "k_proj", "kernel"),
                 ("layers_0", "mlp", "up_proj", "kernel")):
        was, now = params, seeded
        for key in path:
            was, now = was[key], now[key]
        assert now is was, path
    assert -2.0 < float(seeded["exit_gate"]["bias"][0]) < 0.0
    scales = [x for path, x in jax.tree_util.tree_leaves_with_path(seeded)
              if getattr(path[-1], "key", None) == "scale"]
    assert len(scales) == 4 * 2 + 1
    assert all(float(jnp.std(s)) > 0.03 for s in scales)
    again = family.seed_check_weights(params, 2)
    np.testing.assert_array_equal(again["ln_f"]["scale"],
                                  seeded["ln_f"]["scale"])


def test_check_reference_is_the_comparison(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries every number beside its limit and is ``ok`` only where all
    hold (the tiny configuration, bf16 compute)."""
    monkeypatch.setattr(family, "LIMITS", TINY_LIMITS)
    model, device = kit.tiny("ouro"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"] and seen["over"] == [], seen
    assert set(TINY_LIMITS) <= set(seen) and seen["limits"] == TINY_LIMITS
    assert (seen["tokens"], seen["passes"]) == (
        family.REFERENCE_SEQUENCES * model.seq_len, 4)
    assert sum(seen["mass"]) == pytest.approx(1.0, abs=2e-3)
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 600
    # one number over its limit fails the cell
    monkeypatch.setattr(family, "LIMITS", dict(TINY_LIMITS, p_abs_max=0.0))
    again = family.check_reference(model, params, 5, device)
    assert not again["ok"] and again["over"] == ["p_abs_max"]


def _sound_reference():
    """The reference's terms on the check's seeded weights and batch, once
    a process: no fault's patch reaches it."""
    def evaluate():
        params = family.seed_check_weights(
            ouro.init_params(CFG, jax.random.key(3)), 3)
        tok, tgt = kit.batch(3, rows=2, seq=40, vocab=CFG.vocab_size)
        want = jax.jit(lambda p: ouro_f32.terms(
            p, tok, tgt, row_block=8, **family.reference_dims(CFG)))(params)
        return params, tok, tgt, jax.device_get(want)
    return kit.sound(("ouro", 3), evaluate)


@pytest.mark.parametrize("name", ("sound",) + faults.FAULTS)
def test_a_fault_fails_the_cells_comparison_and_the_sound_system_passes(
        name, monkeypatch) -> None:
    """Each stand-in of ``ouro_faults.py`` patched into the bf16 SYSTEM
    against the sound reference under the cell's comparison (limits at this
    size's readings): every fault is over at least one limit."""
    monkeypatch.setattr(family, "LIMITS", TINY_LIMITS)
    params, tok, tgt, want = _sound_reference()
    patches, system_cfg = ((), None) if name == "sound" else faults.fault(
        name, CFG)
    with patched(patches):
        got = jax.device_get(jax.jit(lambda p: ouro.loss_terms(
            system_cfg or CFG, p, tok, tgt))(params))
    verdict = family.judge({
        "loss": float(got["loss"]), "nll": got["nll"], "p": got["p"],
        "reference_loss": float(want["loss"]), "reference_nll": want["nll"],
        "reference_p": want["p"]})
    assert verdict["ok"] == (name == "sound"), verdict
    if name in ("entropy_term_dropped", "entropy_sign_turned",
                "losses_averaged_unweighted"):   # the loss alone sees them
        assert verdict["over"] == ["loss_abs_diff"]


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size; the classic
    path's program (``make_grad_step``) sees the loss the fused step saw on
    the first batch, with a finite f32 gradient a leaf, the statistics at
    their leaf; and the three gauges reach the optimizer's sink."""
    model = kit.tiny("ouro")
    with kit.ft_steps(model) as run:
        assert all(np.isfinite(run.losses)) and len(set(run.losses)) == 3
        params = family.init_state(model, 7, run.device)["params"]
        loss, grads = kit.grad_step(model)(
            params, *run.source.device_batch(0, run.device))
        seen = kit.routing_gauges(run, key=ouro.EXIT_GAUGES[0])
        # the leaf that carries the statistics never moves
        assert not np.any(run.group.state["params"][ouro.EXIT_STATS])
    assert float(loss) == pytest.approx(run.losses[0], abs=1e-5)
    assert jax.tree_util.tree_structure(grads) == \
        jax.tree_util.tree_structure(params)
    assert all(g.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))
    mean_pass, entropy, last = (seen[k] for k in ouro.EXIT_GAUGES)
    np.testing.assert_allclose(grads[ouro.EXIT_STATS][:2],
                               [mean_pass, entropy], rtol=0.2)
    assert 1.0 < mean_pass < 4.0 and 0.0 < entropy < np.log(4) + 1e-6
    # the full-depth model's plain loss, near the objective at this size
    assert last == pytest.approx(run.losses[-1], abs=0.5)


def test_two_groups_heal_the_gate_and_the_gauges_state() -> None:
    """The classic path (grad -> averaged gradients -> update behind the
    commit gate) and the heal carry the gate's leaves and the statistics'
    state like any other: both groups at rest on one digest, the three
    gauges finite on both sinks."""
    with kit.two_groups_one_healed(kit.tiny("ouro")) as run:
        for group in run.groups:
            seen = group.opt.metrics.snapshot()
            assert all(np.isfinite(seen[k]) for k in ouro.EXIT_GAUGES), seen
            kept = optim.step_stats(group.state["opt"])
            assert kept.shape == (3,) and bool(jnp.all(jnp.isfinite(kept)))
        np.testing.assert_array_equal(*(
            np.asarray(g.state["params"]["exit_gate"]["kernel"])
            for g in run.groups))


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("ouro")
