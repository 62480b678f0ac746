"""The Keye family (benchmark/families/keye.py) at the small size of
tests/test_keye.py, which holds the model to its reference: the
configuration the family builds, the share (the shares' routed parts add
up to the uncut layer), the cell's own two comparisons, their verdicts
and the faults they must catch, and the model through the one step
maker, the one optimizer and the fault-tolerant loop. A file of its own
so that the two run on two of tier-1's workers."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark import keye_flops
from benchmark.families import keye as family
from benchmark.reference import keye_f32
from benchmark.tests import keye_faults
from torchft_tpu.models import keye
from torchft_tpu.ops import dsa

CFG = keye.KEYE_CONFIGS["keye_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
BIAS = keye.BALANCE_BIAS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 64
_batch = kit.batch


@functools.lru_cache(maxsize=None)
def _params(seed=4):
    return family.seed_check_params(kit.seeded_params(keye, CFG32, seed), seed)


# -- the configuration ---------------------------------------------------------


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b-ep8.json")) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_experts_held) == (
        128, 0, 16)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.rope_theta, cfg.mrope_section) == (
        2048, 32, 4, 128, 1e7, (16, 24, 24))
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_rope_dim,
            cfg.index_topk, cfg.index_kl_weight) == (16, 64, 32, 2048, 1.0)
    assert (cfg.d_expert, cfg.top_k, cfg.rms_eps, cfg.vocab_size,
            cfg.init_depth) == (768, 8, 1e-6, 19072, 48)
    assert 4 <= cfg.n_layers <= 6          # the floor and the driver's count
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "num_local_experts", "vocab_size"]
    assert (model.seq_len, model.rows, cfg.remat) == (16384, 2, True)
    assert model.tx.held_experts == (0, 16)
    # every number of the catalog row, under its key, but the four reduced
    for key, value in (
            ("head_dim", 128), ("hidden_size", 2048),
            ("intermediate_size", 6144), ("max_position_embeddings", 262144),
            ("max_window_layers", 48), ("moe_intermediate_size", 768),
            ("num_attention_heads", 32), ("num_experts_per_tok", 8),
            ("num_key_value_heads", 4), ("rms_norm_eps", 1e-6),
            ("rope_theta", 10000000), ("decoder_sparse_step", 1)):
        assert config[key] == value, key
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128,
        "num_local_experts": 128, "vocab_size": 151936}
    for key in ("share", "deployment", "departures", "assumed", "sizing"):
        assert config[key], key
    shapes = jax.eval_shape(lambda: keye.init_params(cfg, jax.random.key(0)))

    def size(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    # ISSUE 66's hand count: attention 18.87 M, indexer 2.26 M, an expert
    # 4.719 M, a layer 96.9 M, table + head 78.12 M
    layer = shapes["layers_0"]
    assert size(layer["attn"]) == pytest.approx(18.87e6, rel=1e-3)
    assert size(layer["indexer"]) == pytest.approx(2.26e6, rel=2e-3)
    assert size(layer["moe"]) == pytest.approx(75.50e6 + 0.262e6, rel=1e-3)
    assert size(layer) == pytest.approx(96.9e6, rel=1e-3)
    assert size(shapes["wte"]) + size(shapes["lm_head"]) == 2 * 19072 * 2048
    assert size(shapes) == cfg.n_layers * size(layer) + 2 * 19072 * 2048 + 2048
    # benchmark/keye_flops.py against the issue's arithmetic, a layer
    # FORWARD and a token: projections 37.7 MFLOP, indexer projections 4.5,
    # scores over every causal pair 16.8, the core over the chosen keys
    # 31.5, the second q.k for p-bar 15.7, router 0.5, held experts 9.4
    one = keye_flops.train_flops_per_token(**dict(
        keye_flops.config_dims(config), n_layers=1, vocab=0))
    hd = 16 * 64
    assert one["gqa_proj"] / 3 == pytest.approx(37.7e6, rel=2e-3)
    assert one["index_proj"] / 2 == pytest.approx(4.5e6, rel=1e-2)
    assert (one["index_scores"]
            - 4.0 * hd * keye_flops.chosen_pairs(16384, 2048) / 16384
            ) == pytest.approx(16.8e6, rel=2e-3)
    assert one["dsa_core"] / 3 == pytest.approx(31.5e6, rel=2e-3)
    assert one["index_target"] == pytest.approx(15.7e6, rel=3e-3)
    assert one["router"] / 3 == pytest.approx(0.5e6, rel=5e-2)
    assert one["routed_held"] / 3 == pytest.approx(9.4e6, rel=5e-3)
    assert keye_flops.chosen_pairs(16384, 2048) == (
        16384 * 2048 - 2048 * 2047 / 2)
    assert (keye_flops.chosen_pairs(16384, 2048)
            / keye_flops.causal_pairs(16384)) == pytest.approx(0.234, abs=1e-3)
    assert keye_flops.chosen_pairs(64, 2048) == keye_flops.causal_pairs(64)
    dims = dict(keye_flops.config_dims(config), batch=2)
    # a kernel is read against the PUBLISHED work: a quarter of what the
    # core's kernels compute
    assert keye_flops.kernel_flops("dsa_fwd", **dims) == (
        2 * keye_flops.chosen_pairs(16384, 2048) * 32 * 2 * 256)
    assert keye_flops.kernel_flops("dsa_select", **dims) == (
        2 * keye_flops.causal_pairs(16384) * 2 * hd)
    assert all(keye_flops.kernel_bytes(k, **dims) > 0
               for k in keye_flops.KERNELS)
    assert model.flops_per_token == keye_flops.train_flops_per_token(
        **keye_flops.config_dims(config))["total"]
    for key, value in (("attention_bias", True), ("norm_topk_prob", False),
                       ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
                       ("tie_word_embeddings", True),
                       ("num_local_experts", 128)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    with pytest.raises(ValueError, match="indexer_num_kv_heads"):
        family.build(dict(config, sa_config=dict(
            config["sa_config"], indexer_num_kv_heads=2)))


def test_the_tiny_file_is_the_tiny_configuration() -> None:
    assert dataclasses.replace(kit.tiny("keye").cfg, remat=False,
                               xent_chunks=0) == dataclasses.replace(
        CFG, embed_std=CFG.init_std)


# -- the share -----------------------------------------------------------------


def test_the_shares_routed_parts_add_up_to_the_uncut_layer() -> None:
    """Two shares of four experts each: what every chip computes alike
    (attention over the chosen keys, the indexer, the router over all
    eight) counted once, their routed parts add up to the layer with all
    eight held — the model's, which is the uncut reference's."""
    whole = dataclasses.replace(CFG32, n_layers=1, n_experts_held=8)
    params = keye.init_params(whole, jax.random.key(2))
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: 0.1 * jax.random.normal(jax.random.key(9), x.shape)
        if p[-1].key == BIAS else x, params)
    tokens, targets = _batch(2)
    positions = jnp.broadcast_to(jnp.arange(S), (3, S))
    tables = (keye.mrope_tables(whole, positions),
              keye._index_tables(whole, positions))
    x = keye.embed(whole, params, tokens)

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def layer_of(cfg, first):
        layer = dict(params["layers_0"])
        moe = dict(layer["moe"])
        for name in ("gate_proj", "up_proj", "down_proj"):
            moe[name] = {"kernel": moe[name]["kernel"][
                first:first + cfg.n_experts_held]}
        layer["moe"] = moe
        return keye._layer(cfg, layer, x, tables, dsa)[0]

    h = jax.jit(lambda x: keye._attn_mixer(
        whole, params["layers_0"], x, tables, dsa)[0])(x)
    uncut = layer_of(whole, 0)
    parts = [layer_of(dataclasses.replace(whole, first_expert=first,
                                          n_experts_held=4), first) - h
             for first in (0, 4)]
    np.testing.assert_allclose(h + sum(parts), uncut, atol=2e-6)
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)
    want = jax.jit(lambda p: keye_f32.terms(
        p, tokens, targets, **family.reference_dims(whole)))(params)
    got = jax.jit(lambda p: keye.loss_terms(whole, p, tokens, targets))(
        params)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=2e-5)


# -- the cell's comparisons ------------------------------------------------------


def _tight(monkeypatch, **limits):
    """The limits a float32 system at the small size is held to: its
    readings are rounding (1e-6), so every fault stands far off."""
    for name, value in dict(
            HIDDEN_REL_L2_RMS_MAX=1e-4, HIDDEN_REL_L2_MAX=1e-3,
            TOP_K_DISAGREEMENT_MAX=0.0, KEY_SET_OVERLAP_MIN=0.9999,
            REFERENCE_LOSS_ATOL=1e-5, INDEX_KL_ATOL=1e-5, **limits).items():
        monkeypatch.setattr(family, name, value)


@functools.lru_cache(maxsize=None)
def _sound_kernels():
    return jax.device_get(jax.jit(family.kernel_comparison(CFG32))(
        family.kernel_inputs(CFG32, 3, 2, S)))


def test_the_cells_own_comparison_at_the_small_size(monkeypatch) -> None:
    _tight(monkeypatch)
    tokens, targets = _batch(4)
    seen = family.per_token_errors(CFG32, _params(), _params(), tokens,
                                   targets)
    verdict = family.judge(seen)
    assert verdict["ok"], verdict
    assert verdict["top8_disagreement"] == 0.0
    assert verdict["key_set_overlap"] == 1.0
    assert (verdict["bad_sets"], verdict["late_keys"]) == (0, 0)
    assert verdict["dsa_selected_share"] == pytest.approx(702 / 2080, rel=1e-3)
    assert len(verdict["held_share"]) == CFG.n_layers
    # the seeded biases are what both sides read
    unbiased = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x)
        if p[-1].key in (BIAS, "bias") else x, _params())
    assert not family.judge(family.per_token_errors(
        CFG32, unbiased, _params(), tokens, targets))["ok"]
    for name, value in (("bad_sets", 1), ("late_keys", 1),
                        ("overlap", 0.5), ("index_kl", 9.0)):
        assert not family.judge(dict(seen, **{name: np.float32(value)}))["ok"]


def test_the_kernels_own_comparison_and_its_verdict() -> None:
    """``kernel_comparison`` + ``judge_kernels``: the sound calls pass
    leaf by leaf (float32 at the small size: rounding), every leaf has a
    limit that judges it alone, so do the overlap and the sets' sizes."""
    sound = _sound_kernels()
    verdict = family.judge_kernels(sound)
    assert verdict["ok"] and verdict["kernels_over"] == [], verdict
    assert set(family.KERNEL_LEAVES) <= set(sound)
    for name in family.KERNEL_LEAVES:
        over = dict(sound, **{name: 1.5 * family.KERNEL_REL_L2_MAX[name]})
        assert family.judge_kernels(over)["kernels_over"] == [name]
    assert family.judge_kernels(dict(sound, overlap=0.9))[
        "kernels_over"] == ["overlap"]
    assert family.judge_kernels(dict(sound, bad_sets=1))[
        "kernels_over"] == ["bad_sets"]


def test_check_reference_is_both_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict (the worst of its sequences') and
    the kernels', and is ``ok`` only where both are (the tiny
    configuration, bf16 compute; the limits are set for the cell's
    size)."""
    monkeypatch.setattr(family, "HIDDEN_REL_L2_RMS_MAX", 0.03)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_MAX", 0.08)
    monkeypatch.setattr(family, "TOP_K_DISAGREEMENT_MAX", 0.1)
    monkeypatch.setattr(family, "KEY_SET_OVERLAP_MIN", 0.9)
    monkeypatch.setattr(family, "REFERENCE_LOSS_ATOL", 2e-2)
    monkeypatch.setattr(family, "INDEX_KL_ATOL", 2e-2)
    model, device = kit.tiny("keye"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"hidden_rel_l2_rms", "top8_disagreement", "key_set_overlap",
            "bad_sets", "dsa_selected_share", "kernel_rel_l2",
            "kernel_overlap"} <= set(seen)
    assert seen["kernels_over"] == []
    monkeypatch.setattr(family, "KERNEL_REL_L2_MAX",
                        dict(family.KERNEL_REL_L2_MAX, dk=0.0))
    again = family.check_reference(model, params, 5, device)
    assert not again["ok"] and again["kernels_over"] == ["dk"]
    assert again["hidden_rel_l2_rms"] == seen["hidden_rel_l2_rms"]


@pytest.mark.parametrize("fault", [
    "topk_2047", "no_relu", "w_unscaled", "pbar_one_head", "window_4096",
    "bf16_scores", "streams_swapped"])
def test_a_fault_fails_the_cells_comparison(fault, monkeypatch) -> None:
    """Each fault of ``benchmark/tests/keye_faults.py`` (the stand-ins
    the chip is handed) fails at least one limit of the comparison that
    is to show it, at the small size, where the sound system passes all
    of them (on streams that differ: an image span's)."""
    _tight(monkeypatch)
    which, patch, kw = keye_faults.faults(CFG32)[fault]
    tokens, targets = _batch(4)
    positions = keye_faults.image_positions(S)
    sound = kit.sound(("keye", "whole"), lambda: keye_faults.run_whole(
        family, CFG32, _params(), tokens, targets, positions))
    assert sound["ok"], sound
    with patch():
        if which == "whole":
            seen = keye_faults.run_whole(family, CFG32, _params(), tokens,
                                         targets, positions, **kw)
        else:
            seen = family.judge_kernels(jax.device_get(jax.jit(
                family.kernel_comparison(CFG32))(
                    family.kernel_inputs(CFG32, 3, 2, S))))
    assert not seen["ok"], seen
    if which == "kernels":
        assert family.judge_kernels(_sound_kernels())["ok"]


# -- the family, the optimizer and the fault-tolerant loop --------------------


def test_the_warm_up_is_a_schedule_and_only_matrices_decay() -> None:
    model = kit.tiny("keye")
    params = keye.init_params(model.cfg, jax.random.key(0))
    opt = model.tx.init(params)
    counts = [x for x in jax.tree_util.tree_leaves(opt)
              if x.shape == () and jnp.issubdtype(x.dtype, jnp.integer)]
    assert counts and all(int(c) == 0 for c in counts)
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = model.tx.update(zero, opt, params)
    assert np.any(updates["layers_0"]["indexer"]["q_proj"]["kernel"])
    for name in ("scale", "bias"):
        assert not np.any(updates["layers_0"]["indexer"]["k_norm"][name])
    assert not np.any(updates["layers_0"]["attn"]["q_norm"]["scale"])


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size; the loop
    reports the cross entropy plus the indexer's term, and the optimizer
    wrapper's routing gauges arrive on its sink."""
    with kit.ft_steps(kit.tiny("keye")) as run:
        assert all(np.any(b) for b in kit.bias_leaves(run.params))
        seen = kit.routing_gauges(run)
        assert 0.0 < seen["moe_held_share"] < 1.0
        assert seen["moe_load_max_over_mean"] >= 1.0
        # the indexer trains: its leaves moved, by L_I alone
        first = family.init_state(kit.tiny("keye"), 7, run.device)["params"]
        assert not np.array_equal(
            first["layers_0"]["indexer"]["q_proj"]["kernel"],
            run.params["layers_0"]["indexer"]["q_proj"]["kernel"])


def test_a_healed_groups_digest_equals_its_donors() -> None:
    with kit.two_groups_one_healed(kit.tiny("keye")) as run:
        biases = [kit.bias_leaves(jax.device_get(g.state["params"]))
                  for g in run.groups]
        for a, b in zip(*biases):
            assert np.any(a) and np.array_equal(a, b)


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("keye")
