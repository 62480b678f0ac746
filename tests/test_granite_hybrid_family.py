"""The Granite 4.0-H family (benchmark/families/granite_hybrid.py) at the
small size of tests/test_granite_hybrid.py, which holds the model to its
reference: the cell's own two comparisons and their verdicts, every model
fault of ``benchmark/tests/granite_faults.py`` under the first, the configuration the
family builds, and the model through the one step maker, the one optimizer
and the fault-tolerant loop. A file of its own so that the two run on two
of tier-1's workers."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark import granite_flops
from benchmark.families import granite_hybrid as family
from benchmark.reference import granite_hybrid_f32
from benchmark.tests import granite_faults as faults
from benchmark.tests.lfm2_faults import patched
from torchft_tpu.models import granite_hybrid
from torchft_tpu.models.granite_hybrid import ATTENTION, MAMBA

CFG = granite_hybrid.GRANITE_HYBRID_CONFIGS["granite_hybrid_tiny"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = os.path.join(ROOT, "benchmark", "configs",
                         "granite-4.0-h-micro-vp8.json")
# the whole model's limits are set for the cell's size; at the tiny one
# (bf16 compute, 64 channels a token, ONE layer of each kind) the sound
# system reads rms 0.0073 / largest 0.019 / loss 1.2e-4 and the mildest
# faults rms 0.018 / 0.039 (fp8 in the MLPs) and 0.043 / 0.107 (the softmax
# at 1/4)
TINY_LIMITS = dict(HIDDEN_REL_L2_RMS_MAX=0.012, HIDDEN_REL_L2_MAX=0.03,
                   REFERENCE_LOSS_ATOL=5e-3)


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    with open(PUBLISHED) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_kernel, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff) == (2048, 64, 64, 1, 128, 4, 32, 8, 64,
                                        8192)
    assert cfg.layer_types == (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
                12.0, 0.22, 0.015625, 8.0)
    assert cfg.rms_eps == 1e-5 and cfg.remat and cfg.xent_chunks == 8
    assert (model.rows, model.seq_len, model.vocab_draw) == (2, 8192, 12544)
    assert model.flops_per_token == granite_flops.train_flops_per_token(
        **granite_flops.config_dims(config))["total"]
    assert family.flops_per_token(model) == pytest.approx(4.818e9, rel=1e-3)
    for key, value in (("hidden_act", "gelu"), ("attention_bias", True),
                       ("tie_word_embeddings", False),
                       ("num_local_experts", 8), ("mamba_proj_bias", True),
                       ("position_embedding_type", "rope"),
                       ("shared_intermediate_size", 4096),
                       ("num_hidden_layers", 9)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    # the tiny configuration is the same family at other numbers
    tiny = kit.fresh_tiny("granite_hybrid", layers=4)
    assert tiny.cfg == dataclasses.replace(CFG, remat=True, xent_chunks=2)
    assert kit.tiny("granite_hybrid").cfg.layer_types == (MAMBA, ATTENTION)
    assert (tiny.rows, tiny.seq_len, tiny.vocab_draw) == (2, 32, 256)


def test_the_configuration_is_the_catalogs_row_cut_in_depth_and_vocabulary():
    """Every published number under its own key; ``reduced`` names exactly
    the three keys that differ; the parameter count is the tree's and the
    issue's hand count."""
    with open(PUBLISHED) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    published = dict(config, **config["published"])
    assert published["num_hidden_layers"] == 40 == len(
        published["layer_types"])
    assert [i for i, k in enumerate(published["layer_types"])
            if k == ATTENTION] == [5, 15, 25, 35]
    assert config["layer_types"] == published["layer_types"][:10]
    assert config["vocab_size"] * 8 == published["vocab_size"] == 100352
    assert config["vocab_size"] % 128 == 0
    assert (config["hidden_size"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_d_state"],
            config["mamba_n_groups"], config["mamba_d_conv"],
            config["mamba_chunk_size"], config["mamba_expand"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["shared_intermediate_size"]) == (
                2048, 64, 64, 128, 1, 4, 256, 2, 32, 8, 8192)
    for key in ("deployment", "sizing", "assumed", "departures",
                "vocab_share"):
        assert config[key], key
    params = jax.eval_shape(lambda: granite_hybrid.init_params(
        family.build(config).cfg, jax.random.key(0)))
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    mamba = 2048 * 8512 + 4096 * 2048 + 5 * 4352 + 4096 + 3 * 64
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    assert count == (9 * mamba + attn + 10 * (mlp + 2 * 2048)
                     + 12544 * 2048 + 2048) == 772160448
    assert str(count) in config["sizing"].replace(" ", "")


def test_the_optimizer_decays_matrices_alone_behind_a_warm_up() -> None:
    model = kit.tiny("granite_hybrid")
    params = granite_hybrid.init_params(model.cfg, jax.random.key(0))
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = model.tx.init(params)
    updates, state = model.tx.update(zero, state, params)
    # a zero gradient moves what the weight decay reaches, and nothing else
    mamba = updates["layers_0"]["mamba"]
    for moved in (updates["wte"]["embedding"], mamba["in_proj"]["kernel"],
                  mamba["out_proj"]["kernel"],
                  updates["layers_1"]["attn"]["k_proj"]["kernel"],
                  updates["layers_0"]["mlp"]["down_proj"]["kernel"]):
        assert np.any(moved)
    for still in (mamba["conv"]["kernel"], mamba["conv"]["bias"],
                  mamba["A_log"], mamba["D"], mamba["dt_bias"],
                  mamba["norm"]["scale"], updates["ln_f"]["scale"],
                  updates["layers_0"]["norm"]["scale"],
                  updates["layers_1"]["post_norm"]["scale"]):
        assert not np.any(still)
    # step c runs at peak x (c + 1) / warm-up: the first at 4e-4 / 2000
    w = params["wte"]["embedding"]
    np.testing.assert_allclose(
        updates["wte"]["embedding"], -(4e-4 / 2000) * 0.1 * w, rtol=1e-5)


def test_the_check_seeds_what_one_or_zero_would_hide() -> None:
    params = granite_hybrid.init_params(CFG, jax.random.key(2))
    seeded = family.seed_check_weights(params, 2)
    assert seeded["wte"]["embedding"] is params["wte"]["embedding"]
    mamba, was = seeded["layers_0"]["mamba"], params["layers_0"]["mamba"]
    assert mamba["in_proj"]["kernel"] is was["in_proj"]["kernel"]
    assert mamba["conv"]["bias"] is was["conv"]["bias"]
    attn, attn_was = seeded["layers_1"]["attn"], params["layers_1"]["attn"]
    for name in ("q_proj", "k_proj"):
        np.testing.assert_allclose(
            attn[name]["kernel"],
            family.CHECK_QK_GAIN * attn_was[name]["kernel"], rtol=1e-6)
    assert attn["v_proj"]["kernel"] is attn_was["v_proj"]["kernel"]
    scales = [x for path, x in jax.tree_util.tree_leaves_with_path(seeded)
              if getattr(path[-1], "key", None) == "scale"]
    assert len(scales) == 4 * 2 + 3 + 1
    assert all(float(jnp.std(s)) > 0.03 for s in scales)
    for name in ("D", "A_log", "dt_bias"):
        assert float(jnp.std(mamba[name] - was[name])) > 0.03, name
    assert float(jnp.min(jnp.abs(was["conv"]["bias"]))) >= 0.0
    assert float(jnp.std(was["conv"]["bias"])) > 0.2      # hides nothing
    again = family.seed_check_weights(params, 2)
    np.testing.assert_array_equal(again["ln_f"]["scale"],
                                  seeded["ln_f"]["scale"])


def test_check_reference_is_both_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict and the scan's, and is ``ok`` only
    where both are (the tiny configuration, bf16 compute)."""
    for name, value in TINY_LIMITS.items():
        monkeypatch.setattr(family, name, value)
    monkeypatch.setattr(family, "SCAN_REL_L2_MAX",
                        {n: 0.03 for n in family.SCAN_LEAVES})
    model, device = kit.tiny("granite_hybrid"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"hidden_rel_l2_rms", "hidden_rel_l2_max", "abs_diff", "worst_at",
            "scan_rel_l2"} <= set(seen)
    assert seen["tokens"] == family.REFERENCE_SEQUENCES * model.seq_len
    assert seen["scan_over"] == []
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 600
    # one leaf over its limit fails the scan's verdict, and with it the cell
    monkeypatch.setattr(family, "SCAN_REL_L2_MAX",
                        dict(family.SCAN_REL_L2_MAX, dB=0.0))
    again = family.judge_scan(seen["scan_rel_l2"])
    assert not again["ok"] and again["scan_over"] == ["dB"]


# one layer of each kind: what a fault case costs is compiling its system
FAULT_CFG = dataclasses.replace(CFG, layer_types=(MAMBA, ATTENTION))


def _sound_reference():
    """The reference's terms on the check's seeded weights and batch, once
    a process: no fault's patch reaches it."""
    def evaluate():
        params = family.seed_check_weights(
            granite_hybrid.init_params(FAULT_CFG, jax.random.key(3)), 3)
        tok, tgt = kit.batch(3, rows=2, seq=40, vocab=CFG.vocab_size)
        want = jax.jit(lambda p: granite_hybrid_f32.terms(
            p, tok, tgt, row_block=8,
            **family.reference_dims(FAULT_CFG)))(params)
        return tok, tgt, want
    return kit.sound(("granite_hybrid", 3), evaluate)


def _judged(name, monkeypatch):
    for limit, value in TINY_LIMITS.items():
        monkeypatch.setattr(family, limit, value)
    tok, tgt, want = _sound_reference()
    params = granite_hybrid.init_params(FAULT_CFG, jax.random.key(3))
    patches, weights, system_cfg, attn_fn = (
        ((), None, None, None) if name == "sound"
        else faults.fault(name, FAULT_CFG, params))
    system = family.seed_check_weights(
        params if weights is None else weights, 3)
    with patched(patches):
        got = jax.jit(lambda p: granite_hybrid.loss_terms(
            system_cfg or FAULT_CFG, p, tok, tgt, attn_fn))(system)
    h = np.asarray(got["hidden"], np.float32).reshape(-1, CFG.d_model)
    h_ref = np.asarray(want["hidden"]).reshape(-1, CFG.d_model)
    return family.judge({
        "error": np.linalg.norm(h - h_ref, axis=-1)
        / np.linalg.norm(h_ref, axis=-1),
        "loss": got["loss"], "reference_loss": want["loss"]})


@pytest.mark.parametrize("name", ("sound",) + faults.FAULTS)
def test_a_fault_fails_the_cells_comparison_and_the_sound_system_passes(
        name, monkeypatch) -> None:
    """Each stand-in of ``granite_faults.py`` in the bf16 system against
    the sound reference under the cell's comparison (limits at this
    size's readings): every fault is over a limit but the scan's state in
    bf16, which no limit on the hidden state holds at any size — the
    scan's own comparison does (``tests/test_granite_hybrid.py``)."""
    verdict = _judged(name, monkeypatch)
    if name in ("sound", "scan_state_bf16"):
        assert verdict["ok"], verdict
    else:
        assert not verdict["ok"], verdict
    if name == "logits_scaling_left_out":       # the loss alone sees it
        assert verdict["hidden_rel_l2_rms"] < TINY_LIMITS[
            "HIDDEN_REL_L2_RMS_MAX"]


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size; and the classic
    path's program (``make_grad_step``) sees the loss the fused step saw
    on the first batch, with a finite f32 gradient a leaf."""
    model = kit.tiny("granite_hybrid")
    with kit.ft_steps(model) as run:
        assert all(np.isfinite(run.losses)) and len(set(run.losses)) == 3
        params = family.init_state(model, 7, run.device)["params"]
        loss, grads = kit.grad_step(model)(
            params, *run.source.device_batch(0, run.device))
    assert float(loss) == pytest.approx(run.losses[0], abs=1e-5)
    assert jax.tree_util.tree_structure(grads) == \
        jax.tree_util.tree_structure(params)
    assert all(g.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("granite_hybrid")
