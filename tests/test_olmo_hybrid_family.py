"""The Olmo Hybrid family (benchmark/families/olmo_hybrid.py) at the small
size of tests/test_olmo_hybrid.py, which holds the model to its
reference: the cell's own two comparisons and their verdicts, the
configuration the family builds, and the model through the one step
maker, the one optimizer and the fault-tolerant loop. A file of its own
so that the two run on two of tier-1's workers."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark import olmo_hybrid_flops
from benchmark.families import olmo_hybrid as family
from torchft_tpu.models import olmo_hybrid
from torchft_tpu.models.olmo_hybrid import FULL, LINEAR

# the model's tests are not about how many heads share a grid step
pytestmark = pytest.mark.usefixtures("one_head_a_step")
CFG = olmo_hybrid.OLMO_HYBRID_CONFIGS["olmo_hybrid_tiny"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b-vp8.json")) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.d_model, cfg.n_heads, cfg.key_dim, cfg.value_dim, cfg.d_ff,
            cfg.conv_kernel, cfg.head_dim) == (3840, 30, 96, 192, 11008, 4,
                                               128)
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL)
    assert cfg.allow_neg_eigval and cfg.rope_theta is None
    assert cfg.rms_eps == 1e-6 and cfg.remat
    assert (model.rows, model.vocab_draw) == (1, 12544)
    assert model.seq_len in (8192, 4096)        # the job's rule
    assert model.flops_per_token == olmo_hybrid_flops.train_flops_per_token(
        **olmo_hybrid_flops.config_dims(config))["total"]
    assert family.flops_per_token(model) == pytest.approx(
        5.5e9 if model.seq_len == 8192 else 5.41e9, rel=5e-3)
    for key, value in (("hidden_act", "gelu"), ("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("num_key_value_heads", 6),
                       ("linear_num_value_heads", 60),
                       ("num_hidden_layers", 5)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    # the tiny configuration is the same family at other numbers
    tiny = kit.tiny("olmo_hybrid")
    assert tiny.cfg == dataclasses.replace(CFG, remat=True, xent_chunks=2)
    assert (tiny.rows, tiny.seq_len, tiny.vocab_draw) == (2, 32, 256)


def test_the_optimizer_decays_matrices_alone_behind_a_warm_up() -> None:
    model = kit.tiny("olmo_hybrid")
    params = olmo_hybrid.init_params(model.cfg, jax.random.key(0))
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = model.tx.init(params)
    updates, state = model.tx.update(zero, state, params)
    # a zero gradient moves what the weight decay reaches, and nothing else
    gdn = updates["layers_0"]["gdn"]
    for moved in (updates["lm_head"]["kernel"], updates["wte"]["embedding"],
                  gdn["qkv_proj"]["kernel"], gdn["a_proj"]["kernel"],
                  updates["layers_3"]["attn"]["q_proj"]["kernel"],
                  updates["layers_0"]["mlp"]["down_proj"]["kernel"]):
        assert np.any(moved)
    for still in (gdn["conv"]["kernel"], gdn["A_log"], gdn["dt_bias"],
                  gdn["o_norm"]["scale"], updates["ln_f"]["scale"],
                  updates["layers_0"]["post_attn_norm"]["scale"],
                  updates["layers_3"]["attn"]["q_norm"]["scale"]):
        assert not np.any(still)
    # step c runs at peak x (c + 1) / warm-up: the first at 4e-4 / 2000
    w = params["lm_head"]["kernel"]
    np.testing.assert_allclose(
        updates["lm_head"]["kernel"], -(4e-4 / 2000) * 0.1 * w, rtol=1e-5)
    second, _ = model.tx.update(zero, state, params)
    np.testing.assert_allclose(
        second["lm_head"]["kernel"], -(2 * 4e-4 / 2000) * 0.1 * w, rtol=1e-5)


def test_the_check_seeds_norms_and_heads_on_both_sides() -> None:
    params = olmo_hybrid.init_params(CFG, jax.random.key(2))
    seeded = family.seed_check_weights(CFG, params, 2)
    assert seeded["wte"]["embedding"] is params["wte"]["embedding"]
    assert seeded["layers_0"]["gdn"]["qkv_proj"]["kernel"] is \
        params["layers_0"]["gdn"]["qkv_proj"]["kernel"]
    scales = [x for path, x in jax.tree_util.tree_leaves_with_path(seeded)
              if getattr(path[-1], "key", None) == "scale"]
    assert len(scales) == 4 * 2 + 3 + 2 + 1
    assert all(float(jnp.std(s)) > 0.03 for s in scales)
    ratio = (seeded["layers_3"]["attn"]["q_proj"]["kernel"]
             / params["layers_3"]["attn"]["q_proj"]["kernel"])
    by_head = np.asarray(ratio).reshape(CFG.d_model, CFG.n_heads, -1)
    # one factor a head, within the spread, not all alike
    np.testing.assert_allclose(by_head, by_head[:1, :, :1] * np.ones_like(
        by_head), rtol=1e-5)
    factors = by_head[0, :, 0]
    assert np.all((factors >= 0.5 - 1e-6) & (factors <= 2.0 + 1e-6))
    assert np.ptp(factors) > 0.2
    again = family.seed_check_weights(CFG, params, 2)
    np.testing.assert_array_equal(
        again["ln_f"]["scale"], seeded["ln_f"]["scale"])


def test_check_reference_is_both_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict, the gauge and the scan's, and is
    ``ok`` only where both are (the tiny configuration, bf16 compute; the
    whole model's limits are set for the cell's size)."""
    monkeypatch.setattr(family, "HIDDEN_REL_L2_RMS_MAX", 0.12)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_MAX", 0.6)
    monkeypatch.setattr(family, "REFERENCE_LOSS_ATOL", 5e-2)
    monkeypatch.setattr(family, "GDN_REL_L2_MAX",
                        {n: 0.03 for n in family.GDN_LEAVES})
    model, device = kit.tiny("olmo_hybrid"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"hidden_rel_l2_rms", "hidden_rel_l2_max", "abs_diff", "worst_at",
            "beta_over_1", "decay_range", "gdn_rel_l2"} <= set(seen)
    assert seen["tokens"] == family.REFERENCE_SEQUENCES * model.seq_len
    assert seen["gdn_over"] == []
    assert 0.2 < seen["beta_over_1"] < 0.8
    assert 0.0 <= seen["decay_range"][0] < seen["decay_range"][1] <= 1.0
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 600
    monkeypatch.setattr(family, "GDN_REL_L2_MAX",
                        dict(family.GDN_REL_L2_MAX, dk=0.0))
    again = family.check_reference(model, params, 5, device)
    assert not again["ok"] and again["gdn_over"] == ["dk"]
    assert again["hidden_rel_l2_rms"] == seen["hidden_rel_l2_rms"]


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size."""
    with kit.ft_steps(kit.tiny("olmo_hybrid")) as run:
        assert all(np.isfinite(run.losses)) and len(set(run.losses)) == 3


def test_the_grad_step_is_the_fused_steps_gradient() -> None:
    """The classic path's program (``make_grad_step``) and the fused
    step's see one loss on one batch."""
    model = kit.tiny("olmo_hybrid")
    device = jax.devices()[0]
    from benchmark.traffic_gen import BatchSource

    source = BatchSource(9, 0, 0, model.rows, model.seq_len, model.vocab_draw)
    state = family.init_state(model, 9, device)
    batch = source.device_batch(0, device)
    loss, grads = family.make_grad_step(model)(state["params"], *batch)
    want = jax.jit(lambda p: olmo_hybrid.loss_fn(model.cfg, p, *batch))(
        state["params"])
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    assert jax.tree_util.tree_structure(grads) == \
        jax.tree_util.tree_structure(state["params"])
    assert all(g.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("olmo_hybrid")
