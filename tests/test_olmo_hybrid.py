"""``models/olmo_hybrid.py`` on the CPU at the tiny size, seeded weights:
the system against ``benchmark/reference/olmo_hybrid_f32.py`` — loss,
final hidden state and every gradient leaf, in f32 and in the cell's
precision —, both kinds of layer and the other readings' seams, every fault of
``benchmark/tests/olmo_hybrid_faults.py`` under the cell's own
comparison, the vocabulary's share, and the cell's configuration against
the published sizes."""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import olmo_hybrid as family
from benchmark.reference import olmo_hybrid_f32
from benchmark.tests import olmo_hybrid_faults as faults
from benchmark.tests.lfm2_faults import patched
from torchft_tpu.models import olmo_hybrid
from torchft_tpu.models.olmo_hybrid import (
    FULL, LINEAR, OLMO_HYBRID_CONFIGS, OlmoHybridConfig, init_params,
    loss_fn, loss_terms,
)

# the model's tests are not about how many heads share a grid step
pytestmark = pytest.mark.usefixtures("one_head_a_step")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16 = OLMO_HYBRID_CONFIGS["olmo_hybrid_tiny"]
# float32 compute: the comparison is of the mathematics, not of bf16
TINY = dataclasses.replace(BF16, dtype=jnp.float32)
SEQ = 40            # two and a half chunks of 16: a ragged end


def dims(cfg):
    return family.reference_dims(cfg)


def batch(cfg, seed=1, rows=2, seq=SEQ):
    tokens = jax.random.randint(jax.random.key(seed), (rows, seq), 0,
                                cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def seeded(cfg, seed=0):
    """Initial weights with the norms' weights and the attention heads'
    scales drawn as the cell's check seeds them: a norm's weight left out
    would otherwise show nowhere."""
    return family.seed_check_weights(
        cfg, init_params(cfg, jax.random.key(seed)), seed)


_CACHE = {}


def both_sides():
    """System (f32 and bf16 compute) and reference on the same weights
    and batch, once a module: terms and gradient trees."""
    if not _CACHE:
        params, (tok, tgt) = seeded(TINY), batch(TINY)

        def side(terms_fn):
            # jitted: eager, the interpreter's kernels run operation by
            # operation
            @jax.jit
            def run(p):
                terms, pull = jax.vjp(terms_fn, p)
                return terms, pull({
                    "loss": jnp.ones(()),
                    "hidden": jnp.zeros_like(terms["hidden"])})[0]
            return run(params)

        got, grads = side(lambda p: loss_terms(TINY, p, tok, tgt))
        low, grads_low = side(lambda p: loss_terms(BF16, p, tok, tgt))
        want, grads_ref = side(lambda p: olmo_hybrid_f32.terms(
            p, tok, tgt, **dims(TINY)))
        _CACHE.update(params=params, got=got, want=want, low=low, grads=grads,
                      grads_low=grads_low, grads_ref=grads_ref)
    return _CACHE


def leaf_paths(cfg=TINY):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_leaves_with_path(shapes)]


def leaf(tree, path):
    return {jax.tree_util.keystr(p): g
            for p, g in jax.tree_util.tree_leaves_with_path(tree)}[path]


def test_the_tiny_cut_holds_both_kinds_of_layer():
    assert TINY.layer_types == (LINEAR, LINEAR, LINEAR, FULL)
    assert OlmoHybridConfig().layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 8
    assert OlmoHybridConfig().head_dim == 128
    # the widths differ, are no lane tile, and no four divides the heads
    assert TINY.key_dim != TINY.value_dim and TINY.n_heads % 4
    params = init_params(TINY, jax.random.key(0))
    assert set(params["layers_0"]) == {"gdn", "mlp", "post_attn_norm",
                                       "post_mlp_norm"}
    assert set(params["layers_3"]) == {"attn", "mlp", "post_attn_norm",
                                       "post_mlp_norm"}
    assert params["layers_3"]["attn"]["q_norm"]["scale"].shape == (48,)
    with pytest.raises(AssertionError):
        dataclasses.replace(TINY, layer_types=("sliding_attention",))


def test_loss_and_hidden_state_equal_the_references():
    both = both_sides()
    assert float(both["got"]["loss"]) == pytest.approx(
        float(both["want"]["loss"]), abs=2e-5)
    np.testing.assert_allclose(both["got"]["hidden"], both["want"]["hidden"],
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("path", leaf_paths())
def test_every_gradient_leaf_equals_the_references(path):
    both = both_sides()
    got, want = leaf(both["grads"], path), leaf(both["grads_ref"], path)
    assert float(jnp.max(jnp.abs(want))) > 0, "a leaf no gradient reaches"
    np.testing.assert_allclose(
        got, want, atol=2e-4 * float(jnp.max(jnp.abs(want))), rtol=2e-4)


@pytest.mark.parametrize("path", leaf_paths())
def test_every_gradient_leaf_in_the_cells_precision(path):
    """bf16 compute with the f32 islands the model file names: every
    leaf's gradient is f32 and lies along the reference's (48 channels at
    an init of 0.125 make bf16 loud: 5 - 27 % off, cosines 0.969 -
    0.999)."""
    both = both_sides()
    got, want = leaf(both["grads_low"], path), leaf(both["grads_ref"], path)
    assert got.dtype == want.dtype == jnp.float32
    norms = float(jnp.linalg.norm(got) * jnp.linalg.norm(want))
    assert float(jnp.sum(got * want)) / norms > 0.95
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 0.4


def test_the_cells_precision_follows_the_reference():
    both = both_sides()
    low, want = both["low"], both["want"]
    assert low["hidden"].dtype == jnp.bfloat16
    err = jnp.linalg.norm(low["hidden"].astype(jnp.float32) - want["hidden"],
                          axis=-1) / jnp.linalg.norm(want["hidden"], axis=-1)
    assert float(jnp.sqrt(jnp.mean(err ** 2))) < 0.06
    assert float(low["loss"]) == pytest.approx(float(want["loss"]), abs=3e-2)


def test_remat_and_chunked_cross_entropy_change_nothing():
    both, (tok, tgt) = both_sides(), batch(TINY)
    for other in (dict(remat=True), dict(xent_chunks=4)):
        cfg = dataclasses.replace(TINY, **other)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tok, tgt)))(both["params"])
        assert float(loss) == pytest.approx(float(both["got"]["loss"]),
                                            abs=2e-5)
        for a, b in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(both["grads"])):
            np.testing.assert_allclose(
                a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))), rtol=1e-4)


def _pre_norm_linear_layer(cfg, layer, x):
    """The block's other reading for the linear layers, ``x +
    mixer(RMSNorm(x))`` with the same weight, in ``_gdn_sublayer``'s
    place."""
    n = olmo_hybrid.rms_norm(x, layer["post_attn_norm"]["scale"], cfg.rms_eps)
    return x + olmo_hybrid._gdn_mixer(cfg, layer["gdn"], n)


@pytest.mark.parametrize("patches,other", [
    (((olmo_hybrid, "_gdn_sublayer", _pre_norm_linear_layer),), {}),
    ((), dict(rope_theta=10000.0)),
], ids=["pre_norm_linear_layers", "rotated_full_attention"])
def test_the_other_readings_are_other_models(patches, other):
    """The two things the config does not settle (``assumed``): the place
    of the linear layers' norm (the seam ``_gdn_sublayer``) and the
    rotation (the key ``rope_theta``, which the reference reads the same
    way). Each is another model than the one taken: the reference tells
    them apart."""
    both, (tok, tgt) = both_sides(), batch(TINY)
    with patched(patches):
        got = jax.jit(lambda p: loss_terms(
            dataclasses.replace(TINY, **other), p, tok, tgt))(both["params"])
    assert float(jnp.max(jnp.abs(
        got["hidden"] - both["want"]["hidden"]))) > 1e-2
    if other:
        want = jax.jit(lambda p: olmo_hybrid_f32.terms(
            p, tok, tgt, **dims(TINY), **other))(both["params"])
        np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5,
                                   rtol=5e-5)


def test_the_reference_in_row_blocks_is_the_reference():
    both, (tok, tgt) = both_sides(), batch(TINY)
    blocked = jax.jit(lambda p: olmo_hybrid_f32.terms(
        p, tok, tgt, row_block=8, **dims(TINY)))(both["params"])
    np.testing.assert_allclose(blocked["hidden"], both["want"]["hidden"],
                               atol=1e-5, rtol=1e-5)


# -- the faults ---------------------------------------------------------------

# the cell's limits are set for bf16 at the published widths; in f32 at
# this size the sound system reads 1e-5, so the same comparison is held
# to limits a hundred times that
TIGHT = dict(HIDDEN_REL_L2_RMS_MAX=1e-3, HIDDEN_REL_L2_MAX=3e-3,
             REFERENCE_LOSS_ATOL=1e-3)
TIGHT_SCAN = {n: 1e-3 for n in family.GDN_LEAVES}
FAULT_SEQ = 32


def test_the_sound_system_passes_the_tight_limits(monkeypatch):
    for name, value in TIGHT.items():
        monkeypatch.setattr(family, name, value)
    monkeypatch.setattr(family, "GDN_REL_L2_MAX", TIGHT_SCAN)
    params = init_params(TINY, jax.random.key(3))   # seeded by the check
    tok, tgt = batch(TINY, 3, seq=FAULT_SEQ)
    seen = family.per_token_errors(TINY, params, params, tok, tgt, 3,
                                   row_block=None)
    verdict = family.judge(seen)
    assert verdict["ok"], verdict
    assert verdict["tokens"] == 2 * FAULT_SEQ
    assert 0.2 < verdict["beta_over_1"] < 0.8
    scan = family.judge_gdn(jax.device_get(jax.jit(family.gdn_comparison())(
        *family.gdn_inputs(TINY, 3, FAULT_SEQ))))
    assert scan["ok"], scan


@pytest.mark.parametrize("name", faults.FAULTS)
def test_every_fault_fails_the_cells_comparison(name, monkeypatch):
    for key, value in TIGHT.items():
        monkeypatch.setattr(family, key, value)
    monkeypatch.setattr(family, "GDN_REL_L2_MAX", TIGHT_SCAN)
    monkeypatch.setattr(faults, "CHUNK", 8)
    params = init_params(TINY, jax.random.key(3))   # seeded by the check
    tok, tgt = batch(TINY, 3, seq=FAULT_SEQ)
    patches, weights, system_cfg, scan_fn = faults.fault(name, TINY, params)
    assert weights is None
    with patched(patches):
        seen = family.per_token_errors(
            TINY, params, params, tok, tgt, 3, system_cfg=system_cfg,
            row_block=None)
    verdict = family.judge(seen)
    assert not verdict["ok"], (name, verdict)
    assert (scan_fn is not None) == (name in faults.IN_THE_SCAN)
    if scan_fn is not None:
        alone = family.judge_gdn(jax.device_get(jax.jit(
            family.gdn_comparison(scan_fn))(
                *family.gdn_inputs(TINY, 3, FAULT_SEQ))))
        assert not alone["ok"], (name, alone)


# -- the share ----------------------------------------------------------------


def test_the_eight_vocabulary_shares_add_up_to_the_uncut_head():
    """The cell holds rows 0 … V/8 of table and head. With ids drawn
    below V/8 the layers see the same stream whatever is held; the eight
    shares' logits, side by side, are the uncut reference's, and their
    logsumexp terms and target logits give its cross entropy: the layers
    counted once, nothing stands in for a share."""
    ways, rows = 8, TINY.vocab_size
    whole_cfg = dataclasses.replace(TINY, vocab_size=ways * rows)
    whole = seeded(whole_cfg)
    tok, tgt = batch(TINY)                      # ids below the share's rows
    uncut = jax.jit(lambda p: olmo_hybrid_f32.terms(
        p, tok, tgt, **dims(whole_cfg)))(whole)

    def held(w):
        p = jax.tree_util.tree_map(lambda a: a, whole)
        # every share looks its ids up in rows 0 … V/8 (the ids lie there)
        p["wte"] = {"embedding": whole["wte"]["embedding"][:rows]}
        p["lm_head"] = {"kernel": whole["lm_head"]["kernel"][
            :, w * rows:(w + 1) * rows]}
        return p

    share = jax.jit(lambda p: loss_terms(TINY, p, tok, tgt))
    first = share(held(0))
    np.testing.assert_allclose(first["hidden"], uncut["hidden"], atol=5e-5,
                               rtol=5e-5)
    h = first["hidden"].astype(jnp.float32)
    logits = [jnp.einsum("bsd,dv->bsv", h, held(w)["lm_head"]["kernel"],
                         precision="highest") for w in range(ways)]
    h_ref = uncut["hidden"]
    np.testing.assert_allclose(
        jnp.concatenate(logits, axis=-1),
        jnp.einsum("bsd,dv->bsv", h_ref, whole["lm_head"]["kernel"],
                   precision="highest"), atol=2e-4, rtol=2e-4)
    # what each of the eight chips would hand to the exchange
    lse = [jax.nn.logsumexp(x, axis=-1) for x in logits]
    target = jnp.zeros(tgt.shape, jnp.float32)
    for w, x in enumerate(logits):
        local = tgt - w * rows
        mine = (local >= 0) & (local < rows)
        target += jnp.where(mine, jnp.take_along_axis(
            x, jnp.clip(local, 0, rows - 1)[..., None], axis=-1)[..., 0], 0.0)
    combined = jnp.mean(jax.nn.logsumexp(jnp.stack(lse), axis=0) - target)
    assert float(combined) == pytest.approx(float(uncut["loss"]), abs=2e-5)
    # the share's own loss is over ITS rows: share 0's term alone
    assert float(first["loss"]) == pytest.approx(
        float(jnp.mean(lse[0] - target)), abs=2e-5)


# -- the cell's configuration -------------------------------------------------


def cell_config():
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b-vp8.json")) as f:
        return json.load(f)


def test_the_cells_configuration_is_the_published_one_cut_as_it_says():
    config = cell_config()
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 32,
        "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 8,
        "vocab_size": 100352}
    assert {"published", "vocab_share", "deployment", "assumed",
            "departures", "sizing"} <= set(config)
    # one whole period, the model's own first four layers
    assert config["layer_types"] == config["published"]["layer_types"][:4]
    share = config["vocab_share"]
    assert share["vocab_ways"] * config["vocab_size"] == \
        config["published"]["vocab_size"]
    cfg = family.build(config).cfg
    assert cfg == OlmoHybridConfig(
        vocab_size=12544, layer_types=(LINEAR, LINEAR, LINEAR, FULL),
        remat=True, xent_chunks=config["job"]["xent_chunks"])
    # every other number of the published config (the catalog's row
    # Olmo-Hybrid-7B, copied here: the guides are not part of a checkout)
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 8,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }
    assert config["source"] == ("https://huggingface.co/allenai/"
                                "Olmo-Hybrid-7B/blob/main/config.json")
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    # counted from the parameter tree: ISSUE 56 reckoned 928.6 M
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 928862196
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        shapes["layers_0"]["gdn"])) == 88750332
    for key in ("block", "rope", "A_log_and_dt_bias", "seq_len", "optimizer",
                "initializer_range", "l2_norm_eps"):
        assert key in config["assumed"], key
