"""``models/phi4flash.py`` on the CPU at the tiny size, seeded weights:
the system against ``benchmark/reference/phi4flash_f32.py`` — loss, final
hidden state and every gradient leaf —, remat on and off, the gradients
that come back into ``m``, ``k`` and ``v``, the vocabulary's share, and
the cell's configuration against the published sizes."""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4flash_f32
from torchft_tpu.models import phi4flash as M
from torchft_tpu.models.phi4flash import (
    PHI4FLASH_CONFIGS, Phi4FlashConfig, init_params, layer_kind,
    lambda_init, loss_fn, loss_terms,
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 compute: the comparison is of the mathematics, not of bf16
TINY = dataclasses.replace(PHI4FLASH_CONFIGS["phi4flash_tiny"],
                           dtype=jnp.float32)
SEQ = 48            # three windows of 16: the band is narrower than the mask


def dims(cfg):
    return dict(layer_ids=cfg.layer_ids, n_layers=cfg.n_published_layers,
                n_head=cfg.n_heads, n_kv=cfg.n_kv_heads, window=cfg.window,
                state=cfg.d_state, rank=cfg.dt_rank, eps=cfg.ln_eps)


def batch(cfg, seed=1, rows=2):
    tokens = jax.random.randint(jax.random.key(seed), (rows, SEQ), 0,
                                cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def seeded(cfg, seed=0):
    """Initial weights with the zero biases drawn non-zero, as the cell's
    check seeds them: a bias left out would otherwise show nowhere."""
    from benchmark.families.phi4flash import seed_biases

    return seed_biases(init_params(cfg, jax.random.key(seed)), seed)


_CACHE = {}


def both_sides():
    """System and reference on the same weights and batch, once a
    module: terms and gradient trees."""
    if not _CACHE:
        params, (tok, tgt) = seeded(TINY), batch(TINY)

        # jitted: eager, the interpreter's kernels run operation by operation
        @jax.jit
        def system(p):
            terms, pull = jax.vjp(lambda q: loss_terms(TINY, q, tok, tgt), p)
            return terms, pull({"loss": jnp.ones(()), "hidden": jnp.zeros_like(
                terms["hidden"])})[0]

        @jax.jit
        def reference(p):
            terms, pull = jax.vjp(
                lambda q: phi4flash_f32.terms(q, tok, tgt, **dims(TINY)), p)
            return terms, pull({"loss": jnp.ones(()), "hidden": jnp.zeros_like(
                terms["hidden"])})[0]

        (got, grads), (want, grads_ref) = system(params), reference(params)
        _CACHE.update(params=params, got=got, want=want, grads=grads,
                      grads_ref=grads_ref)
    return _CACHE


def leaf_paths(cfg=TINY):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_leaves_with_path(shapes)]


def test_the_tiny_cut_holds_every_kind_of_layer():
    assert TINY.kinds == ("mamba", "swa", "mamba", "full", "gmu", "cross")
    assert TINY.window < SEQ
    # the published 32: 9 Mamba, 8 windowed, 1 full, 7 GMU, 7 cross
    kinds = [layer_kind(i, 32) for i in range(32)]
    assert [kinds.count(k) for k in M.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    # a reader without its source in the cut is refused
    with pytest.raises(AssertionError):
        dataclasses.replace(TINY, layer_ids=(0, 1, 17, 18))
    with pytest.raises(AssertionError):
        dataclasses.replace(TINY, layer_ids=(0, 1, 16, 19))


def test_loss_and_hidden_state_equal_the_references():
    both = both_sides()
    assert float(both["got"]["loss"]) == pytest.approx(
        float(both["want"]["loss"]), abs=2e-5)
    np.testing.assert_allclose(both["got"]["hidden"], both["want"]["hidden"],
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("path", leaf_paths())
def test_every_gradient_leaf_equals_the_references(path):
    both = both_sides()
    got, want = (
        {jax.tree_util.keystr(p): g
         for p, g in jax.tree_util.tree_leaves_with_path(both[side])}[path]
        for side in ("grads", "grads_ref"))
    assert float(jnp.max(jnp.abs(want))) > 0, "a leaf no gradient reaches"
    np.testing.assert_allclose(
        got, want, atol=2e-4 * float(jnp.max(jnp.abs(want))), rtol=2e-4)


def test_the_reference_in_row_blocks_is_the_reference():
    both, (tok, tgt) = both_sides(), batch(TINY)
    blocked = jax.jit(lambda p: phi4flash_f32.terms(
        p, tok, tgt, row_block=16, **dims(TINY)))(both["params"])
    np.testing.assert_allclose(blocked["hidden"], both["want"]["hidden"],
                               atol=1e-5, rtol=1e-5)


def test_remat_on_and_off_give_the_same_gradients():
    both, (tok, tgt) = both_sides(), batch(TINY)
    remat = dataclasses.replace(TINY, remat=True)
    grads = jax.jit(jax.grad(lambda p: loss_fn(remat, p, tok, tgt)))(
        both["params"])
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(both["grads"])):
        np.testing.assert_allclose(
            a, b, atol=1e-6 * float(jnp.max(jnp.abs(b))), rtol=1e-5)


def test_the_gradients_into_the_memory_are_the_sums_over_its_readers():
    """``m``, ``k`` and ``v`` each have two readers in the cut (their own
    layer and a later one). With the later reader's output matrix zeroed
    nothing comes back from it, so the difference of the source layer's
    gradients with and without it is what ``_fan_out`` summed in: it must
    be there under remat (every layer a checkpoint of its own), and the
    whole gradient the one without remat."""
    cfg = dataclasses.replace(TINY, remat=True)
    params, (tok, tgt) = both_sides()["params"], batch(TINY)
    grads_of = jax.jit(jax.grad(lambda q: loss_fn(cfg, q, tok, tgt)))
    whole = grads_of(params)
    # (the reader's output matrix, a leaf of the source layer)
    for (reader, mixer, proj), (layer, *leaf) in (
            (("layers_4", "gmu", "out_proj"),
             ("layers_2", "ssm", "x_proj", "kernel")),
            (("layers_5", "attn", "o_proj"),
             ("layers_3", "attn", "qkv_proj", "kernel"))):
        cut = jax.tree_util.tree_map(lambda a: a, params)
        cut[reader][mixer][proj]["kernel"] = jnp.zeros_like(
            cut[reader][mixer][proj]["kernel"])
        with_reader, without, plain = (
            g[layer][leaf[0]][leaf[1]][leaf[2]]
            for g in (whole, grads_of(cut), both_sides()["grads"]))
        # the reader's share of the source's gradient is a real part of it
        assert float(jnp.linalg.norm(with_reader - without)) > 1e-3 * float(
            jnp.linalg.norm(with_reader))
        np.testing.assert_allclose(
            with_reader, plain, atol=1e-6 * float(jnp.max(jnp.abs(plain))),
            rtol=1e-5)


def test_fan_out_adds_its_readers_cotangents_in_float32():
    x = jnp.ones((4,), jnp.bfloat16)
    ys, pull = jax.vjp(lambda a: M._fan_out(a, 3), x)
    assert len(ys) == 3 and all(y is not None for y in ys)
    g = jnp.full((4,), 1 + 2 ** -8, jnp.float32).astype(jnp.bfloat16)
    big = jnp.full((4,), 256.0, jnp.bfloat16)
    (dx,) = pull((big, g, g))
    assert dx.dtype == jnp.bfloat16
    # 256 + 1 + 1 = 258: a bf16 running sum would stay at 256
    np.testing.assert_array_equal(np.asarray(dx, np.float32), 258.0)


def test_the_eight_vocabulary_slices_add_up_to_the_uncut_head():
    """The cell holds rows 0 … V/8 of the table. With ids drawn below V/8
    the layers see the same stream whatever is held, and the eight slices'
    logsumexp terms and target logits give the uncut head's cross
    entropy: the layers counted once, nothing stands in for a slice."""
    ways, rows = 8, TINY.vocab_size
    whole_cfg = dataclasses.replace(TINY, vocab_size=ways * rows)
    whole = seeded(whole_cfg)
    held = jax.tree_util.tree_map(lambda a: a, whole)
    held["wte"] = {"embedding": whole["wte"]["embedding"][:rows]}
    tok, tgt = batch(TINY)                      # ids below the slice's rows
    uncut = jax.jit(lambda p: loss_terms(whole_cfg, p, tok, tgt))(whole)
    share = jax.jit(lambda p: loss_terms(
        dataclasses.replace(TINY, vocab_ways=ways), p, tok, tgt))(held)
    np.testing.assert_allclose(share["hidden"], uncut["hidden"], atol=1e-6)
    # what each of the eight chips would hand to the exchange
    h = share["hidden"].astype(jnp.float32)
    lse, target = [], jnp.zeros(tgt.shape, jnp.float32)
    for w in range(ways):
        table = whole["wte"]["embedding"][w * rows:(w + 1) * rows]
        logits = jnp.einsum("bsd,vd->bsv", h, table, precision="highest")
        lse.append(jax.nn.logsumexp(logits, axis=-1))
        local = tgt - w * rows
        mine = (local >= 0) & (local < rows)
        target += jnp.where(mine, jnp.take_along_axis(
            logits, jnp.clip(local, 0, rows - 1)[..., None], axis=-1)[..., 0],
            0.0)
    combined = jnp.mean(jax.nn.logsumexp(jnp.stack(lse), axis=0) - target)
    assert float(combined) == pytest.approx(float(uncut["loss"]), abs=2e-5)
    # the share's own loss is over ITS rows: slice 0's term alone
    assert float(share["loss"]) == pytest.approx(
        float(jnp.mean(lse[0] - target)), abs=2e-5)


def test_chunked_cross_entropy_and_bf16_compute_follow_the_reference():
    params, (tok, tgt) = both_sides()["params"], batch(TINY)
    chunked = jax.jit(lambda p: loss_fn(
        dataclasses.replace(TINY, xent_chunks=4), p, tok, tgt))(params)
    assert float(chunked) == pytest.approx(
        float(both_sides()["want"]["loss"]), abs=2e-5)
    bf16 = jax.jit(lambda p: loss_terms(
        PHI4FLASH_CONFIGS["phi4flash_tiny"], p, tok, tgt))(params)
    assert bf16["hidden"].dtype == jnp.bfloat16
    want = both_sides()["want"]["hidden"]
    err = jnp.linalg.norm(bf16["hidden"].astype(jnp.float32) - want, axis=-1)
    assert float(jnp.sqrt(jnp.mean(
        (err / jnp.linalg.norm(want, axis=-1)) ** 2))) < 0.04


# -- the cell's configuration --------------------------------------------------


def cell_config():
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning-vp8.json")) as f:
        return json.load(f)


def test_the_cells_configuration_is_the_published_one_cut_as_it_says():
    from benchmark import phi4flash_flops
    from benchmark.families import phi4flash as family

    config = cell_config()
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 200064}
    assert {"published", "vocab_share", "deployment", "assumed",
            "departures", "sizing"} <= set(config)
    share = config["vocab_share"]
    assert share["vocab_ways"] * config["vocab_size"] == \
        share["padded_vocab_size"] >= config["published"]["vocab_size"]
    cfg = family.build(config).cfg
    assert cfg == Phi4FlashConfig(
        vocab_size=25088, vocab_ways=8, layer_ids=(0, 1, 16, 17, 18, 19),
        remat=True, xent_chunks=4)
    # counted from the parameter tree: ISSUE 47's 697.3 M
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == 697_299_072 and f"{n / 1e6:.1f} M" in config["sizing"]
    # the published model whole: 3.85 B
    full = jax.eval_shape(lambda: init_params(Phi4FlashConfig(),
                                              jax.random.key(0)))
    assert sum(x.size for x in jax.tree_util.tree_leaves(full)) == \
        pytest.approx(3.853e9, rel=1e-3)
    parts = phi4flash_flops.train_flops_per_token(
        **phi4flash_flops.config_dims(config))
    assert parts["ssm_scan"] == 0.0 and parts["total"] == pytest.approx(
        4.582e9, rel=1e-3)
    # weight decay on matrices only, A_log not among them
    mask = family.decayed(shapes)
    assert mask["layers_0"]["ssm"]["conv"]["kernel"]
    assert not mask["layers_0"]["ssm"]["A_log"]
    assert not mask["layers_1"]["attn"]["lambda_q1"]
    assert not mask["ln_f"]["bias"]
