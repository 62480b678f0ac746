"""Chunked cross entropy vs the dense reference.

The chunked path must be numerically interchangeable with dense
log_softmax — both in value and in (dx, dw) gradients — because the
flagship configs use it for every training loss (models/transformer.py
cites ops/xent.py). An unsharded head takes the fused sweep over row tiles
(loss and both gradients in one scan, each logit built once); a
vocabulary-sharded head keeps the online-logsumexp scan and its recompute.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchft_tpu.ops.xent import (
    _row_tiles,
    chunked_cross_entropy,
    hidden_cross_entropy,
)


def _dense_ce(x, w, targets):
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    )


@pytest.mark.parametrize("n,d,v,chunks", [
    (64, 16, 128, 8),
    (33, 8, 96, 4),     # n not a multiple of anything interesting
    (16, 32, 64, 1),    # single chunk == dense
])
def test_chunked_ce_value(n, d, v, chunks) -> None:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, v)) * 0.5, jnp.float32)
    t = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    got = chunked_cross_entropy(x, w, t, chunks)
    want = _dense_ce(x, w, t)
    np.testing.assert_allclose(
        float(got), float(want), atol=1e-6, rtol=1e-6
    )


@pytest.mark.parametrize("chunks", [2, 8])
def test_chunked_ce_grads(chunks) -> None:
    n, d, v = 48, 12, 64
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, v)) * 0.5, jnp.float32)
    t = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)

    gx, gw = jax.grad(
        lambda x, w: chunked_cross_entropy(x, w, t, chunks),
        argnums=(0, 1),
    )(x, w)
    rx, rw = jax.grad(
        lambda x, w: _dense_ce(x, w, t), argnums=(0, 1)
    )(x, w)
    np.testing.assert_allclose(
        np.asarray(gx), np.asarray(rx), atol=1e-6, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(gw), np.asarray(rw), atol=1e-6, rtol=1e-5
    )


def test_chunked_ce_jit_and_extreme_logits() -> None:
    # online logsumexp must stay finite where naive exp overflows
    n, d, v = 8, 4, 32
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((n, d)) * 100.0, jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, v)) * 100.0, jnp.float32)
    t = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    got = jax.jit(
        lambda x, w, t: chunked_cross_entropy(x, w, t, 4)
    )(x, w, t)
    want = _dense_ce(x, w, t)
    assert np.isfinite(float(got))
    np.testing.assert_allclose(
        float(got), float(want), atol=1e-4, rtol=1e-5
    )


def _operands(seed, n, d, v, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    w = jnp.asarray(rng.standard_normal((d, v)) * 0.5, jnp.float32)
    t = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    return x, w, t


def _assert_grads_close(got, want, atol=1e-6, rtol=1e-5) -> None:
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=atol, rtol=rtol)


# a prime N, an N no chunk count here divides, and V = 100 that neither 3
# nor 8 divides: the fused sweep cuts rows, so none of them is refused
@pytest.mark.parametrize("chunks", [1, 3, 8])
@pytest.mark.parametrize("n", [31, 33, 64, 96])
def test_fused_value_and_grads_any_n(n, chunks) -> None:
    x, w, t = _operands(10 + n, n, 12, 100)
    got, got_g = jax.value_and_grad(
        lambda x, w: chunked_cross_entropy(x, w, t, chunks),
        argnums=(0, 1))(x, w)
    want, want_g = jax.value_and_grad(
        lambda x, w: _dense_ce(x, w, t), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(got), float(want), atol=1e-6,
                               rtol=1e-6)
    _assert_grads_close(got_g, want_g)


@pytest.mark.parametrize("n,chunks,want", [
    # the cells: at least xent_chunks tiles, and a tile of at most 4 096
    # rows (at 8 192 two cells' steps peaked over their parents')
    (32768, 3, (8, 4096, 0)),   # c111m
    (24576, 3, (6, 4096, 0)),   # olmoe
    (16384, 3, (4, 4096, 0)),   # c1p3b: the smallest divisor from 4 up
    (32768, 4, (8, 4096, 0)),   # the share cells
    (2048, 8, (8, 256, 0)),     # chip_smoke.py: the chunk count alone
    (33, 8, (11, 3, 0)),        # 11 tiles of 3 rows: over half of 33 / 8
    (31, 3, (3, 11, 2)),        # a prime: ceil(31 / 3) rows, 2 padded
    (31, 8, (8, 4, 1)),
    (34, 8, (7, 5, 1)),         # 17 tiles would leave 2 rows of 4.25
    (5, 8, (5, 1, 0)),          # more chunks than rows
    (64, 1, (1, 64, 0)),
    (8209, 1, (3, 2737, 2)),    # a prime over the row cap: padded tiles
])
def test_row_tiles_rule(n, chunks, want) -> None:
    tiles, rows, pad = _row_tiles(n, chunks)
    assert (tiles, rows, pad) == want
    assert tiles * rows == n + pad and pad < rows
    # never a larger tile than the caller's chunk count asks for
    assert rows <= min(4096, -(-n // min(chunks, n)))


def test_fused_head_passed_as_embedding_transpose() -> None:
    # the tied heads (lfm2, phi4flash) hand the sweep ``embedding.T``: the
    # gradient has to come back in the embedding's own layout
    x, w, t = _operands(20, 48, 12, 72)
    emb = jnp.asarray(w.T)
    got = jax.grad(
        lambda x, e: chunked_cross_entropy(x, e.T, t, 4), argnums=(0, 1)
    )(x, emb)
    want = jax.grad(
        lambda x, e: _dense_ce(x, e.T, t), argnums=(0, 1))(x, emb)
    assert got[1].shape == emb.shape
    _assert_grads_close(got, want)


def test_hidden_cross_entropy_bf16_hidden() -> None:
    # the models' call: bf16 [B, S, D] hidden states, f32 head; the
    # adapter casts to f32, so the gradient returns as bf16 and agrees
    # with the dense path to bf16's last bit
    x, w, t = _operands(21, 64, 16, 96, jnp.bfloat16)
    h, tt = x.reshape(2, 32, 16), t.reshape(2, 32)
    got, got_g = jax.value_and_grad(
        lambda h, w: hidden_cross_entropy(h, w, tt, 3), argnums=(0, 1)
    )(h, w)
    want, want_g = jax.value_and_grad(
        lambda h, w: _dense_ce(h.reshape(-1, 16), w, t), argnums=(0, 1)
    )(h, w)
    np.testing.assert_allclose(float(got), float(want), atol=1e-6,
                               rtol=1e-6)
    assert got_g[0].dtype == jnp.bfloat16 and got_g[0].shape == h.shape
    _assert_grads_close(got_g, want_g, atol=1e-6, rtol=2 ** -7)


def test_fused_scaled_and_summed_calls_on_one_head() -> None:
    # joyai's shape of use: the head's loss plus 0.3 of a second call on
    # the same head (MTP), so the backward rule sees a cotangent that is
    # not 1 and the head's gradient is a sum of two residuals
    x, w, t = _operands(22, 40, 12, 64)
    x2, _, t2 = _operands(23, 40, 12, 64)

    def both(ce):
        return lambda x, x2, w: ce(x, w, t) + 0.3 * ce(x2, w, t2)

    got, got_g = jax.value_and_grad(
        both(lambda x, w, t: chunked_cross_entropy(x, w, t, 4)),
        argnums=(0, 1, 2))(x, x2, w)
    want, want_g = jax.value_and_grad(both(_dense_ce),
                                      argnums=(0, 1, 2))(x, x2, w)
    np.testing.assert_allclose(float(got), float(want), atol=1e-6,
                               rtol=1e-6)
    _assert_grads_close(got_g, want_g)


@pytest.mark.parametrize("chunks", [1, 3])
def test_fused_out_of_range_targets_clip_as_dense(chunks) -> None:
    # dense take_along_axis clips: ids past either end count as the first
    # or the last row of the head, in the loss and in the gradients
    x, w, t = _operands(24, 31, 8, 50)
    wild = t.at[0].set(-7).at[5].set(50).at[30].set(10 ** 6)

    def dense_clipped(x, w):
        return _dense_ce(x, w, jnp.clip(wild, 0, 49))

    got, got_g = jax.value_and_grad(
        lambda x, w: chunked_cross_entropy(x, w, wild, chunks),
        argnums=(0, 1))(x, w)
    want, want_g = jax.value_and_grad(dense_clipped, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(got), float(want), atol=1e-6,
                               rtol=1e-6)
    _assert_grads_close(got_g, want_g)


@pytest.mark.parametrize("n,chunks", [(64, 4), (31, 3)])
def test_fused_primal_loss_equals_differentiated_loss(n, chunks) -> None:
    # evaluation and check_reference take the primal (no gradient
    # matmuls); a training step takes the forward rule: one loss, to the bit
    x, w, t = _operands(25, n, 12, 100)

    def ce(x, w):
        return chunked_cross_entropy(x, w, t, chunks)

    primal = jax.jit(ce)(x, w)
    trained, _ = jax.jit(jax.value_and_grad(ce, argnums=(0, 1)))(x, w)
    assert np.asarray(primal).tobytes() == np.asarray(trained).tobytes()


def _walk(jaxpr, inside_scan=0):
    """(equation, number of scans around it) of a jaxpr and every jaxpr
    its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_scan
        deeper = inside_scan + (eqn.primitive.name == "scan")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, deeper)


def _vocab_matmuls(fn, *args, v):
    """The scans of ``fn``'s jaxpr and its dot_generals over an operand or
    a result with a dimension of the vocabulary's size."""
    found = list(_walk(jax.make_jaxpr(fn)(*args).jaxpr))
    scans = [e for e, _ in found if e.primitive.name == "scan"]
    dots = [
        (e, depth) for e, depth in found
        if e.primitive.name == "dot_general" and any(
            v in a.aval.shape for a in (*e.invars, *e.outvars))
    ]
    return scans, dots


def test_fused_builds_each_logit_once() -> None:
    # the mechanism's count, where counts are honest: the differentiated
    # loss holds the head's three matmuls (logits, dx, dW) inside ONE scan
    # — the recompute path held four in two — and the primal holds the
    # logits' alone
    n, d, v = 64, 12, 104
    x, w, t = _operands(26, n, d, v)

    def ce(x, w):
        return chunked_cross_entropy(x, w, t, 4)

    scans, dots = _vocab_matmuls(jax.grad(ce, argnums=(0, 1)), x, w, v=v)
    assert len(scans) == 1
    assert len(dots) == 3 and all(depth == 1 for _, depth in dots)
    rows = n // 4
    assert sorted(e.outvars[0].aval.shape for e, _ in dots) == sorted(
        [(rows, v), (rows, d), (d, v)])

    scans, dots = _vocab_matmuls(ce, x, w, v=v)
    assert len(scans) == 1
    assert [e.outvars[0].aval.shape for e, _ in dots] == [(rows, v)]


def test_sharded_primitive_still_recomputes() -> None:
    # the vocabulary-sharded head's primitive keeps its two scans: its
    # gradients wait for the shards' combined lse
    from torchft_tpu.ops.xent import chunked_lse_and_target

    n, d, v = 64, 12, 104
    x, w, t = _operands(27, n, d, v)
    mask = jnp.ones((n,), bool)

    def nll(x, w):
        lse, tl = chunked_lse_and_target(x, w, t, mask, 4)
        return jnp.mean(lse - tl)

    scans, dots = _vocab_matmuls(jax.grad(nll, argnums=(0, 1)), x, w,
                                 v=v // 4)
    assert len(scans) == 2 and len(dots) == 4


def test_grad_accumulation_through_fused_loss() -> None:
    # make_grad_step(microbatches=2) differentiates the loss inside a
    # lax.scan: the fused forward rule has to trace there and give the
    # full batch's loss and gradients
    import dataclasses

    from torchft_tpu.models import CONFIGS, init_params, make_grad_step

    cfg = dataclasses.replace(CONFIGS["tiny"], xent_chunks=3)
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(28)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, cfg.max_seq_len)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    l1, g1 = make_grad_step(cfg)(params, tokens, targets)
    l2, g2 = make_grad_step(cfg, microbatches=2)(params, tokens, targets)
    # as tests/test_models.py: bf16 activations, so slicing the batch is
    # agreement at bf16 reassociation, not to the bit
    np.testing.assert_allclose(float(l1), float(l2), atol=1e-3, rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=0.05)


def test_model_loss_chunked_matches_dense() -> None:
    # the model-level switch: same config with/without xent_chunks must
    # produce the same loss and grads
    import dataclasses

    from torchft_tpu.models import CONFIGS, init_params, loss_fn

    cfg_dense = CONFIGS["tiny"]
    assert cfg_dense.xent_chunks == 0
    cfg_chunked = dataclasses.replace(cfg_dense, xent_chunks=4)
    params = init_params(cfg_dense, jax.random.key(0))
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(
        rng.integers(0, cfg_dense.vocab_size, (2, 64)), jnp.int32
    )
    targets = jnp.roll(tokens, -1, axis=1)

    l_dense, g_dense = jax.value_and_grad(
        lambda p: loss_fn(cfg_dense, p, tokens, targets)
    )(params)
    l_chunk, g_chunk = jax.value_and_grad(
        lambda p: loss_fn(cfg_chunked, p, tokens, targets)
    )(params)
    np.testing.assert_allclose(
        float(l_dense), float(l_chunk), atol=1e-5, rtol=1e-5
    )
    flat_d, _ = jax.tree_util.tree_flatten(g_dense)
    flat_c, _ = jax.tree_util.tree_flatten(g_chunk)
    for a, b in zip(flat_d, flat_c):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-3
        )


def test_llama_loss_chunked_matches_dense() -> None:
    import dataclasses

    from torchft_tpu.models.llama import (
        LlamaConfig, llama_init_params, llama_loss_fn,
    )

    cfg = LlamaConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, remat=False,
    )
    cfg_c = dataclasses.replace(cfg, xent_chunks=4)
    params = llama_init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                         jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    l_d = float(llama_loss_fn(cfg, params, tokens, targets))
    l_c = float(llama_loss_fn(cfg_c, params, tokens, targets))
    np.testing.assert_allclose(l_d, l_c, atol=1e-5, rtol=1e-5)


def test_vocab_parallel_ce_value_and_grads() -> None:
    # Megatron-style vocab-parallel CE over a sharded lm head must match
    # the dense single-device loss in value and (dh, dw) gradients
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchft_tpu.ops.xent import make_vocab_parallel_cross_entropy
    from torchft_tpu.parallel import ft_mesh

    mesh = ft_mesh({"tensor": 4}, devices=jax.devices()[:4])
    n, d, v = 32, 16, 64
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, v)) * 0.5, jnp.float32)
    t = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    ws = jax.device_put(w, NamedSharding(mesh, P(None, "tensor")))

    loss = make_vocab_parallel_cross_entropy(mesh, "tensor", num_chunks=2)
    got = jax.jit(loss)(h, ws, t)
    want = _dense_ce(h, w, t)
    np.testing.assert_allclose(float(got), float(want), atol=1e-6,
                               rtol=1e-6)

    gh, gw = jax.jit(jax.grad(loss, argnums=(0, 1)))(h, ws, t)
    rh, rw = jax.grad(
        lambda h, w: _dense_ce(h, w, t), argnums=(0, 1)
    )(h, w)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(rh),
                               atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               atol=1e-6, rtol=1e-5)


def test_vocab_parallel_ce_gradient_sharding_preserved() -> None:
    # dw must come back vocab-sharded (no hidden all-gather of the head)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchft_tpu.ops.xent import make_vocab_parallel_cross_entropy
    from torchft_tpu.parallel import ft_mesh

    mesh = ft_mesh({"tensor": 4}, devices=jax.devices()[:4])
    n, d, v = 16, 8, 32
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    w = jax.device_put(
        jnp.asarray(rng.standard_normal((d, v)) * 0.5, jnp.float32),
        NamedSharding(mesh, P(None, "tensor")),
    )
    t = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    loss = make_vocab_parallel_cross_entropy(mesh, "tensor")
    gw = jax.jit(jax.grad(loss, argnums=1))(h, w, t)
    assert gw.sharding.spec == P(None, "tensor")
