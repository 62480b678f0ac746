"""``ops/s6.py``: the Mamba-1 selective scan's kernels against the
recurrence itself, position by position — forward and every gradient, a
leaf at a time, whatever the chunk. Interpreter-mode Pallas on the CPU, so
the shapes are small; the kernels at the cell's widths are compiled for a
described v5e."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.phi4flash_f32 import selective_scan
from one_program import value_and_pullback
from torchft_tpu.ops import s6
from torchft_tpu.ops.s6 import _choose_chunk, _lane_block, s6_scan

LEAVES = "x dt A B C D".split()


def scan(x, dt, A, B, C, D, chunk=None):
    """``s6_scan`` at a chunk of the test's choosing: the public function
    takes none (it picks one from the sequence length)."""
    if chunk is None:
        return s6_scan(x, dt, A, B, C, D)
    return s6._s6(x, dt, A, B, C, D, chunk, s6._interpret())


def recurrence(*args):
    with jax.default_matmul_precision("highest"):
        return selective_scan(*args)


def inputs(seed, b, s, c, n, dt_scale=1.0, a_scale=1.0):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (b, s, c), jnp.float32)
    dt = dt_scale * jax.nn.softplus(
        jax.random.normal(k[1], (b, s, c), jnp.float32) - 2.0)
    A = -a_scale * jnp.exp(
        jax.random.uniform(k[2], (c, n), jnp.float32, 0.0, 2.5))
    B = jax.random.normal(k[3], (b, s, n), jnp.float32) * 0.5
    C = jax.random.normal(k[4], (b, s, n), jnp.float32) * 0.5
    D = jax.random.normal(k[5], (c,), jnp.float32)
    dy = jax.random.normal(k[6], (b, s, c), jnp.float32)
    return (x, dt, A, B, C, D), dy


# (case, rows, S, C, N, chunk, dt_scale, a_scale)
CASES = [
    # two rows, two lane blocks, three chunks: every grid axis moves
    ("three-chunks", 2, 48, 1024, 16, 16, 1.0, 1.0),
    ("padded-40-of-48", 1, 40, 128, 16, 16, 1.0, 1.0),
    # 8 states: 16 positions a row of the group's tile
    ("chosen-from-the-shape", 1, 24, 64, 8, None, 1.0, 1.0),
    # a chunk's total decay underflows: exp(-16 x 30 x ...) == 0
    ("decay-underflows", 1, 32, 128, 16, 16, 60.0, 1.0),
    # next to no decay: position 63 is right only with what crossed
    # three chunk boundaries, and dA with what came back across them
    ("state-crosses-three-boundaries", 1, 64, 128, 16, 16, 1.0, 1e-3),
]
_RUNS = {}


def _run(case):
    """Forward and pullback of the kernels and of the recurrence, once a
    case (the interpreter's time goes with the grid)."""
    if case[0] not in _RUNS:
        _, rows, s, c, n, chunk, dt_scale, a_scale = case
        args, dy = inputs(len(case[0]), rows, s, c, n, dt_scale, a_scale)
        want, grads_want = value_and_pullback(recurrence, args, dy)
        got, grads = value_and_pullback(
            lambda *a: scan(*a, chunk=chunk), args, dy)
        _RUNS[case[0]] = {"y": (got, want),
                          **dict(zip(LEAVES, zip(grads, grads_want)))}
    return _RUNS[case[0]]


@pytest.mark.parametrize("leaf", ["y"] + LEAVES)
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_scan_equals_the_recurrence(case, leaf):
    """A case's name is the boundary it crosses (chunks, a padded end,
    lane blocks, an underflowing decay, a state carried over three
    boundaries) at S 32 - 64; a case runs once for its seven leaves, each
    side one program (``tests/one_program.py``)."""
    got, want = _run(case)[leaf]
    assert got.shape == want.shape
    assert np.all(np.isfinite(np.asarray(got)))
    tol = 2e-5 if leaf == "y" else 5e-5
    np.testing.assert_allclose(
        got, want, atol=tol * float(jnp.max(jnp.abs(want))), rtol=tol)


def test_last_position_needs_the_carried_state():
    """The case above is no test unless dropping the carry shows: with
    next to no decay the last chunk's own positions give a fraction of
    ``y`` at the end."""
    (x, dt, A, B, C, D), _ = inputs(7, 1, 64, 128, 16, 1.0, 1e-3)
    whole = scan(x, dt, A, B, C, D, chunk=16)[:, -1]
    alone = scan(x[:, 48:], dt[:, 48:], A, B[:, 48:], C[:, 48:], D,
                 chunk=16)[:, -1]
    assert float(jnp.max(jnp.abs(whole - alone))) > 0.1 * float(
        jnp.max(jnp.abs(whole)))


def test_result_does_not_depend_on_the_chunk():
    args, dy = inputs(11, 1, 64, 128, 16)
    runs = [value_and_pullback(lambda *a: scan(*a, chunk=q), args, dy)
            for q in (16, 64)]
    np.testing.assert_allclose(runs[0][0], runs[1][0], atol=1e-4, rtol=1e-4)
    for a, b in zip(runs[0][1], runs[1][1]):
        np.testing.assert_allclose(
            a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))), rtol=1e-4)


def test_bf16_operands_f32_inside():
    """bf16 in and out; what lies between is f32: the result is the
    recurrence on the rounded inputs, to the rounding of ``y``, and the
    gradients leave in their operands' types."""
    (x, dt, A, B, C, D), dy = inputs(3, 1, 32, 128, 16)
    xb, Bb, Cb = (z.astype(jnp.bfloat16) for z in (x, B, C))
    got, pull = jax.vjp(lambda *a: scan(*a, chunk=16), xb, dt, A, Bb, Cb, D)
    assert got.dtype == jnp.bfloat16
    want = recurrence(xb.astype(jnp.float32), dt, A,
                      Bb.astype(jnp.float32), Cb.astype(jnp.float32), D)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want,
        atol=2 ** -7 * float(jnp.max(jnp.abs(want))))
    assert [g.dtype for g in pull(dy.astype(jnp.bfloat16))] == [
        jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16,
        jnp.float32]


def test_shapes_the_kernel_refuses():
    (x, dt, A, B, C, D), _ = inputs(1, 1, 16, 64, 16)
    with pytest.raises(ValueError, match="do not fit"):
        s6_scan(x, dt, A, jnp.concatenate([B, B], axis=2), C, D)
    with pytest.raises(ValueError, match="do not fit"):
        s6_scan(x, dt[:, :8], A, B, C, D)
    with pytest.raises(ValueError, match="divides 128"):
        s6_scan(x, dt, A[:, :12], B[..., :12], C[..., :12], D)
    assert _choose_chunk(8192) == s6._CHUNK and _choose_chunk(24) == 32
    assert [_lane_block(c) for c in (5120, 768, 128, 64)] == [
        512, 256, 128, 64]


# -- the kernels at the cell's widths, for a described v5e --------------------


def test_both_kernels_compile_for_the_v5e_at_the_cells_widths(one_chip):
    """[1, 1024] of 5120 channels and 16 states at the cell's chunk: Mosaic
    takes the aligned dynamic row slices, the two transposing products and
    the sublane sums, and nothing ``[B, S, C, N]`` is planned (the states
    kept are ``S / chunk`` of them)."""
    from jax.experimental.compilation_cache import compilation_cache

    b, s, c, n = 1, 1024, 5120, 16

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16, f32 = jnp.bfloat16, jnp.float32

    def both(x, dt, a, bm, cm, d, dy):
        y, pull = jax.vjp(
            lambda *z: s6._s6(*z, s6._CHUNK, False), x, dt, a, bm, cm, d)
        return y, pull(dy)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(both).lower(
            sd((b, s, c), bf16), sd((b, s, c), f32), sd((c, n), f32),
            sd((b, s, n), bf16), sd((b, s, n), bf16), sd((c,), f32),
            sd((b, s, c), bf16),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "s6_fwd" in text and "s6_bwd" in text
    per_position_states = b * s * c * n * 4
    assert compiled.memory_analysis().temp_size_in_bytes < \
        per_position_states / 8
