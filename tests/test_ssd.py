"""``ops/ssd.py``: the chunked state-space scan against the recurrence
itself, position by position — forward and every gradient, whatever the
chunk. Interpreter-mode Pallas on the CPU, so the shapes are small."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from one_program import value_and_pullback
from torchft_tpu.ops import ssd
from torchft_tpu.ops.ssd import (
    _choose_chunk, _head_block, _heads_per_block, ssd_scan)


def scan(x, dt, A, B, C, D, chunk=None):
    """``ssd_scan`` at a chunk of the test's choosing: the public function
    takes none (it picks one from the sequence length)."""
    if chunk is None:
        return ssd_scan(x, dt, A, B, C, D)
    return ssd._ssd(x, dt, A, B, C, D, chunk, ssd._interpret())


def recurrence(x, dt, A, B, C, D):
    """``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t + D
    x_t`` with a ``lax.scan`` over ``t``; float32, ``highest``."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    rep = h // g
    with jax.default_matmul_precision("highest"):
        Bh, Ch = jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2)

        def step(state, t):
            xt, dtt, bt, ct = t                       # [b,h,p] [b,h] [b,h,n]
            state = (jnp.exp(dtt * A)[..., None, None] * state
                     + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
            return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

        _, y = jax.lax.scan(
            step, jnp.zeros((b, h, p, n), jnp.float32),
            tuple(jnp.moveaxis(z, 1, 0) for z in (x, dt, Bh, Ch)))
        return jnp.moveaxis(y, 0, 1) + D[None, None, :, None] * x


def inputs(seed, b, s, h, g, p, n, dt_scale=1.0, a_scale=1.0):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (b, s, h, p), jnp.float32)
    dt = dt_scale * jax.nn.softplus(
        jax.random.normal(k[1], (b, s, h), jnp.float32) - 2.0)
    A = -a_scale * jnp.exp(
        jax.random.uniform(k[2], (h,), jnp.float32, 0.0, 2.5))
    B = jax.random.normal(k[3], (b, s, g, n), jnp.float32) * 0.5
    C = jax.random.normal(k[4], (b, s, g, n), jnp.float32) * 0.5
    D = jax.random.normal(k[5], (h,), jnp.float32)
    dy = jax.random.normal(k[6], (b, s, h, p), jnp.float32)
    return (x, dt, A, B, C, D), dy


# (case, S, H, G, P, N, chunk, dt_scale, a_scale)
CASES = [
    ("one-chunk-is-the-sequence", 32, 2, 1, 8, 16, 32, 1.0, 1.0),
    ("four-chunks", 64, 2, 1, 8, 16, 16, 1.0, 1.0),
    ("padded-40-of-48", 40, 2, 2, 8, 16, 16, 1.0, 1.0),
    ("chosen-from-the-shape", 24, 2, 1, 8, 16, None, 1.0, 1.0),
    ("one-head-a-group", 32, 4, 4, 8, 16, 16, 1.0, 1.0),
    ("eight-heads-a-group", 32, 8, 1, 8, 16, 16, 1.0, 1.0),
    ("two-lane-blocks-a-group", 32, 8, 2, 64, 16, 16, 1.0, 1.0),
    # a group's heads in blocks of eight (512 lanes) on the grid's fourth
    # axis: two blocks, eight (Granite's one group of 64; a padded end),
    # and two groups of one block each
    ("two-head-blocks-one-group", 32, 16, 1, 64, 16, 16, 1.0, 1.0),
    ("eight-head-blocks-one-group-padded", 24, 64, 1, 64, 16, 16, 1.0, 1.0),
    ("two-groups-of-one-head-block", 32, 16, 2, 64, 16, 16, 1.0, 1.0),
    # a chunk's total decay underflows: exp(-16 x 30 x ...) == 0
    ("decay-underflows", 32, 2, 1, 8, 16, 16, 60.0, 1.0),
    # next to no decay: position 63 is right only with what crossed
    # three chunk boundaries
    ("state-crosses-three-boundaries", 64, 2, 1, 8, 16, 16, 1.0, 1e-3),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_scan_equals_the_recurrence(case):
    """A case's name is the boundary it crosses, at the least size that
    does: one chunk, four chunks (three boundaries), a padded end (40 of
    48), a chunk chosen from the shape, one / eight heads a group, two lane
    blocks a group (P 64), two and eight head blocks a group (H/G 16 and 64
    on ONE B and C: ``dB`` and ``dC`` add up over the blocks, the state is
    carried a block) and two groups of one block, a decay that underflows
    within a chunk, a state that three boundaries must carry. S is 24 - 64 and what a case costs is
    compiling its kernels and the recurrence's scan: each side is one
    program (``tests/one_program.py``)."""
    _, s, h, g, p, n, chunk, dt_scale, a_scale = case
    # two rows in one case: the interpreter's time goes with the grid
    rows = 2 if case[0] == "four-chunks" else 1
    args, dy = inputs(len(case[0]), rows, s, h, g, p, n, dt_scale, a_scale)
    want, grads_want = value_and_pullback(recurrence, args, dy)
    got, grads = value_and_pullback(
        lambda *a: scan(*a, chunk=chunk), args, dy)
    assert np.all(np.isfinite(np.asarray(got)))
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=2e-5)
    for name, a, b in zip("x dt A B C D".split(), grads, grads_want):
        assert np.all(np.isfinite(np.asarray(a))), name
        # dA is the sum over a chunk's positions of differences of O(1)
        # terms (dcum); where every decay underflows the true value is
        # tiny and what is left of the f32 cancellation shows in it
        tol = 1e-3 if (name, case[0]) == ("A", "decay-underflows") else 5e-5
        np.testing.assert_allclose(
            a, b, atol=tol * float(jnp.max(jnp.abs(b))), rtol=tol,
            err_msg=name)


def test_last_position_needs_the_carried_state():
    """The case above is no test unless dropping the carry shows: with
    next to no decay the last chunk's own positions give a fraction of
    ``y`` at the end."""
    args, _ = inputs(7, 1, 64, 2, 1, 8, 16, 1.0, 1e-3)
    whole = scan(*args, chunk=16)[:, -1]
    x, dt, A, B, C, D = args
    alone = scan(x[:, 48:], dt[:, 48:], A, B[:, 48:], C[:, 48:], D,
                 chunk=16)[:, -1]
    assert float(jnp.max(jnp.abs(whole - alone))) > 0.1 * float(
        jnp.max(jnp.abs(whole)))


@pytest.mark.parametrize("chunks, heads, groups, head_dim", [
    ((8, 64), 4, 2, 8), ((16, 32), 4, 2, 8),
    # two head blocks on one group
    ((16, 64), 16, 1, 64)])
def test_result_does_not_depend_on_the_chunk(chunks, heads, groups, head_dim):
    args, dy = inputs(11, 1, 64, heads, groups, head_dim, 16)
    runs = [value_and_pullback(lambda *a: scan(*a, chunk=q), args, dy)
            for q in chunks]
    np.testing.assert_allclose(runs[0][0], runs[1][0], atol=1e-4, rtol=1e-4)
    for a, b in zip(runs[0][1], runs[1][1]):
        np.testing.assert_allclose(
            a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))), rtol=1e-4)


def test_bf16_operands_f32_inside():
    """bf16 in and out; what lies between is f32: the result is the
    recurrence on the rounded inputs, to the rounding of ``y``."""
    (x, dt, A, B, C, D), _ = inputs(3, 1, 32, 2, 1, 8, 16)
    xb, Bb, Cb = (z.astype(jnp.bfloat16) for z in (x, B, C))
    got = scan(xb, dt, A, Bb, Cb, D, chunk=16)
    assert got.dtype == jnp.bfloat16
    want = recurrence(xb.astype(jnp.float32), dt, A,
                      Bb.astype(jnp.float32), Cb.astype(jnp.float32), D)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want,
        atol=2 ** -7 * float(jnp.max(jnp.abs(want))))


def test_shapes_the_kernel_refuses():
    (x, dt, A, B, C, D), _ = inputs(1, 1, 16, 3, 1, 8, 16)
    with pytest.raises(ValueError, match="do not fit"):
        ssd_scan(x, dt, A, jnp.concatenate([B, B], axis=2), C, D)
    with pytest.raises(ValueError, match="do not fit"):
        ssd_scan(x, dt[:, :8], A, B, C, D)
    # on the chip a group's heads must fill whole 128-lane blocks
    with pytest.raises(ValueError, match="no multiple of 128"):
        _heads_per_block(3, 8, interpret=False)
    assert _heads_per_block(8, 64, interpret=False) == 2
    assert _heads_per_block(1, 64, interpret=False) == 1
    # (heads a lane block, heads a grid step): Nemotron's group of 8 is one
    # step, Granite's 64 are eight; a group no eight divides goes in the
    # widest blocks of whole lane tiles that do, or whole
    assert _head_block(8, 64, interpret=False) == (2, 8)
    assert _head_block(64, 64, interpret=False) == (2, 8)
    assert _head_block(12, 64, interpret=False) == (2, 6)
    assert _head_block(6, 64, interpret=False) == (2, 6)
    assert _head_block(3, 16, interpret=True) == (3, 3)
    assert _choose_chunk(8192) == 256 and _choose_chunk(24) == 32


# -- Nemotron's call is what it was before the grid took head blocks --------

PIN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "ssd_nemo3_call.json")
PIN_LEAVES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def pin_readings():
    """``ssd_scan`` and its six gradients at ``nemo3-ep16-solo-steady``'s
    widths — [2, 512, 64, 64], 8 groups, state 128, bf16 ``x, B, C, dy`` as
    the model hands them — on seeded inputs: a leaf's sum, the sum of its
    magnitudes and 24 entries spread over it. ``python tests/test_ssd.py``
    prints them as the data file holds them."""
    b, s, h, p, g, n = 2, 512, 64, 64, 8, 128
    k = jax.random.split(jax.random.key(2033), 7)
    bf, f32 = jnp.bfloat16, jnp.float32
    args = (
        jax.random.normal(k[0], (b, s, h, p), f32).astype(bf),
        jax.nn.softplus(jax.random.normal(k[1], (b, s, h), f32) - 2.0),
        -jnp.exp(jax.random.uniform(k[2], (h,), f32, 0.0, 2.5)),
        (jax.random.normal(k[3], (b, s, g, n), f32) * 0.5).astype(bf),
        (jax.random.normal(k[4], (b, s, g, n), f32) * 0.5).astype(bf),
        jax.random.normal(k[5], (h,), f32))
    dy = jax.random.normal(k[6], (b, s, h, p), f32).astype(bf)
    y, grads = value_and_pullback(ssd_scan, args, dy)
    out = {}
    for name, leaf in zip(PIN_LEAVES, (y,) + tuple(grads)):
        a = np.asarray(leaf.astype(f32)).astype(np.float64).ravel()
        at = np.linspace(0, a.size - 1, 24).astype(int)
        out[name] = {"sum": float(a.sum()), "abs": float(np.abs(a).sum()),
                     "samples": [float(v) for v in a[at]]}
    return out


def test_nemotrons_call_computes_what_it_computed_before_head_blocks():
    """PR 68 gave the kernels' grid a fourth axis of head blocks; at
    Nemotron's group of 8 heads it has one step and the body is the one
    it was, but for ``C·Bᵀ`` and the sums of ``dB`` / ``dC`` passing
    through scratch. The data file holds what the PARENT's kernels (commit
    8f28f33, before ``ops/ssd.py`` was touched) gave in the interpreter
    here: every leaf but ``dB`` and ``dC`` is that to the bit, and those
    two, whose last two matmuls now stand under a ``pl.when``, within the
    rounding of their bf16 results — every sampled entry equal, the sums
    within 1e-7 of the magnitudes' (read: 2e-8; one bf16 step of one entry
    is 1e-9 of it)."""
    with open(PIN) as f:
        want = json.load(f)
    got = pin_readings()
    for name in PIN_LEAVES:
        assert got[name]["samples"] == want[name]["samples"], name
        if name in ("dB", "dC"):
            assert abs(got[name]["sum"] - want[name]["sum"]) <= (
                1e-7 * want[name]["abs"]), name
        else:
            assert got[name] == want[name], name


if __name__ == "__main__":
    print(json.dumps(pin_readings(), indent=1))
