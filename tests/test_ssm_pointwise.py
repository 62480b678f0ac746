"""``ops/ssm_pointwise.py``: the two fused stages around the Mamba-2
scan, and LFM2's gated short convolution (the convolution's kernel body
with two multiplicands in the bias's and silu's place), against plain
f32 formulas — value and every gradient, whatever the blocks. Interpreter-mode Pallas on the
CPU, so the shapes are small. The formulas here are the oracle (and
``scripts/ssm_pointwise_micro.py``'s jnp side);
``benchmark/reference/nemotron_h_f32.py`` stays the independent one."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import ssm_pointwise as sp

# tests/conftest.py: of the files that compile for minutes, one at a time
pytestmark = pytest.mark.usefixtures("one_compiling_file_at_a_time")

F32 = jnp.float32
# sha256 of ``conv_silu``'s result (as f32 bytes) on
# ``conv_inputs(11, 2, 48, 256, 4)`` at blocks (16, 128), f32 and bf16
# operands, in the interpreter on the CPU at commit 2d59480
CONV_SILU_AT_2D59480 = {
    "f32": "8e9d60642e6514fcaf1e34bc7c5cf1b05fe749f3f604e86dbb28821b2f629e58",
    "bf16": "8386fb57eef04996647e3f812a9d70f2f6c9f89b0810d713d3e1560b67672ed9",
}


def conv_silu_formula(x, taps, bias):
    """``silu(b + Σ_j w_j ⊙ x_{t-(K-1)+j})``: tap ``j`` multiplies the
    position ``K-1-j`` back, zeros before the sequence's start; f32, the
    result in ``x``'s dtype."""
    K, S = taps.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    conv = bias.astype(F32) + sum(
        taps[j].astype(F32) * padded[:, j:j + S] for j in range(K))
    return jax.nn.silu(conv).astype(x.dtype)


def gated_norm_formula(y, z, scale, groups: int, eps: float):
    """``RMSNorm_grouped(y ⊙ silu(z))·scale``: the gate first, then each
    of the ``groups`` runs of channels normalised alone; f32, the result
    in ``y``'s dtype."""
    B, S, I = z.shape
    gated = (y.astype(F32) * jax.nn.silu(z.astype(F32))).reshape(
        B, S, groups, I // groups)
    var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    return ((gated * jax.lax.rsqrt(var + eps)).reshape(B, S, I)
            * scale.astype(F32)).astype(y.dtype)


def gated_conv_formula(bcx, taps):
    """``C ⊙ Σ_j w_j ⊙ (B ⊙ X)_{t-(K-1)+j}``, ``[B ; C ; X]`` along the
    channels, zeros before the sequence's start; f32, the result in
    ``bcx``'s dtype."""
    (K, C), S = taps.shape, bcx.shape[1]
    b, c, x = (bcx[..., i * C:(i + 1) * C].astype(F32) for i in range(3))
    padded = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    return (c * sum(taps[j].astype(F32) * padded[:, j:j + S]
                    for j in range(K))).astype(bcx.dtype)


def gated_inputs(seed, b, s, c, k, dtype=F32):
    key = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(key[0], (b, s, 3 * c), F32).astype(dtype),
            jax.random.uniform(key[1], (k, c), F32, -0.5, 0.5),
            jax.random.normal(key[2], (b, s, c), F32).astype(dtype))


def gconv(bcx, taps, blocks=None):
    """``gated_conv`` at blocks of the test's choosing."""
    if blocks is None:
        return sp.gated_conv(bcx, taps)
    return sp._gconv(bcx, taps, blocks, sp._interpret())


def conv_inputs(seed, b, s, c, k, dtype=F32):
    key = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(key[0], (b, s, c), F32).astype(dtype),
            jax.random.uniform(key[1], (k, c), F32, -0.5, 0.5),
            jax.random.uniform(key[2], (c,), F32, -0.5, 0.5),
            jax.random.normal(key[3], (b, s, c), F32).astype(dtype))


def gate_inputs(seed, b, s, width, dtype=F32):
    key = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(key[0], (b, s, width), F32).astype(dtype),
            jax.random.normal(key[1], (b, s, width), F32).astype(dtype),
            1.0 + 0.1 * jax.random.normal(key[2], (width,), F32),
            jax.random.normal(key[3], (b, s, width), F32).astype(dtype))


def conv(x, taps, bias, blocks=None):
    """``conv_silu`` at blocks of the test's choosing: the public
    function takes none (it picks them from the shape)."""
    if blocks is None:
        return sp.conv_silu(x, taps, bias)
    return sp._conv(x, taps, bias, blocks, sp._interpret())


def gate(y, z, scale, groups, blocks=None, eps=1e-5):
    if blocks is None:
        return sp.gated_norm(y, z, scale, groups, eps)
    return sp._gate(y, z, scale, y.shape[-1] // groups, eps, blocks,
                    sp._interpret())


def assert_close(got, want, tol, name=""):
    assert np.all(np.isfinite(np.asarray(got, np.float32))), name
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol * float(jnp.max(jnp.abs(want.astype(F32)))), rtol=tol,
        err_msg=name)


# (case, B, S, C, K, (rows, lanes) a block or None)
CONV_CASES = [
    ("one-block-is-the-sequence", 1, 32, 128, 4, (32, 128)),
    ("four-blocks-the-halo-crosses-three-edges", 1, 64, 128, 4, (16, 128)),
    ("a-sequence-that-is-no-multiple-of-the-block", 1, 40, 128, 4, (16, 128)),
    ("two-batch-rows", 2, 32, 128, 4, (16, 128)),
    ("three-channel-blocks", 1, 32, 384, 4, (16, 128)),
    ("rows-and-channel-blocks-together", 2, 48, 256, 4, (16, 128)),
    ("two-taps", 1, 32, 128, 2, (16, 128)),
    ("one-tap-is-no-convolution", 1, 32, 128, 1, (16, 128)),
    ("blocks-chosen-from-the-shape", 2, 24, 64, 4, None),
    ("a-sequence-shorter-than-the-taps", 1, 2, 64, 4, None),
]


@pytest.mark.parametrize("case", CONV_CASES, ids=[c[0] for c in CONV_CASES])
def test_conv_silu_equals_the_formula(case):
    name, b, s, c, k, blocks = case
    x, taps, bias, dy = conv_inputs(len(name), b, s, c, k)
    want, pull = jax.vjp(conv_silu_formula, x, taps, bias)
    got, pull_got = jax.vjp(lambda *a: conv(*a, blocks=blocks), x, taps, bias)
    assert_close(got, want, 2e-6, "value")
    for leaf, g, w in zip(("x", "taps", "bias"), pull_got(dy), pull(dy)):
        assert g.dtype == w.dtype and g.shape == w.shape, leaf
        assert_close(g, w, 1e-5, leaf)


# (case, B, S, C, K, (rows, lanes) a block or None)
GATED_CASES = [
    ("one-block-is-the-sequence", 1, 32, 128, 3, (32, 128)),
    ("four-blocks-the-halo-crosses-three-edges", 1, 64, 128, 3, (16, 128)),
    ("a-sequence-that-is-no-multiple-of-the-block", 1, 40, 128, 3, (16, 128)),
    ("two-batch-rows", 2, 32, 128, 3, (16, 128)),
    ("three-channel-blocks-a-third", 1, 32, 384, 3, (16, 128)),
    ("rows-and-channel-blocks-together", 2, 48, 256, 3, (16, 128)),
    ("four-taps", 1, 32, 128, 4, (16, 128)),
    ("one-tap-is-no-convolution", 1, 32, 128, 1, (16, 128)),
    ("blocks-chosen-from-the-shape", 2, 24, 64, 3, None),
    ("a-sequence-shorter-than-the-taps", 1, 2, 64, 3, None),
]


@pytest.mark.parametrize("case", GATED_CASES, ids=[c[0] for c in GATED_CASES])
def test_gated_conv_equals_the_formula(case):
    name, b, s, c, k, blocks = case
    bcx, taps, dy = gated_inputs(len(name), b, s, c, k)
    want, pull = jax.vjp(gated_conv_formula, bcx, taps)
    got, pull_got = jax.vjp(lambda *a: gconv(*a, blocks=blocks), bcx, taps)
    assert got.shape == (b, s, c)
    assert_close(got, want, 2e-6, "value")
    (d_bcx, d_taps), (w_bcx, w_taps) = pull_got(dy), pull(dy)
    assert d_bcx.dtype == w_bcx.dtype and d_bcx.shape == w_bcx.shape
    # the one cotangent array, a third at a time: dB, dC, dX
    for i, leaf in enumerate(("dB", "dC", "dX")):
        assert_close(d_bcx[..., i * c:(i + 1) * c],
                     w_bcx[..., i * c:(i + 1) * c], 1e-5, leaf)
    assert d_taps.dtype == w_taps.dtype and d_taps.shape == w_taps.shape
    assert_close(d_taps, w_taps, 1e-5, "taps")


def test_gated_conv_bf16_operands_f32_inside():
    """bf16 in and out; ``B ⊙ X``, the taps' sums and ``C ⊙`` are f32:
    the f32 formula on the rounded inputs to the one rounding of the
    result, forward and backward; the taps' gradient is an f32 sum."""
    bf16 = jnp.bfloat16
    bcx, taps, dy = gated_inputs(3, 2, 48, 128, 3, bf16)
    got, pull_got = jax.vjp(lambda *a: gconv(*a, blocks=(16, 128)), bcx, taps)
    want, pull = jax.vjp(gated_conv_formula, bcx.astype(F32), taps)
    assert got.dtype == bf16
    assert_close(got, want, 2 ** -8 + 1e-5, "value")
    (d_bcx, d_taps), (w_bcx, w_taps) = pull_got(dy), pull(dy.astype(F32))
    assert d_bcx.dtype == bf16 and d_taps.dtype == taps.dtype
    assert_close(d_bcx, w_bcx, 2 ** -8 + 1e-5, "bcx")
    assert_close(d_taps, w_taps, 1e-5, "taps")


def test_gated_conv_rows_start_from_zeros_and_need_their_halo():
    """Nothing of batch row 0's tail reaches row 1, forward or backward;
    and a block's first rows differ from the same rows convolved alone
    (the cases above are no test unless dropping the halo shows)."""
    bcx, taps, dy = gated_inputs(5, 2, 32, 128, 3)
    bcx = bcx.at[0].multiply(1e3)
    both, pull = jax.vjp(lambda a: gconv(a, taps, (16, 128)), bcx)
    alone, pull_alone = jax.vjp(lambda a: gconv(a, taps, (16, 128)), bcx[1:])
    np.testing.assert_array_equal(both[1:], alone)
    np.testing.assert_array_equal(pull(dy)[0][1:], pull_alone(dy[1:])[0])
    whole = gconv(bcx[1:], taps, (16, 128))[:, 16:18]
    cut = gconv(bcx[1:, 16:], taps, (16, 128))[:, :2]
    assert float(jnp.max(jnp.abs(whole - cut))) > 0.05


def test_conv_silu_forward_is_bit_for_bit_what_it_was():
    """``conv_silu`` shares its kernel body with ``gated_conv`` since PR
    38; its forward values are those of the body it had alone (sha256 of
    the result on seeded inputs, recorded on the parent commit
    2d59480)."""
    import hashlib

    x, taps, bias, _ = conv_inputs(11, 2, 48, 256, 4)
    digests = {}
    for name, args in (("f32", (x, taps, bias)),
                       ("bf16", (x.astype(jnp.bfloat16), taps, bias))):
        out = conv(*args, blocks=(16, 128))
        digests[name] = hashlib.sha256(
            np.asarray(out.astype(F32)).tobytes()).hexdigest()
    assert digests == CONV_SILU_AT_2D59480


# (case, B, S, I, groups, (rows, lanes) a block or None)
GATE_CASES = [
    ("a-group-of-one-lane-tile", 1, 32, 256, 2, (32, 256)),
    ("a-group-of-four-lane-tiles", 1, 16, 1024, 2, (16, 512)),
    ("four-sequence-blocks", 1, 64, 256, 2, (16, 256)),
    ("a-sequence-that-is-no-multiple-of-the-block", 1, 40, 256, 2, (16, 128)),
    ("two-batch-rows", 2, 32, 256, 2, (16, 256)),
    ("a-group-a-channel-block", 1, 32, 512, 4, (16, 128)),
    ("two-groups-a-channel-block", 2, 32, 512, 4, (16, 256)),
    ("one-norm-over-all", 1, 32, 256, 1, (16, 256)),
    ("blocks-chosen-from-the-shape-narrow-groups", 2, 24, 64, 2, None),
]


@pytest.mark.parametrize("case", GATE_CASES, ids=[c[0] for c in GATE_CASES])
def test_gated_norm_equals_the_formula(case):
    name, b, s, width, groups, blocks = case
    y, z, scale, dout = gate_inputs(len(name), b, s, width)
    want, pull = jax.vjp(
        lambda *a: gated_norm_formula(*a, groups, 1e-5), y, z, scale)
    got, pull_got = jax.vjp(
        lambda *a: gate(*a, groups, blocks=blocks), y, z, scale)
    assert_close(got, want, 2e-6, "value")
    for leaf, g, w in zip(("y", "z", "scale"), pull_got(dout), pull(dout)):
        assert g.dtype == w.dtype and g.shape == w.shape, leaf
        assert_close(g, w, 1e-5, leaf)


def test_a_row_starts_from_zeros_whatever_the_row_before_ended_on():
    """Nothing of batch row 0's tail reaches row 1's first positions,
    forward or backward: row 1 alone gives what row 1 gives behind a row
    of large values."""
    x, taps, bias, dy = conv_inputs(5, 2, 32, 128, 4)
    x = x.at[0].multiply(1e3)
    both, pull = jax.vjp(lambda a: conv(a, taps, bias, (16, 128)), x)
    alone, pull_alone = jax.vjp(
        lambda a: conv(a, taps, bias, (16, 128)), x[1:])
    np.testing.assert_array_equal(both[1:], alone)
    np.testing.assert_array_equal(pull(dy)[0][1:], pull_alone(dy[1:])[0])


def test_the_first_positions_need_the_rows_before_their_block():
    """The cases above are no test unless dropping the halo shows: a
    block's first rows differ from the same rows convolved alone."""
    x, taps, bias, _ = conv_inputs(9, 1, 32, 128, 4)
    whole = conv(x, taps, bias, (16, 128))[:, 16:19]
    alone = conv(x[:, 16:], taps, bias, (16, 128))[:, :3]
    assert float(jnp.max(jnp.abs(whole - alone))) > 0.05


@pytest.mark.parametrize("stage", ["conv_silu", "gated_norm"])
def test_bf16_operands_f32_inside(stage):
    """bf16 in and out; what lies between is f32: the result is the f32
    formula on the rounded inputs, to the one rounding of the result,
    and so is every gradient."""
    bf16 = jnp.bfloat16
    if stage == "conv_silu":
        x, taps, bias, dy = conv_inputs(3, 2, 48, 128, 4, bf16)
        args, cot = (x, taps, bias), dy
        got, pull_got = jax.vjp(lambda *a: conv(*a, blocks=(16, 128)), *args)
        want, pull = jax.vjp(
            conv_silu_formula, x.astype(F32), taps, bias)
    else:
        y, z, scale, dout = gate_inputs(4, 2, 48, 256, bf16)
        args, cot = (y, z, scale), dout
        got, pull_got = jax.vjp(
            lambda *a: gate(*a, 2, blocks=(16, 128)), *args)
        want, pull = jax.vjp(
            lambda *a: gated_norm_formula(*a, 2, 1e-5),
            y.astype(F32), z.astype(F32), scale)
    assert got.dtype == bf16
    # half a unit in bf16's last place, and f32's rounding before it
    assert_close(got, want, 2 ** -8 + 1e-5, "value")
    grads, wants = pull_got(cot), pull(cot.astype(F32))
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    assert_close(grads[0], wants[0], 2 ** -8 + 1e-5, "first operand")
    if stage == "gated_norm":
        assert_close(grads[1], wants[1], 2 ** -8 + 1e-5, "z")
    # the parameters' gradients are f32 sums: no rounding of their own
    for g, w in zip(grads[-2:] if stage == "conv_silu" else grads[-1:],
                    wants[-2:] if stage == "conv_silu" else wants[-1:]):
        assert_close(g, w, 1e-5, "parameter")


def test_shapes_the_kernels_refuse():
    x, taps, bias, _ = conv_inputs(1, 1, 16, 128, 4)
    with pytest.raises(ValueError, match="do not fit"):
        sp.conv_silu(x, taps[:, :64], bias)
    with pytest.raises(ValueError, match="do not fit"):
        sp.conv_silu(x, taps, bias[:64])
    with pytest.raises(ValueError, match="the halo holds 8 rows"):
        sp.conv_silu(x, jnp.zeros((10, 128)), bias)
    bcx, taps3, _ = gated_inputs(1, 1, 16, 128, 3)
    with pytest.raises(ValueError, match="do not fit"):
        sp.gated_conv(bcx[..., :256], taps3)
    with pytest.raises(ValueError, match="do not fit"):
        sp.gated_conv(bcx, taps3[:, :64])
    with pytest.raises(ValueError, match="the halo holds 8 rows"):
        sp.gated_conv(bcx, jnp.zeros((10, 128)))
    y, z, scale, _ = gate_inputs(1, 1, 16, 256)
    with pytest.raises(ValueError, match="do not fit"):
        sp.gated_norm(y, z[:, :8], scale, 2, 1e-5)
    with pytest.raises(ValueError, match="do not fit"):
        sp.gated_norm(y, z, scale, 3, 1e-5)
    with pytest.raises(ValueError, match="do not fit"):
        sp.gated_norm(y, z, scale[:128], 2, 1e-5)
    # on the chip channels come in whole 128-lane tiles
    with pytest.raises(ValueError, match="no multiple of 128 lanes"):
        sp._refuse_lanes("conv_silu", 96, interpret=False)
    sp._refuse_lanes("conv_silu", 96, interpret=True)
    sp._refuse_lanes("conv_silu", 6144, interpret=False)


def test_blocks_are_chosen_from_the_shape():
    # LFM2's: [4, 8192, 3 x 2048], a third a time
    assert sp._lane_block(2048) == 512 and sp._row_block(8192, 512) == 512
    # the cell's: [4, 8192, 6144] and [4, 8192, 4096] in 8 groups
    assert sp._lane_block(6144) == 512 and sp._row_block(8192, 512) == 512
    assert sp._lane_block(4096, 512) == 512
    # whole groups only: a wide group is a block, narrow ones share one
    assert sp._lane_block(4096, 4096) == 4096
    assert sp._row_block(8192, 4096) == 64
    assert sp._lane_block(1024, 128) == 512
    # no multiple of a lane tile: the whole width (off the TPU)
    assert sp._lane_block(64) == 64 and sp._lane_block(64, 32) == 64
    # a short sequence whole; a divisor where one is near; else ragged
    assert sp._row_block(24, 512) == 24
    assert sp._row_block(8960, 512) == 448
    assert sp._row_block(1000, 512) == 512
