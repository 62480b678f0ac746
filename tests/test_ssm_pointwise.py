"""``ops/ssm_pointwise.py``: the two fused stages around the Mamba-2
scan against the plain f32 formulas they replaced in the model — value
and every gradient, whatever the blocks. Interpreter-mode Pallas on the
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
