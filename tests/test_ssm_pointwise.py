"""``ops/ssm_pointwise.py``: the two fused stages around the Mamba-2
scan, LFM2's gated short convolution (the convolution's kernel body
with two multiplicands in the bias's and silu's place), and the two
around the delta rule (l2 norms and decay before it; head norm, then a
sigmoid gate, behind it), and the two around Laguna's attention call (the
rotation before it, the gate a head behind it: the jnp forms the model
had, to the bit but for a contracted multiply-add), against plain
f32 formulas — value and every gradient, whatever the blocks. Interpreter-mode Pallas on the
CPU, so the shapes are small. The formulas here are the oracle (and
``scripts/ssm_pointwise_micro.py``'s jnp side);
``benchmark/reference/nemotron_h_f32.py`` stays the independent one."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from one_program import value_and_pullback
from torchft_tpu.models import common
from torchft_tpu.ops import ssm_pointwise as sp


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


L2_EPS = 1e-6


def l2_normed(x):
    """A head's l2 normalisation, as ``models/kimi_linear.py`` has it."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def head_norm_then_gate(o, scale, gate, eps):
    """``RMSNorm_head(o) ⊙ σ(gate)``: the norm first."""
    o = o.astype(F32)
    normed = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)
    return normed * scale.astype(F32) * jax.nn.sigmoid(gate.astype(F32))


def kda_qkg_formula(qkv, f, dt_bias, a_log):
    """``q̃ / ‖q̃‖ · D^{-1/2}``, ``k̃ / ‖k̃‖`` and ``v`` in ``qkv``'s dtype,
    ``−exp(A_log_h) · softplus(f + dt_bias)`` in f32; a head at a time,
    f32 inside."""
    (B, S, W), H = f.shape, a_log.shape[0]
    D = W // H
    q, k, v = (qkv[..., i * W:(i + 1) * W].reshape(B, S, H, D)
               for i in range(3))
    g = -jnp.exp(a_log.astype(F32))[:, None] * jax.nn.softplus(
        f.astype(F32).reshape(B, S, H, D) + dt_bias.astype(F32).reshape(H, D))
    return ((l2_normed(q) * D ** -0.5).astype(qkv.dtype).reshape(B, S, W),
            l2_normed(k).astype(qkv.dtype).reshape(B, S, W),
            v.reshape(B, S, W), g.reshape(B, S, W))


def kda_ogate_formula(o, gate, scale, eps):
    B, S, W = o.shape
    heads = (B, S, W // scale.shape[0], scale.shape[0])
    return head_norm_then_gate(o.reshape(heads), scale, gate.reshape(heads),
                               eps).astype(o.dtype).reshape(B, S, W)


def qkg_inputs(seed, b, s, h, d, dtype=F32):
    """The operands, and a cotangent for each of ``q, k, v, g``;
    ``dt_bias`` and ``A_log`` as ``models/kimi_linear.py`` draws them,
    their first channels and heads at the initialiser's extremes (a step
    of 1e-3 and of 1e-1, a rate of 1 and of 16)."""
    key = jax.random.split(jax.random.key(seed), 8)
    w = h * d
    dt = jnp.exp(jax.random.uniform(
        key[2], (w,), F32, np.log(1e-3), np.log(1e-1)))
    dt = dt.at[0].set(1e-3).at[1].set(1e-1)
    a_log = jnp.log(jax.random.uniform(key[3], (h,), F32, 1.0, 16.0))
    a_log = a_log.at[0].set(0.0).at[-1].set(np.log(16.0))
    cots = tuple(jax.random.normal(key[4 + i], (b, s, w), F32).astype(
        F32 if i == 3 else dtype) for i in range(4))
    return (jax.random.normal(key[0], (b, s, 3 * w), F32).astype(dtype),
            2.0 * jax.random.normal(key[1], (b, s, w), F32).astype(dtype),
            dt + jnp.log(-jnp.expm1(-dt)), a_log), cots


def qkg(qkv, f, dt_bias, a_log, blocks=None):
    """``kda_qkg`` at blocks of the test's choosing."""
    if blocks is None:
        return sp.kda_qkg(qkv, f, dt_bias, a_log, l2_normed)
    head = f.shape[-1] // a_log.shape[0]
    return sp._qkg(qkv, f, dt_bias, jnp.repeat(a_log, head), head, l2_normed,
                   blocks, sp._interpret())


def ogate(o, gate, scale, blocks=None, eps=1e-5):
    if blocks is None:
        return sp.kda_ogate(o, gate, scale, eps, head_norm_then_gate)
    return sp._ogate(o, gate, scale, scale.shape[0], eps,
                     head_norm_then_gate, blocks, sp._interpret())


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
    want, grads_want = value_and_pullback(
        conv_silu_formula, (x, taps, bias), dy)
    got, grads = value_and_pullback(
        lambda *a: conv(*a, blocks=blocks), (x, taps, bias), dy)
    assert_close(got, want, 2e-6, "value")
    for leaf, g, w in zip(("x", "taps", "bias"), grads, grads_want):
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
    want, (w_bcx, w_taps) = value_and_pullback(
        gated_conv_formula, (bcx, taps), dy)
    got, (d_bcx, d_taps) = value_and_pullback(
        lambda *a: gconv(*a, blocks=blocks), (bcx, taps), dy)
    assert got.shape == (b, s, c)
    assert_close(got, want, 2e-6, "value")
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
    want, grads_want = value_and_pullback(
        lambda *a: gated_norm_formula(*a, groups, 1e-5), (y, z, scale), dout)
    got, grads = value_and_pullback(
        lambda *a: gate(*a, groups, blocks=blocks), (y, z, scale), dout)
    assert_close(got, want, 2e-6, "value")
    for leaf, g, w in zip(("y", "z", "scale"), grads, grads_want):
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


# (case, B, S, heads, a head's channels, (rows, lanes) a block or None)
KDA_CASES = [
    ("one-block-heads-of-16", 1, 32, 4, 16, None),
    ("two-channel-blocks-of-two-heads", 1, 32, 4, 128, (32, 256)),
    ("four-sequence-blocks", 1, 64, 2, 128, (16, 256)),
    ("a-sequence-that-is-no-multiple-of-the-block", 1, 40, 2, 128, (16, 128)),
    ("two-batch-rows", 2, 32, 2, 128, (16, 256)),
    ("a-head-a-channel-block", 1, 32, 3, 128, (16, 128)),
    ("rows-and-channel-blocks-together", 2, 48, 4, 128, (16, 256)),
    ("blocks-chosen-from-the-shape-narrow-heads", 2, 24, 4, 16, None),
]
KDA_IDS = [c[0] for c in KDA_CASES]


@pytest.mark.parametrize("case", KDA_CASES, ids=KDA_IDS)
def test_kda_qkg_equals_the_formula(case):
    """Value and the ``jax.vjp`` of every operand, the parameters at the
    initialiser's extremes, and a head whose ``q̃`` is all zeros at a
    position (``L2_EPS`` alone under the root)."""
    name, b, s, h, d, blocks = case
    (qkv, f, dt_bias, a_log), cots = qkg_inputs(len(name), b, s, h, d)
    qkv = qkv.at[0, 3, :d].set(0.0)
    want, (w_qkv, *wants) = value_and_pullback(
        kda_qkg_formula, (qkv, f, dt_bias, a_log), cots)
    got, (d_qkv, *grads) = value_and_pullback(
        lambda *a: qkg(*a, blocks=blocks), (qkv, f, dt_bias, a_log), cots)
    for leaf, g, w in zip(("q", "k", "v", "g"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape == f.shape, leaf
        assert_close(g, w, 2e-6, leaf)
    assert not np.any(np.asarray(got[0][0, 3, :d]))
    # the one cotangent array, a third at a time: dq̃, dk̃, dv
    assert d_qkv.dtype == w_qkv.dtype and d_qkv.shape == w_qkv.shape
    w = h * d
    for i, leaf in enumerate(("dq~", "dk~", "dv")):
        assert_close(d_qkv[..., i * w:(i + 1) * w],
                     w_qkv[..., i * w:(i + 1) * w], 1e-5, leaf)
    np.testing.assert_array_equal(d_qkv[..., 2 * w:], cots[2])
    for leaf, g, w in zip(("f", "dt_bias", "A_log"), grads, wants):
        assert g.dtype == w.dtype and g.shape == w.shape, leaf
        assert_close(g, w, 1e-5, leaf)


@pytest.mark.parametrize("case", KDA_CASES, ids=KDA_IDS)
def test_kda_ogate_equals_the_formula(case):
    name, b, s, h, d, blocks = case
    o, gate, _, dy = gate_inputs(len(name), b, s, h * d)
    scale = jnp.linspace(0.5, 1.5, d)
    want, grads_want = value_and_pullback(
        lambda *a: kda_ogate_formula(*a, 1e-5), (o, gate, scale), dy)
    got, grads = value_and_pullback(
        lambda *a: ogate(*a, blocks=blocks), (o, gate, scale), dy)
    assert_close(got, want, 2e-6, "value")
    for leaf, g, w in zip(("o", "gate", "scale"), grads, grads_want):
        assert g.dtype == w.dtype and g.shape == w.shape, leaf
        assert_close(g, w, 1e-5, leaf)


@pytest.mark.parametrize("stage", ["kda_qkg", "kda_ogate"])
def test_kda_bf16_operands_f32_inside(stage):
    """bf16 in and out, the decay out and its cotangent in f32; what
    lies between is f32: the f32 formula on the rounded inputs to the
    one rounding of each result, and so is every gradient; the
    parameters' are f32 sums."""
    bf16, one = jnp.bfloat16, 2 ** -8 + 1e-5

    def up(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)

    if stage == "kda_qkg":
        args, cot = qkg_inputs(3, 2, 48, 2, 128, bf16)
        got, pull_got = jax.vjp(lambda *a: qkg(*a, blocks=(16, 128)), *args)
        want, pull = jax.vjp(kda_qkg_formula, *up(args))
        assert [g.dtype for g in got] == [bf16, bf16, bf16, F32]
        for leaf, g, w in zip(("q", "k", "v"), got[:3], want[:3]):
            assert_close(g, w, one, leaf)
        assert_close(got[3], want[3], 2e-6, "g")
    else:
        o, gate, _, cot = gate_inputs(4, 2, 48, 256, bf16)
        args = (o, gate, jnp.linspace(0.5, 1.5, 128))
        got, pull_got = jax.vjp(lambda *a: ogate(*a, blocks=(16, 128)), *args)
        want, pull = jax.vjp(
            lambda *a: kda_ogate_formula(*a, 1e-5), *up(args))
        assert got.dtype == bf16
        assert_close(got, want, one, "value")
    grads, wants = pull_got(cot), pull(up(cot))
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    # two bf16 operands, then the parameters
    for g, w in zip(grads[:2], wants[:2]):
        assert_close(g, w, one, "operand")
    for g, w in zip(grads[2:], wants[2:]):
        assert_close(g, w, 1e-5, "parameter")


def test_the_kernels_call_the_callers_head_functions():
    """What a head's normalisation and gate ARE is the caller's: another
    function in their place changes the result (``models/kimi_linear.py``
    hands over its seams, which the benchmark's faults patch), and a
    counter sees ``q``'s call before ``k``'s, pair by pair, forward and
    backward."""
    (qkv, f, dt_bias, a_log), cots = qkg_inputs(7, 1, 32, 2, 128)
    calls = []

    def q_dropped(x):
        calls.append(len(calls) % 2)
        return x.astype(F32) if calls[-1] == 0 else l2_normed(x)

    def run(*a):
        return sp._qkg(*a, 128, q_dropped, (16, 128), sp._interpret())

    got, pull = jax.vjp(run, qkv, f, dt_bias, jnp.repeat(a_log, 128))
    want = kda_qkg_formula(qkv, f, dt_bias, a_log)
    assert calls and len(calls) % 2 == 0
    np.testing.assert_allclose(got[0], qkv[..., :256] * 128 ** -0.5,
                               rtol=1e-6)
    assert_close(got[1], want[1], 2e-6, "k")
    np.testing.assert_allclose(pull(cots)[0][..., :256],
                               cots[0] * 128 ** -0.5, rtol=1e-6)
    o, gate, _, _ = gate_inputs(7, 1, 32, 256)
    scale = jnp.linspace(0.5, 1.5, 128)
    gate_first = sp.kda_ogate(
        o, gate, scale, 1e-5, lambda o, s, z, eps: head_norm_then_gate(
            o * jax.nn.sigmoid(z), s, jnp.full_like(z, 1e9), eps))
    assert float(jnp.max(jnp.abs(gate_first - ogate(o, gate, scale)))) > 0.05


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
    (qkv, f, dt_bias, a_log), _ = qkg_inputs(1, 1, 16, 2, 128)
    with pytest.raises(ValueError, match="kda_qkg: .* do not fit"):
        sp.kda_qkg(qkv[..., :512], f, dt_bias, a_log, l2_normed)
    with pytest.raises(ValueError, match="kda_qkg: .* do not fit"):
        sp.kda_qkg(qkv, f, dt_bias[:128], a_log, l2_normed)
    with pytest.raises(ValueError, match="kda_qkg: .* do not fit"):
        sp.kda_qkg(qkv, f, dt_bias, jnp.zeros((3,)), l2_normed)
    with pytest.raises(ValueError, match="kda_ogate: .* do not fit"):
        sp.kda_ogate(y, z[:, :8], scale[:128], 1e-5, head_norm_then_gate)
    with pytest.raises(ValueError, match="kda_ogate: .* do not fit"):
        sp.kda_ogate(y, z, scale[:96], 1e-5, head_norm_then_gate)
    # on the chip channels come in whole 128-lane tiles, and so do heads
    with pytest.raises(ValueError, match="kda_qkg: a head's: 16 channels "
                                         "are no multiple of 128 lanes"):
        sp._refuse_lanes("kda_qkg: a head's", 16, interpret=False)
    with pytest.raises(ValueError, match="no multiple of 128 lanes"):
        sp._refuse_lanes("conv_silu", 96, interpret=False)
    sp._refuse_lanes("conv_silu", 96, interpret=True)
    sp._refuse_lanes("conv_silu", 6144, interpret=False)


def test_on_the_chip_a_head_is_whole_lane_tiles(monkeypatch):
    """Heads of 16 run in the interpreter (the tiny model's); where the
    kernels would be compiled they are refused by name, before any
    kernel is built."""
    monkeypatch.setattr(sp, "_interpret", lambda: False)
    (qkv, f, dt_bias, a_log), _ = qkg_inputs(1, 1, 16, 4, 16)
    with pytest.raises(ValueError, match="kda_qkg: a head's: 16 channels "
                                         "are no multiple of 128 lanes"):
        sp.kda_qkg(qkv, f, dt_bias, a_log, l2_normed)
    # the head norm takes any head whose blocks can be whole heads AND
    # whole lane tiles: 4 heads of 16 are half a tile, 2 of 192 are three
    with pytest.raises(ValueError, match="kda_ogate: 64 channels in heads "
                                         "of 16 are no whole blocks of 128"):
        sp.kda_ogate(f, f, jnp.ones((16,)), 1e-5, head_norm_then_gate)
    odd = jnp.ones((1, 16, 3 * 192))
    with pytest.raises(ValueError, match="kda_ogate: 576 channels in heads "
                                         "of 192 are no whole blocks of 384"):
        sp.kda_ogate(odd, odd, jnp.ones((192,)), 1e-5, head_norm_then_gate)


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
    # kimi's: [4, 8192, 4096] in 32 heads of 128, four heads a block
    assert sp._lane_block(4096, 128) == 512
    # no multiple of a lane tile: the whole width (off the TPU)
    assert sp._lane_block(64) == 64 and sp._lane_block(64, 32) == 64
    # Olmo Hybrid's head norm: [1, 8192, 5760] in 30 heads of 192, two
    # heads (three lane tiles) a block; an odd number of such heads is no
    # whole blocks
    assert sp._whole_tiles(192) == 384 and sp._whole_tiles(128) == 128
    assert sp._lane_block(5760, 192) == 384
    assert sp._row_block(8192, 384) == 512
    assert sp._lane_block(3 * 192, 192) == 3 * 192
    # a short sequence whole; a divisor where one is near; else ragged
    assert sp._row_block(24, 512) == 24
    assert sp._row_block(8960, 512) == 448
    assert sp._row_block(1000, 512) == 512


# -- the delta rule's two stages at the cell's widths, for a described v5e ----


def test_the_kda_kernels_compile_for_the_v5e_at_the_cells_widths(one_chip):
    """[1, 8192] of 32 heads of 128 at the blocks the shape chooses:
    Mosaic takes the seams' jnp code and its ``jax.vjp`` on a head's
    lane tile, and the blocks fit the kernels' VMEM (the chip's compiler
    alone: nothing runs)."""
    from jax.experimental.compilation_cache import compilation_cache

    b, s, h, d = 1, 8192, 32, 128
    w, bf16 = h * d, jnp.bfloat16
    blocks = sp._row_block(s, sp._lane_block(w, d)), sp._lane_block(w, d)
    assert blocks == (512, 512)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(qkv, f, dt_bias, a_chan, cots, o, gate, scale, dy):
        qkg, pull = jax.vjp(lambda *a: sp._qkg(
            *a, d, l2_normed, blocks, False), qkv, f, dt_bias, a_chan)
        y, pull_y = jax.vjp(lambda *a: sp._ogate(
            *a, d, 1e-5, head_norm_then_gate, blocks, False), o, gate, scale)
        return qkg, pull(cots), y, pull_y(dy)

    wide = sd((b, s, w), bf16)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(both).lower(
            sd((b, s, 3 * w), bf16), wide, sd((w,), F32), sd((w,), F32),
            (wide, wide, wide, sd((b, s, w), F32)), wide, wide,
            sd((d,), F32), wide).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    for kernel in ("kda_qkg_fwd", "kda_qkg_bwd", "kda_ogate_fwd",
                   "kda_ogate_bwd"):
        assert kernel in text, kernel



def silu_gate_after_norm(o, scale, gate, eps):
    """Olmo Hybrid's seam: the head norm, then a SiLU gate."""
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * r * scale * (gate * jax.nn.sigmoid(gate))


@pytest.mark.parametrize("heads,head", [(4, 24), (6, 192)])
def test_kda_ogate_takes_the_heads_width_and_the_gates_activation(heads, head):
    """A head of any width (the weight's) and the caller's activation: a
    SiLU after the norm at 24 and at 192 lanes (two heads to three tiles
    on the chip), forward and the three cotangents against jnp."""
    k = jax.random.split(jax.random.key(heads), 4)
    o, gate, dy = (jax.random.normal(k[i], (2, 40, heads * head))
                   for i in range(3))
    scale = 1.0 + 0.1 * jax.random.normal(k[3], (head,))

    def formula(o, gate, scale):
        shape = o.shape[:2] + (heads, head)
        return silu_gate_after_norm(
            o.reshape(shape), scale, gate.reshape(shape), 1e-6
        ).reshape(o.shape)

    got, pull = jax.vjp(lambda o, gate, scale: sp.kda_ogate(
        o, gate, scale, 1e-6, silu_gate_after_norm), o, gate, scale)
    want, pull_ref = jax.vjp(formula, o, gate, scale)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for a, b in zip(pull(dy), pull_ref(dy)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_the_head_norm_compiles_for_the_v5e_at_heads_of_192(one_chip):
    """[1, 8192] of 30 heads of 192 with the SiLU seam at the blocks the
    shape chooses (512 rows x two heads): Mosaic takes a head that starts
    between lane tiles, forward and backward."""
    from jax.experimental.compilation_cache import compilation_cache

    b, s, h, d = 1, 8192, 30, 192
    w, bf16 = h * d, jnp.bfloat16
    blocks = sp._row_block(s, sp._lane_block(w, d)), sp._lane_block(w, d)
    assert blocks == (512, 384)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(o, gate, scale, dy):
        y, pull = jax.vjp(lambda *a: sp._ogate(
            *a, d, 1e-6, silu_gate_after_norm, blocks, False), o, gate, scale)
        return y, pull(dy)

    wide = sd((b, s, w), bf16)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(both).lower(
            wide, wide, sd((d,), F32), wide).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "kda_ogate_fwd" in text and "kda_ogate_bwd" in text


# -- around the attention call: the rotation, and the gate a head -------------


def rotary_formula(x, freqs, factor, head):
    """What ``models/laguna.py`` did until PR 62: ``common.rotary`` on
    ``[B, S, H, D]``, then the flash call's turn to the heads first."""
    b, s, w = x.shape
    return common.rotary(x.reshape(b, s, w // head, head), freqs,
                         factor).transpose(0, 2, 1, 3)


def gate_heads_formula(o, gate):
    """Likewise: the flash call's result turned back to ``[B, S, H, D]``,
    an f32 multiply by the head's gate, one rounding."""
    b, h, s, d = o.shape
    return (o.transpose(0, 2, 1, 3).astype(F32) * gate[..., None]).astype(
        o.dtype).reshape(b, s, h * d)


def rotary_inputs(seed, b, s, heads, head, half, dtype=jnp.bfloat16):
    """``x``, the frequencies and a cotangent, heads first."""
    key = jax.random.split(jax.random.key(seed), 2)
    return (jax.random.normal(key[0], (b, s, heads * head), F32).astype(dtype),
            jnp.asarray(1e4 ** (-np.arange(half) / half), F32),
            jax.random.normal(key[1], (b, heads, s, head), F32).astype(dtype))


def head_gate_inputs(seed, b, s, heads, head, dtype=jnp.bfloat16):
    """``o`` heads first, a gate in (0, 1) and a cotangent."""
    key = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(key[0], (b, heads, s, head), F32).astype(dtype),
            jax.nn.sigmoid(jax.random.normal(key[1], (b, s, heads), F32)),
            jax.random.normal(key[2], (b, s, heads * head), F32).astype(dtype))


def ulps_apart_traced(got, want):
    """``(share of elements that differ, the most they differ by in units
    of the last place)`` of two bf16 arrays of one shape, as arrays (the
    micro script jits it: its arrays are half a gigabyte)."""
    def ordered(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.int16).astype(jnp.int32)
        return jnp.where(bits < 0, -(bits & 0x7FFF), bits)
    return (jnp.mean((got != want).astype(F32)),
            jnp.max(jnp.abs(ordered(got) - ordered(want))))


def ulps_apart(got, want):
    share, most = ulps_apart_traced(got, want)
    return float(share), int(most)


def assert_rounds_alike(got, want, name):
    """Bit for bit, but for what a contracted multiply-add moves: two
    products and a sum in f32 are the jnp form's, and where XLA's CPU
    compiler fuses one product into the sum on one side the f32 value
    moves by half a place of the PRODUCT's last digit, which shows in
    bf16 on a rounding boundary or where the two products cancel — a few
    elements in ten thousand, by one place of bf16 or by a few of f32's
    of the operands' size."""
    assert got.dtype == want.dtype and got.shape == want.shape, name
    share, _ = ulps_apart(got, want)
    assert share < 2e-3, (name, share)
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.abs(g - w) <= 2.0 ** -7 * np.abs(w)
                  + 1e-6 * np.abs(w).max()), name


# (case, B, S, heads, a head's lanes, half the turned lanes, the factor,
#  (rows, lanes) a block or None)
ROTARY_CASES = [
    ("the-whole-head", 1, 32, 2, 128, 64, 1.0, None),
    ("half-a-head-and-a-factor", 1, 32, 2, 128, 32, 1.25, None),
    ("64-heads-in-blocks-of-four", 1, 16, 64, 128, 64, 1.0, (16, 512)),
    ("48-heads-half-a-head", 1, 16, 48, 128, 32, 1.25, (16, 512)),
    ("8-heads-two-batch-rows-two-row-blocks", 2, 32, 8, 128, 64, 1.0,
     (16, 512)),
    ("a-head-of-32-lanes-three-heads-a-block", 2, 24, 3, 32, 8, 1.25, None),
]


@pytest.mark.parametrize("case", ROTARY_CASES,
                         ids=[c[0] for c in ROTARY_CASES])
def test_rotary_equals_the_jnp_form(case):
    """bf16 in and out, f32 inside: the rotated values and ``dx`` are
    ``common.rotary``'s and its autodiff's, heads first."""
    name, b, s, heads, head, half, factor, blocks = case
    x, freqs, dy = rotary_inputs(len(name), b, s, heads, head, half)

    def kernel(x):
        cos, sin = sp.rotary_tables(freqs, s, head, factor)
        if blocks is None:
            return sp.rotary(x, cos, sin, half)
        return sp._rotary(x, cos, sin, half, blocks, sp._interpret())

    want, (dx_want,) = value_and_pullback(
        lambda x: rotary_formula(x, freqs, factor, head), (x,), dy)
    got, (dx,) = value_and_pullback(kernel, (x,), dy)
    assert got.shape == (b, heads, s, head)
    assert_rounds_alike(got, want, "value")
    assert_rounds_alike(dx, dx_want, "dx")
    lanes = x.reshape(b, s, heads, head).transpose(0, 2, 1, 3)
    # the lanes beyond pass; the turned ones turn (but at position 0)
    np.testing.assert_array_equal(got[..., 2 * half:], lanes[..., 2 * half:])
    assert float(jnp.max(jnp.abs(
        (got - lanes)[:, :, 1:, :2 * half].astype(F32)))) > 0.1


# (case, B, S, heads, a head's lanes, rows a block or None)
HEAD_GATE_CASES = [
    ("two-heads-one-block", 1, 32, 2, 128, None),
    ("64-heads", 1, 16, 64, 128, None),
    ("48-heads-two-row-blocks", 1, 32, 48, 128, 16),
    ("two-batch-rows-three-heads-of-32", 2, 48, 3, 32, 16),
]


@pytest.mark.parametrize("case", HEAD_GATE_CASES,
                         ids=[c[0] for c in HEAD_GATE_CASES])
def test_gate_heads_equals_the_jnp_form(case):
    """One f32 multiply and one rounding forward and in ``do``: bit for
    bit; ``dgate`` is an f32 sum over a head's lanes in another order."""
    name, b, s, heads, head, rows = case
    o, gate, dy = head_gate_inputs(len(name), b, s, heads, head)
    want, (do_want, dg_want) = value_and_pullback(
        gate_heads_formula, (o, gate), dy)
    got, (do, dg) = value_and_pullback(
        sp.gate_heads if rows is None else
        lambda o, g: sp._hgate(o, g, rows, sp._interpret()), (o, gate), dy)
    assert got.dtype == o.dtype and got.shape == (b, s, heads * head)
    np.testing.assert_array_equal(got, want)
    assert do.dtype == o.dtype and do.shape == o.shape
    np.testing.assert_array_equal(do, do_want)
    assert dg.dtype == F32 and dg.shape == gate.shape
    np.testing.assert_allclose(
        dg, dg_want, rtol=1e-6, atol=1e-6 * float(jnp.max(jnp.abs(dg_want))))


def test_what_the_attention_kernels_refuse(monkeypatch):
    """Shapes that do not fit, a gate that is not f32, and a sequence
    that is no whole row blocks, each by name and before a kernel is
    built; on the chip a head that is no whole lane tiles."""
    x, freqs, _ = rotary_inputs(1, 1, 16, 2, 128, 64)
    cos, sin = sp.rotary_tables(freqs, 16, 128)
    with pytest.raises(ValueError, match="rotary: .* do not fit"):
        sp.rotary(x[:, :8], cos, sin, 64)
    with pytest.raises(ValueError, match="rotary: .* do not fit"):
        sp.rotary(x[..., :192], cos, sin, 64)
    with pytest.raises(ValueError, match="rotary: .* over 256 lanes"):
        sp.rotary(x, cos, sin, 128)
    o, gate, _ = head_gate_inputs(1, 1, 16, 2, 128)
    with pytest.raises(ValueError, match="gate_heads: .* do not fit"):
        sp.gate_heads(o, gate[..., :1])
    with pytest.raises(ValueError, match="gate_heads: .*bfloat16 gate"):
        sp.gate_heads(o, gate.astype(jnp.bfloat16))
    # two heads of 128: blocks of 2048 rows, or of the 16 in 1040 .. 2048
    # that divide the sequence: 2072 = 8 x 7 x 37 has none
    assert sp._row_block(2072, 256, sp._ATTN_BLOCK_ELEMS) == 2048
    long = jnp.zeros((1, 2072, 256), jnp.bfloat16)
    table = jnp.zeros((2072, 128), F32)
    with pytest.raises(ValueError, match="rotary: a sequence of 2072 is no "
                                         "whole blocks of 2048 rows"):
        sp.rotary(long, table, table, 64)
    with pytest.raises(ValueError, match="gate_heads: a sequence of 2072 is "
                                         "no whole blocks of 2048 rows"):
        sp.gate_heads(jnp.zeros((1, 2, 2072, 128), jnp.bfloat16),
                      jnp.zeros((1, 2072, 2), F32))
    monkeypatch.setattr(sp, "_interpret", lambda: False)
    narrow, f, _ = rotary_inputs(1, 1, 16, 4, 32, 16)
    with pytest.raises(ValueError, match="rotary: a head's: 32 channels "
                                         "are no multiple of 128 lanes"):
        sp.rotary(narrow, *sp.rotary_tables(f, 16, 32), 16)
    with pytest.raises(ValueError, match="gate_heads: a head's: 32 channels "
                                         "are no multiple of 128 lanes"):
        sp.gate_heads(jnp.zeros((1, 4, 16, 32), jnp.bfloat16),
                      jnp.zeros((1, 16, 4), F32))


def test_the_attention_kernels_compile_for_the_v5e_at_the_cells_widths(
        one_chip):
    """[4, 8192] of 64 heads of 128 turned whole and of 48 turned over
    half a head, and the gate at 64 heads, at the blocks the shapes
    choose: Mosaic takes the rolls inside a lane tile, the heads' loops
    with their lane offsets of whole tiles, the gate's lanes rolled by a
    loop's index and a head's column of it broadcast along the lanes (the
    chip's compiler alone: nothing runs)."""
    from jax.experimental.compilation_cache import compilation_cache

    b, s, d, bf16 = 4, 8192, 128, jnp.bfloat16
    for heads in (64, 48, 8):       # eight heads a block, 512 rows
        assert sp._lane_block(heads * d, d, sp._ROTARY_LANES) == 1024
    # the gate's heads eight a trip of its loop; what divides the tiny ones
    assert [sp._head_groups(h) for h in (64, 48, 6, 4, 3)] == [8, 8, 6, 4, 3]
    rows = functools.partial(sp._row_block, s, elems=sp._ATTN_BLOCK_ELEMS)
    assert rows(1024) == 512 and rows(64 * d) == rows(48 * d) == 64

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(q, q48, cos, sin, dq, dq48, o, gate, dy):
        whole, pull = jax.vjp(lambda x: sp.rotary(x, cos, sin, 64), q)
        part, pull48 = jax.vjp(lambda x: sp.rotary(x, cos, sin, 32), q48)
        y, pull_y = jax.vjp(sp.gate_heads, o, gate)
        return whole, pull(dq), part, pull48(dq48), y, pull_y(dy)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkey = pytest.MonkeyPatch()
    monkey.setattr(sp, "_interpret", lambda: False)
    try:
        text = jax.jit(both).lower(
            sd((b, s, 64 * d), bf16), sd((b, s, 48 * d), bf16),
            sd((s, d), F32), sd((s, d), F32), sd((b, 64, s, d), bf16),
            sd((b, 48, s, d), bf16), sd((b, 64, s, d), bf16),
            sd((b, s, 64), F32), sd((b, s, 64 * d), bf16)
        ).compile().as_text()
    finally:
        monkey.undo()
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    for kernel in ("rotary_fwd", "rotary_bwd", "head_gate_fwd",
                   "head_gate_bwd"):
        assert kernel in text, kernel
