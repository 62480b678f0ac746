"""Flash-attention kernel tests (interpret mode on CPU; the same kernel
compiles for TPU)."""

import contextlib
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from one_program import value_and_pullback
from torchft_tpu.ops.attention import reference_attention
from torchft_tpu.ops.flash import flash_attention


def _rand(shape, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 256, 4, 64), (1, 128, 2, 32)])
def test_flash_matches_reference(causal, shape) -> None:
    q, k, v = (_rand(shape, i) for i in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    expected = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5
    )


def test_flash_bf16() -> None:
    shape = (1, 128, 2, 64)
    q, k, v = (_rand(shape, i, jnp.bfloat16) for i in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    expected = reference_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(expected, dtype=np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_flash_gradients_match_reference() -> None:
    shape = (1, 128, 2, 32)
    q, k, v = (_rand(shape, i) for i in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


def test_flash_rejects_ragged_seq() -> None:
    q = _rand((1, 100, 2, 32), 0)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, q, q, block_q=64, block_k=64, interpret=True)


def test_flash_jit_under_model_dispatch() -> None:
    # the dispatch in ops/attention.py picks the reference path on CPU;
    # force the pallas path via interpret and jit the whole thing
    shape = (1, 128, 2, 32)
    q, k, v = (_rand(shape, i) for i in range(3))
    fn = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True
        )
    )
    out = fn(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(reference_attention(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_streamed_variant_matches(causal) -> None:
    # force the k-streamed kernel by shrinking the resident threshold
    import torchft_tpu.ops.flash as flash_mod

    old = flash_mod._RESIDENT_KV_BYTES
    flash_mod._RESIDENT_KV_BYTES = 0
    try:
        shape = (1, 256, 2, 32)
        q, k, v = (_rand(shape, i) for i in range(3))
        out = flash_attention(q, k, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)
        expected = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5
        )
    finally:
        flash_mod._RESIDENT_KV_BYTES = old


@pytest.mark.parametrize("causal", [True, False])
def test_fused_backward_matches_reference(causal) -> None:
    # The FUSED pallas backward (dQ + dKV kernels over recomputed P)
    # must produce the same gradients as differentiating the reference.
    shape = (2, 128, 2, 32)
    q, k, v = (_rand(shape, i + 10) for i in range(3))
    g = _rand(shape, 99)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)
        return jnp.sum(out * g)

    def ref_loss(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) * g)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


@pytest.mark.parametrize("backward", ["flash_bwd", "pair"])
def test_streamed_backward_matches(backward, request) -> None:
    # Long-context (streamed) regime: the one backward kernel, and the
    # streamed dq + dkv kernels it falls back to where its accumulators do
    # not fit; gradients must match the reference exactly.
    import torchft_tpu.ops.flash as flash_mod

    if backward == "pair":
        request.getfixturevalue("pair")

    old = flash_mod._RESIDENT_KV_BYTES
    flash_mod._RESIDENT_KV_BYTES = 0
    try:
        shape = (1, 128, 2, 32)
        q, k, v = (_rand(shape, i + 20) for i in range(3))
        g = _rand(shape, 77)

        def flash_loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=64,
                                  block_k=64, interpret=True)
            return jnp.sum(out * g)

        def ref_loss(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) * g)

        got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4
            )
    finally:
        flash_mod._RESIDENT_KV_BYTES = old


def test_causal_attention_propagates_a_kernel_error(monkeypatch) -> None:
    # On a TPU the Mosaic kernel is THE path: a kernel that raises must
    # reach the caller, never be swapped for the XLA reference behind
    # their back (the device-hiding fallback this dispatch used to have).
    import torchft_tpu.ops.attention as attention
    import torchft_tpu.ops.flash as flash

    def refused(*_args, **_kwargs):
        raise ValueError("mosaic refused this kernel")

    q = _rand((1, 128, 2, 32), 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "flash_attention", refused)
    with pytest.raises(ValueError, match="mosaic refused this kernel"):
        attention.causal_attention(q, q, q)
    # and off the TPU the reference is chosen without touching the kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    out = attention.causal_attention(q, q, q)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(reference_attention(q, q, q)),
    )


def test_flash_shape_error_names_the_shape() -> None:
    q = _rand((1, 192, 2, 32), 0)  # 192 is not a multiple of 128
    with pytest.raises(ValueError, match=r"seq len 192 of q\(1, 192, 2, 32\)"):
        flash_attention(q, q, q, interpret=True)


# ----------------------------------------------------------- PR 24 contracts
# (a) what the MXU is fed, (b) bf16 numerics, (c) the diagonal split,
# (d) the tile rule. All through the Pallas interpreter.

_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
_REGIMES = {"resident": None, "streamed": 0}
# PR 74: a call's backward is ONE kernel in either regime wherever dk's and
# dv's whole-head accumulators fit VMEM (``_fuses_backward``: every shape
# this file runs); the pair is the rule's fallback, which the ``pair``
# fixture forces
_FUSED_KERNELS = ("flash_fwd", "flash_bwd")
_KERNELS_OF = {"resident": _FUSED_KERNELS, "resident-pair": _KERNELS,
               "streamed": _FUSED_KERNELS, "streamed-pair": _KERNELS}
_MATMULS_A_TILE = {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4,
                   "flash_bwd": 5}


@pytest.fixture
def pair():
    """The backward as ``flash_dq`` + ``flash_dkv``: what
    ``_fuses_backward`` falls back to where dk's and dv's whole-head
    accumulators do not fit, forced here by a limit nothing fits."""
    with _pair_forced():
        yield


@contextlib.contextmanager
def _pair_forced():
    """:func:`pair` for a part of a test (or of a trace: the rule is asked
    while a program is traced)."""
    import torchft_tpu.ops.flash as flash_mod
    from jax.experimental.pallas import tpu as pltpu

    fits = flash_mod._FUSED_PARAMS
    flash_mod._FUSED_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=0)
    try:
        yield
    finally:
        flash_mod._FUSED_PARAMS = fits


def _eqns(jaxpr, kernel=None):
    """Every equation of ``jaxpr`` — loops, branches and kernel bodies
    included — with the name of the ``pallas_call`` it stands inside
    (``None`` outside any)."""
    for eqn in jaxpr.eqns:
        yield eqn, kernel
        inside = (eqn.params["name"] if eqn.primitive.name == "pallas_call"
                  else kernel)
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inside)


def _kernel_dots(jaxpr):
    """{kernel name: [(lhs dtype, rhs dtype, out dtype), ...]} of every
    dot_general inside every pallas_call of ``jaxpr``."""
    found = {}
    for eqn, kernel in _eqns(jaxpr):
        if eqn.primitive.name == "dot_general" and kernel is not None:
            found.setdefault(kernel, []).append(
                tuple(v.aval.dtype for v in (*eqn.invars, *eqn.outvars))
            )
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("regime,kernel", [
    (regime, kernel) for regime, kernels in sorted(_KERNELS_OF.items())
    for kernel in kernels])
def test_kernel_matmuls_are_f32_whatever_arrives(kernel, regime, dtype,
                                                 request) -> None:
    # The dtype contract of the module docstring: operands are upcast as
    # they are loaded and every dot_general of every kernel is f32 x f32 ->
    # f32, for bf16 and for f32 inputs (on the v5e casting P / dS down to
    # bf16 first was measured slower: PERF.md, PR 24); results leave in the
    # input dtype. Unequal blocks: the loop body and the straight-line
    # diagonal tiles are both in the jaxpr. ``flash_bwd`` builds a tile's
    # P and dS once: five matmuls where dq and dkv together run seven.
    q = jnp.zeros((1, 512, 2, 64), dtype)
    if regime.endswith("-pair"):
        request.getfixturevalue("pair")

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, block_q=256, block_k=128, interpret=True,
            _resident_kv_bytes=_REGIMES[regime.split("-")[0]],
        )
        assert out.dtype == dtype
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(grad)(q, q, q)
    assert all(g.dtype == dtype for g in jax.eval_shape(grad, q, q, q))
    dots = _kernel_dots(jaxpr.jaxpr)
    assert set(dots) == set(_KERNELS_OF[regime])
    per_tile = _MATMULS_A_TILE[kernel]
    assert len(dots[kernel]) >= per_tile
    assert len(dots[kernel]) % per_tile == 0
    f32 = jnp.dtype(jnp.float32)
    for operands_and_result in dots[kernel]:
        assert operands_and_result == (f32, f32, f32), dots[kernel]


def _f32_reference_grads(q, k, v, cot):
    return _out_and_grads(
        lambda q, k, v: reference_attention(q, k, v, causal=True),
        *(x.astype(jnp.float32) for x in (q, k, v, cot)))


_REFERENCES = {}


def _reference_once(key, evaluate):
    """A reference's ``(out, dq, dk, dv)`` once a ``key``: the operands are
    seeded by shape, so the cases of one shape — its regimes, its block
    sizes — are held to one evaluation of the reference."""
    if key not in _REFERENCES:
        _REFERENCES[key] = evaluate()
    return _REFERENCES[key]


def _out_and_grads(fn, q, k, v, cot):
    """``(out, dq, dk, dv)`` of ``fn(q, k, v)`` under the cotangent ``cot``
    as one program (``tests/one_program.py``): the forward runs once, where
    ``fn(...)`` beside ``jax.grad`` ran it twice."""
    out, grads = value_and_pullback(fn, (q, k, v), cot)
    return (out, *grads)


@pytest.mark.parametrize("blocks", [None, 128], ids=["rule", "b128"])
@pytest.mark.parametrize("regime", sorted(_REGIMES))
@pytest.mark.parametrize("shape", [
    (2, 512, 4, 64), (1, 512, 2, 128),
    # a value width of its own ([B, S, H, Dqk, Dv]; latent attention's
    # 192 / 128 and a narrow pair), at sequence lengths no other case has
    (1, 640, 2, 192, 128), (2, 384, 2, 48, 32),
])
def test_bf16_kernels_match_the_f32_reference(shape, regime, blocks) -> None:
    # bf16 in, forward and dq/dk/dv against the reference evaluated in f32
    # on the same bf16 values: chip_smoke.py's 0.02 x max|ref|, not looser.
    qk_shape, v_shape = shape[:4], (*shape[:3], shape[-1])
    q, k, v, cot = (_rand(s, i + 30, jnp.bfloat16) for i, s in
                    enumerate((qk_shape, qk_shape, v_shape, v_shape)))

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=blocks, block_k=blocks,
            interpret=True, _resident_kv_bytes=_REGIMES[regime],
        )

    got = _out_and_grads(flash, q, k, v, cot)
    want = _reference_once(("bf16", shape),
                           lambda: _f32_reference_grads(q, k, v, cot))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
        assert err <= 0.02 * float(jnp.max(jnp.abs(b))), (name, err)


@pytest.mark.parametrize("regime", sorted(_REGIMES))
@pytest.mark.parametrize(
    "seq_len,block_q,block_k",
    [(1024, 128, 128), (1024, 128, 256), (1024, 256, 128),
     (1024, 512, 512), (768, 384, 256), (768, 256, 384)],
)
def test_diagonal_split_at_unequal_blocks(seq_len, block_q, block_k,
                                          regime) -> None:
    # Unmasked bodies below the diagonal, masked ones on it: a wrong loop
    # bound at block_q != block_k (or where neither edge divides the
    # other, the looped diagonal) shows at the file's f32 tolerances.
    shape = (1, seq_len, 2, 32)
    q, k, v, cot = (_rand(shape, i + 40) for i in range(4))

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k,
            interpret=True, _resident_kv_bytes=_REGIMES[regime],
        )

    got = _out_and_grads(flash, q, k, v, cot)
    want = _reference_once(("diagonal", seq_len),
                           lambda: _f32_reference_grads(q, k, v, cot))
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), atol=2e-5, rtol=2e-5
    )
    for name, a, b in zip(("dq", "dk", "dv"), got[1:], want[1:]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4,
            err_msg=f"{name} mismatch",
        )


@pytest.mark.parametrize(
    "seq_len,block_q,block_k",
    [(1024, 128, 128), (1024, 128, 512), (1024, 512, 256), (768, 384, 256),
     (768, 128, 384), (2048, 512, 512)],
)
def test_causal_sweep_is_the_closed_form_of_the_tile_predicates(
        seq_len, block_q, block_k) -> None:
    from torchft_tpu.ops.flash import _causal_sweep, _tile_full, _tile_live

    nq, nk = seq_len // block_q, seq_len // block_k
    for rows, n_own, n_other in ((True, nq, nk), (False, nk, nq)):
        for idx in range(n_own):
            full, diagonal = (
                range(int(lo), int(hi)) for lo, hi in
                _causal_sweep(idx, block_q, block_k, seq_len, rows)
            )
            for other in range(n_other):
                qi, ki = (idx, other) if rows else (other, idx)
                is_live = _tile_live(qi, ki, block_q, block_k)
                is_full = _tile_full(qi, ki, block_q, block_k)
                # the predicates against the element-wise mask itself
                rows_, cols_ = np.meshgrid(
                    np.arange(qi * block_q, (qi + 1) * block_q),
                    np.arange(ki * block_k, (ki + 1) * block_k),
                    indexing="ij",
                )
                assert is_live == bool((rows_ >= cols_).any())
                assert is_full == bool((rows_ >= cols_).all())
                assert (other in full) == is_full
                assert (other in diagonal) == (is_live and not is_full)


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize(
    "seq_len", [128, 256, 384, 512, 1024, 1536, 2048, 4096, 8192]
)
def test_tile_rule_is_a_pure_function_of_the_shape(seq_len, head_dim,
                                                   itemsize) -> None:
    from torchft_tpu.ops.flash import (
        _VMEM_BUDGET, _choose_blocks, _vmem_estimate,
    )

    block_q, block_k = _choose_blocks(seq_len, head_dim, itemsize)
    assert (block_q, block_k) == _choose_blocks(seq_len, head_dim, itemsize)
    for block in (block_q, block_k):
        assert seq_len % block == 0
        assert block >= 128 or block == seq_len
    assert _vmem_estimate(
        seq_len, head_dim, itemsize, block_q, block_k
    ) <= _VMEM_BUDGET
    # explicit arguments come back untouched, each on its own
    assert _choose_blocks(seq_len, head_dim, itemsize, 128, 64) == (128, 64)
    assert _choose_blocks(seq_len, head_dim, itemsize, 64, None) == (
        64, block_k)
    assert _choose_blocks(seq_len, head_dim, itemsize, None, 128) == (
        block_q, 128)


def test_tile_rule_on_short_and_ragged_sequences() -> None:
    from torchft_tpu.ops.flash import _choose_blocks

    assert _choose_blocks(64, 64, 2) == (64, 64)      # the sequence itself
    assert _choose_blocks(192, 32, 4) == (128, 128)   # the wrapper refuses
    assert _choose_blocks(2048, 64, 2) == _choose_blocks(2048, 128, 2)
    # an explicit block longer than the sequence is clamped to it
    assert _choose_blocks(64, 64, 2, 128, 128) == (64, 64)


def test_tile_rule_takes_both_widths() -> None:
    from torchft_tpu.ops.flash import (
        _RESIDENT_KV_BYTES, _VMEM_BUDGET, _choose_blocks, _vmem_estimate,
    )

    # one width named twice is one width
    for seq_len in (2048, 8192):
        assert _vmem_estimate(seq_len, 128, 2, 512, 512, 128) == \
            _vmem_estimate(seq_len, 128, 2, 512, 512)
        assert _choose_blocks(seq_len, 64, 2, v_dim=64) == \
            _choose_blocks(seq_len, 64, 2)
    # latent attention at 8k: K + V of a head are 5.2 MB, so the kernels
    # stream, one tile a grid step, and the k edge is the streamed one
    assert 8192 * (192 + 128) * 2 > _RESIDENT_KV_BYTES
    assert _choose_blocks(8192, 192, 2, v_dim=128) == (512, 1024)
    assert _vmem_estimate(8192, 192, 2, 512, 1024, 128) <= _VMEM_BUDGET
    # every shape a cell ran before stays resident and square
    for seq_len, head_dim in ((2048, 64), (2048, 128), (4096, 128)):
        assert _choose_blocks(seq_len, head_dim, 2) == (512, 512)
    # streamed, but the long edge does not divide the sequence
    assert _choose_blocks(8192 + 512, 128, 2) == (512, 512)
    # a narrower v needs less than a v as wide as q and k
    assert _vmem_estimate(8192, 192, 2, 512, 512, 128) < \
        _vmem_estimate(8192, 192, 2, 512, 512)
    # the regime's edge counts both widths: 4096 x (128 + 128) x 2 is
    # resident to the byte, 4096 x (192 + 128) x 2 is not
    assert _vmem_estimate(4096, 128, 2, 512, 512) > \
        _vmem_estimate(4096, 192, 2, 512, 512, 128)


# sha256 of the jaxpr (source positions cut out) of the gradient of a
# flash call with ONE head width: forward, dq and dkv, the block shapes,
# the grids and every instruction of the kernels. A change that moves
# these moves what the c111m / c1p3b / olmoe cells run; regenerate on
# purpose only. Pinned first as the commit before the value width
# (a4592dd) traced them; the streamed causal call regenerated in PR 45
# (its grid enumerates the live tiles only); all three regenerated in
# PR 51: lse leaves the forward, and lse and delta reach dq, as [BH, 1, S]
# rows, and the kernels turn them to and from the tile's [BQ, 1] columns
# in VMEM (f8b02ef3..., 0af33485... and 049a6f74... before; every output
# stayed bit for bit: scripts/flash_micro.py --parent on the chip); the two
# streamed calls regenerated in PR 60 (7fb6fbf7... and 373a1e81... before):
# their three builders stand under a jit of their own (a row of this
# call's tiles is one tile long, so the forward's body is the one-tile
# body; test_the_chunked_body_is_one_tile_long holds the chunked one). The
# resident call did not move. The two streamed calls regenerated in PR 74
# (648e7a25... and 1c16c841... before): their backward is the one kernel
# ``flash_bwd`` (dq, dk and dv bit for bit the pair's on the chip at every
# streamed cell's call: scripts/flash_micro.py --parent), and the resident
# call with them (4f629dc8... before): ``flash_bwd`` with K and V whole in
# VMEM (dq bit for bit the resident ``flash_dq``'s on the chip at the five
# resident cells' calls, dk and dv a column's tiles summed top to bottom
# where the resident ``flash_dkv`` added the full ones first; those two
# kernels went with it: the streamed pair is the one fallback).
_EQUAL_WIDTH_JAXPR = {
    ("resident", True):
        "0bcd473cb6b20a2ec87e307b749132528aaffef92bfaad1346a246ffa6a2013b",
    ("streamed", True):
        "e71b01e257097148f2cc4ecdfc264b44b39362fa3f632cc6fa4675c68764d9aa",
    ("streamed", False):
        "1a58a125b93fd9db713476dd43d1fa3ca46528934c1193b308d70a5d63e67b9a",
}


@pytest.mark.parametrize(
    "regime,causal", sorted(_EQUAL_WIDTH_JAXPR),
    ids=lambda value: value if isinstance(value, str)
    else ("causal" if value else "unmasked"),
)
def test_equal_widths_lower_as_before(regime, causal) -> None:
    import hashlib
    import re

    q = jnp.zeros((2, 512, 4, 64), jnp.bfloat16)
    # the causal calls leave the argument to its default, as the pinned
    # commit's test did
    mask = {} if causal else {"causal": False}

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, interpret=True, _resident_kv_bytes=_REGIMES[regime],
            **mask,
        ).astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    text = re.sub(r"/[^ ]*?\.py:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _EQUAL_WIDTH_JAXPR[regime, causal]


# ------------------------------------------------------------ PR 45 contracts
# A streamed causal grid enumerates the live tiles only: (a) the tables,
# (b) the kernels over them against the kernels that take no grid step a
# tile at all.

# every (seq_len, block_q, block_k) this file runs a kernel or a sweep at,
# and the two the cells' 8k calls could take
_SWEPT_SHAPES = [
    (128, 64, 64), (256, 64, 64), (384, 128, 128), (512, 128, 128),
    (512, 256, 128), (512, 512, 512), (640, 128, 128), (768, 128, 384),
    (768, 256, 384), (768, 384, 256), (1024, 128, 128), (1024, 128, 256),
    (1024, 128, 512), (1024, 256, 128), (1024, 512, 256), (1024, 512, 512),
    (2048, 512, 512), (8192, 512, 512), (8192, 512, 1024),
]


@pytest.mark.parametrize("rows", [True, False], ids=["rows", "columns"])
@pytest.mark.parametrize("seq_len,block_q,block_k", _SWEPT_SHAPES)
def test_live_tile_tables(seq_len, block_q, block_k, rows) -> None:
    from torchft_tpu.ops.flash import (
        _grid_steps, _live_tiles, _sweep_ends, _tile_live,
    )

    q_of, k_of = _live_tiles(seq_len, block_q, block_k, rows)
    assert q_of.dtype == k_of.dtype == np.int32
    listed = list(zip(q_of.tolist(), k_of.tolist()))
    nq, nk = seq_len // block_q, seq_len // block_k
    # every listed tile is live, none is listed twice, none is missing
    live = {(qi, ki) for qi in range(nq) for ki in range(nk)
            if _tile_live(qi, ki, block_q, block_k)}
    assert len(listed) == len(set(listed)) and set(listed) == live
    assert _grid_steps(seq_len, block_q, block_k) == (len(live), nq * nk)
    # row-major (forward, dq) or column-major (dkv), swept index ascending
    assert listed == sorted(listed, key=lambda t: t if rows else t[::-1])
    # the accumulators are cleared at a row's (column's) first listed tile
    # and written out at its last: _causal_sweep's ends (the swept index
    # ascends, so it meets either end once a row)
    own_at, swept_at = (0, 1) if rows else (1, 0)
    for own, tiles in itertools.groupby(listed, key=lambda t: t[own_at]):
        swept = [t[swept_at] for t in tiles]
        first, last = _sweep_ends(own, block_q, block_k, seq_len, rows)
        assert (int(first), int(last)) == (swept[0], swept[-1])


def test_grid_steps_at_the_cells_call() -> None:
    from torchft_tpu.ops.flash import _choose_blocks, _grid_steps

    # joyai's and nemo3's 8k calls: 56 of 128 grid steps a head computed
    # nothing until PR 45
    assert _grid_steps(8192, *_choose_blocks(8192, 192, 2, v_dim=128)) == \
        (72, 128)
    assert _grid_steps(8192, *_choose_blocks(8192, 128, 2)) == (72, 128)
    assert _grid_steps(8192, 512, 512) == (136, 256)
    assert _grid_steps(2048, 512, 512) == (10, 16)


def _dkv_in_table_order(q, k, v, do, lse, delta, scale, block_q, block_k):
    """dk and dv as the streamed causal dkv kernel sums them — the kernel's
    own tile body over :func:`_live_tiles` in its order, a column's q
    blocks ascending — with no grid, table lookup or scratch involved."""
    from torchft_tpu.ops.flash import (
        _dkv_tile, _f32, _live_tiles, _tile_full,
    )

    dk = np.zeros(k.shape, np.float32)
    dv = np.zeros(v.shape, np.float32)
    for head in range(q.shape[0]):
        for qi, ki in zip(*_live_tiles(q.shape[1], block_q, block_k, False)):
            rows = slice(qi * block_q, (qi + 1) * block_q)
            cols = slice(ki * block_k, (ki + 1) * block_k)
            tile_dk, tile_dv = _dkv_tile(
                _f32(q[head, rows]) * scale, _f32(k[head, cols]),
                _f32(v[head, cols]), _f32(do[head, rows]),
                lse[head, None, rows], delta[head, None, rows], qi, ki,
                masked=not _tile_full(qi, ki, block_q, block_k),
            )
            dk[head, cols] = np.asarray(jnp.asarray(dk[head, cols]) + tile_dk)
            dv[head, cols] = np.asarray(jnp.asarray(dv[head, cols]) + tile_dv)
    return jnp.asarray(dk).astype(k.dtype), jnp.asarray(dv).astype(v.dtype)


# every (seq_len, block_q, block_k, (Dqk, Dv)) the three kernels are held
# to across the regimes, at the rule's chunk
_REGIME_SHAPES = [
    (1024, 128, 256, (32, 32)), (1024, 256, 256, (32, 32)),
    (1024, 256, 128, (32, 32)),
    # latent attention's 192 / 128 a quarter the size, at the cell's ratio
    (1024, 128, 256, (48, 32)), (768, 128, 128, (48, 32)),
    # neither edge divides the other
    (768, 384, 256, (32, 32)), (768, 256, 384, (32, 32)),
    # shorter than a lane tile (PR 51): the statistics' [1, BQ] rows are
    # then blocks as long as the whole dimension, one tile and several
    (64, 64, 64, (32, 32)), (96, 32, 32, (48, 32)),
]
_DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}
# PR 60: the forward at an EXPLICIT chunk of k tiles a grid step — 1, 2 and
# the whole row of tiles — under the causal mask, under a window shorter
# than a tile, as long as a chunk of two tiles and no multiple of one, and
# without the mask; latent attention's widths on heads of their own, equal
# widths in groups of four
_REGIME_CASES = [
    pytest.param(*shape, _DTYPES[dtype], regime, "causal", 1, None,
                 id="-".join(map(str, (*shape[:3], *shape[3], dtype, regime))))
    for regime, dtype, shape in itertools.product(
        sorted(_REGIMES), _DTYPES, _REGIME_SHAPES)
] + [
    pytest.param(1024, block_q, block_k, widths, jnp.bfloat16, "streamed",
                 mask, group, chunk,
                 id=f"{block_q}-{block_k}-{mask}-g{group}-chunk-{chunk}")
    for (block_q, block_k, widths, group), mask, chunk in itertools.product(
        [(128, 256, (48, 32), 1), (256, 128, (32, 32), 4)],
        ["causal", 96, 512, 450, "unmasked"], [1, 2, "row"])
]


_RESIDENT = {}     # a shape's resident results, shared by its cases


@pytest.mark.parametrize(
    "seq_len,block_q,block_k,widths,dtype,regime,mask,group,chunk",
    _REGIME_CASES)
def test_causal_kernels_agree_across_regimes_bit_for_bit(
        seq_len, block_q, block_k, widths, dtype, regime, mask, group,
        chunk) -> None:
    # Crosses, a case (its shape is its id): at least two q blocks and two k
    # blocks with the diagonal inside a tile, block_q <, = and > block_k,
    # edges of which neither divides the other, a sequence shorter than a
    # lane tile; the chunk cases a window shorter than a tile, as long as a
    # chunk and no multiple of a tile. The costliest case of the driver's
    # run was S 96 (6.9 s; S 1024: 5.7 s): a case costs its compiles, so the
    # shapes stay and each sweep is one program, the resident side once.
    # A streamed causal grid is a table of live tiles, and an accumulator
    # meets them in the order the resident kernels' loops do, so out, lse
    # and dq are the RESIDENT kernels' bit for bit. dk and dv were not while
    # the resident regime had a dkv kernel of its own (until PR 74): the
    # resident column sweep adds its full tiles before its diagonal ones
    # and the streamed one runs a column top to bottom. Both regimes' dk
    # and dv are therefore held, bit for bit where the order is theirs, to
    # the kernel's own tile body summed in the table's order. A grid step
    # of the streamed forward sweeps a chunk of k tiles (PR 60), the live
    # ones in ascending k through the tile body they always had: out and
    # lse are the resident kernel's at EVERY chunk, mask and group (the
    # backward kernels take no chunk: those cases end at the forward).
    from torchft_tpu.ops.flash import (
        _flash_backward_core, _flash_forward, _grid_steps,
    )

    dqk, dv = widths
    heads = 2 * group
    q = _rand((heads, seq_len, dqk), 50, dtype)
    k = _rand((heads // group, seq_len, dqk), 51, dtype)
    v = _rand((heads // group, seq_len, dv), 52, dtype)
    do = _rand((heads, seq_len, dv), 53, dtype)
    scale = 1.0 / dqk ** 0.5
    causal = mask != "unmasked"
    window = mask if isinstance(mask, int) else None
    if chunk == "row":
        chunk = seq_len // block_k
    common = (causal, scale, block_q, block_k, True)

    # one program a sweep and a regime: op by op every part of a case's
    # own shape is a compile of its own, and compiling is what a case
    # costs (its shapes are its id: they stay)
    def forward(threshold, chunk=None):
        out, lse = jax.jit(lambda q, k, v: _flash_forward(
            q, k, v, *common, threshold, window=window, chunk=chunk))(q, k, v)
        return out, lse, jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    # the resident side of a shape is the same for its two cases: once
    key = (seq_len, block_q, block_k, widths, dtype, mask, group)
    if key not in _RESIDENT:
        _RESIDENT[key] = {"forward": forward(_REGIMES["resident"])}
    r_out, r_lse, r_delta = _RESIDENT[key]["forward"]
    out, lse, delta = ((r_out, r_lse, r_delta) if regime == "resident"
                       else forward(_REGIMES[regime], chunk))
    assert out.dtype == r_out.dtype and jnp.array_equal(out, r_out)
    assert jnp.array_equal(lse, r_lse)
    if chunk is not None:
        if causal:
            # a chunk is a grid step where any of its tiles is live
            live, _, chunked = _grid_steps(seq_len, block_q, block_k, window,
                                           chunk)
            assert -(-live // chunk) <= chunked <= live
            assert (chunked < live) == (chunk > 1)
        return

    def backward(threshold, lse, delta):
        return jax.jit(lambda q, k, v, do, lse, delta: _flash_backward_core(
            q, k, v, do, lse, delta, *common, threshold))(
                q, k, v, do, lse, delta)

    if "backward" not in _RESIDENT[key]:
        # lse and delta are the resident kernel's in both regimes (held
        # bit for bit above), so the table's order is summed once a shape
        _RESIDENT[key]["backward"] = backward(
            _REGIMES["resident"], r_lse, r_delta)
        _RESIDENT[key]["table"] = _dkv_in_table_order(
            q, k, v, do, r_lse, r_delta, scale, block_q, block_k)
    resident = _RESIDENT[key]["backward"]
    dq, dk, dv_ = (resident if regime == "resident"
                   else backward(_REGIMES[regime], lse, delta))
    # the backward is ``flash_bwd`` in both regimes (PR 74): one tile body,
    # dkv's, a row's k tiles and a column's q blocks met ascending either
    # way, so dq is the resident kernel's and dk and dv the table's order
    # in BOTH (until PR 74 the resident regime had a dkv kernel of its own
    # that added a column's full tiles before its diagonal ones)
    assert dq.dtype == resident[0].dtype and jnp.array_equal(dq, resident[0])
    want_dk, want_dv = _RESIDENT[key]["table"]
    assert jnp.array_equal(dk, want_dk) and jnp.array_equal(dv_, want_dv)
    # the resident order against the table's: rounding of the last place
    # of an f32 sum, before the cast to the operands' dtype
    for name, a, b in (("dk", dk, want_dk), ("dv", dv_, want_dv)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-5 if dtype == jnp.float32 else 2e-3, rtol=0,
            err_msg=name,
        )


# ------------------------------------------------------------ PR 47 contracts
# A window in the mask: (a) the two predicates and the band's closed form
# against the element-wise mask, (b) the tables a streamed grid enumerates,
# (c) the kernels over the band against the reference with the band mask.

# windows smaller than, equal to, and no multiple of a tile edge; one that
# is shorter than every tile and one a column short of the sequence
_WINDOWS = [96, 128, 200, 1, 511]


@pytest.mark.parametrize("window", _WINDOWS)
@pytest.mark.parametrize(
    "seq_len,block_q,block_k",
    [(512, 128, 128), (512, 128, 256), (512, 256, 128), (768, 384, 256),
     (768, 128, 384)],
)
def test_band_sweep_is_the_closed_form_of_the_tile_predicates(
        seq_len, block_q, block_k, window) -> None:
    from torchft_tpu.ops.flash import (
        _band_sweep, _sweep_ends, _tile_full, _tile_live,
    )

    nq, nk = seq_len // block_q, seq_len // block_k
    for rows, n_own, n_other in ((True, nq, nk), (False, nk, nq)):
        for idx in range(n_own):
            full, diagonal, trailing = (
                range(int(lo), int(hi)) for lo, hi in
                _band_sweep(idx, block_q, block_k, seq_len, rows, window)
            )
            live = []
            for other in range(n_other):
                qi, ki = (idx, other) if rows else (other, idx)
                is_live = bool(_tile_live(qi, ki, block_q, block_k, window))
                is_full = bool(_tile_full(qi, ki, block_q, block_k, window))
                # the predicates against the element-wise mask itself
                r, c = np.meshgrid(
                    np.arange(qi * block_q, (qi + 1) * block_q),
                    np.arange(ki * block_k, (ki + 1) * block_k),
                    indexing="ij",
                )
                seen = (r >= c) & (r - c < window)
                assert is_live == bool(seen.any())
                assert is_full == bool(seen.all())
                assert (other in full) == is_full
                masked = (other in diagonal) + (other in trailing)
                assert masked == (is_live and not is_full)
                live += [other] * is_live
            # the three loops run in the swept index's order
            order = ([*trailing, *full, *diagonal] if rows
                     else [*diagonal, *full, *trailing])
            assert order == live
            first, last = _sweep_ends(
                idx, block_q, block_k, seq_len, rows, window)
            assert (int(first), int(last)) == (live[0], live[-1])


@pytest.mark.parametrize("rows", [True, False], ids=["rows", "columns"])
@pytest.mark.parametrize("window", _WINDOWS)
def test_live_tile_tables_of_the_band(window, rows) -> None:
    from torchft_tpu.ops.flash import _grid_steps, _live_tiles, _tile_live

    seq_len, block_q, block_k = 768, 128, 256
    q_of, k_of = _live_tiles(seq_len, block_q, block_k, rows, window)
    listed = list(zip(q_of.tolist(), k_of.tolist()))
    live = {(qi, ki) for qi in range(seq_len // block_q)
            for ki in range(seq_len // block_k)
            if _tile_live(qi, ki, block_q, block_k, window)}
    assert len(listed) == len(set(listed)) and set(listed) == live
    assert listed == sorted(listed, key=lambda t: t if rows else t[::-1])
    assert _grid_steps(seq_len, block_q, block_k, window)[0] == len(live)


def test_grid_steps_of_the_band_at_the_cells_call() -> None:
    from torchft_tpu.ops.flash import _choose_blocks, _grid_steps

    # phi4flash's windowed call: 64-wide q and k, 128-wide v, 512 keys.
    # K and V of a head stream (3.1 MB), and under a window the rule keeps
    # the square tile: 31 of its 256 are the band, where the causal call
    # takes 72 tiles twice as wide
    blocks = _choose_blocks(8192, 64, 2, v_dim=128, window=512)
    assert blocks == (512, 512)
    assert _grid_steps(8192, *blocks, 512) == (31, 256)
    assert _choose_blocks(8192, 64, 2, v_dim=128) == (512, 1024)
    assert _grid_steps(8192, 512, 1024) == (72, 128)


@pytest.mark.parametrize("regime", sorted(_REGIMES))
@pytest.mark.parametrize("widths", [(32, 32), (64, 128)],
                         ids=["equal", "64-128"])
@pytest.mark.parametrize("window", _WINDOWS[:3])
def test_windowed_kernels_match_the_band_mask(window, widths,
                                              regime) -> None:
    # forward, dq and dkv over the band (resident loops, streamed tables)
    # against the reference with the explicit band mask; unequal blocks,
    # so a column sweep and a row sweep disagree on nothing
    dqk, dv = widths
    q, k = (_rand((1, 512, 2, dqk), i + 60) for i in range(2))
    v, cot = (_rand((1, 512, 2, dv), i + 62) for i in range(2))

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=128, block_k=256, interpret=True,
            _resident_kv_bytes=_REGIMES[regime], window=window,
        )

    def reference(q, k, v):
        return reference_attention(q, k, v, causal=True, window=window)

    got = _out_and_grads(flash, q, k, v, cot)
    want = _reference_once(
        ("windowed", window, widths),
        lambda: _out_and_grads(reference, q, k, v, cot))
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), atol=2e-5, rtol=2e-5
    )
    for name, a, b in zip(("dq", "dk", "dv"), got[1:], want[1:]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4,
            err_msg=f"{name} mismatch",
        )


def test_a_window_is_a_causal_matter() -> None:
    q = _rand((1, 128, 1, 32), 70)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=16, interpret=True)
    # a window as long as the sequence is the causal call: same program
    whole, causal = (str(jax.make_jaxpr(lambda q: flash_attention(
        q, q, q, interpret=True, **kw))(q)) for kw in ({"window": 128}, {}))
    assert whole == causal
    # the band mask itself: one key a row is that key's value
    out = flash_attention(q, q, q, window=1, interpret=True, block_q=64,
                          block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(q), atol=1e-6)


# ------------------------------------------------------------ PR 50 contracts
# A window several k edges long under tiles with block_q != block_k (the
# streamed 512 x 1024 tile at W 4096, in small): the closed forms, the
# tables, the kernels in both regimes, and the rule's table.

# four k edges, no multiple of either edge, and between two and three
_LONG_WINDOWS = [512, 450, 300]
_LONG_SHAPES = [(1024, 64, 128), (1024, 128, 64), (768, 128, 256)]


@pytest.mark.parametrize("window", _LONG_WINDOWS)
@pytest.mark.parametrize("seq_len,block_q,block_k", _LONG_SHAPES)
def test_band_sweep_of_a_window_several_k_edges_long(
        seq_len, block_q, block_k, window) -> None:
    test_band_sweep_is_the_closed_form_of_the_tile_predicates(
        seq_len, block_q, block_k, window)


@pytest.mark.parametrize("rows", [True, False], ids=["rows", "columns"])
@pytest.mark.parametrize("window", _LONG_WINDOWS)
@pytest.mark.parametrize("seq_len,block_q,block_k", _LONG_SHAPES)
def test_live_tile_tables_of_a_long_band(seq_len, block_q, block_k, window,
                                         rows) -> None:
    from torchft_tpu.ops.flash import _grid_steps, _live_tiles

    q_of, k_of = _live_tiles(seq_len, block_q, block_k, rows, window)
    listed = list(zip(q_of.tolist(), k_of.tolist()))
    # against the element-wise band mask itself
    r, c = np.indices((seq_len, seq_len))
    seen = ((r >= c) & (r - c < window)).reshape(
        seq_len // block_q, block_q, seq_len // block_k, block_k
    ).any(axis=(1, 3))
    live = {(int(qi), int(ki)) for qi, ki in zip(*np.nonzero(seen))}
    assert len(listed) == len(set(listed)) and set(listed) == live
    assert listed == sorted(listed, key=lambda t: t if rows else t[::-1])
    assert _grid_steps(seq_len, block_q, block_k, window) == (
        len(live), seen.size)
    # some q block's band is several k blocks long
    assert seen.sum(axis=1).max() >= 3


@pytest.mark.parametrize("regime", sorted(_REGIMES))
@pytest.mark.parametrize("window", _LONG_WINDOWS[:2])
@pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 64)])
def test_long_windowed_kernels_match_the_band_mask(block_q, block_k, window,
                                                   regime) -> None:
    # forward, dq and dkv over a band of several k edges, block_q !=
    # block_k, resident loops and streamed tables alike
    q, k, v, cot = (_rand((1, 1024, 2, 32), i + 80) for i in range(4))

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k,
            interpret=True, _resident_kv_bytes=_REGIMES[regime],
            window=window,
        )

    def reference(q, k, v):
        return reference_attention(q, k, v, causal=True, window=window)

    got = _out_and_grads(flash, q, k, v, cot)
    want = _reference_once(
        ("long windowed", window),
        lambda: _out_and_grads(reference, q, k, v, cot))
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), atol=2e-5, rtol=2e-5
    )
    for name, a, b in zip(("dq", "dk", "dv"), got[1:], want[1:]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4,
            err_msg=f"{name} mismatch",
        )


@pytest.mark.parametrize("seq_len,head_dim,v_dim,window,blocks,steps", [
    # smallthinker's windowed call: the streamed tile from 2 k edges on
    (16384, 128, None, 4096, (512, 1024), (140, 512)),
    (16384, 128, None, 8192, (512, 1024), (216, 512)),
    (16384, 128, None, 2048, (512, 1024), (90, 512)),
    # a key short of that, and the windows the other cells run: square
    (16384, 128, None, 2047, (512, 512), (150, 1024)),
    (16384, 128, None, 1024, (512, 512), (93, 1024)),
    (8192, 64, 128, 512, (512, 512), (31, 256)),
    (8192, 128, None, 512, (512, 512), (31, 256)),
    # no window: what it was
    (16384, 128, None, None, (512, 1024), (272, 512)),
    (8192, 64, 128, None, (512, 1024), (72, 128)),
    (8192, 128, None, None, (512, 1024), (72, 128)),
    (2048, 64, None, None, (512, 512), (10, 16)),
])
def test_the_tile_rule_under_a_window(seq_len, head_dim, v_dim, window,
                                      blocks, steps) -> None:
    """``_choose_blocks`` is a pure function of (sequence, widths,
    window): the streamed 512 x 1024 tile where the kernels stream and the
    window is at least two of its k edges long (PERF.md, PR 50: the chip
    read it 17 % faster over the three kernels at W 4096 and 11 % at W
    2048, and no faster at W 1024), the square tile under a shorter one
    (PR 47), and without a window what it always chose."""
    from torchft_tpu.ops.flash import _choose_blocks, _grid_steps

    got = _choose_blocks(seq_len, head_dim, 2, v_dim=v_dim, window=window)
    assert got == blocks == _choose_blocks(
        seq_len, head_dim, 2, v_dim=v_dim, window=window)
    assert _grid_steps(seq_len, *got, window) == steps


# ------------------------------------------------------------ PR 51 contracts
# The row statistics cross HBM lane-dense: (a) no flash call takes or gives
# a [.., S, 1] f32 array, and dq and dkv take the same two, (b) the calls
# compile for the chip at the cells' shapes and plan no padded copy.

def _flash_calls(jaxpr):
    """{kernel name: [eqn, ...]} of every ``pallas_call`` named ``flash_*``
    in ``jaxpr``."""
    found = {}
    for eqn, _ in _eqns(jaxpr):
        if (eqn.primitive.name == "pallas_call"
                and (eqn.params["name"] or "").startswith("flash_")):
            found.setdefault(eqn.params["name"], []).append(eqn)
    return found


def _traced_flash_calls(names=("flash_calls", "flash_calls_grouped")):
    """``(flash_calls, flash_calls_grouped)`` so far in this process
    (``utils/metrics.py::TRACED``): read it on both sides of a trace."""
    from torchft_tpu.utils.metrics import TRACED

    seen = TRACED.snapshot()
    return np.array([seen.get(name, 0) for name in names], dtype=int)


_MASKS = {"causal": {}, "window": {"window": 200},
          "unmasked": {"causal": False}}


@pytest.mark.parametrize("widths", [(64, 64), (128, 128), (192, 128)],
                         ids=["64", "128", "192-128"])
@pytest.mark.parametrize("mask", sorted(_MASKS))
@pytest.mark.parametrize("regime", sorted(_REGIMES))
def test_the_row_statistics_cross_hbm_lane_dense(regime, mask,
                                                 widths) -> None:
    # An f32 array whose last dimension is 1 is tiled (8, 128) in HBM: one
    # lane of 128 used, 512 bytes a query row (PERF.md, PRs 47 and 51). lse
    # and delta are [BH, S] between the calls and reach every kernel as the
    # ONE [BH, 1, S] view; the [BQ, 1] columns exist in VMEM only.
    dqk, dv = widths
    b, s, h = 1, 512, 2
    q = jnp.zeros((b, s, h, dqk), jnp.bfloat16)
    v = jnp.zeros((b, s, h, dv), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, block_q=128, block_k=256, interpret=True,
            _resident_kv_bytes=_REGIMES[regime], **_MASKS[mask],
        ).astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v)
    calls = _flash_calls(jaxpr.jaxpr)
    assert {k: len(c) for k, c in calls.items()} == dict.fromkeys(
        _KERNELS_OF[regime], 1)
    f32 = jnp.dtype(jnp.float32)
    for name, (eqn,) in calls.items():
        for var in (*eqn.invars, *eqn.outvars):
            aval = var.aval
            assert not (aval.dtype == f32 and aval.ndim and aval.shape[-1]
                        == 1), (name, aval)
    row = jax.core.ShapedArray((b * h, 1, s), f32)
    (fwd,), (dq,), *dkv = (calls[k] for k in _KERNELS_OF[regime])
    assert fwd.outvars[1].aval == row
    # the very same two operands, not two equal views (one kernel takes
    # them where the backward is ``flash_bwd``)
    for (other,) in dkv:
        assert dq.invars[-2:] == other.invars[-2:]
    assert [x.aval for x in dq.invars[-2:]] == [row, row]


@pytest.mark.parametrize("bh,group,seq_len,widths,window", [
    (8, 1, 2048, (64, 64), None),     # c111m's call: resident
    (4, 1, 8192, (192, 128), None),   # joyai's: streamed, two widths
    (4, 1, 16384, (128, 128), 4096),  # smallthinker's windowed call
    # PR 60: the forward's fullest chunk (eight tiles of K and V twice
    # over, 8.4 MB of Mosaic's 16) and the band's shortest
    (4, 1, 8192, (128, 128), None),   # nemo3's, olmohybrid's, laguna's
    (4, 1, 8192, (128, 128), 512),    # laguna's band
    # PR 74: ``flash_bwd``'s fullest calls — dk's and dv's accumulators and
    # output blocks 32 MiB of its 96 —, each at its cell's group
    (14, 7, 16384, (128, 128), None),     # smallthinker's full call
    (16, 8, 8192, (256, 256), None),      # qwen3next's, 512 x 512 tiles
], ids=["c111m", "joyai", "smallthinker-swa", "nemo3", "laguna-swa",
        "smallthinker", "qwen3next"])
def test_the_calls_compile_for_the_v5e_with_no_padded_statistic(
        one_chip, bh, group, seq_len, widths, window) -> None:
    """Mosaic takes the two turns of a statistic through the transpose
    unit at the cells' tiles (the interpreter takes anything), and the
    program around the kernels holds no ``f32[BH, S, 1]`` array in
    any layout (PERF.md, PR 51: it held lse out of the forward, its slice's
    operand and both of dq's operands so). The backward is
    ``flash_bwd``: Mosaic takes the product contracted over its left
    operand's rows and the whole-head accumulators under the limit the
    call passes (``_FUSED_PARAMS``)."""
    from jax.experimental.compilation_cache import compilation_cache

    from torchft_tpu.ops.flash import (
        _choose_blocks, _flash_backward_core, _flash_forward,
        _fuses_backward,
    )

    dqk, dv = widths
    blocks = _choose_blocks(seq_len, dqk, 2, v_dim=dv, window=window)
    common = (True, 1.0 / dqk ** 0.5, *blocks, False, None)
    assert _fuses_backward(seq_len, dqk, 2, *blocks, dv)

    def sd(width, heads=bh):
        return jax.ShapeDtypeStruct(
            (heads, seq_len, width), jnp.bfloat16, sharding=one_chip)

    def both(q, k, v, g):
        out, lse = _flash_forward(q, k, v, *common, window=window)
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        return out, lse, _flash_backward_core(
            q, k, v, g, lse, delta, *common, window=window)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(both).lower(
            sd(dqk), sd(dqk, bh // group), sd(dv, bh // group),
            sd(dv)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    for kernel in _FUSED_KERNELS:
        assert f"{kernel}/pallas_call" in text, kernel
    assert f"f32[{bh},1,{seq_len}]" in text
    assert f"f32[{bh},{seq_len},1]" not in text


# ------------------------------------------------------------ PR 60 contracts
# A grid step of the streamed forward sweeps a chunk of k tiles: (a) the
# chunk as a pure function of the shape, (b) the steps a head at the cells'
# calls, (c) a body that does not grow with the chunk, (d) the counter. (The
# kernels at an explicit chunk are cases of
# test_causal_kernels_agree_across_regimes_bit_for_bit.)

@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("widths", [(64, 64), (128, 128), (192, 128),
                                    (64, 128)],
                         ids=["64", "128", "192-128", "64-128"])
@pytest.mark.parametrize("window", [None, 512, 4096])
@pytest.mark.parametrize("seq_len", [512, 2048, 4096, 8192, 8192 + 512,
                                     16384, 32768])
def test_chunk_rule_is_a_pure_function_of_the_shape(seq_len, window, widths,
                                                    itemsize) -> None:
    from torchft_tpu.ops.flash import (
        _CHUNK_LADDER, _VMEM_BUDGET, _choose_blocks, _choose_chunk,
        _forward_vmem_estimate, _grid_steps, _resident,
    )

    dqk, dv = widths
    if window is not None and window >= seq_len:
        window = None           # the wrapper's: the causal call itself
    blocks = _choose_blocks(seq_len, dqk, itemsize, v_dim=dv, window=window)
    shape = (seq_len, dqk, itemsize, *blocks, dv, window)
    chunk = _choose_chunk(*shape)
    assert chunk == _choose_chunk(*shape) and chunk in _CHUNK_LADDER
    if _resident(seq_len, dqk + dv, itemsize):
        assert chunk == 1
        # and a call that streams by its own threshold has a chunk
        assert _choose_chunk(*shape, True, 0) >= chunk
        return
    num_k = seq_len // blocks[1]
    assert num_k % chunk == 0
    assert chunk == 1 or _forward_vmem_estimate(
        dqk, dv, itemsize, *blocks, chunk) <= _VMEM_BUDGET
    # some row of tiles fills the chunk, and the steps only fall
    live, rectangular, chunked = _grid_steps(seq_len, *blocks, window, chunk)
    assert chunked <= live <= rectangular
    rows = seq_len // blocks[0]
    assert chunk <= num_k and chunked >= rows
    # without the mask the row is the whole row of tiles
    assert _choose_chunk(*shape[:6], None, False) >= chunk


@pytest.mark.parametrize("seq_len,dqk,dv,window,blocks,chunk,steps", [
    # the seven cells whose K and V stream: joyai's and kimi's latent
    # attention, then nemo3's, olmohybrid's and laguna's full calls
    (8192, 192, 128, None, (512, 1024), 4, (72, 128, 24)),
    (8192, 128, 128, None, (512, 1024), 8, (72, 128, 16)),
    # phi4flash's full call and its band, laguna's band
    (8192, 64, 128, None, (512, 1024), 8, (72, 128, 16)),
    (8192, 64, 128, 512, (512, 512), 2, (31, 256, 23)),
    (8192, 128, 128, 512, (512, 512), 2, (31, 256, 23)),
    # smallthinker's full call and its band of 4096 keys
    (16384, 128, 128, None, (512, 1024), 8, (272, 512, 48)),
    (16384, 128, 128, 4096, (512, 1024), 4, (140, 512, 56)),
    # the resident cells (c111m, c1p3b, olmoe, lfm2): nothing to chunk
    (2048, 64, 64, None, (512, 512), 1, (10, 16, 10)),
    (2048, 128, 128, None, (512, 512), 1, (10, 16, 10)),
    (4096, 128, 128, None, (512, 512), 1, (36, 64, 36)),
    (8192, 64, 64, None, (512, 512), 1, (136, 256, 136)),
])
def test_the_chunk_rule_at_the_cells_calls(seq_len, dqk, dv, window, blocks,
                                           chunk, steps) -> None:
    """``_choose_chunk`` at every cell's call, and the grid steps a head
    its forward takes there (``_grid_steps``' third): the ladder's readings
    stand beside ``_CHUNK_LADDER`` (PERF.md, PR 60)."""
    from torchft_tpu.ops.flash import (
        _choose_blocks, _choose_chunk, _grid_steps,
    )

    assert _choose_blocks(seq_len, dqk, 2, v_dim=dv, window=window) == blocks
    got = _choose_chunk(seq_len, dqk, 2, *blocks, dv, window)
    assert got == chunk == _choose_chunk(seq_len, dqk, 2, *blocks, dv, window)
    assert _grid_steps(seq_len, *blocks, window, chunk) == steps
    assert _grid_steps(seq_len, *blocks, window) == steps[:2]


def _forward_kernel_dots(chunk, mask):
    from torchft_tpu.ops.flash import _flash_forward

    q = jnp.zeros((2, 2048, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: _flash_forward(
        q, k, v, mask != "unmasked", 0.125, 128, 128, True, 0,
        window=_MASKS[mask].get("window"), chunk=chunk))(q, q, q)
    (eqn,) = _flash_calls(jaxpr.jaxpr)["flash_fwd"]
    return (len(_kernel_dots(jaxpr.jaxpr)["flash_fwd"]),
            eqn.params["grid_mapping"].grid)


@pytest.mark.parametrize("mask", sorted(_MASKS))
def test_the_chunked_body_is_one_tile_long(mask) -> None:
    # A chunk's tiles are met in straight-line groups of ``_STRAIGHT``
    # tiles, and a group is TRACED as a loop of one tile (Mosaic's lowering
    # lays it out whole): the traced body — what every run pays to trace —
    # holds a tile's two matmuls once a group size (twice under a mask: the
    # body with it and the body without), whatever the chunk, and no group
    # longer than the chunk. At one tile a step the two bodies stand under
    # ``pl.when`` and there is no loop.
    from torchft_tpu.ops.flash import _STRAIGHT

    bodies = 1 if mask == "unmasked" else 2
    dots = {n: _forward_kernel_dots(n, mask) for n in (1, 2, 4, 8, 16)}
    for n, (count, _) in dots.items():
        groups = sum(size <= n for size in _STRAIGHT) if n > 1 else 1
        assert count == 2 * bodies * groups, (n, count)
    assert dots[2][0] <= dots[4][0] == dots[8][0] == dots[16][0]
    # and the grid shrinks with the chunk
    steps = [int(np.prod(grid[1:])) for _, grid in dots.values()]
    assert steps == sorted(steps, reverse=True) and steps[-1] < steps[0]


def test_the_wrapper_counts_the_chunked_calls() -> None:
    names = ("flash_calls", "flash_calls_chunked")

    def trace(seq_len, **kw):
        q = jnp.zeros((1, seq_len, 2, 64), jnp.bfloat16)
        before = _traced_flash_calls(names)
        jaxpr = jax.make_jaxpr(lambda q: flash_attention(
            q, q, q, interpret=True, **kw))(q)
        (eqn,) = _flash_calls(jaxpr.jaxpr)["flash_fwd"]
        return (tuple(_traced_flash_calls(names) - before),
                eqn.params["grid_mapping"].grid)

    # resident: one tile's worth of nothing to chunk
    assert trace(1024) == ((1, 0), (2, 2))
    # streamed, two tiles a row: a chunk of two, a grid step a q block
    assert trace(1024, _resident_kv_bytes=0) == ((1, 1), (2, 2))
    # streamed, a tile a row (explicit blocks): the one-tile body
    assert trace(1024, _resident_kv_bytes=0, block_q=1024,
                 block_k=1024) == ((1, 0), (2, 1))


# ------------------------------------------------------------ PR 74 contracts
# A streamed call's backward is ONE kernel, ``flash_bwd``: (a) against the
# pair it replaces and the f32 reference, (b) dk's and dv's accumulators
# cleared and written once a group, (c) which calls take it, as a pure
# function of the shape, (d) the counter. (Its matmuls a tile, its operands
# and its compile for the v5e are cases of the tests above.)

_FUSED_CASES = [
    # (block_q, block_k, (Dqk, Dv), group) x mask x regime: latent
    # attention's 192 / 128 on heads of their own under every mask,
    # phi4flash's 64 / 128 in groups of four, and smallthinker's seven
    # heads a key/value head; a streamed case of each shape, resident ones
    # where the masks differ most
    pytest.param(*shape, mask, regime, id="-".join(map(str, (
        *shape[:2], *shape[2], f"g{shape[3]}", mask, regime))))
    for shape, cases in (
        ((64, 128, (48, 32), 1), (
            ("causal", "streamed"), (96, "streamed"), (300, "streamed"),
            ("unmasked", "streamed"), ("causal", "resident"),
            ("unmasked", "resident"))),
        ((128, 64, (16, 32), 4), (
            ("causal", "streamed"), (300, "streamed"),
            ("unmasked", "streamed"), (300, "resident"))),
        ((64, 128, (32, 32), 7), (
            ("causal", "streamed"), (96, "streamed"), (96, "resident"))))
    for mask, regime in cases
]


@pytest.mark.parametrize("block_q,block_k,widths,group,mask,regime",
                         _FUSED_CASES)
def test_flash_bwd_against_the_pair_and_the_f32_reference(
        block_q, block_k, widths, group, mask, regime) -> None:
    """``flash_bwd`` at a few tiles (S 512: 8 x 4 or 4 x 8), bf16, under
    the causal mask, a window shorter than a k tile of 128 keys (96), one
    several k edges of 64 long (300) and no mask, in both regimes (the
    pair is the streamed one in either: it is the fallback of both). At
    equal head counts dk and dv are ``flash_dq`` + ``flash_dkv``'s BIT FOR
    BIT — the tile is dkv's and a row-major sweep meets a column's q
    blocks ascending — and dq a rounding of one f32 sum met in another
    order apart. A group's dk and dv are the float32 sum over its heads in one rounding
    either way, head-major here and a tile's heads together there: both
    stand inside ``tests/test_flash_grouped.py``'s limit from the float32
    reference's gradients summed over the copies, the one kernel no
    further than the pair."""
    from torchft_tpu.ops.flash import _flash_backward_core, _flash_forward

    dqk, dv = widths
    seq_len, kv = 512, 2
    heads = kv * group
    q, k, v, do = (_rand(shape, i + 74, jnp.bfloat16) for i, shape in
                   enumerate(((heads, seq_len, dqk), (kv, seq_len, dqk),
                              (kv, seq_len, dv), (heads, seq_len, dv))))
    causal = mask != "unmasked"
    window = mask if isinstance(mask, int) else None
    scale = dqk ** -0.5

    def reference(q, k, v, do):
        """dq, dk, dv of the float32 reference on the copies, the
        key/value gradients summed over them; [BH, S, D] is a batch of
        one-head sequences."""
        def loss(q, k, v):
            out = reference_attention(
                q[:, :, None], jnp.repeat(k, group, axis=0)[:, :, None],
                jnp.repeat(v, group, axis=0)[:, :, None], causal=causal,
                scale=scale, window=window)
            return jnp.sum(out[:, :, 0] * do)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @jax.jit
    def everything(q, k, v, do):
        common = (causal, scale, block_q, block_k, True, _REGIMES[regime])
        out, lse = _flash_forward(q, k, v, *common, window=window)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)

        def backward():
            return _flash_backward_core(q, k, v, do, lse, delta, *common,
                                        window=window)

        one = backward()
        with _pair_forced():        # the rule is asked while this traces
            pair_ = backward()
        return one, pair_, reference(
            *(x.astype(jnp.float32) for x in (q, k, v, do)))

    one, pair_, ref = everything(q, k, v, do)

    def off(a, b):
        return np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))

    for name, a, b, r in zip(("dq", "dk", "dv"), one, pair_, ref):
        top = float(jnp.abs(r).max())
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape == r.shape
        assert off(a, r).max() <= 0.02 * top, name
        if group == 1 and name != "dq":
            assert jnp.array_equal(a, b), name
            continue
        # the same f32 sums met in another order: a bf16 place of the
        # largest element at most, and no further from the reference
        assert off(a, b).max() <= 2 ** -7 * top, name
        assert np.sqrt(np.mean(off(a, r) ** 2)) <= 1.001 * np.sqrt(
            np.mean(off(b, r) ** 2)), name


@pytest.mark.parametrize("group", [1, 4, 7])
@pytest.mark.parametrize("window", [None, 96, 128, 450, 512, "unmasked"])
@pytest.mark.parametrize("seq_len,block_q,block_k", [
    (1024, 128, 256), (1024, 256, 128), (768, 384, 256), (768, 128, 128)])
def test_dk_and_dv_are_cleared_and_written_once_a_group(
        seq_len, block_q, block_k, window, group) -> None:
    """``flash_bwd``'s grid, step by step, with the kernel's own
    predicates: the leading axis over the query heads (a group's
    consecutive), under it the live tiles row-major. Every column of
    every key/value head is cleared exactly once, before its first term
    — at ``_sweep_ends``' top q block of the group's first head —, and
    written exactly once, after its last — at the bottom q block of the
    group's last head —, whatever the mask, the window and the ratio of
    the tile's edges."""
    from torchft_tpu.ops.flash import _live_tiles, _sweep_ends

    causal = window != "unmasked"
    if not causal:
        window = None
    nq, nk = seq_len // block_q, seq_len // block_k
    tiles = (list(zip(*_live_tiles(seq_len, block_q, block_k, True, window)))
             if causal else [(qi, ki) for qi in range(nq) for ki in range(nk)])
    kv_heads = 2
    events = {}
    for head in range(kv_heads * group):
        for qi, ki in tiles:
            top, bottom = ((int(e) for e in _sweep_ends(
                ki, block_q, block_k, seq_len, False, window))
                if causal else (0, nq - 1))
            seen = events.setdefault((head // group, int(ki)), [])
            if qi == top and head % group == 0:
                seen.append("clear")
            seen.append("add")
            if qi == bottom and head % group == group - 1:
                seen.append("write")
    assert len(events) == kv_heads * nk      # no column without a tile
    for column, seen in events.items():
        assert seen[0] == "clear" and seen[-1] == "write", column
        assert seen.count("clear") == seen.count("write") == 1, column
        assert set(seen[1:-1]) == {"add"}, column


@pytest.mark.parametrize("cell,seq_len,dqk,dv,window,fused", [
    # the fourteen configurations that call the kernels (keye2 calls none):
    # K and V of a head resident in five, streamed in nine
    ("c111m", 2048, 64, 64, None, True),
    ("c1p3b", 2048, 128, 128, None, True),
    ("olmoe", 4096, 128, 128, None, True),
    ("lfm2", 8192, 64, 64, None, True),
    ("granite4h", 8192, 64, 64, None, True),
    ("joyai", 8192, 192, 128, None, True),
    ("kimi", 8192, 192, 128, None, True),
    ("nemo3", 8192, 128, 128, None, True),
    ("olmohybrid", 8192, 128, 128, None, True),
    ("ouro", 8192, 128, 128, None, True),
    ("laguna", 8192, 128, 128, None, True),
    ("laguna-swa", 8192, 128, 128, 512, True),
    ("phi4flash", 8192, 64, 128, None, True),
    ("phi4flash-swa", 8192, 64, 128, 512, True),
    ("smallthinker", 16384, 128, 128, None, True),
    ("smallthinker-swa", 16384, 128, 128, 4096, True),
    ("qwen3next", 8192, 256, 256, None, True),
    # no cell's: the longest call the limit admits at 128-wide heads, and
    # the shapes that keep flash_dq + flash_dkv
    ("32k-128", 32768, 128, 128, None, True),
    ("32k-256", 32768, 256, 256, None, False),
    ("64k-128", 65536, 128, 128, None, False),
])
def test_the_fused_backward_rule_at_the_cells_calls(
        cell, seq_len, dqk, dv, window, fused, monkeypatch) -> None:
    """``_fuses_backward`` as a pure function of the shape: every call
    whose two whole-head accumulators and output blocks fit, beside its
    operands (K and V whole where they are resident), the limit
    ``flash_bwd`` passes — every resident call, and the streamed ones up
    to 32k x 128; one byte under the estimate a call falls back to the
    pair."""
    import torchft_tpu.ops.flash as flash_mod
    from jax.experimental.pallas import tpu as pltpu

    blocks = flash_mod._choose_blocks(seq_len, dqk, 2, v_dim=dv,
                                      window=window)
    shape = (seq_len, dqk, 2, *blocks, dv)
    assert flash_mod._fuses_backward(*shape) is fused
    assert flash_mod._fuses_backward(*shape) is fused
    resident = flash_mod._resident(seq_len, dqk + dv, 2)
    assert resident == (cell in ("c111m", "c1p3b", "olmoe", "lfm2",
                                 "granite4h"))
    estimate = flash_mod._fused_vmem_estimate(seq_len, dqk, dv, 2, *blocks,
                                              resident)
    limit = flash_mod._FUSED_PARAMS.vmem_limit_bytes
    assert fused == (estimate <= limit)
    # the whole-head arrays are the estimate's bulk: 8 S (Dqk + Dv) at
    # bf16, and a resident call's K and V twice more
    assert estimate > (8 + 4 * resident) * seq_len * (dqk + dv)
    streamed = flash_mod._fused_vmem_estimate(seq_len, dqk, dv, 2, *blocks)
    assert flash_mod._fuses_backward(*shape, 0) == (streamed <= limit)
    monkeypatch.setattr(flash_mod, "_FUSED_PARAMS", pltpu.CompilerParams(
        vmem_limit_bytes=estimate - 1))
    assert not flash_mod._fuses_backward(*shape)
    monkeypatch.setattr(flash_mod, "_FUSED_PARAMS", pltpu.CompilerParams(
        vmem_limit_bytes=estimate))
    assert flash_mod._fuses_backward(*shape)


def test_the_wrapper_counts_the_fused_backwards(request) -> None:
    names = ("flash_calls", "flash_calls_fused_bwd")
    q = jnp.zeros((1, 1024, 2, 64), jnp.bfloat16)

    def trace(**kw):
        before = _traced_flash_calls(names)
        jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
            q, q, q, interpret=True, **kw).astype(jnp.float32))))(q)
        return (tuple(_traced_flash_calls(names) - before),
                sorted(_flash_calls(jaxpr.jaxpr)))

    # the one kernel in either regime, counted where the call is traced
    assert trace() == ((1, 1), sorted(_FUSED_KERNELS))
    assert trace(_resident_kv_bytes=0) == ((1, 1), sorted(_FUSED_KERNELS))
    # the accumulators over the limit: the pair, not counted
    request.getfixturevalue("pair")
    assert trace() == ((1, 0), sorted(_KERNELS))
    assert trace(_resident_kv_bytes=0) == ((1, 0), sorted(_KERNELS))
