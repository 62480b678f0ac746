"""Flash-attention kernel tests (interpret mode on CPU; the same kernel
compiles for TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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


def test_streamed_backward_matches() -> None:
    # Long-context (streamed) regime now runs the k/q-streamed fused
    # backward kernels; gradients must match the reference exactly.
    import torchft_tpu.ops.flash as flash_mod

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
