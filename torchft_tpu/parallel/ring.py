"""Ring attention: sequence/context parallelism over the ICI mesh.

The reference has NO long-context machinery (SURVEY.md §2c: SP/CP absent) —
this is a first-class TPU-native addition per the framework goals. Sequence
length is sharded over a mesh axis; each device holds a Q/K/V block and
K/V blocks rotate around the ring via ``lax.ppermute`` while a streaming
(online-softmax) accumulator builds exact attention — compute on block t
overlaps the transfer of block t+1 on ICI, so attention over N×seq context
costs N ring steps of local flash-style work (Ring Attention,
https://arxiv.org/abs/2310.01889; blockwise parallel transformers).

Everything is ordinary jax inside ``shard_map`` — no host transfers, static
shapes, `lax.fori_loop` control flow — so XLA pipelines the ppermute with
the MXU matmuls. A pallas flash kernel can replace the local block math
(ops/attention.py) without touching the ring structure.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

__all__ = ["ring_attention", "make_ring_attention"]


def _local_attention_step(q, k, v, o, m, l, q_offset, k_offset, scale,
                          causal):
    """One streaming-softmax accumulation of a (q-block, kv-block) pair.

    q: [B, Sq, H, D], k/v: [B, Sk, H, D]
    o: [B, Sq, H, D] accumulator (numerator), m/l: [B, H, Sq] running
    max / denominator. Returns updated (o, m, l).
    """
    import jax.numpy as jnp

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B,H,Sq,Sk]

    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
        s = jnp.where(mask[None, None, :, :], s, -jnp.inf)

    m_block = jnp.max(s, axis=-1)                   # [B,H,Sq]
    m_new = jnp.maximum(m, m_block)
    # Guard fully-masked rows (m_new == -inf): exp(-inf - -inf) = nan.
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])              # [B,H,Sq,Sk]
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    alpha = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
    alpha = jnp.where(jnp.isneginf(m), 0.0, alpha)  # first block: no history
    l_new = alpha * l + jnp.sum(p, axis=-1)
    o_new = (
        o * alpha.transpose(0, 2, 1)[..., None]
        + jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    )
    return o_new, m_new, l_new


def _ring_attention_sharded(q, k, v, axis_name: str, causal: bool,
                            scale: Optional[float]):
    """Per-device body under shard_map: q,k,v are LOCAL seq blocks
    [B, S_local, H, D]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    s_local = q.shape[1]
    d = q.shape[-1]
    eff_scale = scale if scale is not None else 1.0 / (d ** 0.5)

    o0 = jnp.zeros(q.shape, dtype=jnp.float32)
    m0 = jnp.full((q.shape[0], q.shape[2], s_local), -jnp.inf,
                  dtype=jnp.float32)
    l0 = jnp.zeros((q.shape[0], q.shape[2], s_local), dtype=jnp.float32)

    qf = q.astype(jnp.float32)

    def body(t, carry):
        o, m, l, k_t, v_t = carry
        src_block = (idx - t) % n  # whose kv block we hold at ring step t
        o, m, l = _local_attention_step(
            qf, k_t.astype(jnp.float32), v_t.astype(jnp.float32),
            o, m, l,
            q_offset=idx * s_local,
            k_offset=src_block * s_local,
            scale=eff_scale,
            causal=causal,
        )
        # Rotate kv one step around the ring (device i -> i+1), overlapping
        # with the next iteration's compute under XLA's scheduler.
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_n = lax.ppermute(k_t, axis_name, perm)
        v_n = lax.ppermute(v_t, axis_name, perm)
        return o, m, l, k_n, v_n

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zero output
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_flash_fwd_impl(q, k, v, axis_name: str, causal: bool,
                         scale: Optional[float], block_q: int,
                         block_k: int, interpret: bool):
    """Flash-block ring body: each (q-block, kv-block) pair runs the
    pallas flash kernel (ops/flash.py) instead of the einsum online
    softmax, and the per-pair (out, lse) results merge exactly via the
    logaddexp rule. Causality is handled at BLOCK granularity: a kv block
    strictly in the future is skipped outright (lax.cond — no wasted MXU
    work, the n/2 saving dense ring masking forfeits), the diagonal block
    runs the causal kernel, past blocks run unmasked. Returns
    (out [B,Sq,H,D], global lse [B,H,Sq]) — lse is the residual the
    ring backward needs."""
    import jax.numpy as jnp
    from jax import lax

    from torchft_tpu.ops.flash import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    # axis_index only when the causal block schedule needs it: a DEAD
    # axis_index in the non-causal jaxpr survives DCE inside the
    # custom_vjp call and lowers to a naked PartitionId that the SPMD
    # partitioner rejects ("PartitionId instruction is not supported for
    # SPMD partitioning") — jit of the causal=False flash ring failed on
    # exactly this.
    idx = lax.axis_index(axis_name) if causal else None
    b, s_local, h, d = q.shape
    eff_scale = scale if scale is not None else 1.0 / (d ** 0.5)

    o0 = jnp.zeros(q.shape, dtype=jnp.float32)
    lse0 = jnp.full((b, h, s_local), -jnp.inf, dtype=jnp.float32)

    def body(t, carry):
        o_acc, lse_acc, k_t, v_t = carry

        def attend(causal_flag: bool):
            return lambda: flash_attention_with_lse(
                q, k_t, v_t, causal=causal_flag, scale=eff_scale,
                block_q=block_q, block_k=block_k, interpret=interpret,
            )

        if causal:
            src = (idx - t) % n
            o_t, lse_t = lax.cond(
                src > idx,
                lambda: (jnp.zeros(q.shape, q.dtype),
                         jnp.full((b, h, s_local), -jnp.inf, jnp.float32)),
                lambda: lax.cond(
                    src == idx, attend(True), attend(False)
                ),
            )
        else:
            o_t, lse_t = attend(False)()
        # exact two-stream merge (flash-decoding rule)
        lse_new = jnp.logaddexp(lse_acc, lse_t)
        dead = jnp.isneginf(lse_new)
        w_acc = jnp.where(dead, 0.0, jnp.exp(lse_acc - lse_new))
        w_t = jnp.where(dead, 0.0, jnp.exp(lse_t - lse_new))
        o_new = (
            o_acc * w_acc.transpose(0, 2, 1)[..., None]
            + o_t.astype(jnp.float32)
            * w_t.transpose(0, 2, 1)[..., None]
        )
        perm = [(i, (i + 1) % n) for i in range(n)]
        return (o_new, lse_new, lax.ppermute(k_t, axis_name, perm),
                lax.ppermute(v_t, axis_name, perm))

    o, lse, _, _ = lax.fori_loop(0, n, body, (o0, lse0, k, v))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_attention_sharded_flash(q, k, v, axis_name, causal, scale,
                                  block_q, block_k, interpret):
    out, _ = _ring_flash_fwd_impl(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    return out


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale, block_q,
                        block_k, interpret):
    out, lse = _ring_flash_fwd_impl(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, scale, block_q, block_k,
                        interpret, residuals, g):
    """Ring-structured FlashAttention-2 backward. With the GLOBAL lse and
    delta = rowsum(dO ⊙ O) — both q-sharded, both local — every
    (q-block, kv-block) pair's dq/dk/dv contributions are independent, so
    the backward rides the SAME ring schedule as the forward: kv blocks
    rotate together with their dk/dv accumulators, each device adds its
    pair's contribution as the block passes through, and after n hops
    every accumulator is home. dq accumulates locally. Future pairs are
    skipped at block granularity (lax.cond), the diagonal pair runs the
    causal kernels, past pairs the unmasked ones — the same n/2 compute
    saving as the forward."""
    import jax.numpy as jnp
    from jax import lax

    from torchft_tpu.ops.flash import flash_block_attention_bwd

    q, k, v, out, lse = residuals
    n = lax.psum(1, axis_name)
    # Same dead-axis_index hazard as the forward: only materialize idx
    # when the causal schedule uses it.
    idx = lax.axis_index(axis_name) if causal else None
    b, s_local, h, d = q.shape
    eff_scale = scale if scale is not None else 1.0 / (d ** 0.5)

    # delta is local: out and its cotangent are q-sharded. [B, H, Sq]
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1)

    dq0 = jnp.zeros(q.shape, dtype=jnp.float32)
    dkv0 = jnp.zeros(k.shape, dtype=jnp.float32)

    def body(t, carry):
        dq_acc, k_t, v_t, dk_t, dv_t = carry

        def pair_bwd(causal_flag: bool):
            return lambda: flash_block_attention_bwd(
                q, k_t, v_t, g, lse, delta, causal=causal_flag,
                scale=eff_scale, block_q=block_q, block_k=block_k,
                interpret=interpret,
            )

        if causal:
            src = (idx - t) % n
            dq_t, dk_p, dv_p = lax.cond(
                src > idx,
                lambda: (jnp.zeros(q.shape, q.dtype),
                         jnp.zeros(k.shape, k.dtype),
                         jnp.zeros(v.shape, v.dtype)),
                lambda: lax.cond(
                    src == idx, pair_bwd(True), pair_bwd(False)
                ),
            )
        else:
            dq_t, dk_p, dv_p = pair_bwd(False)()

        dq_acc = dq_acc + dq_t.astype(jnp.float32)
        dk_t = dk_t + dk_p.astype(jnp.float32)
        dv_t = dv_t + dv_p.astype(jnp.float32)
        perm = [(i, (i + 1) % n) for i in range(n)]
        return (
            dq_acc,
            lax.ppermute(k_t, axis_name, perm),
            lax.ppermute(v_t, axis_name, perm),
            lax.ppermute(dk_t, axis_name, perm),
            lax.ppermute(dv_t, axis_name, perm),
        )

    dq, _, _, dk, dv = lax.fori_loop(
        0, n, body, (dq0, k, v, dkv0, dkv0)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention_sharded_flash.defvjp(_ring_flash_vjp_fwd,
                                     _ring_flash_vjp_bwd)


def make_ring_attention(mesh, axis_name: str = "seq", causal: bool = True,
                        scale: Optional[float] = None,
                        block_impl: str = "einsum",
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Build a jittable attention fn over sequence-sharded q,k,v.

    Inputs/outputs are GLOBAL arrays [B, S, H, D] sharded on S over
    ``axis_name`` (use `jax.device_put` with PartitionSpec(None, axis_name,
    None, None)). Wraps the per-device ring in shard_map.

    ``block_impl``: "einsum" (default) runs the local block math as XLA
    einsums. "flash" runs each local block through the pallas flash
    kernel and merges (out, lse) streams (MXU-tiled blocks, future kv
    blocks skipped at block granularity). Both are differentiable: the
    flash path carries a ring-structured FlashAttention-2 custom VJP
    (kv blocks and their dk/dv accumulators rotate together; see
    _ring_flash_vjp_bwd). ``interpret`` runs those flash blocks through
    the Pallas interpreter (CPU tests)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)
    if block_impl == "flash":
        # positional binding: custom_vjp with nondiff_argnums rejects
        # keyword arguments
        def fn(q, k, v):
            return _ring_attention_sharded_flash(
                q, k, v, axis_name, causal, scale, block_q, block_k,
                interpret,
            )
    elif block_impl == "einsum":
        fn = functools.partial(
            _ring_attention_sharded,
            axis_name=axis_name,
            causal=causal,
            scale=scale,
        )
    else:
        raise ValueError(
            f"unknown block_impl {block_impl!r}; have 'einsum', 'flash'"
        )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


def ring_attention(q, k, v, mesh, axis_name: str = "seq",
                   causal: bool = True, scale: Optional[float] = None,
                   block_impl: str = "einsum",
                   block_q: int = 128, block_k: int = 128):
    """One-shot convenience wrapper around make_ring_attention."""
    return make_ring_attention(
        mesh, axis_name, causal, scale,
        block_impl=block_impl, block_q=block_q, block_k=block_k,
    )(q, k, v)
