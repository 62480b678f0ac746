"""Pallas flash-attention (forward) kernel for TPU.

Streams K/V blocks through VMEM with an online-softmax accumulator so the
[S, S] score matrix never materializes in HBM; per q-block the causal loop
runs only over the k-blocks at or before the diagonal, so causal attention
does half the FLOPs of the dense path. Scores/accumulation in f32 on the
MXU (preferred_element_type), inputs/outputs bf16.

Backward: fused FlashAttention-2-style pallas kernels in the resident-KV
regime — residuals are (q, k, v, out, lse); delta = rowsum(dO·O) is a
cheap XLA reduce; a dQ kernel sweeps k-blocks per q-block and a dK/dV
kernel sweeps q-blocks per k-block, recomputing P = exp(S − lse) tile by
tile so nothing [S, S]-shaped ever touches HBM in either direction. Both
regimes are fused: resident kernels hold K/V (resp. Q/dO) in VMEM for
short/medium sequences; streamed kernels ride tiles over the innermost
grid dimension with VMEM scratch accumulators for long context.

Mosaic layout note: per-row statistics (lse, delta) ride through HBM as
[BH, S, 1] so every block spec keeps its last two dims tile-legal
(second-to-last divisible by 8, last equal to the array dim); inside the
kernels they stay 2-D [BQ, 1] column vectors — Mosaic's tiled layout
prefers 2-D keepdims math over 1-D vectors. (jax's reference TPU kernel
broadcasts lse across 128 lanes instead; the singleton lane column costs
128x less HBM traffic and lowers fine.)

``interpret=True`` runs the same kernels through the Pallas interpreter
(the CPU tests); the default compiles them with Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "flash_attention",
    "flash_attention_with_lse",
    "flash_block_attention_bwd",
]

_NEG_INF = -1e30  # avoid nan from (-inf) - (-inf) in the running max


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                  block_k: int, seq_len: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, D]
    d = q.shape[-1]

    num_k_blocks = seq_len // block_k
    if causal:
        # blocks strictly after the diagonal contribute nothing
        last_block = ((qi + 1) * block_q + block_k - 1) // block_k
        upper = jnp.minimum(num_k_blocks, last_block)
    else:
        upper = num_k_blocks

    acc0 = jnp.zeros((block_q, d), dtype=jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q, 1), dtype=jnp.float32)

    def body(ki, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)


def _flash_streamed_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                           m_ref, l_ref, *, block_q: int, block_k: int,
                           num_k_blocks: int, causal: bool, scale: float):
    """K-blocks ride the innermost grid dimension: only (block_k, d) K/V
    tiles are VMEM-resident at a time, so sequence length is bounded by
    HBM, not VMEM. acc/m/l live in VMEM scratch across the k sweep."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: k-blocks strictly above the diagonal contribute nothing.
    relevant = (
        ki * block_k < (qi + 1) * block_q if causal else ki >= 0
    )

    @pl.when(relevant)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale   # [BQ, D]
        k = k_ref[0].astype(jnp.float32)           # [BK, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[:, :1]                      # [BQ, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l)


# KV footprint above which the k-streamed kernel is used (resident variant
# holds all of K+V in VMEM, which is faster for short/medium sequences).
_RESIDENT_KV_BYTES = 2 * 1024 * 1024


def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool,
                   resident_kv_bytes: Optional[int] = None):
    """q,k,v: [BH, S, D] -> (out [BH, S, D], lse [BH, S] f32)."""
    bh, seq_len, d = q.shape
    threshold = (_RESIDENT_KV_BYTES if resident_kv_bytes is None
                 else resident_kv_bytes)
    kv_bytes = 2 * seq_len * d * q.dtype.itemsize
    # lse travels as [BH, S, 1] (see module docstring: tile-legal specs)
    out_shapes = (
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((bh, seq_len, 1), jnp.float32),
    )
    if kv_bytes <= threshold:
        grid = (bh, seq_len // block_q)
        kernel = functools.partial(
            _flash_kernel,
            block_q=block_q,
            block_k=block_k,
            seq_len=seq_len,
            causal=causal,
            scale=scale,
        )
        out, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ],
            out_shape=out_shapes,
            interpret=interpret,
            name="flash_fwd",
        )(q, k, v)
        return out, lse[..., 0]

    # Long context: stream K/V tiles via the grid.
    num_k_blocks = seq_len // block_k
    grid = (bh, seq_len // block_q, num_k_blocks)
    kernel = functools.partial(
        _flash_streamed_kernel,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=num_k_blocks,
        causal=causal,
        scale=scale,
    )
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=out_shapes,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[..., 0]


# ------------------------------------------------------------- backward pass
# FlashAttention-2 style fused backward: residuals are (q, k, v, out, lse);
# delta = rowsum(dO * O) is a cheap XLA elementwise+reduce; two kernels
# recompute P = exp(S - lse) tile-by-tile — dQ sweeps k-blocks per q-block,
# dK/dV sweeps q-blocks per k-block. Nothing [S, S]-shaped ever
# materializes in HBM in either direction.


def _bwd_p_ds(q_scaled, k, v, do, lse, delta, qi, ki, block_q: int,
              block_k: int, causal: bool):
    """Shared score recompute for every backward kernel: P = exp(S − lse)
    with the causal mask, and dS = P ⊙ (dO·Vᵀ − Δ). One definition so
    mask/softmax changes can never diverge between regimes. lse and delta
    are [BQ, 1] column vectors (2-D keepdims math lowers best on Mosaic)."""
    s = jax.lax.dot_general(
        q_scaled, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta)
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q: int, block_k: int,
                         seq_len: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale      # [BQ, D]
    do = do_ref[0].astype(jnp.float32)            # [BQ, D]
    lse = lse_ref[0]                              # [BQ, 1]
    delta = delta_ref[0]                          # [BQ, 1]
    d = q.shape[-1]

    num_k_blocks = seq_len // block_k
    if causal:
        last_block = ((qi + 1) * block_q + block_k - 1) // block_k
        upper = jnp.minimum(num_k_blocks, last_block)
    else:
        upper = num_k_blocks

    dq0 = jnp.zeros((block_q, d), dtype=jnp.float32)

    def body(ki, dq):
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        _, ds = _bwd_p_ds(
            q, k, v, do, lse, delta, qi, ki, block_q, block_k, causal
        )
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(0, upper, body, dq0)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, block_k: int,
                          seq_len: int, causal: bool, scale: float):
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)              # [BK, D]
    v = v_ref[0].astype(jnp.float32)
    d = k.shape[-1]

    num_q_blocks = seq_len // block_q
    lower = (ki * block_k) // block_q if causal else 0

    dk0 = jnp.zeros((block_k, d), dtype=jnp.float32)
    dv0 = jnp.zeros((block_k, d), dtype=jnp.float32)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(
            jnp.float32
        ) * scale
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * block_q, block_q), :]    # [BQ, 1]
        delta = delta_ref[0, pl.ds(qi * block_q, block_q), :]
        p, ds = _bwd_p_ds(
            q, k, v, do, lse, delta, qi, ki, block_q, block_k, causal
        )
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # q already carries `scale`, so ds^T @ q includes dL/dk's scale
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    dk, dv = jax.lax.fori_loop(lower, num_q_blocks, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_dq_streamed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                  delta_ref, dq_ref, dq_acc, *,
                                  block_q: int, block_k: int,
                                  num_k_blocks: int, causal: bool,
                                  scale: float):
    """K/V tiles ride the innermost grid dim (long-context regime); dq
    accumulates in VMEM scratch across the k sweep."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    relevant = (
        ki * block_k < (qi + 1) * block_q if causal else ki >= 0
    )

    @pl.when(relevant)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        _, ds = _bwd_p_ds(
            q, k, v, do, lse, delta, qi, ki, block_q, block_k, causal
        )
        dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_streamed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, dk_ref, dv_ref, dk_acc,
                                   dv_acc, *, block_q: int, block_k: int,
                                   num_q_blocks: int, causal: bool,
                                   scale: float):
    """Q/dO tiles ride the innermost grid dim; dk/dv accumulate in VMEM
    scratch across the q sweep."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: this q block contributes iff its last row can see the k
    # block's first column
    relevant = (
        (qi + 1) * block_q > ki * block_k if causal else qi >= 0
    )

    @pl.when(relevant)
    def _accumulate():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32) * scale
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        p, ds = _bwd_p_ds(
            q, k, v, do, lse, delta, qi, ki, block_q, block_k, causal
        )
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # q already carries `scale`, so ds^T @ q includes dL/dk's scale
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward_streamed(q, k, v, g, lse, delta, causal: bool,
                             scale: float, block_q: int, block_k: int,
                             interpret: bool):
    bh, seq_len, d = q.shape
    num_q_blocks = seq_len // block_q
    num_k_blocks = seq_len // block_k
    lse = lse[..., None]      # [BH, S, 1] — tile-legal spec layout
    delta = delta[..., None]

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_streamed_kernel, block_q=block_q,
            block_k=block_k, num_k_blocks=num_k_blocks, causal=causal,
            scale=scale,
        ),
        grid=(bh, num_q_blocks, num_k_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, g, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_streamed_kernel, block_q=block_q,
            block_k=block_k, num_q_blocks=num_q_blocks, causal=causal,
            scale=scale,
        ),
        grid=(bh, num_k_blocks, num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _flash_backward(q, k, v, out, lse, g, causal: bool, scale: float,
                    block_q: int, block_k: int, interpret: bool,
                    resident_kv_bytes: Optional[int] = None):
    """Fused pallas backward: delta from (out, g), then the kernel core."""
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [BH, S]
    return _flash_backward_core(
        q, k, v, g, lse, delta, causal, scale, block_q, block_k,
        interpret, resident_kv_bytes,
    )


def _flash_backward_core(q, k, v, g, lse, delta, causal: bool,
                         scale: float, block_q: int, block_k: int,
                         interpret: bool,
                         resident_kv_bytes: Optional[int] = None):
    """Kernel core with EXTERNAL lse/delta ([BH, S] f32): resident variant
    (full K/V resp. Q/dO in VMEM) below the threshold, streamed tiles
    above it. External statistics are what make the ring backward work —
    with the GLOBAL lse and delta, each (q-block, kv-block) pair's
    dq/dk/dv contributions are independent (FlashAttention-2), so pairs
    can be revisited in any order/placement and summed."""
    bh, seq_len, d = q.shape
    threshold = (_RESIDENT_KV_BYTES if resident_kv_bytes is None
                 else resident_kv_bytes)
    kv_bytes = 2 * seq_len * d * q.dtype.itemsize
    if kv_bytes > threshold:
        return _flash_backward_streamed(
            q, k, v, g, lse, delta, causal, scale, block_q, block_k,
            interpret,
        )
    lse = lse[..., None]      # [BH, S, 1] — tile-legal spec layout
    delta = delta[..., None]

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
        seq_len=seq_len, causal=causal, scale=scale,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, seq_len // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, g, lse, delta)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
        seq_len=seq_len, causal=causal, scale=scale,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, seq_len // block_k),
        in_specs=[
            pl.BlockSpec((1, seq_len, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _reference(q, k, v, causal: bool, scale: float):
    """[BH,S,D] layout adapter over ops.attention.reference_attention."""
    from torchft_tpu.ops.attention import reference_attention

    out = reference_attention(
        q[:, :, None], k[:, :, None], v[:, :, None], causal=causal,
        scale=scale,
    )
    return out[:, :, 0].astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret,
           resident_kv_bytes):
    out, _ = _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret,
        resident_kv_bytes,
    )
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               resident_kv_bytes):
    out, lse = _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret,
        resident_kv_bytes,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret,
               resident_kv_bytes, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(
        q, k, v, out, lse, g, causal, scale, block_q, block_k, interpret,
        resident_kv_bytes,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def _bshd_prologue(q, scale, block_q, block_k):
    """Shared [B,S,H,D]-surface plumbing: scale default, block clamping,
    divisibility validation, and the [B,S,H,D] <-> [B*H,S,D] layout
    pair. One place, three wrappers."""
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"flash attention: seq len {s} of q{tuple(q.shape)} must be a "
            f"multiple of the block sizes ({block_q}, {block_k})"
        )

    def merge(x):  # [B,S,H,D] -> [B*H, S, D]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    def unmerge(x):  # [B*H, S, D] -> [B,S,H,D]
        return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return float(scale), block_q, block_k, merge, unmerge


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: int = 128, block_k: int = 128,
                             interpret: bool = False):
    """Forward-only flash attention returning ``(out, lse)`` with
    out [B, S, H, D] and lse [B, H, S] (log-sum-exp of the scaled scores,
    max-folded). The lse output is what makes results MERGEABLE: two
    attention results over disjoint key sets combine exactly via
    ``lse' = logaddexp(lse_a, lse_b); out' = sum_i out_i * exp(lse_i -
    lse')`` — the blockwise/ring/flash-decoding composition rule
    (parallel/ring.py uses it for the flash-block ring path). No custom
    VJP is defined on THIS surface; for gradients use
    ``flash_attention``, or the ring paths in parallel/ring.py — the
    flash ring differentiates via its own ring-structured VJP built on
    ``flash_block_attention_bwd``."""
    b, s, h, _ = q.shape
    scale, block_q, block_k, merge, unmerge = _bshd_prologue(
        q, scale, block_q, block_k
    )
    out, lse = _flash_forward(
        merge(q), merge(k), merge(v), causal, scale,
        block_q, block_k, interpret,
    )
    return unmerge(out), lse.reshape(b, h, s)


def flash_block_attention_bwd(q, k, v, do, lse, delta, causal: bool,
                              scale: Optional[float] = None,
                              block_q: int = 128, block_k: int = 128,
                              interpret: bool = False):
    """Gradient CONTRIBUTIONS of one (q-block, kv-block) pair under
    global softmax statistics.

    q, k, v, do: [B, S, H, D] (q and k blocks the same length);
    lse, delta: [B, H, S] f32 — the GLOBAL log-sum-exp of q's full
    (cross-block) attention row and the global delta = rowsum(dO ⊙ O).
    Returns (dq, dk, dv) for this pair only; summing over every pair a
    q row attends to yields the exact full gradients (FlashAttention-2
    decomposition — P = exp(S − lse) is already globally normalized, so
    pair contributions are independent). This is the building block of
    the ring-attention backward (parallel/ring.py): the diagonal pair
    runs causal=True, past pairs causal=False."""
    b, s, h, _ = q.shape
    scale, block_q, block_k, merge, unmerge = _bshd_prologue(
        q, scale, block_q, block_k
    )

    def merge_stat(x):  # [B,H,S] -> [BH, S]
        return x.reshape(b * h, s)

    dq, dk, dv = _flash_backward_core(
        merge(q), merge(k), merge(v), merge(do),
        merge_stat(lse.astype(jnp.float32)),
        merge_stat(delta.astype(jnp.float32)),
        causal, scale, block_q, block_k, interpret,
    )
    return unmerge(dq), unmerge(dk), unmerge(dv)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False,
                    _resident_kv_bytes: Optional[int] = None):
    """[B, S, H, D] flash attention (pallas on TPU).

    Sequence length must be a multiple of the block sizes (pad upstream if
    needed; the model configs here use powers of two).

    ``_resident_kv_bytes`` overrides the resident-vs-streamed regime
    threshold for THIS call (0 forces the streamed kernels); used by
    chip_smoke.py and the tests to run both regimes at one shape without
    touching shared state.
    """
    scale, block_q, block_k, merge, unmerge = _bshd_prologue(
        q, scale, block_q, block_k
    )
    out = _flash(merge(q), merge(k), merge(v), causal, scale,
                 block_q, block_k, interpret, _resident_kv_bytes)
    return unmerge(out)
