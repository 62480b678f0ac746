"""Memory-efficient cross entropy: online logsumexp over vocab chunks.

The flagship configs pair a small d_model with a 32k vocab, so the logits
tensor dwarfs everything else the train step touches: [B, S, V] f32 at the
125m bench shape is ~1 GB written + read back per step, pure HBM traffic
(the reference has no analog — its torch models never fuse this; XLA can't
either, because log_softmax needs the full row before the gather).

The core primitive ``chunked_lse_and_target`` never materializes [N, V]:
a lax.scan over vocab chunks runs the classic online-softmax recurrence
on [N, V/C] tiles — running row max m, running sumexp s rescaled by
exp(m_old - m_new), plus the target logit gathered from whichever chunk
holds it. Its custom VJP re-runs the same scan, rebuilding each chunk's
logits on the fly and accumulating

    dlogits_c = exp(logits_c - lse) * g_lse + onehot_c * g_tl
    dx       += dlogits_c @ w_c^T               [N, D]
    dw_c      = dlogits_c^T @ x                 [V/C, D] per chunk

so backward peak memory matches forward (one [N, V/C] tile live at a
time) at the cost of recomputing the chunk matmuls — the same
FLOPs-for-HBM trade as flash attention, applied to the lm head. Because
the VJP is written for GENERIC cotangents (g_lse, g_tl), the primitive
composes under further transformations — in particular the
vocab-parallel loss below differentiates through psum/logaddexp on top
of it.

``make_vocab_parallel_cross_entropy`` is the TP-native loss for a
column-parallel (vocab-sharded) lm head: each device computes its
shard's (lse, target-logit) pair locally via the chunked scan, then the
shards combine with a pmax-stabilized logaddexp psum — Megatron's
vocab-parallel cross entropy, done the TPU way (shard_map + XLA
collectives, no gathered logits anywhere).

Numerics match the dense log_softmax path up to fp reassociation of the
sumexp (tests pin this to ~1e-6 in f32). Out-of-range targets clamp
exactly like dense take_along_axis (clip semantics), so flipping
xent_chunks can never change a loss value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "chunked_cross_entropy",
    "chunked_lse_and_target",
    "hidden_cross_entropy",
    "make_vocab_parallel_cross_entropy",
]


def _scan_chunks(x, w, targets, mask, num_chunks: int):
    """Forward scan: returns (lse [N], target_logit [N]); target_logit is
    0 where ``mask`` is False. targets are pre-clamped by callers."""
    n, d = x.shape
    v = w.shape[1]
    # checked here so both the primal AND the custom-VJP forward hit it
    if v % num_chunks:
        raise ValueError(
            f"vocab size {v} is not divisible by xent chunk count "
            f"{num_chunks} (set xent_chunks to a divisor of the vocab)"
        )
    vc = v // num_chunks
    w_chunks = w.T.reshape(num_chunks, vc, d)  # [C, Vc, D]

    m0 = jnp.full((n,), -jnp.inf, dtype=jnp.float32)
    s0 = jnp.zeros((n,), dtype=jnp.float32)
    t0 = jnp.zeros((n,), dtype=jnp.float32)

    def body(carry, inputs):
        m, s, tl = carry
        ci, wc = inputs  # wc: [Vc, D]
        logits_c = (x @ wc.T).astype(jnp.float32)  # [N, Vc]
        m_new = jnp.maximum(m, jnp.max(logits_c, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits_c - m_new[:, None]), axis=-1
        )
        # gather the target logit if it lives in this chunk
        local = targets - ci * vc
        in_chunk = (local >= 0) & (local < vc)
        picked = jnp.take_along_axis(
            logits_c, jnp.clip(local, 0, vc - 1)[:, None], axis=-1
        )[:, 0]
        tl = jnp.where(in_chunk, picked, tl)
        return (m_new, s, tl), None

    (m, s, tl), _ = jax.lax.scan(
        body, (m0, s0, t0),
        (jnp.arange(num_chunks), w_chunks),
    )
    return m + jnp.log(s), jnp.where(mask, tl, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def chunked_lse_and_target(x, w, targets, mask, num_chunks: int = 8):
    """(lse [N], target_logit [N]) of logits = x @ w, never materializing
    [N, V]. x: [N, D], w: [D, V] with V % num_chunks == 0, targets: [N]
    int32 (clamped to [0, V-1]), mask: [N] bool — rows where False report
    target_logit 0 and receive no onehot gradient (used by the
    vocab-parallel loss for out-of-shard targets)."""
    t = jnp.clip(targets, 0, w.shape[1] - 1)
    return _scan_chunks(x, w, t, mask, num_chunks)


def _lse_fwd(x, w, targets, mask, num_chunks: int):
    v = w.shape[1]
    t = jnp.clip(targets, 0, v - 1)
    lse, tl = _scan_chunks(x, w, t, mask, num_chunks)
    return (lse, tl), (x, w, t, mask, lse)


def _lse_bwd(num_chunks: int, residuals, cotangents):
    x, w, targets, mask, lse = residuals
    g_lse, g_tl = cotangents  # [N], [N]
    n, d = x.shape
    v = w.shape[1]
    vc = v // num_chunks
    w_chunks = w.T.reshape(num_chunks, vc, d)  # [C, Vc, D]
    g_tl = jnp.where(mask, g_tl, 0.0)

    dx0 = jnp.zeros((n, d), dtype=jnp.float32)

    def body(dx, inputs):
        ci, wc = inputs
        logits_c = (x @ wc.T).astype(jnp.float32)       # [N, Vc]
        p = jnp.exp(logits_c - lse[:, None])            # d lse / d logits
        local = targets - ci * vc
        in_chunk = (local >= 0) & (local < vc)
        onehot = (
            jax.nn.one_hot(jnp.clip(local, 0, vc - 1), vc,
                           dtype=jnp.float32)
            * in_chunk[:, None]
        )
        dlogits = p * g_lse[:, None] + onehot * g_tl[:, None]
        dx = dx + dlogits @ wc.astype(jnp.float32)      # [N, D]
        dwc = dlogits.T @ x.astype(jnp.float32)         # [Vc, D]
        return dx, dwc

    dx, dw_chunks = jax.lax.scan(
        body, dx0, (jnp.arange(num_chunks), w_chunks)
    )
    dw = dw_chunks.reshape(v, d).T  # [D, V]
    zeros_t = np.zeros(targets.shape, dtype=jax.dtypes.float0)
    zeros_m = np.zeros(mask.shape, dtype=jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(w.dtype), zeros_t, zeros_m


chunked_lse_and_target.defvjp(_lse_fwd, _lse_bwd)


def chunked_cross_entropy(x, w, targets, num_chunks: int = 8):
    """Mean next-token NLL of softmax(x @ w) rows vs integer targets.

    Equals ``mean(-log_softmax(x @ w)[i, targets[i]])`` without ever
    holding [N, V] in memory (see module docstring).
    """
    mask = jnp.ones(targets.shape, dtype=bool)
    lse, tl = chunked_lse_and_target(x, w, targets, mask, num_chunks)
    return jnp.mean(lse - tl)


def hidden_cross_entropy(h, w, targets, num_chunks: int):
    """Model-facing adapter: mean CE of [B, S, D] hidden states against
    [B, S] targets through vocab projection ``w`` [D, V], chunked. One
    definition so every model family's loss dispatch stays in lockstep
    (transformer.loss_fn, llama.llama_loss_fn).

    Assumes an UNSHARDED (replicated) lm head: the chunk reshape + scan
    is opaque to GSPMD, so a vocab-sharded ``w`` (tp_rules_gpt) may be
    silently all-gathered here every step. For a TP-sharded head, build
    the loss with make_vocab_parallel_cross_entropy instead — it runs
    this same scan per shard and combines with psum."""
    d = h.shape[-1]
    return chunked_cross_entropy(
        h.astype(jnp.float32).reshape(-1, d),
        w.astype(jnp.float32),
        targets.reshape(-1),
        num_chunks,
    )


def make_vocab_parallel_cross_entropy(mesh, axis_name: str = "tensor",
                                      num_chunks: int = 1):
    """Build a jittable mean-CE loss for a VOCAB-SHARDED lm head.

    Returns ``loss(h, w, targets)`` where h: [N, D] and targets: [N] are
    replicated over ``axis_name`` and w: [D, V] is sharded on its vocab
    dim (the tp_rules_gpt/Megatron column-parallel lm head). Each device
    runs the chunked scan on its local [D, V/tp] shard only; shards
    combine with a pmax-stabilized logaddexp-psum for the global lse and
    a psum for the target logit (exactly one shard owns each target).
    No [N, V] or [N, V/tp] gather ever forms, and gradients flow through
    the collectives (max-subtraction is gradient-neutral, so the pmax is
    stop_gradient'ed).

    Inputs/outputs are replicated over every OTHER mesh axis too (specs
    below say so); compose batch sharding outside if needed.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def sharded(h, w_local, targets):
        from jax import lax

        idx = lax.axis_index(axis_name)
        vloc = w_local.shape[1]
        v_global = vloc * lax.psum(1, axis_name)
        # dense-path clip parity for out-of-range ids (see module doc)
        targets = jnp.clip(targets, 0, v_global - 1)
        t_loc = targets - idx * vloc
        mask = (t_loc >= 0) & (t_loc < vloc)
        lse_loc, tl_loc = chunked_lse_and_target(
            h.astype(jnp.float32), w_local.astype(jnp.float32),
            t_loc, mask, num_chunks,
        )
        # stabilizer: max over shards of a gradient-stopped copy
        # (pmax has no differentiation rule; all_gather + max do, and
        # max-subtraction is gradient-neutral anyway)
        m = jnp.max(
            lax.all_gather(lax.stop_gradient(lse_loc), axis_name),
            axis=0,
        )
        lse = m + jnp.log(lax.psum(jnp.exp(lse_loc - m), axis_name))
        tl = lax.psum(tl_loc, axis_name)
        return lse - tl  # per-row nll [N]

    f = shard_map(
        sharded,
        mesh=mesh,
        in_specs=(P(), P(None, axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )

    def loss(h, w, targets):
        return jnp.mean(f(h, w, targets))

    return loss
