"""The head's cross entropy without the ``[N, V]`` logits: two paths, by
whether the head is sharded.

The logits dwarf everything else the head touches: ``[T, V]`` f32 is 6.6 GB
at Cerebras-GPT-111M's cell (T = 16 rows x 2 048 = 32 768, d 768, V 50 304),
4.9 GB at OLMoE's (T 24 576, d 2 048) and 2.1 - 3.3 GB at the cells that
hold an 8- to 32-way share of a vocabulary (T 32 768, V 16 160 - 25 088), on
a 16 GB chip. Both paths cut them into ``num_chunks`` pieces and hold one.

**Unsharded head: ``chunked_cross_entropy``, the fused sweep.** The mean
NLL is a ``custom_vjp`` whose FORWARD rule sweeps tiles of rows, a whole row
of the vocabulary at a time, so a row's exact log-sum-exp is known before
its ``dlogits`` are formed and each logit is built once:

    z    = x_r @ w                                 [rows, V] f32
    lse  = max(z) + log(sum(exp(z - max(z))))      [rows]
    dz   = (exp(z - lse) - onehot(targets)) / N    [rows, V] f32
    dx_r = dz @ w^T                                [rows, D]
    dW  += x_r^T @ dz                              [D, V] f32 carry

The residuals are ``dx`` and ``dW``; the backward rule multiplies them by
the scalar cotangent and does nothing else. A call that is not
differentiated (evaluation, a reference check) runs the same sweep without
the last three lines. That is 6 T d V FLOP a differentiated call, the three
matmuls any head needs, and one ``[rows, V]`` tile of logits through HBM:
written by the matmul, read for the max, for the sum and to form ``dz``,
and ``dz`` written and read by two matmuls, 28 B an element where nothing
fuses and 16 as XLA compiles it for the v5e (the max rides the first
matmul, each gradient matmul forms ``dz`` from the logits as it reads
them). ``num_chunks`` is into how many pieces at least the logits are cut:
the sweep asks for that many tiles, or for as many as keep a tile within
4 096 rows (at 8 192, the tile of the chunked scan it replaced, OLMoE's
fused step peaked 4.1 % over its parent's and Phi-4-mini-flash's 2.2 %),
and takes the smallest divisor of N from there (24 576 rows in 3 pieces: 6
tiles of 4 096; 16 384 in 3: 4 of 4 096), so a tile is never larger than
N / ``num_chunks`` rows of the vocabulary; where no divisor leaves a tile at
least half the rows asked for (a prime N), the tiles are ceil(N / tiles
asked for) rows and the last is padded with rows that count for nothing in
the loss or the gradients. V need not divide. A tile costs a read and a
write of the f32 ``dW`` carry (8 d V bytes), which is why the tiles are not
smaller still; a head whose 4 096 rows of logits are still too much (a
vocabulary of 256k unsharded is 4 GiB) raises ``num_chunks``.

**Vocabulary-sharded head: ``chunked_lse_and_target`` under
``make_vocab_parallel_cross_entropy``, the recompute.** A shard cannot form
``exp(z - lse)`` before the shards' ``lse`` have been combined, so its
gradients cannot be had in the forward sweep. Its primitive scans VOCABULARY
chunks with the online-softmax recurrence on ``[N, V/C]`` tiles (running row
max m, running sumexp s rescaled by exp(m_old - m_new), the target logit
gathered from whichever chunk holds it) and its custom VJP runs the scan
again, rebuilding each chunk's logits:

    dlogits_c = exp(logits_c - lse) * g_lse + onehot_c * g_tl
    dx       += dlogits_c @ w_c^T               [N, D]
    dw_c      = dlogits_c^T @ x                 [V/C, D] per chunk

8 T d V FLOP for 6 of use, and the f32 tile goes through HBM in both scans
(12 B an element forward, 20 B backward): the FLOPs-for-HBM trade of flash
attention, kept where the combine between the scans is a collective.
Because that VJP is written for GENERIC cotangents (g_lse, g_tl), the
primitive composes under further transformations - the vocab-parallel loss
differentiates through psum/logaddexp on top of it: each device computes its
shard's (lse, target-logit) pair locally, then the shards combine with a
pmax-stabilized logaddexp psum - Megatron's vocab-parallel cross entropy,
done the TPU way (shard_map + XLA collectives, no gathered logits anywhere).

**A weight a row: ``weighted_cross_entropy``, the fused sweep again.** A
loss that mixes rows unequally — ``models/ouro.py``: four heads a token,
row ``i`` of pass ``t`` weighed by an exit probability that is itself
differentiated — wants ``Σ_i w_i ℓ_i`` and every ``ℓ_i``. The same sweep
over row tiles with ``dz = w_i (softmax − onehot)`` in the place of ``/ N``,
the rows' own ``ℓ`` handed back beside the sum, and ``ℓ`` as the cotangent
of ``w`` (``∂ Σ w ℓ / ∂ w_i = ℓ_i``): 6 T d V and one tile through HBM, as
above. ``ℓ`` comes back for its VALUE: a cotangent on it is dropped (what
would differentiate it is the recompute path below).

What is rounded where is the same on every path: operands as the caller
casts them (``hidden_cross_entropy``: f32), default matmul precision, f32
logits, f32 ``lse``, ``dlogits`` formed in f32. Numerics match the dense
log_softmax path up to fp reassociation of the sumexp (tests pin this to
~1e-6 in f32). Out-of-range targets clamp exactly like dense
take_along_axis (clip semantics), so flipping xent_chunks can never change a
loss value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "chunked_cross_entropy",
    "weighted_cross_entropy",
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


# the most rows a tile of the fused sweep holds: at 8 192 the fused steps of
# two cells peaked 2 - 4 % over their parents' (PERF.md section 6, PR 49)
_TILE_ROWS = 4096


def _row_tiles(n: int, num_chunks: int):
    """(tiles, rows a tile, rows of padding) of the fused sweep. At least
    ``num_chunks`` tiles and as many as keep a tile within ``_TILE_ROWS``;
    from there the smallest divisor of ``n``, unless it would leave a tile
    under half as many rows as asked for; then tiles of ceil(n / tiles
    asked for) rows, the last one padded."""
    c = max(1, min(max(num_chunks, -(-n // _TILE_ROWS)), n))
    for tiles in range(c, 2 * c + 1):
        if n % tiles == 0:
            return tiles, n // tiles, 0
    rows = -(-n // c)
    tiles = -(-n // rows)
    return tiles, rows, tiles * rows - n


def _sweep(x, w, targets, num_chunks: int, with_grads: bool):
    """One scan over row tiles: the mean NLL and, ``with_grads``, its
    gradients (dx [N, D], dW [D, V], both f32) for a cotangent of 1."""
    n, d = x.shape
    v = w.shape[1]
    tiles, rows, pad = _row_tiles(n, num_chunks)
    t = jnp.clip(targets, 0, v - 1)
    scanned = (x, t)
    if pad:
        scanned = (jnp.pad(x, ((0, pad), (0, 0))), jnp.pad(t, (0, pad)),
                   jnp.arange(tiles * rows) < n)
    cols = jnp.arange(v)

    def tile(xr, tr, live=None):
        """A tile's summed NLL and its dz; ``live`` is False on padding."""
        z = (xr @ w).astype(jnp.float32)                # [rows, V]
        hit = cols[None, :] == tr[:, None]
        m = jnp.max(z, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(z - m[:, None]), axis=-1))
        nll = lse - jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
        scale = 1.0 / n
        if live is not None:
            nll = jnp.where(live, nll, 0.0)
            scale = jnp.where(live, scale, 0.0)[:, None]
        if not with_grads:
            return jnp.sum(nll), None
        return jnp.sum(nll), (jnp.exp(z - lse[:, None]) - hit) * scale

    def loss_body(total, inputs):
        return total + tile(*inputs)[0], None

    def grad_body(carry, inputs):
        total, dw = carry
        tile_total, dz = tile(*inputs)
        xr = inputs[0]
        dxr = dz @ w.T.astype(jnp.float32)              # [rows, D]
        dw = dw + xr.T.astype(jnp.float32) @ dz         # [D, V]
        return (total + tile_total, dw), dxr

    scanned = tuple(a.reshape(tiles, rows, *a.shape[1:]) for a in scanned)
    zero = jnp.zeros((), jnp.float32)
    if not with_grads:
        return jax.lax.scan(loss_body, zero, scanned)[0] / n
    (total, dw), dx = jax.lax.scan(
        grad_body, (zero, jnp.zeros((d, v), jnp.float32)), scanned)
    return total / n, dx.reshape(tiles * rows, d)[:n], dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_cross_entropy(x, w, targets, num_chunks: int = 8):
    """Mean next-token NLL of softmax(x @ w) rows vs integer targets.

    Equals ``mean(-log_softmax(x @ w)[i, targets[i]])`` with one tile of
    at most N / ``num_chunks`` rows of logits in memory at a time, and with
    both gradients computed in the sweep that computes the loss (see module
    docstring). x: [N, D], w: [D, V], targets: [N] int (clamped to
    [0, V-1]); any N, any V.
    """
    return _sweep(x, w, targets, num_chunks, with_grads=False)


def _ce_fwd(x, w, targets, num_chunks: int):
    loss, dx, dw = _sweep(x, w, targets, num_chunks, with_grads=True)
    return loss, (dx.astype(x.dtype), dw.astype(w.dtype))


def _ce_bwd(num_chunks: int, residuals, g):
    dx, dw = residuals
    zeros_t = np.zeros(dx.shape[:1], dtype=jax.dtypes.float0)
    return (g * dx).astype(dx.dtype), (g * dw).astype(dw.dtype), zeros_t


chunked_cross_entropy.defvjp(_ce_fwd, _ce_bwd)


def _weighted_sweep(x, w, targets, weights, num_chunks: int,
                    with_grads: bool):
    """:func:`_sweep` with a weight a row: ``(Σ_i w_i ℓ_i, ℓ [N])`` and,
    ``with_grads``, the sum's gradients ``dx`` [N, D] and ``dW`` [D, V]
    (f32) for a cotangent of 1. Padding rows weigh nothing."""
    n, d = x.shape
    v = w.shape[1]
    tiles, rows, pad = _row_tiles(n, num_chunks)
    scanned = (x, jnp.clip(targets, 0, v - 1), weights.astype(jnp.float32))
    if pad:
        scanned = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                        for a in scanned)
    cols = jnp.arange(v)

    def tile(xr, tr, wr):
        """A tile's rows' NLL and its dz."""
        z = (xr @ w).astype(jnp.float32)                # [rows, V]
        hit = cols[None, :] == tr[:, None]
        m = jnp.max(z, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(z - m[:, None]), axis=-1))
        nll = lse - jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
        if not with_grads:
            return nll, None
        return nll, (jnp.exp(z - lse[:, None]) - hit) * wr[:, None]

    def loss_body(total, inputs):
        nll, _ = tile(*inputs)
        return total + jnp.sum(inputs[2] * nll), nll

    def grad_body(carry, inputs):
        total, dw = carry
        nll, dz = tile(*inputs)
        xr = inputs[0]
        dxr = dz @ w.T.astype(jnp.float32)              # [rows, D]
        dw = dw + xr.T.astype(jnp.float32) @ dz         # [D, V]
        return (total + jnp.sum(inputs[2] * nll), dw), (nll, dxr)

    scanned = tuple(a.reshape(tiles, rows, *a.shape[1:]) for a in scanned)
    zero = jnp.zeros((), jnp.float32)
    if not with_grads:
        total, nll = jax.lax.scan(loss_body, zero, scanned)
        return total, nll.reshape(-1)[:n]
    (total, dw), (nll, dx) = jax.lax.scan(
        grad_body, (zero, jnp.zeros((d, v), jnp.float32)), scanned)
    return total, nll.reshape(-1)[:n], dx.reshape(tiles * rows, d)[:n], dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def weighted_cross_entropy(x, w, targets, weights, num_chunks: int = 8):
    """``(Σ_i weights_i ℓ_i, ℓ [N])`` with ``ℓ_i =
    -log_softmax(x @ w)[i, targets[i]]``: :func:`chunked_cross_entropy`'s
    sweep with a weight a row, all three gradients of the SUM computed in
    the sweep that computes it (``weights``' is ``ℓ``). x: [N, D], w: [D,
    V], targets: [N] int (clamped), weights: [N] f32; any N, any V. ``ℓ``
    is returned for its value: its cotangent is dropped (module
    docstring)."""
    return _weighted_sweep(x, w, targets, weights, num_chunks, False)


def _wce_fwd(x, w, targets, weights, num_chunks: int):
    total, nll, dx, dw = _weighted_sweep(x, w, targets, weights, num_chunks,
                                         True)
    return (total, nll), (dx.astype(x.dtype), dw.astype(w.dtype),
                          nll.astype(weights.dtype))


def _wce_bwd(num_chunks: int, residuals, cotangents):
    dx, dw, nll = residuals
    g, _dropped = cotangents
    zeros_t = np.zeros(dx.shape[:1], dtype=jax.dtypes.float0)
    return ((g * dx).astype(dx.dtype), (g * dw).astype(dw.dtype), zeros_t,
            (g * nll).astype(nll.dtype))


weighted_cross_entropy.defvjp(_wce_fwd, _wce_bwd)


def hidden_cross_entropy(h, w, targets, num_chunks: int):
    """Model-facing adapter: mean CE of [B, S, D] hidden states against
    [B, S] targets through vocab projection ``w`` [D, V], a tile of rows at
    a time. One definition so every model family's loss dispatch stays in
    lockstep (transformer.loss_fn, llama.llama_loss_fn).

    Assumes an UNSHARDED (replicated) lm head: the sweep needs a whole row
    of the vocabulary to form its gradients, so a vocab-sharded ``w``
    (tp_rules_gpt) may be silently all-gathered here every step. For a
    TP-sharded head, build the loss with make_vocab_parallel_cross_entropy
    instead - it scans vocabulary chunks per shard and combines with
    psum."""
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
