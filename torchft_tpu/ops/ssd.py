"""Pallas kernels for the selective state-space scan of Mamba-2
(arXiv:2405.21060) in its chunked, state-space-dual form: forward and
backward under one ``jax.custom_vjp``.

Per head, with a state ``S ∈ R^{P×N}``, ``S_0 = 0`` and ``a_t = Δ_t·A``:

    S_t = exp(a_t)·S_{t-1} + Δ_t · x_t ⊗ B_t,     y_t = S_t·C_t + D·x_t.

``B`` and ``C`` belong to a GROUP of ``H / G`` heads; ``A`` and ``D`` are
a scalar a head. In chunks of ``Q`` positions, with ``cum_i`` the sum of
``a`` from the chunk's first position to ``i`` and ``u_j = Δ_j x_j``:

    y_i    = Σ_{j<=i} (C_i·B_j) exp(cum_i − cum_j) u_j        (inside)
           + exp(cum_i) · S_prev·C_i + D·x_i                  (carried in)
    S_next = exp(cum_Q)·S_prev + Σ_j exp(cum_Q − cum_j) u_j ⊗ B_j

which is the recurrence whatever ``Q`` is. Every exponent is a
difference of cumulative sums taken the right way round, so it is <= 0:
a chunk whose total decay underflows gives 0, never inf or nan.

The kernels. One grid step is one (batch, group, chunk, HEAD BLOCK): a
group's ``H/G`` heads are taken ``hb`` at a time (``_head_block``: the
most whole 128-lane blocks within ``_STEP_LANES`` = 512 lanes that divide
the group — 8 heads of 64 channels), the head blocks innermost, the chunks
next and in order: a TPU core runs its grid sequentially anyway, so the
dependence between chunks costs nothing, and the state of the group's
heads is carried from chunk to chunk in a VMEM scratch ``[H/G/hb, N,
hb·P]`` f32, a slot a head block — it never goes to HBM in the forward
pass proper. ``B`` and ``C`` keep their block index over a chunk's head
blocks, so they are fetched once a (batch, group, chunk), and ``C·Bᵀ``
[Q, Q] is computed ONCE for the group, at its first head block, into a
scratch the others read; per head the decay mask ``L`` [Q, Q] is built in
f32 from the cumulative sums (a column and a row of them), multiplied in,
and ``(C Bᵀ ∘ L)·u`` is one matmul. Inside a step heads are taken in lane
blocks of 128 (two heads of 64 channels): a 64-wide result uses the MXU's
128 columns no better than a 128-wide one, so each head's matmul runs
over the block and a select keeps its own lanes — no 64-lane slice is
ever cut. Neither ``L`` nor a per-position state exists outside VMEM;
nothing ``[B, S, H, P, N]`` is formed anywhere.

What a step holds in VMEM at Q 256, P 64, N 128 (PR 68; until then a step
held the GROUP whole, blocks ``[Q, (H/G)·P]`` and a body of ``H/G / 2``
unrolled head pairs: at Nemotron-H's ``H/G`` = 8 that is this step, at
Granite 4.0-H's ONE group of 64 heads the v5e's compiler refused it — a
scoped allocation of 21.95 MiB against the default limit of 16 MiB, and
the body eight times the program): the step's blocks are ``[256, 512]``
whatever ``H/G`` is — forward ``x``, ``y`` (bf16, 256 KiB, two buffers
each), the saved-state block ``[128, 512]`` f32 (256 KiB, two buffers),
the per-head columns ``[256, 8]`` f32 (a lane tile wide in VMEM: 128 KiB
each), ``C·Bᵀ`` (256 KiB) and temporaries of ``[256, 512]`` f32 (512 KiB
each); backward ``x``, ``dy``, ``dx``, five column outputs, ``B·Cᵀ`` and
``Σ_h W_hᵀ`` (256 KiB each) and the two ``[256, 128]`` f32 sums — and only
the state scratch grows with the group: 256 KiB at ``H/G`` 8 (one slot),
2 MiB at 64 (eight). Both kernels compile inside the default limit at both
(``tests/test_ssd.py`` holds Nemotron-H's call to what the kernels gave
before the head-block axis: on the chip every leaf to the bit and 5.07 /
13.22 ms a call at [4, 8192, 64, 64], 8 groups, against 5.17 / 13.28 —
my chip run, PR 68).

``ssd_bwd`` walks the chunks LAST TO FIRST with the state's cotangent
``dS`` in the same scratch. ``dB`` and ``dC`` are the GROUP's: their
block is revisited over a chunk's consecutive head blocks, the state's
parts and ``Σ_h W_hᵀ`` add up in f32 scratch, and the two matmuls with
the summed tile and the write happen at the last block (head blocks
outside the chunks would need partial sums in HBM and a sum in XLA). It
recomputes the transposed tile
(``B·Cᵀ ∘ Lᵀ``: as ``flash_dkv``, so ``Mᵀ·dy`` is a plain matmul) and
reads the state that entered the chunk. **What the backward keeps**: the
scan's inputs and the chunk-boundary states ``[B, G, S/Q, N, (H/G)·P]``
f32, written by the forward kernel only when it runs as the vjp's
forward rule (268 MB a layer at 32 768 tokens and Q 256; under
``jax.checkpoint`` it lives for that layer's backward alone).
Everything else is recomputed. With ``g_j = (Mᵀ dy)_j + exp(cum_Q −
cum_j)·B_j·dS`` (the cotangent of ``u_j``):

    dx_j = Δ_j g_j + D dy_j            dΔ_j = x_j·g_j + A·da_j
    dC_i = Σ_h [(W_h B)_i + exp(cum_i) dy_i·S_prev]
    dB_j = Σ_h [(W_hᵀ C)_j + exp(cum_Q − cum_j) u_j·dS]
    W_h  = (dy uᵀ) ∘ L                  (summed over the group's heads
                                         BEFORE the two matmuls)
    dA = Σ da·Δ,   dD = Σ dy·x

``a_k`` stands in every decay that spans position ``k``, so with ``G_ij
= L_ij (C_i·B_j)(dy_i·u_j)``, ``t_j = exp(cum_Q − cum_j) u_j·(B_j·dS)``:

    da_k = Σ_{i>=k} Σ_{j<k} G_ij                 (inside the chunk)
         + Σ_{i>=k} exp(cum_i) dy_i·(S_prev·C_i)  (what was carried in)
         + Σ_{j<k} t_j + exp(cum_Q) <dS, S_prev>  (what is carried on)

The first is taken as ``Σ_{i>=k} (Σ_j G_ij − Σ_j G_ji)``: the terms with
both indices past ``k`` cancel, and they cancel to f32 rounding only
because BOTH sums are taken from the one f32 tile ``G`` — measured on
the v5e (PERF.md, PR 33): with the column sums taken as ``u_j·(Mᵀdy)_j``
instead (equal on paper, but rounded to bf16 by the MXU on another
path) ``dA`` was 25 % off at Q 256. The kernel writes ``dx, dB, dC`` and
the per-position pieces of ``dΔ``, ``da`` and ``dD``; the two
cumulative sums inside a chunk and the sums for ``dA`` and ``dD`` are
XLA on ``[B, S, H]`` arrays (8 MB). Nothing accumulates over more than
one chunk.

What is which dtype: ``x, B, C`` arrive and ``y, dx, dB, dC`` leave in
the input dtype (bf16 in the models); ``Δ`` (already through its
softplus), ``A``, ``D`` are f32; cumulative sums (XLA, f32), decays,
the state and every accumulator are f32. As in ``ops/flash.py`` every
operand is upcast as it is loaded and the MXU takes f32 operands in one
pass at Mosaic's default precision.

Layout: ``x`` is read as ``[B, S, H·P]`` and ``B, C`` as ``[B, S, G·N]``
— the shapes the model's split of ``xBC`` already has, no transpose of
anything large. The per-head scalars reach the kernel a head block at a
time, as ``[B, H/hb, S, hb]`` columns and ``[B, H/hb, hb, S]`` rows, both
tile-legal (a block's last dimension is the array's).

The chunk is ``_CHUNK`` (the whole of a shorter sequence, rounded up to
the sublane tile); a sequence that is no multiple is padded with ``Δ = 0`` positions at its end (decay 1, input
0: they change nothing before them). Measured on the v5e at [4, 8192,
64, 64], G 8, N 128 (PERF.md, PR 33): the per-head ``[Q, Q]`` vector
work grows with ``Q`` and the grid steps' own cost with ``1 / Q``.

Off the TPU the same kernels run in Pallas's interpreter (the CPU
tests), chosen from the backend alone. On the TPU ``(H/G)·P`` must be a
multiple of 128 lanes or the whole width. A test that wants other head
blocks sets ``_STEP_LANES`` (nothing here is jitted on it).
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan"]

_NEG = -1e30     # exp(_NEG) == 0: the mask above the diagonal
_LANES = 128
# positions a chunk; see the module docstring
_CHUNK = 256
# lanes of a group's heads one grid step takes: 8 heads of 64 channels
_STEP_LANES = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _f32(a):
    return a.astype(jnp.float32)


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """``a·bᵀ``."""
    return _dot(a, b, ((1,), (1,)))


def _col(cols, h: int):
    """Column ``h`` of ``[Q, H/G]`` as ``[Q, 1]`` (a select and a lane
    sum: exact, and no one-lane slice)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    return jnp.sum(jnp.where(lane == h, cols, 0.0), axis=1, keepdims=True)


def _by_head(parts, head_dim: int):
    """Head ``k``'s lanes of ``parts[k]``, side by side (``parts[0]`` has
    the block's width; the others broadcast against it)."""
    out = parts[0]
    if len(parts) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for k in range(1, len(parts)):
            out = jnp.where(lane >= k * head_dim, parts[k], out)
    return out


def _spread(cols, width: int, head_dim: int):
    """``[rows, 1]`` columns, one a head of a lane block, over the
    block's lanes: ``[rows, width]`` with head ``k``'s value on lanes
    ``k·P .. (k+1)·P``."""
    first = jnp.broadcast_to(cols[0], (cols[0].shape[0], width))
    return _by_head([first] + list(cols[1:]), head_dim)


def _head_sums(prod, n_heads: int, head_dim: int):
    """``[Q, width]`` -> per head of the lane block its lanes' sum
    ``[Q, 1]``."""
    if n_heads == 1:
        return [jnp.sum(prod, axis=1, keepdims=True)]
    lane = jax.lax.broadcasted_iota(jnp.int32, prod.shape, 1)
    return [jnp.sum(jnp.where((lane >= k * head_dim)
                              & (lane < (k + 1) * head_dim), prod, 0.0),
                    axis=1, keepdims=True) for k in range(n_heads)]


def _gather_cols(cols, like):
    """Per-head ``[Q, 1]`` columns -> ``[Q, H/G]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, like, 1)
    out = jnp.zeros(like, jnp.float32)
    for h, c in enumerate(cols):
        out = jnp.where(lane == h, c, out)
    return out


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, dtc_ref, cumc_ref, cumr_ref, d_ref,
                    y_ref, *rest, head_dim: int, heads_per_block: int,
                    save_states: bool):
    """One (batch, group, chunk, head block): ``y`` of the block's heads
    over the chunk and the state they leave, the state they entered with
    written out for the backward where asked. ``C·Bᵀ`` is the group's:
    computed at the chunk's first head block, read by the others."""
    cb_ref, state = rest[-2:]
    ci, j = pl.program_id(2), pl.program_id(3)

    @pl.when(ci == 0)
    def _init():
        state[j] = jnp.zeros(state.shape[1:], state.dtype)

    P, hpb = head_dim, heads_per_block
    Q, hb = dtc_ref.shape[2], dtc_ref.shape[3]
    W = hpb * P
    bm, cm = _f32(b_ref[0]), _f32(c_ref[0])            # [Q, N]
    dtc, cumc, cumr = dtc_ref[0, 0], cumc_ref[0, 0], cumr_ref[0, 0]
    sprev = state[j]                                    # [N, hb·P]
    if save_states:
        rest[0][0, 0, 0] = sprev

    @pl.when(j == 0)
    def _group():
        cb_ref[...] = _dot_nt(cm, bm)                   # [Q, Q]: C_i·B_j

    cb = cb_ref[...]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    carried = _dot(cm, sprev)                           # [Q, hb·P]
    bt = bm.T                                           # [N, Q]
    last = cumc[Q - 1:Q, :]                             # [1, hb]
    for p in range(hb // hpb):
        heads = range(p * hpb, (p + 1) * hpb)
        lanes = slice(p * W, (p + 1) * W)
        xp = _f32(x_ref[0, :, lanes])                   # [Q, W]
        cum_cols = [_col(cumc, h) for h in heads]
        cum_w = _spread(cum_cols, W, P)
        u = xp * _spread([_col(dtc, h) for h in heads], W, P)
        inside = _by_head([
            _dot(cb * jnp.exp(jnp.where(
                tri, cum_cols[k] - cumr[h:h + 1, :], _NEG)), u)
            for k, h in enumerate(heads)], P)
        y = (inside + jnp.exp(cum_w) * carried[:, lanes]
             + d_ref[0, :, lanes] * xp)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        last_w = _spread([_col(last, h) for h in heads], W, P)   # [1, W]
        state[j, :, lanes] = (sprev[:, lanes] * jnp.exp(last_w)
                              + _dot(bt, u * jnp.exp(last_w - cum_w)))


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, dtc_ref, cumc_ref, cumr_ref, d_ref,
                    st_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref,
                    dcumc_ref, dcumr_ref, on_ref, dd_ref, edge_ref,
                    bct_ref, wt_ref, db_acc, dc_acc, ds, *,
                    head_dim: int, heads_per_block: int):
    """One (batch, group, chunk, head block), chunks last to first;
    ``ds`` carries the cotangent of the state the block's heads leave.
    ``B·Cᵀ`` is computed at the chunk's first head block; ``Σ_h W_hᵀ``
    and the state's parts of ``dB`` / ``dC`` add up over the group's head
    blocks in scratch and leave at the last."""
    ci, j = pl.program_id(2), pl.program_id(3)

    @pl.when(ci == 0)
    def _init():
        ds[j] = jnp.zeros(ds.shape[1:], ds.dtype)

    P, hpb = head_dim, heads_per_block
    Q, hb = dtc_ref.shape[2], dtc_ref.shape[3]
    W = hpb * P
    bm, cm = _f32(b_ref[0]), _f32(c_ref[0])            # [Q, N]
    dtc, cumc, cumr = dtc_ref[0, 0], cumc_ref[0, 0], cumr_ref[0, 0]
    sprev = st_ref[0, 0, 0]                             # [N, hb·P]
    dsn = ds[j]

    @pl.when(j == 0)
    def _group():
        bct_ref[...] = _dot_nt(bm, cm)                  # [Q, Q]: B_j·C_i
        wt_ref[...] = jnp.zeros_like(wt_ref)
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    bct = bct_ref[...]
    # the transposed tile: rows are j, columns i, live where i >= j
    tri_t = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
             >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0))
    carried = _dot(cm, sprev)                           # [Q, hb·P]
    b_ds = _dot(bm, dsn)                                # [Q, hb·P]
    ct = cm.T                                           # [N, Q]
    last = cumc[Q - 1:Q, :]
    wt = wt_ref[...]                                    # Σ_h W_hᵀ so far
    db, dc = db_acc[...], dc_acc[...]
    ddt_cols, dcum_cols, on_cols, dd_cols = [], [], [], []
    for p in range(hb // hpb):
        heads = range(p * hpb, (p + 1) * hpb)
        lanes = slice(p * W, (p + 1) * W)
        xp, dyp = _f32(x_ref[0, :, lanes]), _f32(dy_ref[0, :, lanes])
        cum_cols = [_col(cumc, h) for h in heads]
        cum_w = _spread(cum_cols, W, P)
        dt_w = _spread([_col(dtc, h) for h in heads], W, P)
        last_w = _spread([_col(last, h) for h in heads], W, P)
        u = xp * dt_w
        lane = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 1)
        mt_dy, g_cols = [], []
        for k, h in enumerate(heads):
            lt = jnp.exp(jnp.where(
                tri_t, cumr[h:h + 1, :] - cum_cols[k], _NEG))   # [j, i]
            mt_dy.append(_dot(bct * lt, dyp))
            own = ((lane >= k * P) & (lane < (k + 1) * P)) if hpb > 1 else None
            w_h = _dot_nt(u if own is None else jnp.where(own, u, 0.0),
                          dyp) * lt                      # (u_j·dy_i) L_ij
            wt = wt + w_h
            # G transposed; Σ_j G_ij is a row over i and Σ_i G_ij a
            # column over j, both from this one tile
            gt = bct * w_h
            dcumr_ref[0, 0, h:h + 1, :] = jnp.sum(gt, axis=0, keepdims=True)
            g_cols.append(jnp.sum(gt, axis=1, keepdims=True))
        to_end = jnp.exp(last_w - cum_w)                # exp(cum_Q − cum_j)
        g = _by_head(mt_dy, P) + to_end * b_ds[:, lanes]
        dx_ref[0, :, lanes] = (dt_w * g + d_ref[0, :, lanes] * dyp).astype(
            dx_ref.dtype)
        dy_in = dyp * jnp.exp(cum_w)                    # exp(cum_i) dy_i
        ddt_cols += _head_sums(xp * g, hpb, P)
        dcum_cols += [a - b for a, b in zip(
            _head_sums(dy_in * carried[:, lanes], hpb, P), g_cols)]
        on_cols += _head_sums(u * to_end * b_ds[:, lanes], hpb, P)
        dd_cols += _head_sums(dyp * xp, hpb, P)
        # exp(cum_Q) <dS, S_prev>, by lane
        edge_ref[0, 0, 0, :, lanes] = jnp.exp(last_w) * jnp.sum(
            dsn[:, lanes] * sprev[:, lanes], axis=0, keepdims=True)
        dc = dc + _dot_nt(dy_in, sprev[:, lanes])
        db = db + _dot_nt(u * to_end, dsn[:, lanes])
        ds[j, :, lanes] = dsn[:, lanes] * jnp.exp(last_w) + _dot(ct, dy_in)
    wt_ref[...] = wt
    db_acc[...] = db
    dc_acc[...] = dc

    @pl.when(j == pl.num_programs(3) - 1)
    def _leave():
        db_ref[0] = (db + _dot(wt, cm)).astype(db_ref.dtype)
        dc_ref[0] = (dc + _dot(wt.T, bm)).astype(dc_ref.dtype)

    like = (Q, hb)
    ddt_ref[0, 0] = _gather_cols(ddt_cols, like)
    dcumc_ref[0, 0] = _gather_cols(dcum_cols, like)
    on_ref[0, 0] = _gather_cols(on_cols, like)
    dd_ref[0, 0] = _gather_cols(dd_cols, like)


def _heads_per_block(hg: int, head_dim: int, interpret: bool) -> int:
    """Heads of a group taken together, side by side on the lanes: as
    many as fill 128 (a divisor of the group's heads)."""
    width = hg * head_dim
    if not interpret and width % _LANES and hg > 1:
        raise ValueError(
            f"ssd_scan: a group's {hg} heads of {head_dim} channels are "
            f"{width} lanes, no multiple of {_LANES}")
    hpb = max(1, _LANES // head_dim)
    while hg % hpb:
        hpb -= 1
    return hpb


def _head_block(hg: int, head_dim: int, interpret: bool) -> tuple:
    """``(heads a lane block, heads a grid step)``: a grid step takes the
    most whole lane blocks of a group's heads that divide it and lie
    within ``_STEP_LANES`` (whole 128-lane tiles, or the group whole)."""
    hpb = _heads_per_block(hg, head_dim, interpret)
    fits = [hb for hb in range(hpb, hg + 1, hpb)
            if hg % hb == 0 and hb * head_dim <= _STEP_LANES
            and (hb == hg or hb * head_dim % _LANES == 0)]
    # no such block: the group whole where one lane block is (a narrow
    # group off the TPU), else a lane block a step
    return hpb, max(fits, default=hg if hg * head_dim <= _LANES else hpb)


def _layouts(x, dt, a_head, bm, cm, d_head, chunk: int, hb: int):
    """The kernels' operands from the public ones (padded to whole
    chunks), the heads in blocks of ``hb`` (``H/hb`` blocks, a group's
    side by side): ``x [B, S, H·P]``, ``B, C [B, S, G·N]``, the columns
    ``Δ`` and ``cum`` ``[B, H/hb, S, hb]``, the row ``cum`` ``[B, H/hb,
    hb, S]`` and ``D`` by lane ``[H/hb, 1, hb·P]``."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    pad = (-s) % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
                         for z in (x, dt, bm, cm))
    sp = s + pad
    dt = _f32(dt)
    cum = jnp.cumsum((dt * _f32(a_head)).reshape(b, sp // chunk, chunk, h),
                     axis=2).reshape(b, sp, h // hb, hb)
    return (x.reshape(b, sp, h * p), bm.reshape(b, sp, g * n),
            cm.reshape(b, sp, g * n),
            dt.reshape(b, sp, h // hb, hb).transpose(0, 2, 1, 3),
            cum.transpose(0, 2, 1, 3), cum.transpose(0, 2, 3, 1),
            jnp.repeat(_f32(d_head), p).reshape(h // hb, 1, hb * p))


def _specs(chunk: int, nb: int, hb: int, p: int, n: int, at):
    """Block specs of the seven operands both kernels read, on the grid
    (batch, group, chunk, head block): ``at`` maps the grid's chunk index
    to the chunk (the backward's runs down); a group's ``nb`` blocks of
    ``hb`` heads lie side by side, so block ``j`` of group ``g`` is block
    ``g·nb + j`` of the heads. ``B`` and ``C`` keep their index over a
    group's head blocks and are fetched once a chunk."""
    return [
        pl.BlockSpec((1, chunk, hb * p),
                     lambda b, g, c, j: (b, at(c), g * nb + j)),
        pl.BlockSpec((1, chunk, n), lambda b, g, c, j: (b, at(c), g)),
        pl.BlockSpec((1, chunk, n), lambda b, g, c, j: (b, at(c), g)),
        pl.BlockSpec((1, 1, chunk, hb),
                     lambda b, g, c, j: (b, g * nb + j, at(c), 0)),
        pl.BlockSpec((1, 1, chunk, hb),
                     lambda b, g, c, j: (b, g * nb + j, at(c), 0)),
        pl.BlockSpec((1, 1, hb, chunk),
                     lambda b, g, c, j: (b, g * nb + j, 0, at(c))),
        pl.BlockSpec((1, 1, hb * p), lambda b, g, c, j: (g * nb + j, 0, 0)),
    ]


def _forward(x, dt, a_head, bm, cm, d_head, chunk: int, interpret: bool,
             save_states: bool):
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    hg = h // g
    hpb, hb = _head_block(hg, p, interpret)
    nb = hg // hb
    ops = _layouts(x, dt, a_head, bm, cm, d_head, chunk, hb)
    sp = ops[0].shape[1]
    nc = sp // chunk
    f32 = jnp.float32
    out_shape = [jax.ShapeDtypeStruct((b, sp, h * p), x.dtype)]
    out_specs = [pl.BlockSpec((1, chunk, hb * p),
                              lambda b, g, c, j: (b, c, g * nb + j))]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, g, nc, n, hg * p), f32))
        out_specs.append(pl.BlockSpec((1, 1, 1, n, hb * p),
                                      lambda b, g, c, j: (b, g, c, 0, j)))
    out = pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, head_dim=p,
                          save_states=save_states, heads_per_block=hpb),
        grid=(b, g, nc, nb),
        in_specs=_specs(chunk, nb, hb, p, n, lambda c: c),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((nb, n, hb * p), f32)],
        interpret=interpret, name="ssd_fwd",
    )(*ops)
    y = out[0][:, :s].reshape(b, s, h, p)
    return y, (out[1] if save_states else None)


def _backward(x, dt, a_head, bm, cm, d_head, states, dy, chunk: int,
              interpret: bool):
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    hg = h // g
    hpb, hb = _head_block(hg, p, interpret)
    nb = hg // hb
    ops = _layouts(x, dt, a_head, bm, cm, d_head, chunk, hb)
    sp = ops[0].shape[1]
    nc = sp // chunk
    dy = jnp.pad(dy, ((0, 0), (0, sp - s), (0, 0), (0, 0))).reshape(
        b, sp, h * p)

    def at(c):
        return nc - 1 - c

    specs = _specs(chunk, nb, hb, p, n, at)
    wide, group, _, cols, _, rows, _ = specs
    f32 = jnp.float32
    dx, db, dc, ddt, dcum_c, dcum_r, on, dd, edge = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, head_dim=p, heads_per_block=hpb),
        grid=(b, g, nc, nb),
        in_specs=specs + [
            pl.BlockSpec((1, 1, 1, n, hb * p),
                         lambda b, g, c, j: (b, g, at(c), 0, j)),
            wide,
        ],
        out_specs=[
            wide, group, group, cols, cols, rows, cols, cols,
            pl.BlockSpec((1, 1, 1, 1, hb * p),
                         lambda b, g, c, j: (b, g, at(c), 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, h * p), x.dtype),
            jax.ShapeDtypeStruct((b, sp, g * n), bm.dtype),
            jax.ShapeDtypeStruct((b, sp, g * n), cm.dtype),
            jax.ShapeDtypeStruct((b, h // hb, sp, hb), f32),
            jax.ShapeDtypeStruct((b, h // hb, sp, hb), f32),
            jax.ShapeDtypeStruct((b, h // hb, hb, sp), f32),
            jax.ShapeDtypeStruct((b, h // hb, sp, hb), f32),
            jax.ShapeDtypeStruct((b, h // hb, sp, hb), f32),
            jax.ShapeDtypeStruct((b, g, nc, 1, hg * p), f32),
        ],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, n), f32),
                        pltpu.VMEM((chunk, n), f32),
                        pltpu.VMEM((nb, n, hb * p), f32)],
        interpret=interpret, name="ssd_bwd",
    )(*ops, states, dy)

    def heads(z):           # [B, H/hb, S, hb] -> [B, S, H]
        return z.transpose(0, 2, 1, 3).reshape(b, sp, h)

    def chunks(z):          # [B, S, H] -> [B, S/Q, Q, H]
        return z.reshape(b, nc, chunk, h)

    # the module docstring's da_k: a sum from k to the chunk's end, a
    # sum from its start to before k, and a term the whole chunk shares
    dcum = chunks(heads(dcum_c)
                  + dcum_r.transpose(0, 3, 1, 2).reshape(b, sp, h))
    on = chunks(heads(on))
    edge = edge.reshape(b, g, nc, hg, p).sum(-1).transpose(0, 2, 1, 3)
    da = (jnp.cumsum(dcum[:, :, ::-1], axis=2)[:, :, ::-1]
          + jnp.cumsum(on, axis=2) - on
          + edge.reshape(b, nc, 1, h)).reshape(b, sp, h)
    da, ddt, dd = da[:, :s], heads(ddt)[:, :s], heads(dd)[:, :s]
    dt32, a32 = _f32(dt), _f32(a_head)
    return (
        dx[:, :s].reshape(x.shape),
        (ddt + da * a32).astype(dt.dtype),
        jnp.sum(da * dt32, axis=(0, 1)).astype(a_head.dtype),
        db[:, :s].reshape(bm.shape), dc[:, :s].reshape(cm.shape),
        jnp.sum(dd, axis=(0, 1)).astype(d_head.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, a_head, bm, cm, d_head, chunk, interpret):
    return _forward(x, dt, a_head, bm, cm, d_head, chunk, interpret,
                    False)[0]


def _ssd_fwd(x, dt, a_head, bm, cm, d_head, chunk, interpret):
    y, states = _forward(x, dt, a_head, bm, cm, d_head, chunk, interpret,
                         True)
    return y, (x, dt, a_head, bm, cm, d_head, states)


def _ssd_bwd(chunk, interpret, residuals, dy):
    return _backward(*residuals, dy, chunk, interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def _choose_chunk(seq_len: int) -> int:
    """``_CHUNK``, or the whole of a shorter sequence rounded up to the
    16 rows of a packed bf16 tile."""
    return min(_CHUNK, -(-seq_len // 16) * 16)


def ssd_scan(x, dt, A, B, C, D):
    """The selective scan of the module's docstring.

    ``x [B, S, H, P]``, ``dt [B, S, H]`` (``Δ``: positive, f32), ``A
    [H]`` (negative, f32), ``B, C [B, S, G, N]`` with ``G`` dividing
    ``H`` (head ``h`` reads group ``h // (H/G)``), ``D [H]`` ->
    ``y [B, S, H, P]`` in ``x``'s dtype, differentiable in all six. The
    chunk is chosen from the sequence length (:func:`_choose_chunk`);
    the result does not depend on it beyond rounding
    (``tests/test_ssd.py`` runs ``_ssd`` at others)."""
    h, g = x.shape[2], B.shape[2]
    if h % g or B.shape != C.shape or dt.shape != x.shape[:3]:
        raise ValueError(
            f"ssd_scan: x{tuple(x.shape)} dt{tuple(dt.shape)} "
            f"B{tuple(B.shape)} C{tuple(C.shape)} do not fit")
    return _ssd(x, dt, A, B, C, D, _choose_chunk(x.shape[1]), _interpret())
