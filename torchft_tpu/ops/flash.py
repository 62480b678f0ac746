"""Pallas flash-attention kernels for TPU: forward and backward.

Forward: K/V tiles go through VMEM with an online-softmax accumulator, so
the [S, S] score matrix never materializes in HBM. Backward:
FlashAttention-2 style — residuals are (q, k, v, out, lse); delta =
rowsum(dO·O) is a cheap XLA reduce; P = exp(S − lse) is recomputed tile
by tile. Two regimes: resident kernels hold K/V whole in VMEM up to
``_RESIDENT_KV_BYTES`` and loop over tiles inside the kernel; streamed
kernels ride the tiles over the innermost grid dimension with VMEM
scratch accumulators (long context). In both the backward is ONE kernel,
``flash_bwd`` (below: "One backward kernel"); ``flash_dq``, which sweeps
k-tiles per q-tile, and ``flash_dkv``, q-tiles per k-tile, are what a call
too long for it falls back to.

Two widths. q and k are ``Dqk`` wide, v (and out, dO, dv) ``Dv``: one
for the GPT and OLMoE families, 192 and 128 for latent attention
(``models/joyai.py``), whose 192 is fed as ONE 192-lane operand. The
other way, the score as ``q_nope·k_nope + q_rope·k_r`` with the 64-wide
rotary key never broadcast to the heads in HBM, was measured on the v5e
at [2, 8192, 32, .] (PERF.md, PR 31): its forward is 11 % faster (20.4
against 21.9 + 1.05 ms to lay k out), and its backward would be a second
family of kernels for about 3 % of a step; it is not here. v is not
padded and P·V is ``Dv`` wide. A call with ``Dqk == Dv`` traces to the
program it traced to before there were two (``tests/test_flash.py`` pins
the jaxpr).

Which head. k and v have ``KV`` heads where q has ``H = KV · group``, and
query head ``i`` reads key/value head ``i // group`` — WHERE IT LIES: in
the merged ``[B·H, S, D]`` / ``[B·KV, S, D]`` layout row ``b`` of q reads
row ``b // group`` of k and v, and that division stands in the K and V
index maps of ``flash_fwd``, ``flash_bwd`` and ``flash_dq``
(``_heads_of``), whose bodies, tiles and grids are what they are for one
head a head. No copy of a key/value head a query head exists in HBM,
before or after a kernel (until PR 55 the models repeated K and V
``group`` times, XLA laid the copies out twice, ``flash_dkv`` wrote dK and
dV ``H`` heads wide and a ``group``-way sum followed: ≈ 20 H-wide arrays a
layer-step, 235 MB each at 28 heads of 16k). In the resident regime
consecutive heads of a group keep the K / V block index and Pallas does
not fetch the block again. dk and dv are the float32 sum over the whole
group, rounded ONCE, where the copies' gradients were each rounded and
then summed (``_at_group_head``): ``flash_bwd`` meets a group's query
heads one after the other on its leading axis and keeps the sums in its
whole-head accumulators ("One backward kernel" below); the fallback's
``flash_dkv`` leads over the ``B·KV`` key/value heads and takes one more
grid axis, INNERMOST, over the group's query heads — a column's tile runs
for head ``b · group + g`` at step ``g``, the K / V block stays where it
is, and its two ``[BK, D]`` scratch accumulators are cleared at the
column's first tile of head 0 and written at its last tile of head
``group − 1``. ``group == 1`` — every MHA call, latent attention, the
ring — traces to a program with no ``// 1`` in a map and no extra axis
(the rule the ``window=None`` path and the ``Dqk == Dv`` zeros follow;
``tests/test_flash.py`` pins the jaxprs). Measured on the v5e (PERF.md,
PR 55): the kernels take the same time grouped as on the copies, within
0.5 %, at 7, 16, 4 and 2 heads a key/value head (PR 74: ``flash_bwd`` 0.1
- 1.3 ms a call faster on K and V where they lie).

What is which dtype. q, k, v, dO arrive and out, dq, dk, dv leave in the
input dtype (bf16 in the models). Inside a kernel every operand is upcast
to f32 as it is loaded and every ``dot_general`` is f32 x f32 -> f32
(``preferred_element_type``); scores, P, dS, the running max / sum, lse,
delta and all accumulators are f32. The softmax ``scale`` is multiplied
into the f32 q once per q tile ([BQ, D], not per score tile), so dSᵀ·Q
already is dk and dS·K takes ``scale`` once, at the end of dq. Measured
on the v5e (PERF.md, PR 24): at Mosaic's default precision the MXU takes
f32 operands in ONE pass and rounds them to bf16 itself — with bf16
inputs the outputs are bit for bit those of a kernel that casts P and dS
to bf16 first — so that cast buys no MXU time and costs vector work
(-0.6 % of the 111m step); it is left out.

The causal sweep. Tiles wholly above the diagonal are skipped; tiles
wholly at or below it (``_tile_full``) run a body with no iota, compare or
select; only the tiles that straddle the diagonal run the masked body.
``_causal_sweep`` is the one closed form of both bounds for the row sweeps
(forward, dq) and the column sweep (dkv); ``_sweep`` runs the full tiles
in a loop and the diagonal ones straight-line (a fixed count where one
tile edge divides the other). A streamed causal grid takes a step only for
a tile it computes: its one inner axis enumerates the live tiles
(``_live_tiles``: two int32 tables built from ``_tile_live`` at trace
time and prefetched as scalars, which the index maps read the q and the k
block from), row-major for the forward and dq, column-major for dkv — the
order the rectangular grid met them in, so every result is bit for bit
what that grid gave — and the accumulators are cleared and written out at
``_sweep_ends``, ``_causal_sweep``'s first and last live block. At S 8192
with 512 x 1024 tiles that is 72 grid steps a head where the rectangle
has 128 (``_grid_steps``). Both ways of sparing the dead steps were
measured on the v5e (PERF.md): clamping their index maps so that they
fetch nothing (PR 31) gained nothing, and not taking them (PR 45) gained
8.9 / 10.0 / 4.2 ms a call of 41.2 / 51.5 / 51.0 (forward / dq / dkv at
[128, 8192, 192 / 128]), 0.6 - 1.4 us a step not taken. Without the mask
the streamed grid is the rectangle of blocks, as it was.

A window (``window=W``: row ``t`` sees columns ``t − (W − 1) … t``) gives
the mask a trailing edge. ``_tile_live`` / ``_tile_full`` take it, so the
streamed grid enumerates the band's tiles and nothing else (31 of 256 a
head at S 8192, W 512, 512 x 512 tiles, where the causal call takes 72
of 512 x 1024), and ``_band_sweep`` is the closed form of the resident
sweeps' three ranges: tiles the trailing edge cuts, full tiles, tiles on
the diagonal (all three as loops; the masked body applies both edges).
``_choose_blocks`` keeps square tiles under a window shorter than two k
edges of the streamed tile (2048 keys) and takes the streamed 512 x 1024
from there on (140 grid steps a head for 252 at S 16384, W 4096).

dK/dV recomputes its tile TRANSPOSED (Sᵀ = K·Qᵀ, [BK, BQ]): Pᵀ·dO and
dSᵀ·Q are then plain matmuls and no [BQ, BK] tile goes through the
transpose unit (``flash_dkv`` -20 % at 64-wide, -26 % at 128-wide heads).

One backward kernel. ``flash_dq`` runs S, dP and dS·K — three matmuls a
live tile — and ``flash_dkv`` Sᵀ, dPᵀ, dSᵀ·Q and Pᵀ·dO: seven, of which
the second score tile and the second dP are time and not work, and the
exp, the mask and ``p * (dp - delta)`` over the f32 tile are made twice
as well; both kernels are MXU-bound on what they execute (PERF.md §7).
The backward is therefore ``flash_bwd``: the tile built once,
as dkv builds it (``_bwd_tile``: transposed, the statistics as the [1, BQ]
rows HBM holds, so no column form of them is made at all), dk and dv its
two plain products and dq the ONE product whose left operand is
contracted over its rows, ``(dSᵀ)ᵀ·K`` — five matmuls and one [BK, BQ]
turn through the transpose unit. The other orientation (P and dS [BQ, BK],
dq plain, dk and dv each contracted over rows: two turns) read 1.4 – 6.8 %
slower at every streamed cell's call (PERF.md, PR 74). The grid is dq's
ROW sweep in either regime (streamed: its live-tile tables as they are,
dq in scratch across a row as before; resident: dq the carry of the q
block's loop over its row, ``_sweep``), and dk and dv accumulate in two
``[S, D]`` f32 accumulators that stay in VMEM for the whole sweep of one
key/value head, a tile adding into rows ``ki * block_k`` and on. The query heads of a group are
consecutive on the leading grid axis, so the accumulators see the whole
group: a column of tiles has its rows cleared at its first tile of the
group's first head and rounded once, into the head's whole-``[S, D]``
output block (index ``b // group``: it leaves VMEM when the leading index
moves on), at its last tile of the group's last (the resident body, whose
grid steps are q blocks, clears all rows at the first and writes all at
the last). A row-major sweep meets a
column's q blocks ascending, as the column sweep did, and the tile's
arithmetic is dkv's own: at equal head counts dq, dk and dv are the pair's
BIT FOR BIT on the v5e at every streamed cell's call (the interpreter's
dq differs in f32's last place: its turned product sums in another
order); a group's dk and dv sum head-major where the pair's summed a tile's
heads together — f32's last place, before the one rounding. The
accumulators and the output blocks are ``8 · S · (Dqk + Dv)`` bytes at
bf16 (20 MiB at 8 192 x (192 + 128), 32 MiB at 16 384 x 256), so the call
passes ``_FUSED_PARAMS``' ``vmem_limit_bytes``, and ``_fuses_backward`` —
a pure function of the shape — sends a call whose
``_fused_vmem_estimate`` does not fit it (32k x 256 and beyond; no call
with resident K and V) to ``flash_dq`` + ``flash_dkv``, whose VMEM does
not grow with the sequence. Measured on the v5e (PERF.md, PR 74): the
backward alone 84.2 -> 62.6 ms a call at [128, 8192, 192 | 128], 84.5 ->
60.7 at [56, 16384, 128], 27.1 -> 19.4 under W 512 at [256, 8192, 128] —
x 0.71 - 0.76, the count of matmuls —, and with K and V resident 7.39 ->
5.69 at [192, 2048, 64], 4.14 -> 2.75 at [128, 2048, 128], 52.8 -> 36.6 at
[128 | 32, 8192, 64]: x 0.67 - 0.77 (at 64-wide heads the tile's vector
work, made once, is worth as much as the matmuls); dq bit for bit there
too, dk and dv f32's last place from the resident ``flash_dkv``'s, which
adds a column's full tiles before its diagonal ones.

Tiles. ``block_q`` / ``block_k`` default to ``None``: ``_choose_blocks``
picks them from (S, D, itemsize) alone — the largest of 512 / 256 / 128
that divides S with ``_vmem_estimate`` <= ``_VMEM_BUDGET``. A tile's cost
is mostly per loop trip (lane-sparse statistics columns, loop-carried
accumulators, no overlap of MXU and vector work across trips), so at
S 2048 512 x 512 tiles run the three kernels 2.3-3.9x faster than
128 x 128 at both head widths, although the diagonal wastes more (10 of
16 tiles computed, against 136 of 256). In the streamed regime a grid
step of dq and dkv is one tile, and at 512 x 512 the step's own cost is
of the order of the tile's arithmetic: there a 512 x 1024 tile is tried
first (``_STREAMED_TILES``). Explicit arguments win
(parallel/ring.py and the tests pass them).

Chunks. A grid step of the streamed FORWARD fetches ``n`` consecutive k
tiles of K and V (one ``(1, n * block_k, D)`` block each; chunks start at
multiples of ``n`` tiles) and sweeps the live ones inside the kernel, in
ascending k. The inner grid axis enumerates the (q block, chunk) pairs
that hold a live tile (``_live_tiles(chunk=n)``; a dead tile of a fetched
chunk is fetched and not computed): 24 grid steps a head for 72 at S 8192
and ``n`` 4, 48 for 272 at S 16384 and 8. What a step saves by itself is
little (measured on the v5e, PERF.md, PR 60: the same tiles one after the
other inside a step, each through the scratch as before, 22.3 -> 21.5 ms
at [128, 8192, 128]); what pays is that the chunk's tiles are laid out in
STRAIGHT-LINE GROUPS of ``_STRAIGHT`` tiles — as many groups of four as
the live run holds, then a pair, then a single —, ``(acc, m, l)`` carried
as values inside a group and through the VMEM scratch between groups:
within a group Mosaic schedules one tile's matmuls beside its neighbour's
softmax, which neither a grid step nor a loop trip lets it do (a loop
over the tiles with the carry as values read SLOWER than one tile a
step, 22.3 -> 22.7 - 26.3 ms; values through ``lax.cond`` 30 ms). A group
is traced as a ``fori_loop`` of ONE tile with ``unroll`` its length, which
Mosaic's lowering lays out whole, so the traced body holds a tile's body
once a group size and mask, whatever ``n``. A group runs the masked body
for all its tiles where any of them is cut by an edge of the mask (the
mask leaves a full tile's scores as they are), the unmasked body
otherwise. Every live tile is computed by ``_fwd_tile`` in the order it
was always met, so ``out`` and ``lse`` are bit for bit what one tile a
step gave. ``n`` is ``_choose_chunk``'s, a pure function of the shape
(``_CHUNK_LADDER``); at ``n == 1`` the body is the one-tile body. The
backward keeps one tile a step: dq's and dkv's readings moved 1 - 3 % when
they swept chunks (PERF.md, PR 58), under what longer bodies cost every
run to trace. The streamed builders stand under a ``jax.jit`` of their
own, so a body is traced and lowered once a shape and process, not at
each call site (``_flash_forward_streamed``).

Mosaic layout note: per-row statistics (lse, delta) are [BH, S] f32 in
HBM and reach EVERY kernel as the one view of that array whose minor
dimension is the sequence: [BH, 1, S] rows, q positions along the lanes,
4 bytes a query row. The forward writes lse so and dq and dkv are handed
the same two operands. A [BH, S, 1] column — the form the forward's and
dq's tile arithmetic takes, rows of their score tiles being q positions
([BQ, 1] keepdims) — is tiled (8, 128) in HBM like any f32 array: one
lane of 128 used, 512 bytes a query row, 128 x the logical size there and
in every DMA (a 4 KB tile for 32 useful bytes). PERF.md has what that
cost while the statistics travelled so: one call of 160 heads at 8k
planned 15.04 GiB (PR 47); 335 MB a call at 80 heads of 8k, written by
the forward, read back by XLA to slice it, written twice more for dq
(PR 50); 1.86 GiB of ``c111m``'s peak and 2 % of its step (PR 51). So the
[BQ, 1] column lives in VMEM only: the forward turns ``m + log(l)`` into
its [1, BQ] row once a q block (``_wide_to_row``), the fallback's dq turns
both rows into columns once a q block (``_rows_to_cols``: into scratch at
the first step of its sweep), and ``flash_bwd`` and dkv, whose transposed
tile has q positions along its columns, read the rows as they are. Both turns go through the transpose unit as a
128-lane-wide copy: exact, where an identity product at the MXU's default precision
would round lse to bf16. Every result is bit for bit what the column
layout gave (``scripts/flash_micro.py --parent``).

``interpret=True`` runs the same kernels through the Pallas interpreter
(the CPU tests); the default compiles them with Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.utils.metrics import TRACED

__all__ = [
    "flash_attention",
    "flash_attention_with_lse",
    "flash_block_attention_bwd",
]

_NEG_INF = -1e30  # avoid nan from (-inf) - (-inf) in the running max


# ------------------------------------------------------------ causal tiles
# Tile (qi, ki) covers rows [qi*BQ, (qi+1)*BQ) and columns [ki*BK,
# (ki+1)*BK) of the score matrix. Under the causal mask it is
#   live  iff its first column is at or before its last row, and
#   full  iff its last column is at or before its first row (no element
#         masked: the body needs no iota, compare or select).
# Live tiles that are not full straddle the diagonal and take the mask.
# Under a window of W keys (row t sees columns t − (W − 1) … t) the mask
# has a second, trailing edge W − 1 columns behind the diagonal: a tile is
#   live  iff, besides, its last column is at or after its first row's
#         earliest key, and
#   full  iff, besides, its first column is at or after its last row's.
# Live tiles that are not full straddle either edge (both, where W is
# shorter than a tile) and take the mask of both.


def _tile_live(qi, ki, block_q: int, block_k: int,
               window: Optional[int] = None):
    live = ki * block_k < (qi + 1) * block_q
    if window is None:
        return live
    return live & ((ki + 1) * block_k - 1 >= qi * block_q - (window - 1))


def _tile_full(qi, ki, block_q: int, block_k: int,
               window: Optional[int] = None):
    full = (ki + 1) * block_k - 1 <= qi * block_q
    if window is None:
        return full
    return full & (ki * block_k >= (qi + 1) * block_q - window)


def _causal_sweep(idx, block_q: int, block_k: int, seq_len: int,
                  rows: bool):
    """The two loop ranges ``(full, diagonal)`` of one causal sweep, each
    ``(lo, hi)``: the closed forms of :func:`_tile_full` and
    :func:`_tile_live` along a row of tiles (``rows``: ``idx`` is the q
    block, the loop runs over k blocks — forward and dq) or along a column
    (``idx`` is the k block, the loop runs over q blocks — dkv, and the
    ends of a column in ``flash_bwd``). One definition, so no two kernels
    can disagree on which tiles carry the mask."""
    if rows:
        full_end = (idx * block_q + 1) // block_k
        live_end = jnp.minimum(
            seq_len // block_k,
            ((idx + 1) * block_q + block_k - 1) // block_k,
        )
        return (0, full_end), (full_end, live_end)
    num_q_blocks = seq_len // block_q
    live_start = (idx * block_k) // block_q
    full_start = jnp.minimum(
        num_q_blocks, ((idx + 1) * block_k + block_q - 2) // block_q
    )
    return (full_start, num_q_blocks), (live_start, full_start)


def _band_sweep(idx, block_q: int, block_k: int, seq_len: int, rows: bool,
                window: int):
    """:func:`_causal_sweep` under a window: the three loop ranges
    ``(full, diagonal, trailing)`` of one sweep of the band — the closed
    forms of the two predicates with both edges. ``trailing`` are the live
    tiles that only the window's edge masks (before the full ones along a
    row, after them along a column); where no tile is full the other two
    ranges meet and every live tile takes the mask."""
    num_q, num_k = seq_len // block_q, seq_len // block_k
    if rows:
        first_row, past_row = idx * block_q, (idx + 1) * block_q
        live_start = jnp.maximum(
            (jnp.maximum(first_row - window + 2, 0) + block_k - 1)
            // block_k - 1, 0)
        live_end = jnp.minimum(num_k, (past_row + block_k - 1) // block_k)
        full_start = (jnp.maximum(past_row - window, 0) + block_k - 1
                      ) // block_k
        full_end = (first_row + 1) // block_k
        full_start = jnp.clip(full_start, live_start, live_end)
        full_end = jnp.clip(full_end, full_start, live_end)
        return ((full_start, full_end), (full_end, live_end),
                (live_start, full_start))
    first_col, past_col = idx * block_k, (idx + 1) * block_k
    live_start = first_col // block_q
    live_end = jnp.minimum(num_q, (past_col + window - 2) // block_q + 1)
    full_start = jnp.minimum(live_end, (past_col + block_q - 2) // block_q)
    full_end = jnp.clip((first_col + window) // block_q, full_start, live_end)
    return ((full_start, full_end), (live_start, full_start),
            (full_end, live_end))


def _sweep_ends(idx, block_q: int, block_k: int, seq_len: int, rows: bool,
                window: Optional[int] = None):
    """``(first, last)`` block of one causal sweep's live tiles: the k
    blocks of q block ``idx`` (``rows``) or the q blocks of k block ``idx``,
    from :func:`_causal_sweep`'s ranges (:func:`_band_sweep`'s under a
    window)."""
    if window is not None:
        _, diagonal, trailing = _band_sweep(
            idx, block_q, block_k, seq_len, rows, window)
        return ((trailing[0], diagonal[1] - 1) if rows
                else (diagonal[0], trailing[1] - 1))
    full, diagonal = _causal_sweep(idx, block_q, block_k, seq_len, rows)
    return ((full[0], diagonal[1] - 1) if rows
            else (diagonal[0], full[1] - 1))


def _row_ranges(qi, block_q: int, block_k: int, seq_len: int, causal: bool,
                window: Optional[int] = None):
    """``(live, full)`` of q block ``qi``'s row of tiles, each ``(lo, hi)``
    over k blocks: the tiles that are computed and, among them, the run
    that no edge of the mask cuts — :func:`_causal_sweep`'s ranges
    (:func:`_band_sweep`'s under a window) joined; without the mask the
    whole row both times."""
    if not causal:
        whole = (0, seq_len // block_k)
        return whole, whole
    if window is None:
        full, diagonal = _causal_sweep(qi, block_q, block_k, seq_len, True)
        return (full[0], diagonal[1]), full
    full, diagonal, trailing = _band_sweep(
        qi, block_q, block_k, seq_len, True, window)
    return (trailing[0], diagonal[1]), full


def _live_tiles(seq_len: int, block_q: int, block_k: int, rows: bool,
                window: Optional[int] = None, chunk: int = 1):
    """``(q_of, k_of)``: the q block and the k block of the t-th live tile
    under the causal mask (the band's, under a window), as two ``int32``
    tables — row-major (a q block's
    k blocks ascending: forward and dq) or, ``rows`` false, column-major (a
    k block's q blocks ascending: dkv). What a streamed causal grid
    enumerates; :func:`_tile_live` alone decides which tiles are in it.
    With a ``chunk`` of k tiles a grid step (the forward: ``rows``),
    ``k_of`` is the chunk — tiles ``k_of * chunk`` and on — and a chunk is
    listed where any of its tiles is live."""
    q_of, k_of = np.indices(
        (seq_len // block_q, seq_len // block_k), dtype=np.int32
    )
    if not rows:
        q_of, k_of = q_of.T, k_of.T
    live = _tile_live(q_of, k_of, block_q, block_k, window)
    if chunk > 1:
        live = live.reshape(len(live), -1, chunk).any(axis=-1)
        q_of, k_of = np.indices(live.shape, dtype=np.int32)
    return q_of[live], k_of[live]


def _grid_steps(seq_len: int, block_q: int, block_k: int,
                window: Optional[int] = None,
                chunk: Optional[int] = None) -> Tuple[int, ...]:
    """``(live, rectangular)`` grid steps a head of one streamed sweep at
    these tiles: what a causal call takes and what a call without the mask
    does (72 and 128 at S 8192 with 512 x 1024 tiles; under a window of
    512 keys 31 of the 256 tiles of 512 x 512). With a ``chunk``, a third:
    the steps of the causal forward at that many k tiles a step (24 of the
    72 at 4)."""
    steps = (len(_live_tiles(seq_len, block_q, block_k, True, window)[0]),
             (seq_len // block_q) * (seq_len // block_k))
    if chunk is None:
        return steps
    return steps + (len(_live_tiles(
        seq_len, block_q, block_k, True, window, chunk)[0]),)


def _sweep(idx, block_q: int, block_k: int, seq_len: int, causal: bool,
           rows: bool, tile, carry, window: Optional[int] = None):
    """Run ``tile(i, carry, masked=...)`` over every live tile of a row or
    a column of tiles: a loop of unmasked bodies over the full tiles, and
    the masked body on the diagonal ones. Where one block edge divides the
    other, the diagonal tiles are a fixed count (``block_q // block_k`` of
    a row, ``block_k // block_q`` of a column, at least one) and are laid
    out straight-line: a second loop costs more than the mask it saves
    (PERF.md, PR 24)."""
    own, other = (block_q, block_k) if rows else (block_k, block_q)
    if not causal:
        return jax.lax.fori_loop(
            0, seq_len // other, functools.partial(tile, masked=False),
            carry,
        )
    if window is not None:
        # the band: three loops in the order of the swept index
        full, diagonal, trailing = _band_sweep(
            idx, block_q, block_k, seq_len, rows, window)
        for bounds, masked in (
                (trailing if rows else diagonal, True), (full, False),
                (diagonal if rows else trailing, True)):
            carry = jax.lax.fori_loop(
                *bounds, functools.partial(tile, masked=masked), carry)
        return carry
    full, diagonal = _causal_sweep(idx, block_q, block_k, seq_len, rows)
    carry = jax.lax.fori_loop(
        *full, functools.partial(tile, masked=False), carry
    )
    if own % other and other % own:
        return jax.lax.fori_loop(
            *diagonal, functools.partial(tile, masked=True), carry
        )
    for j in range(max(1, own // other)):
        carry = tile(diagonal[0] + j, carry, masked=True)
    return carry


def _f32(ref_slice):
    """Kernel operands are upcast as they are loaded: the v5e's MXU takes
    f32 operands in one pass, and an explicit cast of P or dS back to the
    input dtype costs more vector work than it saves (PERF.md, PR 24)."""
    return ref_slice.astype(jnp.float32)


# A row statistic changes hands between its two forms, the [1, BQ] row HBM
# holds and the [BQ, 1] column a score tile's keepdims arithmetic takes,
# through the transpose unit, as a 128-lane-wide tile, which Mosaic
# transposes natively: every element is copied, none computed, so all 24
# bits arrive (a product with an identity at the MXU's default precision
# would round them to bf16). Once a q block, never a score tile. Measured
# on the v5e (PERF.md, PR 51): a turn costs what its tile's 64 vregs cost
# the transpose unit whatever is in them (an 8-lane tile reads the same),
# so dq's two statistics share one.
_LANES = 128


def _wide_to_row(wide):
    """``[BQ, _LANES]``, a ``[BQ, 1]`` column in every lane (q positions
    down the sublanes: what a score tile's row reductions give, broadcast),
    as the ``[1, BQ]`` row HBM holds."""
    return wide.T[:1]


def _rows_to_cols(lse_row, delta_row):
    """The two ``[1, BQ]`` rows HBM holds as ONE ``[BQ, _LANES]`` tile:
    lse's column in the even lanes, delta's in the odd, so ``[:, :1]`` and
    ``[:, 1:2]`` of it are the two ``[BQ, 1]`` columns and both statistics
    cross the transpose unit in one turn."""
    shape = (_LANES, lse_row.shape[1])
    even = jax.lax.broadcasted_iota(jnp.int32, shape, 0) % 2 == 0
    return jnp.where(even, jnp.broadcast_to(lse_row, shape),
                     jnp.broadcast_to(delta_row, shape)).T


def _scores(q, k, qi, ki, masked: bool, transposed: bool = False,
            window: Optional[int] = None):
    """S = Q·Kᵀ for one tile ([BQ, BK]; ``transposed``: Sᵀ = K·Qᵀ,
    [BK, BQ]); q already carries the softmax scale. ``masked`` adds the
    causal mask (diagonal tiles only) and, under a ``window``, its
    trailing edge."""
    a, b = (k, q) if transposed else (q, k)
    s = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if masked:
        q_axis = 1 if transposed else 0
        q_pos = qi * q.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, q_axis
        )
        k_pos = ki * k.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1 - q_axis
        )
        keep = q_pos >= k_pos
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
        s = jnp.where(keep, s, _NEG_INF)
    return s


def _fwd_tile(q, k, v, acc, m, l, qi, ki, masked: bool,
              window: Optional[int] = None):
    """One online-softmax update: (acc, m, l) after tile (qi, ki). A row
    that a window masks whole in its first tile accumulates at ``m =
    _NEG_INF``; the first score it sees clears that (``alpha`` = 0)."""
    s = _scores(q, k, qi, ki, masked, window=window)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return acc_new, m_new, l_new


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                  block_k: int, seq_len: int, causal: bool, scale: float,
                  window: Optional[int] = None):
    qi = pl.program_id(1)
    q = _f32(q_ref[0]) * scale  # [BQ, Dqk]

    def tile(ki, carry, masked):
        k = _f32(k_ref[0, pl.ds(ki * block_k, block_k), :])
        v = _f32(v_ref[0, pl.ds(ki * block_k, block_k), :])
        return _fwd_tile(q, k, v, *carry, qi, ki, masked, window)

    acc, m, l = _sweep(
        qi, block_q, block_k, seq_len, causal, True, tile,
        (jnp.zeros((block_q, v_ref.shape[-1]), dtype=jnp.float32),
         jnp.full((block_q, 1), _NEG_INF, dtype=jnp.float32),
         jnp.zeros((block_q, 1), dtype=jnp.float32)),
        window=window,
    )
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = _wide_to_row(
        jnp.broadcast_to(m + jnp.log(l), (block_q, _LANES)))


def _streamed_tile(refs, block_q: int, block_k: int, seq_len: int,
                   causal: bool, rows: bool, window: Optional[int] = None,
                   chunk: int = 1):
    """Streamed regime: which tile this grid step computes. Returns ``(qi,
    ki, first, last, refs)``: the tile, the first and the last value the
    swept index (``ki`` of a row sweep, ``qi`` of a column sweep) takes in
    this row or column — where the accumulators are cleared and written
    out — and the kernel's operands. Without the mask the two inner grid
    axes are the blocks themselves. Under it the one inner axis runs over
    the live tiles only and ``refs`` starts with the two tables of
    :func:`_live_tiles`, prefetched as scalars; the sweep's ends are
    :func:`_causal_sweep`'s. Where a step holds a ``chunk`` of k tiles
    (the forward), ``ki``, ``first`` and ``last`` count chunks."""
    if not causal:
        own, swept = pl.program_id(1), pl.program_id(2)
        qi, ki = (own, swept) if rows else (swept, own)
        return (qi, ki, 0,
                seq_len // (block_k * chunk if rows else block_q) - 1, refs)
    q_of, k_of, *refs = refs
    step = pl.program_id(1)
    qi, ki = q_of[step], k_of[step]
    first, last = _sweep_ends(
        qi if rows else ki, block_q, block_k, seq_len, rows,
        window=window
    )
    if chunk > 1:
        first, last = first // chunk, last // chunk
    return qi, ki, first, last, refs


def _full_or_masked(qi, ki, block_q: int, block_k: int, causal: bool, tile,
                    window: Optional[int] = None):
    """Streamed regime: run ``tile(masked)`` for the live tile (qi, ki) —
    masked on the diagonal (and on a window's edge), unmasked between."""
    if not causal:
        tile(False)
        return
    full = _tile_full(qi, ki, block_q, block_k, window=window)
    pl.when(full)(functools.partial(tile, False))
    pl.when(jnp.logical_not(full))(functools.partial(tile, True))


def _heads_of(group: int, rows: bool):
    """``(q_head, kv_head, inner)``: which head of q (out, dO, the
    statistics) and which of k and v a grid step reads, as functions of
    the step's grid indices, and the axes a grouped call adds to the grid.
    Query head ``i`` reads key/value head ``i // group`` (merged layout:
    row ``batch * H + i`` of q, row ``batch * KV + i // group`` =
    ``(batch * H + i) // group`` of k). At ``group == 1`` both are the
    leading index itself and nothing is added: the program such a call
    always traced to, with no ``// 1`` in it."""
    if group == 1:
        return (lambda ids: ids[0]), (lambda ids: ids[0]), ()
    if rows:            # the leading axis runs over the query heads
        return (lambda ids: ids[0]), (lambda ids: ids[0] // group), ()
    # the column sweep: key/value heads lead, the group's heads innermost
    return ((lambda ids: ids[0] * group + ids[-1]), (lambda ids: ids[0]),
            (group,))


def _whole_of(head):
    """The index map of a resident operand: all ``[S, D]`` (``[1, S]``) of
    the head that ``head`` (of :func:`_heads_of`) reads off the grid."""
    return lambda *ids: (head(ids), 0, 0)


def _streamed_grid(heads: int, seq_len: int, block_q: int, block_k: int,
                   causal: bool, rows: bool, window: Optional[int] = None,
                   group: int = 1, chunk: int = 1):
    """A streamed call's ``(grid, tables, by_q, by_k, q_lanes)``: the grid,
    the scalar-prefetch operands and the index maps of a block of q rows
    ([.., BQ, D]), of k rows and of q positions along the lanes ([.., 1,
    BQ], the statistics). Without the mask the grid is the rectangle of
    blocks, swept axis innermost, and nothing is prefetched; under it one
    axis enumerates :func:`_live_tiles` and the maps read the tile's blocks
    off the two tables. ``heads`` is what the leading axis runs over: the
    query heads of a row sweep, the key/value heads of the column sweep.
    Where a key/value head serves a ``group`` of query heads, a row sweep's
    ``by_k`` reads head ``b // group``, and the column sweep takes one more
    grid axis, innermost, over the group's heads: its ``by_q`` and
    ``q_lanes`` read head ``b * group + g`` (:func:`_heads_of`). With a
    ``chunk`` (the forward's row sweep) ``by_k`` counts blocks of
    ``chunk`` k tiles and the grid the chunks that hold a live tile."""
    q_head, kv_head, inner = _heads_of(group, rows)
    if causal:
        tables = _live_tiles(seq_len, block_q, block_k, rows,
                             window=window, chunk=chunk)

        def at(head, of, lanes=False):
            def index_map(*ids_and_tables):
                *ids, q_of, k_of = ids_and_tables
                block = (q_of if of == "q" else k_of)[ids[1]]
                return ((head(ids), 0, block) if lanes
                        else (head(ids), block, 0))
            return index_map

        return (
            (heads, len(tables[0])) + inner,
            tuple(jnp.asarray(t) for t in tables),
            at(q_head, "q"), at(kv_head, "k"), at(q_head, "q", lanes=True),
        )
    num_q, num_k = seq_len // block_q, seq_len // (block_k * chunk)
    q_at, k_at = (1, 2) if rows else (2, 1)   # swept axis innermost
    return (
        ((heads, num_q, num_k) if rows else (heads, num_k, num_q)) + inner,
        (),
        lambda *ids: (q_head(ids), ids[q_at], 0),
        lambda *ids: (kv_head(ids), ids[k_at], 0),
        lambda *ids: (q_head(ids), 0, ids[q_at]),
    )


def _flash_streamed_kernel(*refs, block_q: int, block_k: int, seq_len: int,
                           causal: bool, scale: float,
                           window: Optional[int] = None, chunk: int = 1):
    """K-blocks ride the innermost grid dimension: only ``chunk`` (block_k,
    d) K/V tiles are VMEM-resident at a time, so sequence length is bounded
    by HBM, not VMEM. acc/m/l live in VMEM scratch across the k sweep. A
    chunk's live tiles (:func:`_row_ranges`; a dead tile of a fetched chunk
    is not computed) are met in ascending k in straight-line groups of
    ``_STRAIGHT`` tiles, each traced as a loop of ONE tile that is laid
    out whole when it is lowered: inside a group Mosaic schedules a
    tile's matmuls beside its neighbour's softmax, which neither a grid
    step nor a loop trip lets it do. At one tile a step there is no
    loop."""
    qi, ki, first, last, refs = _streamed_tile(
        refs, block_q, block_k, seq_len, causal, True, window=window,
        chunk=chunk,
    )
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs

    @pl.when(ki == first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _carried():
        return acc_ref[...], m_ref[:, :1], l_ref[:, :1]

    def _keep(acc, m, l):
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l, l_ref.shape)

    def _accumulate(masked):
        _keep(*_fwd_tile(
            _f32(q_ref[0]) * scale, _f32(k_ref[0]), _f32(v_ref[0]),
            *_carried(), qi, ki, masked, window=window,
        ))

    def _accumulate_chunk():
        q = _f32(q_ref[0]) * scale
        lo = ki * chunk          # ki counts chunks; the tiles are lo and on
        (live_lo, live_hi), (full_lo, full_hi) = (
            [jnp.clip(bound, lo, lo + chunk) for bound in bounds]
            for bounds in _row_ranges(qi, block_q, block_k, seq_len, causal,
                                      window))

        def group(size, start):
            # ``size`` consecutive tiles in one straight line, (acc, m, l)
            # carried as values between them and through the scratch
            # between groups; the masked body for all of them where any
            # of them is cut by an edge (it leaves a full tile as it is)
            def body(i, _):
                first = start + i * size

                def run(masked):
                    def tile(j, carry):
                        kt = first + j
                        at = pl.ds((kt - lo) * block_k, block_k)
                        return _fwd_tile(
                            q, _f32(k_ref[0, at, :]), _f32(v_ref[0, at, :]),
                            *carry, qi, kt, masked, window)

                    _keep(*jax.lax.fori_loop(0, size, tile, _carried(),
                                             unroll=size))

                if not causal:
                    run(False)
                    return 0
                full = (first >= full_lo) & (first + size <= full_hi)
                pl.when(full)(functools.partial(run, False))
                pl.when(jnp.logical_not(full))(functools.partial(run, True))
                return 0
            return body

        # the chunk's live tiles in ascending k: as many groups of the
        # longest size as they hold, then of the next
        done = live_lo
        for size in _STRAIGHT:
            if size > chunk:
                continue
            count = (live_hi - done) // size
            jax.lax.fori_loop(0, count, group(size, done), 0)
            done = done + count * size

    if chunk > 1:
        _accumulate_chunk()
    else:
        _full_or_masked(qi, ki, block_q, block_k, causal, _accumulate,
                        window=window)

    @pl.when(ki == last)
    def _finalize():
        l = l_ref[...]          # [BQ, _LANES], every lane alike, as m_ref
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = _wide_to_row(m_ref[...] + jnp.log(l))


# KV footprint above which the k-streamed kernel is used (resident variant
# holds all of K+V in VMEM, which is faster for short/medium sequences).
_RESIDENT_KV_BYTES = 2 * 1024 * 1024


def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool,
                   resident_kv_bytes: Optional[int] = None,
                   window: Optional[int] = None,
                   chunk: Optional[int] = None):
    """q [BH, S, Dqk], k [BKV, S, Dqk], v [BKV, S, Dv] -> (out [BH, S,
    Dv], lse [BH, S] f32); row ``b`` of q reads row ``b // (BH // BKV)``
    of k and v. ``chunk``: the k tiles a streamed grid step sweeps,
    :func:`_choose_chunk`'s where the caller gave none."""
    bh, seq_len, d = q.shape
    dv = v.shape[-1]
    group = bh // k.shape[0]
    if _resident(seq_len, d + dv, q.dtype.itemsize, resident_kv_bytes):
        grid = (bh, seq_len // block_q)
        whole_kv = _whole_of(_heads_of(group, True)[1])
        kernel = functools.partial(
            _flash_kernel,
            block_q=block_q,
            block_k=block_k,
            seq_len=seq_len,
            causal=causal,
            scale=scale,
            window=window,
        )
        out, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, seq_len, d), whole_kv),
                pl.BlockSpec((1, seq_len, dv), whole_kv),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            ],
            out_shape=_forward_out_shapes(q, dv),
            interpret=interpret,
            name="flash_fwd",
        )(q, k, v)
        return out, lse[:, 0, :]

    # Long context: stream K/V tiles via the grid.
    if chunk is None:
        chunk = _choose_chunk(seq_len, d, q.dtype.itemsize, block_q, block_k,
                              dv, window, causal, resident_kv_bytes)
    elif (seq_len // block_k) % chunk:
        raise ValueError(
            f"flash attention: a chunk of {chunk} k tiles does not divide "
            f"the row of {seq_len // block_k}")
    return _flash_forward_streamed(q, k, v, causal, scale, block_q, block_k,
                                   interpret, window, chunk)


def _forward_out_shapes(q, dv: int):
    """``out`` and ``lse`` as the forward's kernels write them: lse leaves
    as [BH, 1, S] rows (module docstring: lane-dense)."""
    bh, seq_len, _ = q.shape
    return (
        jax.ShapeDtypeStruct((bh, seq_len, dv), q.dtype),
        jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32),
    )


# The streamed builders stand under a jit of their own: a body is traced
# and lowered once a shape and process, not at each call site of each
# program a run traces (the models' layers are Python loops under a
# ``jax.checkpoint`` a layer: two forwards and a backward a layer of the
# step's program, and the reference check's and the micro's programs
# again; ``ops/kda.py::_forward`` is the precedent). The resident branches
# stay outside it: the program of every call with resident K and V is
# what it was, to the instruction (``tests/test_flash.py`` pins it).
# ``_STRAIGHT`` is read inside the trace: whoever sets another clears
# jax's caches (``_CHUNK_LADDER`` is read outside, by ``_choose_chunk``).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_forward_streamed(q, k, v, causal: bool, scale: float,
                            block_q: int, block_k: int, interpret: bool,
                            window: Optional[int], chunk: int):
    """The streamed ``flash_fwd`` call: a grid step fetches ``chunk``
    consecutive k tiles of K and V."""
    bh, seq_len, d = q.shape
    dv = v.shape[-1]
    grid, tables, by_q, by_k, q_lanes = _streamed_grid(
        bh, seq_len, block_q, block_k, causal, True, window=window,
        group=bh // k.shape[0], chunk=chunk,
    )
    kernel = functools.partial(
        _flash_streamed_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_len=seq_len,
        causal=causal,
        scale=scale,
        window=window,
        chunk=chunk,
    )
    scratch = [
        pltpu.VMEM((block_q, dv), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), by_q),
                pl.BlockSpec((1, chunk * block_k, d), by_k),
                pl.BlockSpec((1, chunk * block_k, dv), by_k),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dv), by_q),
                pl.BlockSpec((1, 1, block_q), q_lanes),
            ],
            scratch_shapes=scratch,
        ),
        out_shape=_forward_out_shapes(q, dv),
        interpret=interpret,
        name="flash_fwd",
    )(*tables, q, k, v)
    return out, lse[:, 0, :]


# ------------------------------------------------------------- backward pass
# FlashAttention-2 style fused backward: residuals are (q, k, v, out, lse);
# delta = rowsum(dO * O) is a cheap XLA elementwise+reduce; P = exp(S - lse)
# is recomputed tile-by-tile — by ONE kernel (``flash_bwd``: dQ's sweep of
# k-blocks per q-block, dK and dV summed in whole-head accumulators), or,
# where those do not fit VMEM, by two (dQ's sweep, and dK/dV's of q-blocks
# per k-block). Nothing [S, S]-shaped ever materializes in HBM in either
# direction.


def _bwd_p_ds(q, k, v, do, lse, delta, qi, ki, masked: bool,
              transposed: bool = False, window: Optional[int] = None):
    """Shared score recompute for every backward kernel: P = exp(S − lse)
    (masked on diagonal tiles) and dS = P ⊙ (dO·Vᵀ − Δ), both f32 [BQ, BK]
    with lse and delta as [BQ, 1] columns — or, ``transposed``, Pᵀ and dSᵀ
    [BK, BQ] with lse and delta as [1, BQ] rows. One definition so
    mask/softmax changes can never diverge between kernels or regimes.
    q carries the softmax scale."""
    p = jnp.exp(
        _scores(q, k, qi, ki, masked, transposed, window=window) - lse)
    a, b = (v, do) if transposed else (do, v)
    dp = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta)


def _dq_tile(q, k, v, do, lse, delta, qi, ki, masked: bool,
             window: Optional[int] = None):
    """This tile's term of dQ/scale: dS·K, f32 [BQ, D]."""
    _, ds = _bwd_p_ds(q, k, v, do, lse, delta, qi, ki, masked,
                      window=window)
    return jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _turned_tile(q, k, v, do, lse, delta, qi, ki, masked: bool,
                 window: Optional[int] = None):
    """``(dSᵀ, dK term, dV term)`` of one tile recomputed TRANSPOSED (lse
    and delta arrive as [1, BQ] rows): dSᵀ·Q (q carries the scale, so this
    is dL/dK itself) and Pᵀ·dO, f32 [BK, D], are then plain row-by-column
    matmuls and nothing [BQ, BK]-shaped goes through the transpose
    unit."""
    pt, dst = _bwd_p_ds(
        q, k, v, do, lse, delta, qi, ki, masked, transposed=True,
        window=window
    )
    dk = jax.lax.dot_general(
        dst, q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dv = jax.lax.dot_general(
        pt, do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dst, dk, dv


def _dkv_tile(q, k, v, do, lse, delta, qi, ki, masked: bool,
              window: Optional[int] = None):
    """This tile's terms of (dK, dV), f32 [BK, D]: :func:`_turned_tile`'s
    two products."""
    return _turned_tile(q, k, v, do, lse, delta, qi, ki, masked, window)[1:]


def _bwd_tile(q, k, v, do, lse, delta, qi, ki, masked: bool,
              window: Optional[int] = None):
    """This tile's terms of (dQ/scale, dK, dV) from ONE Pᵀ and dSᵀ: the
    tile as dkv builds it (:func:`_turned_tile`), its two plain products,
    and dS·K as the one product whose left operand is contracted over its
    rows — five matmuls and one [BK, BQ] turn through the transpose unit,
    where the pair of kernels runs seven and builds the tile twice."""
    dst, dk, dv = _turned_tile(q, k, v, do, lse, delta, qi, ki, masked,
                               window)
    dq = jax.lax.dot_general(
        dst, k, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dq, dk, dv


def _at_group_head(edge, head: int, group: int, at):
    """``edge`` — where ONE head's sweep of a column of tiles starts (ends)
    — narrowed to where dk's and dv's accumulators are cleared (written
    out). A key/value head serves a ``group`` of query heads and the step
    is at head ``at()`` of them (the innermost grid axis of ``flash_dkv``;
    the leading index modulo the group in ``flash_bwd``, whose leading
    axis runs over the query heads): dk and dv are the float32 sum over
    the whole group, cleared at head 0's first tile and rounded once, at
    head ``group - 1``'s last."""
    if group == 1:
        return edge
    return edge & (at() == head)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      block_q: int, block_k: int, seq_len: int, causal: bool,
                      scale: float, window: Optional[int] = None,
                      group: int = 1):
    """The whole backward with K and V resident: dq's sweep of a q block's
    row of tiles (:func:`_sweep`, dq the loop's carry), each tile built
    ONCE (:func:`_bwd_tile`) and its dk and dv terms added into rows ``ki
    * block_k`` and on of two ``[S, D]`` f32 accumulators — cleared at the
    first q block of a group's first query head, rounded into the
    key/value head's whole output blocks at the last q block of its last
    (:func:`_at_group_head`)."""
    qi = pl.program_id(1)
    q = _f32(q_ref[0]) * scale                    # [BQ, Dqk]
    do = _f32(do_ref[0])                          # [BQ, Dv]
    lse, delta = lse_ref[0], delta_ref[0]         # [1, BQ] rows, as HBM's

    def at():
        return pl.program_id(0) % group

    def every_k_block(fn):
        def body(ki, _):
            fn(pl.ds(pl.multiple_of(ki * block_k, block_k), block_k))
            return 0
        jax.lax.fori_loop(0, seq_len // block_k, body, 0)

    @pl.when(_at_group_head(qi == 0, 0, group, at))
    def _clear():
        def clear(rows):
            dk_acc[rows, :] = jnp.zeros((block_k, dk_acc.shape[1]),
                                        jnp.float32)
            dv_acc[rows, :] = jnp.zeros((block_k, dv_acc.shape[1]),
                                        jnp.float32)
        every_k_block(clear)

    def tile(ki, dq, masked):
        rows = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        dq_term, dk, dv = _bwd_tile(
            q, _f32(k_ref[0, rows, :]), _f32(v_ref[0, rows, :]), do, lse,
            delta, qi, ki, masked, window=window,
        )
        dk_acc[rows, :] = dk_acc[rows, :] + dk
        dv_acc[rows, :] = dv_acc[rows, :] + dv
        return dq + dq_term

    dq = _sweep(
        qi, block_q, block_k, seq_len, causal, True, tile,
        jnp.zeros(q.shape, dtype=jnp.float32), window=window,
    )
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    @pl.when(_at_group_head(qi == seq_len // block_q - 1, group - 1, group,
                            at))
    def _write():
        def write(rows):
            dk_ref[0, rows, :] = dk_acc[rows, :].astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)
        every_k_block(write)


def _flash_bwd_dq_streamed_kernel(*refs, block_q: int, block_k: int,
                                  seq_len: int, causal: bool, scale: float,
                                  window: Optional[int] = None):
    """K/V tiles ride the innermost grid dim (long-context regime); dq
    accumulates in VMEM scratch across the k sweep, beside the q block's
    two statistics as columns (``_rows_to_cols``), made from their rows at
    the sweep's first step."""
    qi, ki, first, last, refs = _streamed_tile(
        refs, block_q, block_k, seq_len, causal, True, window=window
    )
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
     cols) = refs

    @pl.when(ki == first)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        cols[...] = _rows_to_cols(lse_ref[0], delta_ref[0])

    def _accumulate(masked):
        dq_acc[...] = dq_acc[...] + _dq_tile(
            _f32(q_ref[0]) * scale, _f32(k_ref[0]), _f32(v_ref[0]),
            _f32(do_ref[0]), cols[:, :1], cols[:, 1:2], qi, ki, masked,
            window=window,
        )

    _full_or_masked(qi, ki, block_q, block_k, causal, _accumulate,
                    window=window)

    @pl.when(ki == last)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_streamed_kernel(*refs, block_q: int, block_k: int,
                                   seq_len: int, causal: bool, scale: float,
                                   window: Optional[int] = None,
                                   group: int = 1):
    """Q/dO tiles ride the innermost grid dim; dk/dv accumulate in VMEM
    scratch across the q sweep — of every query head of the ``group``
    this key/value head serves, a tile's heads one after the other
    (:func:`_at_group_head`)."""
    qi, ki, first, last, refs = _streamed_tile(
        refs, block_q, block_k, seq_len, causal, False, window=window
    )
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc,
     dv_acc) = refs
    at = functools.partial(pl.program_id, 2 if causal else 3)

    @pl.when(_at_group_head(qi == first, 0, group, at))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate(masked):
        dk, dv = _dkv_tile(
            _f32(q_ref[0]) * scale, _f32(k_ref[0]), _f32(v_ref[0]),
            _f32(do_ref[0]), lse_ref[0], delta_ref[0], qi, ki, masked,
            window=window,
        )
        dk_acc[...] = dk_acc[...] + dk
        dv_acc[...] = dv_acc[...] + dv

    _full_or_masked(qi, ki, block_q, block_k, causal, _accumulate,
                    window=window)

    @pl.when(_at_group_head(qi == last, group - 1, group, at))
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_streamed_kernel(*refs, block_q: int, block_k: int,
                               seq_len: int, causal: bool, scale: float,
                               window: Optional[int] = None, group: int = 1):
    """The whole streamed backward in one kernel: dq's row sweep (K / V
    tiles on the innermost grid dimension, dq in VMEM scratch across a
    row), each live tile built ONCE (:func:`_bwd_tile`) and its dk and dv
    terms added into rows ``ki * block_k`` and on of two ``[S, D]`` f32
    accumulators that stay in VMEM for the whole sweep of a key/value
    head — its ``group`` query heads are consecutive on the leading grid
    axis. A column of tiles is met q blocks ascending, as ``flash_dkv``'s
    column sweep met it: its rows are cleared at its first tile of the
    group's first head and rounded ONCE, into the head's whole ``[S, D]``
    output block, at its last tile of the group's last
    (:func:`_at_group_head`); the block leaves VMEM when the leading
    index moves to the next key/value head."""
    qi, ki, first, last, refs = _streamed_tile(
        refs, block_q, block_k, seq_len, causal, True, window=window
    )
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
     dq_acc, dk_acc, dv_acc) = refs
    # the column's ends: the q blocks whose tiles hold key block ``ki``
    top, bottom = (
        _sweep_ends(ki, block_q, block_k, seq_len, False, window=window)
        if causal else (0, seq_len // block_q - 1))
    cols = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)

    def at():
        return pl.program_id(0) % group

    @pl.when(ki == first)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_at_group_head(qi == top, 0, group, at))
    def _clear():
        dk_acc[cols, :] = jnp.zeros((block_k, dk_acc.shape[1]), jnp.float32)
        dv_acc[cols, :] = jnp.zeros((block_k, dv_acc.shape[1]), jnp.float32)

    def _accumulate(masked):
        dq, dk, dv = _bwd_tile(
            _f32(q_ref[0]) * scale, _f32(k_ref[0]), _f32(v_ref[0]),
            _f32(do_ref[0]), lse_ref[0], delta_ref[0], qi, ki, masked,
            window=window,
        )
        dq_acc[...] = dq_acc[...] + dq
        dk_acc[cols, :] = dk_acc[cols, :] + dk
        dv_acc[cols, :] = dv_acc[cols, :] + dv

    _full_or_masked(qi, ki, block_q, block_k, causal, _accumulate,
                    window=window)

    @pl.when(ki == last)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    @pl.when(_at_group_head(qi == bottom, group - 1, group, at))
    def _write():
        dk_ref[0, cols, :] = dk_acc[cols, :].astype(dk_ref.dtype)
        dv_ref[0, cols, :] = dv_acc[cols, :].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_backward_streamed(q, k, v, g, lse_row, delta_row, causal: bool,
                             scale: float, block_q: int, block_k: int,
                             interpret: bool, window: Optional[int] = None,
                             fused: bool = True):
    """The streamed backward: ``flash_bwd`` alone where ``fused``
    (:func:`_fuses_backward`), else the dq and the dkv call; ``lse_row``
    and ``delta_row`` are the ``[BH, 1, S]`` views every one takes."""
    bh, seq_len, d = q.shape
    dv = v.shape[-1]
    group = bh // k.shape[0]

    def operands(by_q, by_k, q_lanes):
        # q, k, v, dO and the two statistics, as every kernel here takes them
        return [
            pl.BlockSpec((1, block_q, d), by_q),
            pl.BlockSpec((1, block_k, d), by_k),
            pl.BlockSpec((1, block_k, dv), by_k),
            pl.BlockSpec((1, block_q, dv), by_q),
            pl.BlockSpec((1, 1, block_q), q_lanes),
            pl.BlockSpec((1, 1, block_q), q_lanes),
        ]

    grid, tables, by_q, by_k, q_lanes = _streamed_grid(
        bh, seq_len, block_q, block_k, causal, True, window=window,
        group=group,
    )
    if fused:
        whole_kv = _whole_of(_heads_of(group, True)[1])
        return pl.pallas_call(
            functools.partial(
                _flash_bwd_streamed_kernel, block_q=block_q,
                block_k=block_k, seq_len=seq_len, causal=causal,
                scale=scale, window=window, group=group,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(tables),
                grid=grid,
                in_specs=operands(by_q, by_k, q_lanes),
                out_specs=[
                    pl.BlockSpec((1, block_q, d), by_q),
                    pl.BlockSpec((1, seq_len, d), whole_kv),
                    pl.BlockSpec((1, seq_len, dv), whole_kv),
                ],
                scratch_shapes=[
                    pltpu.VMEM((block_q, d), jnp.float32),
                    pltpu.VMEM((seq_len, d), jnp.float32),
                    pltpu.VMEM((seq_len, dv), jnp.float32),
                ],
            ),
            out_shape=(
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ),
            compiler_params=_FUSED_PARAMS,
            interpret=interpret,
            name="flash_bwd",
        )(*tables, q, k, v, g, lse_row, delta_row)

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_streamed_kernel, block_q=block_q,
            block_k=block_k, seq_len=seq_len, causal=causal, scale=scale,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=operands(by_q, by_k, q_lanes),
            out_specs=pl.BlockSpec((1, block_q, d), by_q),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_dq",
    )(*tables, q, k, v, g, lse_row, delta_row)

    grid, tables, by_q, by_k, q_lanes = _streamed_grid(
        k.shape[0], seq_len, block_q, block_k, causal, False, window=window,
        group=group,
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_streamed_kernel, block_q=block_q,
            block_k=block_k, seq_len=seq_len, causal=causal, scale=scale,
            window=window, group=group,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=operands(by_q, by_k, q_lanes),
            out_specs=[
                pl.BlockSpec((1, block_k, d), by_k),
                pl.BlockSpec((1, block_k, dv), by_k),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, dv), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        interpret=interpret,
        name="flash_dkv",
    )(*tables, q, k, v, g, lse_row, delta_row)
    return dq, dk, dv


def _flash_backward(q, k, v, out, lse, g, causal: bool, scale: float,
                    block_q: int, block_k: int, interpret: bool,
                    resident_kv_bytes: Optional[int] = None,
                    window: Optional[int] = None):
    """Fused pallas backward: delta from (out, g), then the kernel core."""
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [BH, S]
    return _flash_backward_core(
        q, k, v, g, lse, delta, causal, scale, block_q, block_k,
        interpret, resident_kv_bytes, window=window,
    )


def _flash_backward_core(q, k, v, g, lse, delta, causal: bool,
                         scale: float, block_q: int, block_k: int,
                         interpret: bool,
                         resident_kv_bytes: Optional[int] = None,
                         window: Optional[int] = None):
    """Kernel core with EXTERNAL lse/delta ([BH, S] f32): ``flash_bwd``
    alone where :func:`_fuses_backward` says so — its resident body (full
    K/V in VMEM) below the threshold, streamed tiles above it —, else the
    streamed ``flash_dq`` and ``flash_dkv``, whose VMEM no sequence
    outgrows. External statistics are what make the ring backward work —
    with the GLOBAL lse and delta, each (q-block, kv-block) pair's
    dq/dk/dv contributions are independent (FlashAttention-2), so pairs
    can be revisited in any order/placement and summed."""
    bh, seq_len, d = q.shape
    dv = v.shape[-1]
    group = bh // k.shape[0]
    # the one view of the [BH, S] statistics that every kernel takes
    # (module docstring): [BH, 1, S] rows, q positions along the lanes
    lse_row, delta_row = lse[:, None, :], delta[:, None, :]
    fused = _fuses_backward(seq_len, d, q.dtype.itemsize, block_q, block_k,
                            dv, resident_kv_bytes)
    if not (fused and _resident(seq_len, d + dv, q.dtype.itemsize,
                                resident_kv_bytes)):
        return _flash_backward_streamed(
            q, k, v, g, lse_row, delta_row, causal, scale, block_q,
            block_k, interpret, window, fused,
        )

    whole_kv = _whole_of(_heads_of(group, True)[1])
    return pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, block_q=block_q, block_k=block_k,
            seq_len=seq_len, causal=causal, scale=scale, window=window,
            group=group,
        ),
        grid=(bh, seq_len // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), whole_kv),
            pl.BlockSpec((1, seq_len, dv), whole_kv),
            pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), whole_kv),
            pl.BlockSpec((1, seq_len, dv), whole_kv),
        ],
        scratch_shapes=[
            pltpu.VMEM((seq_len, d), jnp.float32),
            pltpu.VMEM((seq_len, dv), jnp.float32),
        ],
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        compiler_params=_FUSED_PARAMS,
        interpret=interpret,
        name="flash_bwd",
    )(q, k, v, g, lse_row, delta_row)


def _reference(q, k, v, causal: bool, scale: float):
    """[BH,S,D] layout adapter over ops.attention.reference_attention."""
    from torchft_tpu.ops.attention import reference_attention

    out = reference_attention(
        q[:, :, None], k[:, :, None], v[:, :, None], causal=causal,
        scale=scale,
    )
    return out[:, :, 0].astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret,
           resident_kv_bytes, window=None):
    out, _ = _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret,
        resident_kv_bytes, window=window,
    )
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               resident_kv_bytes, window):
    out, lse = _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret,
        resident_kv_bytes, window=window,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret,
               resident_kv_bytes, window, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(
        q, k, v, out, lse, g, causal, scale, block_q, block_k, interpret,
        resident_kv_bytes, window=window,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


# Score tiles (block_q, block_k) tried, first fit first. Measured on the
# v5e (PERF.md, PR 24): at S 2048 a 512 x 512 score tile runs the three
# kernels 2.3-3.9 times faster than 128 x 128 at 64- and at 128-wide heads
# alike, for the row sweeps and for the column sweep; 1024 gains nothing
# more where K and V are resident.
_TILES = ((512, 512), (256, 256), (128, 128))
# A streamed grid step of the backward is ONE tile (of the forward a chunk
# of them, ``_CHUNK_LADDER``), and a step costs about as much again as a
# 512 x 512 tile's arithmetic, so they try a k edge of 1024 first.
# Measured on the v5e at [2, 8192, 32, 192 / 128] (PERF.md, PR 31):
# forward 27.8 -> 19.5 ms; at 128 / 128 it changes nothing.
_STREAMED_TILES = ((512, 1024),) + _TILES
# Under a window a k edge of 1024 is live for every q block the band
# touches it in, so it computes more above the band than the square tile
# (tiles' area over live pairs 1.25 against 1.125 at W 4096, 2.0 against
# 1.5 at W 1024) for fewer grid steps. It pays where the window is this
# many of those k edges long. Measured on the v5e at [2, 16384, 28, 128]
# (PERF.md, PR 50), forward / dq / dkv in ms, square -> streamed: at W 4096
# 34.1 / 23.0 / 26.1 -> 20.7 / 21.7 / 26.2 (140 grid steps a head for
# 252), at W 2048 21.3 / 14.9 / 16.0 -> 14.1 / 14.7 / 17.4; at W 1024 the
# forward gains (14.0 -> 10.5) what dq and dkv lose (10.1 / 10.6 -> 11.0 /
# 12.6), and at W 512 the square tile stays (PR 47).
_STREAMED_WINDOW_EDGES = 2
# What one kernel instance may plan to hold in VMEM (the v5e's default
# scoped limit is 16 MiB).
_VMEM_BUDGET = 16 * 1024 * 1024


def _resident(seq_len: int, pair: int, itemsize: int,
              threshold: Optional[int] = None) -> bool:
    """Whether K and V of one head (``pair`` = their widths' sum) stay
    whole in VMEM: the regime the kernels' wrappers pick by default
    (``threshold``: a call's own ``_resident_kv_bytes``)."""
    return seq_len * pair * itemsize <= (
        _RESIDENT_KV_BYTES if threshold is None else threshold)


def _vmem_estimate(seq_len: int, head_dim: int, itemsize: int,
                   block_q: int, block_k: int,
                   v_dim: Optional[int] = None) -> int:
    """Bytes the hungriest of the three kernels (dkv) keeps in VMEM at
    these tiles: its pipelined operands and statistics twice (double
    buffering), their f32 copies for one tile, the four f32 score-tile
    temporaries (S, P, dP, dS) and the two f32 accumulators. In the
    resident regime Q and dO are whole sequences. ``head_dim`` is q's and
    k's width, ``v_dim`` v's and dO's where it is another (latent
    attention: 192 and 128): a row of Q with its dO, or of K with its V,
    is ``head_dim + v_dim`` wide."""
    pair = head_dim + (head_dim if v_dim is None else v_dim)
    q_rows = seq_len if _resident(seq_len, pair, itemsize) else block_q
    operands = (q_rows + 2 * block_k) * pair * itemsize
    stats = 2 * q_rows * 4
    upcast = (block_q + block_k) * pair * 4
    return (2 * (operands + stats) + upcast + 4 * block_q * block_k * 4
            + block_k * pair * 4)


def _choose_blocks(seq_len: int, head_dim: int, itemsize: int,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   v_dim: Optional[int] = None,
                   window: Optional[int] = None) -> Tuple[int, int]:
    """``(block_q, block_k)`` as a pure function of the shape: the first
    tile of ``_TILES`` (``_STREAMED_TILES`` where the kernels stream) whose
    edges divide ``seq_len`` and whose :func:`_vmem_estimate` fits
    ``_VMEM_BUDGET`` (the smallest edge when none does, or the whole of a
    shorter sequence). An explicit argument wins over the rule and is
    only clamped to the sequence. Under a ``window`` shorter than
    ``_STREAMED_WINDOW_EDGES`` k edges of the streamed tile the tiles are
    the square ones in either regime: a k edge of 1024 is live for every
    q block whose window touches it, and at 512 keys that doubles the
    band's arithmetic."""
    pair = head_dim + (head_dim if v_dim is None else v_dim)
    band = (window is not None
            and window < _STREAMED_WINDOW_EDGES * _STREAMED_TILES[0][1])
    tiles = (_TILES if band
             or _resident(seq_len, pair, itemsize) else _STREAMED_TILES)
    q_edge = k_edge = min(_TILES[-1][0], seq_len)
    for cand_q, cand_k in tiles:
        if (seq_len % cand_q == 0 and seq_len % cand_k == 0
                and _vmem_estimate(seq_len, head_dim, itemsize, cand_q,
                                   cand_k, v_dim) <= _VMEM_BUDGET):
            q_edge, k_edge = cand_q, cand_k
            break
    return (q_edge if block_q is None else min(block_q, seq_len),
            k_edge if block_k is None else min(block_k, seq_len))


# k tiles a grid step of the streamed forward fetches, longest first, and
# the tiles of a chunk laid out in one straight line, longest group first
# (module docstring). Measured on the v5e (PERF.md, PR 60;
# ``scripts/flash_micro.py --cells streamed --chunks 1 2 4 8``), the
# forward alone in ms a call at one tile a step -> 2 / 4 / 8 tiles:
#   [128, 8192, 192 | 128] (joyai, kimi)   31.5 -> 28.8 / 28.4 / refused
#   [128, 8192, 128] (nemo3)               22.3 -> 19.3 / 18.8 / 18.4
#   [56, 16384, 128] (smallthinker)        36.0 -> 30.7 / 29.5 / 29.5
#   the same under W 4096                  19.1 -> 17.1 / 17.3 / 17.6
#   [256, 8192, 128] under W 512 (laguna)  19.8 -> 19.1 / 18.5 / 18.5
#   [80, 8192, 64 | 128] under W 512       6.96 -> 6.78 / 6.62 / 6.62
# (at 192 | 128 eight tiles of K and V twice over are 10.5 MB and Mosaic
# refuses the kernel: the estimate below reads 20.75, the compiler 21.73). Groups of (2, 1) read 0 - 2.5 % slower than (4, 2, 1) at every
# full call (18.8, 30.3 at eight tiles) and the same under the windows;
# a group of eight does not fit beside eight tiles. Under a window the
# longest row of live tiles bounds the chunk: five tiles at W 4096, where
# the shorter chunks fetch fewer dead tiles, two at W 512, where four and
# eight read 3 % faster than the two the rule takes (more rows' two tiles
# fall in one chunk) for 2.5 and 4.4 times the tiles fetched.
_CHUNK_LADDER = (8, 4, 2, 1)
_STRAIGHT = (4, 2, 1)
# What a straight-line group of FOUR tiles holds live beyond one tile's
# temporaries where a head is wider than one lane tile. Read from the
# compiler for a described v5e (PR 63: the least ``vmem_limit_bytes`` at
# which ``flash_fwd`` lowers, bf16, 512-row q blocks; MiB, estimate without
# this term -> allocation):
#   128 | 128, k edge 1024:  8 tiles 14.75 -> 15.02   4 tiles 10.75 -> 11.02
#   192 | 128, k edge 1024:  8 tiles 17.25 -> 21.73   4 tiles 12.25 -> 15.73
#   256 | 256, k edge  512:  8 tiles 14.00 -> 17.48   4 tiles 10.00 -> 13.48
# (two tiles a step read UNDER the estimate at every width: 5.2 for 9.75 at
# 192 | 128, 8.6 for 8.0 at 256 | 256.) At one lane tile the estimate is
# 0.27 short, at the wider heads 3.48 at either k edge and either chunk.
# Eight tiles at 256 | 256 were taken by the estimate (14.0 of 16) and
# refused on the chip inside a program whose neighbours left the call
# 16 MiB ("scoped allocation with size 17.48M", the cell's own check of
# the call at [64, 8192, 256]); four fit everywhere. 192 | 128 keeps its
# four tiles: 15.75 of 16, its reading to the hundredth.
_WIDE_GROUP_BYTES = 7 * 512 * 1024


def _forward_vmem_estimate(head_dim: int, v_dim: int, itemsize: int,
                           block_q: int, block_k: int, chunk: int) -> int:
    """Bytes the streamed forward keeps in VMEM at ``chunk`` k tiles a
    grid step: its pipelined operands twice (K and V of the chunk, q, out
    and the lse row), the three scratch accumulators, the f32 copies of q
    and of ONE tile of K and V, a tile's temporaries (S, P and the
    carried acc) and, in a straight-line group of ``_STRAIGHT[0]`` tiles
    of heads wider than a lane tile, ``_WIDE_GROUP_BYTES`` more."""
    pair = head_dim + v_dim
    operands = (chunk * block_k + block_q) * pair * itemsize + block_q * 4
    scratch = block_q * (v_dim + 2 * _LANES) * 4
    upcast = (block_q * head_dim + block_k * pair) * 4
    wide = chunk >= _STRAIGHT[0] and max(head_dim, v_dim) > _LANES
    return (2 * operands + scratch + upcast
            + (2 * block_k + v_dim) * block_q * 4
            + (_WIDE_GROUP_BYTES if wide else 0))


def _choose_chunk(seq_len: int, head_dim: int, itemsize: int, block_q: int,
                  block_k: int, v_dim: Optional[int] = None,
                  window: Optional[int] = None, causal: bool = True,
                  resident_kv_bytes: Optional[int] = None) -> int:
    """k tiles a grid step of the forward sweeps, as a pure function of
    the shape: 1 where K and V are resident, else the longest rung of
    ``_CHUNK_LADDER`` that the longest row of live tiles fills, that
    divides the row of tiles (chunks start at multiples of the rung) and
    whose :func:`_forward_vmem_estimate` fits ``_VMEM_BUDGET``."""
    v_dim = head_dim if v_dim is None else v_dim
    if _resident(seq_len, head_dim + v_dim, itemsize, resident_kv_bytes):
        return 1
    num_k = seq_len // block_k
    row = num_k
    if causal:
        row = int(np.bincount(_live_tiles(
            seq_len, block_q, block_k, True, window)[0]).max())
    return next(
        n for n in _CHUNK_LADDER
        if n == 1 or (n <= row and num_k % n == 0
                      and _forward_vmem_estimate(
                          head_dim, v_dim, itemsize, block_q, block_k, n)
                      <= _VMEM_BUDGET))


# What ``flash_bwd`` may plan to hold in VMEM (of the v5e's 128 MiB): dk's
# and dv's accumulators and output blocks are whole sequences of one
# key/value head.
_FUSED_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=96 * 1024 * 1024)


def _fused_vmem_estimate(seq_len: int, head_dim: int, v_dim: int,
                         itemsize: int, block_q: int, block_k: int,
                         resident: bool = False) -> int:
    """Bytes ``flash_bwd`` keeps in VMEM: the two ``[S, D]`` f32
    accumulators, dk's and dv's whole-head output blocks twice (Pallas
    double-buffers an output block), and a tile's share — its pipelined
    operands (``resident``: K and V whole sequences), statistics and dq's
    block twice, their f32 copies, the five f32 score-tile temporaries
    (Sᵀ, Pᵀ, dPᵀ, dSᵀ and dS turned) and dq's accumulator. Held against the compiler for a described v5e (PR 74: the
    least ``vmem_limit_bytes`` at which ``flash_bwd`` lowers, bf16; MiB,
    estimate -> allocation): 8 192 x 192 | 128 34.5 -> 22.4, 8 192 x 128
    29.5 -> 22.6 (22.3 without the mask), 16 384 x 128 45.5 -> 38.6 (38.9
    under W 4 096), 8 192 x 256 on 512 x 512 tiles 42.0 -> 38.7, 8 192 x
    64 | 128 24.5 -> 16.8 (18.8 -> 14.6 under W 512), 32 768 x 128 77.5 ->
    70.5: over at every call, by 3.3 - 12.1 (the compiler counts the
    whole-head arrays and the pipelined blocks and little of a tile's
    temporaries)."""
    pair = head_dim + v_dim
    whole = seq_len * pair * (4 + 2 * itemsize)
    k_rows = seq_len if resident else block_k
    operands = ((block_q + k_rows) * pair + block_q * head_dim) * itemsize
    stats = 2 * block_q * 4
    upcast = (block_q + block_k) * pair * 4
    return (whole + 2 * (operands + stats) + upcast
            + 5 * block_q * block_k * 4 + block_q * head_dim * 4)


def _fuses_backward(seq_len: int, head_dim: int, itemsize: int,
                    block_q: int, block_k: int, v_dim: Optional[int] = None,
                    resident_kv_bytes: Optional[int] = None) -> bool:
    """Whether a call's backward is the ONE kernel ``flash_bwd``, as a pure
    function of the shape: every call whose :func:`_fused_vmem_estimate`
    fits ``_FUSED_PARAMS``' limit — every call with K and V resident (its
    accumulators are no longer than they), and the streamed ones up to 32k
    x 128. A longer or wider call keeps ``flash_dq`` and ``flash_dkv``,
    whose VMEM does not grow with the sequence."""
    v_dim = head_dim if v_dim is None else v_dim
    return _fused_vmem_estimate(
        seq_len, head_dim, v_dim, itemsize, block_q, block_k,
        _resident(seq_len, head_dim + v_dim, itemsize, resident_kv_bytes),
    ) <= _FUSED_PARAMS.vmem_limit_bytes


def _bshd_prologue(q, k, v, scale, block_q, block_k, window=None):
    """Shared [B,S,H,D]-surface plumbing: scale default (from q's
    width), block choice (from the shape where the caller gave none) and
    clamping, validation (the sequence a multiple of the blocks, the query
    heads a multiple of the key/value heads), and the [B,S,H,D] <->
    [B*H,S,D] layout pair, which keeps each array's own head count and
    last dim. One place, three wrappers."""
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q, block_k = _choose_blocks(
        s, d, q.dtype.itemsize, block_q, block_k, v.shape[-1], window
    )
    if s % block_q or s % block_k:
        raise ValueError(
            f"flash attention: seq len {s} of q{tuple(q.shape)} must be a "
            f"multiple of the block sizes ({block_q}, {block_k})"
        )
    if k.shape[2] != v.shape[2] or h % k.shape[2]:
        raise ValueError(
            f"flash attention: the {h} heads of q{tuple(q.shape)} must be a "
            f"multiple of the key/value heads of k{tuple(k.shape)} and "
            f"v{tuple(v.shape)}, which must be as many"
        )

    def merge(x):  # [B,S,H,D] -> [B*H, S, D]
        return x.transpose(0, 2, 1, 3).reshape(-1, s, x.shape[-1])

    def unmerge(x):  # [B*H, S, D] -> [B,S,H,D]
        return x.reshape(b, -1, s, x.shape[-1]).transpose(0, 2, 1, 3)

    return float(scale), block_q, block_k, merge, unmerge


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
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
        q, k, v, scale, block_q, block_k
    )
    out, lse = _flash_forward(
        merge(q), merge(k), merge(v), causal, scale,
        block_q, block_k, interpret,
    )
    return unmerge(out), lse.reshape(b, h, s)


def flash_block_attention_bwd(q, k, v, do, lse, delta, causal: bool,
                              scale: Optional[float] = None,
                              block_q: Optional[int] = None,
                              block_k: Optional[int] = None,
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
        q, k, v, scale, block_q, block_k
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
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    _resident_kv_bytes: Optional[int] = None,
                    window: Optional[int] = None):
    """Flash attention (pallas on TPU): q ``[B, S, H, Dqk]``, k ``[B, S,
    KV, Dqk]``, v ``[B, S, KV, Dv]`` with ``H % KV == 0`` -> ``[B, S, H,
    Dv]``; query head ``i`` reads key/value head ``i // (H / KV)`` where
    it lies (module docstring: nothing is copied, dk and dv come back
    ``KV`` heads wide); the softmax scale defaults to ``1 / sqrt(Dqk)``.

    ``window`` = W (causal calls only): position ``t`` sees keys ``t − (W
    − 1) … t``, W with itself. The sweeps then run over the band's tiles
    alone (module docstring); a window as long as the sequence is the
    causal call itself, and ``None`` traces to the program it always
    traced to.

    ``block_q`` / ``block_k`` left ``None`` are chosen from the shape
    (:func:`_choose_blocks`). Sequence length must be a multiple of the
    block sizes (pad upstream if needed; the model configs here use powers
    of two).

    ``_resident_kv_bytes`` overrides the resident-vs-streamed regime
    threshold for THIS call (0 forces the streamed kernels); used by
    chip_smoke.py and the tests to run both regimes at one shape without
    touching shared state.
    """
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"flash attention: a window ({window}) is a positive "
                f"number of keys under the causal mask")
        if window >= q.shape[1]:
            window = None
    scale, block_q, block_k, merge, unmerge = _bshd_prologue(
        q, k, v, scale, block_q, block_k, window
    )
    TRACED.incr("flash_calls")
    if k.shape[2] != q.shape[2]:
        TRACED.incr("flash_calls_grouped")
    if _choose_chunk(q.shape[1], q.shape[3], q.dtype.itemsize, block_q,
                     block_k, v.shape[3], window, causal,
                     _resident_kv_bytes) > 1:
        TRACED.incr("flash_calls_chunked")
    if _fuses_backward(q.shape[1], q.shape[3], q.dtype.itemsize, block_q,
                       block_k, v.shape[3], _resident_kv_bytes):
        TRACED.incr("flash_calls_fused_bwd")
    out = _flash(merge(q), merge(k), merge(v), causal, scale,
                 block_q, block_k, interpret, _resident_kv_bytes, window)
    return unmerge(out)
