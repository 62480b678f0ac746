"""Pallas kernels for pointwise stages of the sequence mixers: the two
around the Mamba-2 scan (``ops/ssd.py``) — the short causal depthwise
convolution with its silu, and the gate with its grouped RMSNorm —,
LFM2's gated short convolution, which is the first's kernel body with
two multiplicands where that has a bias and a silu, and the two around
the delta rule (``ops/kda.py``) — the heads' normalisation of ``q`` and
``k`` with the decay, and the head norm with the gate after it —, and the
two around Laguna's attention call (``ops/flash.py``) — the rotation of
``q`` and ``k`` from a table, and the gate a head on its output. Each is
ONE kernel forward and ONE backward under its own ``jax.custom_vjp``;
each reads its operands once, in the dtype they arrive in, and writes
its results once. The residuals of all seven are their inputs alone: the
backward kernels recompute what they need.

``conv_silu(x [B, S, C], taps [K, C], bias [C])``:

    conv_t = bias + Σ_{j<K} taps_j ⊙ x_{t-(K-1)+j}        y_t = silu(conv_t)

per channel, zeros before each sequence's start. Backward, with
``dconv = dy · silu'(conv)`` and ``silu'(c) = σ(c)(1 + c(1 − σ(c)))``:

    dx_t = Σ_j taps_j ⊙ dconv_{t+(K-1-j)}          dbias = Σ_t dconv_t
    dtaps_j = Σ_t dconv_t ⊙ x_{t-(K-1)+j}

``gated_conv(bcx [B, S, 3C], taps [K, C])`` (``models/lfm2.py``): ``bcx``
is ``[B ; C ; X]`` along the channels, as one projection writes them;

    u = B ⊙ X      conv_t = Σ_{j<K} taps_j ⊙ u_{t-(K-1)+j}      y_t = C_t ⊙ conv_t

no bias, no activation. Backward, with ``dconv = dy ⊙ C``:

    dC = dy ⊙ conv        du_t = Σ_j taps_j ⊙ dconv_{t+(K-1-j)}
    dB = du ⊙ X           dX = du ⊙ B        dtaps_j = Σ_t dconv_t ⊙ u_{t-(K-1)+j}

The kernels are ``conv_silu``'s (``_conv_fwd_kernel`` / ``_conv_bwd_kernel``
with ``gated=True``; named ``sconv_fwd`` / ``sconv_bwd`` in a trace): the
three thirds of ``bcx`` come in as three views of the one array, what
goes into the window scratch is ``B ⊙ X``, and the one cotangent array
``[dB ; dC ; dX]`` is written a third a grid step (the backward's fourth
grid axis: the work is done at step 0, steps 1 and 2 hand over what it
kept in VMEM).

``gated_norm(y, z [B, S, I], scale [I], groups, eps)``: with ``g = y ⊙
silu(z)`` and, for each of the ``groups`` runs of ``W = I / groups``
channels alone, ``r = rsqrt(mean_W(g²) + eps)`` and ``n = g·r``:
``out = n ⊙ scale``. Backward, with ``dn = dout ⊙ scale``:

    dg = r·(dn − n·mean_W(dn ⊙ n))       dscale = Σ_t dout ⊙ n
    dy = dg ⊙ silu(z)                     dz = dg ⊙ y ⊙ silu'(z)

``kda_qkg(qkv [B, S, 3·H·D], f [B, S, H·D], dt_bias [H·D], a_log [H],
normed)`` (``models/kimi_linear.py``): ``qkv`` is ``[q̃ ; k̃ ; v]`` along
the channels, as the one convolution writes them, and a head is a run of
``D`` channels. With ``normed`` the caller's normalisation of one head —
jnp code over the last axis of a ``[rows, D]`` f32 block, there ``x ·
rsqrt(Σ_D x² + ε)`` —, per head ``h``:

    q = normed(q̃) · D^{-1/2}      k = normed(k̃)
    g = −exp(a_log_h) · softplus(f + dt_bias)

``q``, ``k`` and ``v`` (the third of ``qkv``, copied through the kernel)
leave in ``qkv``'s dtype and ``g`` in f32, each ``[B, S, H·D]``: the layout the scan's
kernels read, so the caller's ``reshape`` to ``[B, S, H, D]`` and
``ops/kda.py``'s back fold away. Backward (``kda_qkg_bwd``), from ``dq,
dk, dv`` and ``dg`` (f32): the kernel takes ``jax.vjp(normed, ·)`` on
the block it loaded — for the l2 norm, with ``r = rsqrt(Σ x² + ε)`` and
``n = x·r``, ``dx = r·(dn − n·Σ_D(dn ⊙ n))`` — at ``dn = dq · D^{-1/2}``
and ``dn = dk``, and

    df = dg ⊙ (−exp(a_log_h)) ⊙ σ(f + dt_bias)      d dt_bias = Σ_t df
    d a_log (a channel; the caller's ``repeat`` sums a head's) = Σ_t dg ⊙ g

``dq̃`` and ``dk̃`` leave as two arrays and XLA lays ``[dq̃ ; dk̃ ; dv]``
out as the convolution's one cotangent (0.55 ms a call at ``[4, 8192,
3·4096]``; written a third a grid step as ``gated_conv``'s is, the
kernel took 7.7 ms against 3.9 of bytes, the next block's 4 MiB of
operands being fetched during a step that only hands a block over:
PERF.md, PR 44). ``df`` leaves in ``f``'s dtype; the two sums accumulate
in f32.

``kda_ogate(o, gate [B, S, H·D], scale [D], eps, gated)``: with
``gated`` the caller's ``(o, scale, gate, eps) -> y`` of one head — jnp
code on ``[rows, D]`` f32 blocks and the weight as ``[1, D]``: the
head's width is the weight's and the gate's activation is the caller's
(Kimi Linear: ``RMSNorm(o) ⊙ scale ⊙ σ(gate)`` at 128; Olmo Hybrid:
``RMSNorm(o) ⊙ scale ⊙ silu(gate)`` at 192, two heads to three lane
tiles) — THE NORM FIRST and the gate after it, where ``gated_norm``
gates first, which is why the two do not share a body — ``y =
gated(o_h, scale, gate_h, eps)`` per head, in ``o``'s dtype. Backward (``kda_ogate_bwd``): ``jax.vjp(gated, ·)`` on
the blocks it loaded, the weight broadcast to a row a position so that
its cotangent comes a row a position too — for the norm-then-gate, with
``r = rsqrt(mean_D(o²) + eps)``, ``n = o·r``, ``s = σ(gate)`` and ``dn =
dy ⊙ scale ⊙ s``:

    do = r·(dn − n·mean_D(dn ⊙ n))         dscale = Σ_{t,h} dy ⊙ n ⊙ s
    dgate = dy ⊙ n ⊙ scale ⊙ s(1 − s)

``dscale`` accumulates in f32 a channel; the heads' sums are added up
outside. Handing the head's function in keeps what it IS in the model
(``benchmark/tests/kimi_faults.py`` puts its faults in those seams'
place, and a fault there must change what the kernels compute); the
kernels own the blocks, the dtypes and the sums.

``rotary(x [B, S, H·D], cos, sin [S, D], half)`` (``models/laguna.py``):
the ``rotate_half`` rotation of ``models/common.py::rotary`` over the
first ``2·half`` lanes of every head, from :func:`rotary_tables`' table
(built once a step: a lane of the head, the sign of ``sin`` and the
lanes that pass in it). With ``p(x)`` the partner lanes (``i ± half``,
a ``pltpu.roll`` inside the head's lane tile):

    y = x ⊙ cos + p(x) ⊙ sin            dx = dy ⊙ cos − p(dy) ⊙ sin

``y`` leaves as ``[B, H, S, D]``, THE HEADS FIRST — the order the flash
call merges its ``[B, S, H, D]`` argument to, so the caller hands it
``y``'s transposed view and XLA folds the two turns away (a kernel that
wrote ``[B, S, H·D]`` would pay a copy of ``q`` a call where XLA's own
fusion wrote the flash call's layout directly) — and ``dy`` arrives so.

``gate_heads(o [B, H, S, D], gate [B, S, H])``: the flash call's output
as it leaves the kernel (heads first, likewise) times a float32 gate a
head a position, to ``[B, S, H·D]`` as the output projection reads it:

    y = o · γ               do = dy · γ               dγ = Σ_D dy ⊙ o

one f32 multiply and one rounding each (the jnp form's bits), ``dγ`` in
f32. A block is ALL the heads of a few rows (the gate's block is then
the whole of its last axis, padded with zeros to whole lane tiles on the
TPU); what the gate is stays the caller's. Both kernels' bodies are
traced a head (the rotation) or a group of eight heads (the gate) long,
whatever a block holds: the rotation's loop over heads is unrolled by the
lowering, the gate's over groups stays a loop.

What is which dtype: ``x``, ``y``, ``z`` and the cotangents arrive, and
the results and ``dx``, ``dy``, ``dz`` leave, in the input dtype (bf16
in the models); every operand is upcast to f32 as it is loaded and all
that lies between — the taps' sums, the sigmoid, the group's mean, the
rsqrt — is f32 and exact (no approximate reciprocal), so the one
rounding is where the jnp formulation had it: after the silu, after the
norm's scale, after the l2 norm and ``q``'s scale, after the gate.
``dtaps``, ``dbias``, ``dscale``, ``d dt_bias`` and ``d a_log`` accumulate
in f32 and leave in their parameter's dtype.

The halo. A grid step is one (batch row, sequence block, channel block
of whole lane tiles); the convolution at a block's first ``K − 1``
positions needs the ``K − 1`` rows before it. Nothing is padded in HBM:
the forward kernel walks a row's sequence blocks in order and keeps an
f32 copy of the block in a VMEM scratch whose first ``_HALO`` (8, the
f32 sublane tile) rows are the previous block's last — zeros in block
0, so nothing of one batch row reaches the next — and takes the ``K``
shifted windows from it: an aligned load of a chunk with the eight rows
before it, rotated down the sublanes (Mosaic takes no unaligned
dynamic index; a packed bf16 block shifts worse still). The backward
kernel walks the blocks LAST TO FIRST: ``x``'s rows before the block
come from a second, one-tile view of the same array (index clamped,
zeroed in block 0), and
``dconv``'s rows AFTER the block, which ``dx`` at the block's end
needs, are the first rows of the block it handled one step before,
carried in its second scratch. ``dtaps``, ``dbias`` and ``dscale`` are
output blocks that stay resident while the grid runs over batch rows
and sequence blocks (the channel block is the outermost axis there).

Inside a grid step the rows go through in chunks (``_CONV_CHUNK``,
``_GATE_CHUNK``) so that a chunk's chain of f32 values is never a
block-sized array in VMEM; sums over rows are kept eight sublanes tall
until the step's end.

Blocks are chosen from the shape (:func:`_lane_block`,
:func:`_row_block`): a channel block of at most ``_LANE_BLOCK`` lanes
(whole norm groups for the gate, whole heads around the delta rule), and as many rows as keep a block near
``_BLOCK_ELEMS`` elements, a divisor of the sequence where there is
one. A sequence that is no multiple of the block ends in a partial
block whose rows past the end are masked in the backward kernels (what
they would add to the sums is garbage) and thrown away by the forward.
On the TPU a channel count, a norm group or ``kda_qkg``'s head that is
no multiple of 128 lanes is refused with a message; ``kda_ogate`` takes
a head of any width whose channel blocks can be whole heads AND whole
lane tiles (``H·D`` a multiple of ``lcm(D, 128)``: 192 in twos; a head
inside such a block is a lane slice that starts between tiles, which
Mosaic shifts) and refuses the rest with a message. The two around the
attention call refuse a sequence that is no whole row blocks (nothing
there is masked) and, on the TPU, a head that is no whole lane tiles.
Off the TPU the same
kernels run in Pallas's interpreter at any width (the CPU tests), chosen
from the backend alone.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.utils.metrics import TRACED

__all__ = ["conv_silu", "gated_conv", "gated_norm", "kda_qkg", "kda_ogate",
           "rotary_tables", "rotary", "gate_heads"]

_LANES = 128
_HALO = 8                    # rows kept beside a block: the f32 sublane tile
_LANE_BLOCK = 512            # the widest channel block
_BLOCK_ELEMS = 256 * 1024    # elements a block: 1 MiB as f32
# rows a pass of the loop inside a grid step (measured on the v5e at the
# cell's shapes, PERF.md PR 34: the convolution's rotated windows want
# few live rows, the norm's chain is short and wants few loop trips)
_CONV_CHUNK = 32
_GATE_CHUNK = 128
# around the attention call (measured on the v5e at the cell's shapes,
# PERF.md PR 62): blocks of twice the elements — the rotation eight heads
# wide (512 x 1024: 79 % of its bytes' floor against 63 % at 512 x 512),
# the gate a head all its heads wide, so few rows (64 x 8192) — the
# rotation 64 rows a pass, the table's two rows held across a block's
# heads, and the gate's heads in groups of eight a loop's trip
_ATTN_BLOCK_ELEMS = 2 * _BLOCK_ELEMS
_ROTARY_LANES = 1024
_ROTARY_CHUNK = 64
_GATE_GROUP = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _f32(a):
    return a.astype(jnp.float32)


def _silu_and_slope(v):
    """``silu(v)`` and ``silu'(v)``."""
    sig = jax.nn.sigmoid(v)
    return v * sig, sig * (1.0 + v * (1.0 - sig))


def _fold_rows(rows: int) -> int:
    return _HALO if rows % _HALO == 0 else rows


def _fold(a):
    """``[rows, W]`` -> the sum of its eight-row slabs ``[8, W]`` (vector
    adds only); as it is where the rows are no multiple of eight."""
    out = a[:_fold_rows(a.shape[0])]
    for k in range(1, a.shape[0] // out.shape[0]):
        out = out + a[k * _HALO:(k + 1) * _HALO]
    return out


def _row_sum(a):
    return jnp.sum(a, axis=0, keepdims=True)


def _past_the_end(first, rows: int, seq_len: int):
    """``[rows, 1]``: which of the sequence's rows ``first, first + 1,
    ...`` lie at or past its end."""
    return first + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) >= seq_len


# ------------------------------------------------------- convolution + silu
def _windows(ref, r0, off: int, rows: int, taps: int):
    """The ``taps`` windows of ``rows`` rows of an f32 scratch that start
    at rows ``r0 + off``, ``r0 + off + 1``, ... ``r0`` is a multiple of
    eight; where it is not static the rows around the windows are loaded
    tile-aligned and rotated down the sublanes (Mosaic takes no
    unaligned dynamic index)."""
    if isinstance(r0, int):
        return [ref[r0 + off + j:r0 + off + j + rows, :] for j in range(taps)]
    lo = off // _HALO * _HALO
    span = -(-(off - lo + taps - 1 + rows) // _HALO) * _HALO
    around = ref[pl.ds(pl.multiple_of(r0 + lo, _HALO), span), :]
    return [around[:rows] if off - lo + j == 0 else
            pltpu.roll(around, shift=span - (off - lo + j), axis=0)[:rows]
            for j in range(taps)]


def _row0(i, chunk: int):
    return i * chunk if isinstance(i, int) else pl.multiple_of(
        i * chunk, chunk)


def _for_chunks(n: int, body, init):
    """``fori_loop`` over a grid step's chunks; a single chunk (a short
    sequence taken whole) straight-line, with a static index."""
    return body(0, init) if n == 1 else jax.lax.fori_loop(0, n, body, init)


def _weighted(w, windows, reverse: bool = False):
    """``Σ_j w_j ⊙ windows[j]`` (``w_{K-1-j}`` where ``reverse``), summed
    in the order of ``j``."""
    k = len(windows)
    total = None
    for j, win in enumerate(windows):
        row = k - 1 - j if reverse else j
        term = w[row:row + 1] * win
        total = term if total is None else total + term
    return total


def _conv_fwd_kernel(*refs, chunk: int, gated: bool):
    """One (batch row, channel block, sequence block), the sequence
    blocks innermost and in order. ``gated``: what is convolved is ``B ⊙
    X`` and the result is ``C ⊙ conv`` (no bias, no silu); else ``x``
    and ``silu(bias + conv)``."""
    if gated:
        b_ref, c_ref, x_ref, w_ref, o_ref, win = refs
    else:
        x_ref, w_ref, b_ref, o_ref, win = refs
    si = pl.program_id(2)
    bs, k = x_ref.shape[1], w_ref.shape[0]

    @pl.when(si == 0)
    def _start():
        win[0:_HALO] = jnp.zeros((_HALO, win.shape[1]), jnp.float32)

    @pl.when(si > 0)
    def _carry():
        win[0:_HALO] = win[bs:bs + _HALO]

    win[_HALO:_HALO + bs] = (
        _f32(b_ref[0]) * _f32(x_ref[0]) if gated else _f32(x_ref[0]))
    w = _f32(w_ref[...])
    bias = None if gated else _f32(b_ref[...])

    def rows(i, carry):
        r0 = _row0(i, chunk)
        conv = _weighted(w, _windows(win, r0, _HALO - (k - 1), chunk, k))
        if gated:
            out = _f32(c_ref[0, pl.ds(r0, chunk), :]) * conv
        else:
            conv = bias + conv
            out = conv * jax.nn.sigmoid(conv)
        o_ref[0, pl.ds(r0, chunk), :] = out.astype(o_ref.dtype)
        return carry

    _for_chunks(bs // chunk, rows, 0)


def _conv_bwd_kernel(*refs, chunk: int, seq_len: int, halo: bool,
                     gated: bool):
    """One (channel block, batch row, sequence block), the sequence
    blocks innermost and LAST TO FIRST; ``dw_ref`` (and ``db_ref``) stay
    resident over a channel block's batch rows and sequence blocks.

    ``gated`` (what is convolved is ``u = B ⊙ X``, the result ``C ⊙
    conv``): the one cotangent array is ``[dB ; dC ; dX]`` along the
    channels, so the grid has a fourth, innermost axis of three — step 0
    does all the work, writes ``dB``'s block and keeps ``dC``'s and
    ``dX``'s in VMEM, steps 1 and 2 hand those to their blocks of the
    same array (the inputs' blocks do not move, so nothing is fetched
    again)."""
    if gated:
        b_ref, c_ref, x_ref, refs = refs[0], refs[1], refs[2], refs[3:]
        prev_b, prev_ref, refs = (
            (refs[0], refs[1], refs[2:]) if halo else (None, None, refs))
        dy_ref, w_ref, dx_ref, dw_ref, win, dwin, keep_c, keep_x = refs
        part = pl.program_id(3)

        @pl.when(part == 1)
        def _dc():
            dx_ref[0] = keep_c[...]

        @pl.when(part == 2)
        def _dx():
            dx_ref[0] = keep_x[...]
    else:
        x_ref, refs = refs[0], refs[1:]
        prev_ref, refs = (refs[0], refs[1:]) if halo else (None, refs)
        dy_ref, w_ref, b_ref, dx_ref, dw_ref, db_ref, win, dwin = refs
    bi, si = pl.program_id(1), pl.program_id(2)
    block = pl.num_programs(2) - 1 - si
    bs, bc = x_ref.shape[1:]
    k = w_ref.shape[0]
    ragged = seq_len % bs != 0
    f32 = jnp.float32

    def work():
        @pl.when((bi == 0) & (si == 0))
        def _zero():
            dw_ref[...] = jnp.zeros(dw_ref.shape, f32)
            if not gated:
                db_ref[...] = jnp.zeros(db_ref.shape, f32)

        # the convolved rows before the block
        before = jnp.zeros((_HALO, bc), f32)
        if halo:
            rows_before = _f32(prev_ref[0])[-_HALO:]
            if gated:
                rows_before = _f32(prev_b[0])[-_HALO:] * rows_before
            before = jnp.where(block == 0, 0.0, rows_before)
        win[0:_HALO] = before
        xb = _f32(b_ref[0]) * _f32(x_ref[0]) if gated else _f32(x_ref[0])
        if ragged:
            xb = jnp.where(_past_the_end(block * bs, bs, seq_len), 0.0, xb)
        win[_HALO:_HALO + bs] = xb

        # dconv's rows after the block: the first rows of the block
        # handled one step before
        @pl.when(si == 0)
        def _start():
            dwin[bs:bs + _HALO] = jnp.zeros((_HALO, bc), f32)

        @pl.when(si > 0)
        def _carry():
            dwin[bs:bs + _HALO] = dwin[0:_HALO]

        w = _f32(w_ref[...])
        bias = None if gated else _f32(b_ref[...])

        def recompute(i, sums):
            r0 = _row0(i, chunk)
            windows = _windows(win, r0, _HALO - (k - 1), chunk, k)
            conv = _weighted(w, windows)
            if not gated:
                conv = bias + conv
            dy = _f32(dy_ref[0, pl.ds(r0, chunk), :])
            gone = _past_the_end(
                block * bs + r0, chunk, seq_len) if ragged else None
            if ragged:
                dy = jnp.where(gone, 0.0, dy)
            if gated:
                keep_c[pl.ds(r0, chunk), :] = (dy * conv).astype(keep_c.dtype)
                dconv = dy * _f32(c_ref[0, pl.ds(r0, chunk), :])
                if ragged:      # C's rows past the end are anything
                    dconv = jnp.where(gone, 0.0, dconv)
            else:
                dconv = dy * _silu_and_slope(conv)[1]
            dwin[pl.ds(r0, chunk), :] = dconv
            parts = [_fold(dconv * win_j) for win_j in windows]
            if not gated:
                parts.append(_fold(dconv))
            return tuple(s + p for s, p in zip(sums, parts))

        n_sums = k if gated else k + 1
        sums = _for_chunks(
            bs // chunk, recompute,
            tuple(jnp.zeros((_fold_rows(chunk), bc), f32)
                  for _ in range(n_sums)))
        for j in range(k):
            dw_ref[j:j + 1, :] += _row_sum(sums[j])
        if not gated:
            db_ref[...] += _row_sum(sums[k])

        def spread(i, carry):
            r0 = _row0(i, chunk)
            at = pl.ds(r0, chunk)
            d_in = _weighted(w, _windows(dwin, r0, 0, chunk, k), reverse=True)
            if gated:
                # du -> dB = du ⊙ X (this step's block), dX = du ⊙ B
                keep_x[at, :] = (d_in * _f32(b_ref[0, at, :])).astype(
                    keep_x.dtype)
                d_in = d_in * _f32(x_ref[0, at, :])
            dx_ref[0, at, :] = d_in.astype(dx_ref.dtype)
            return carry

        _for_chunks(bs // chunk, spread, 0)

    if gated:
        pl.when(part == 0)(work)
    else:
        work()


def _whole_tiles(whole: int) -> int:
    """The fewest lanes that are whole runs of ``whole`` channels AND
    whole 128-lane tiles: ``whole`` itself where it is a multiple of
    128, 384 (two heads, three tiles) for a head of 192."""
    return math.lcm(whole, _LANES)


def _lane_block(width: int, whole: int = _LANES,
                widest: int = _LANE_BLOCK) -> int:
    """Lanes a channel block: the widest multiple of ``whole`` channels
    and of 128 lanes (:func:`_whole_tiles`) up to ``widest`` that
    divides ``width``, at least one such unit; the whole of a width that
    is no multiple of it (off the TPU only)."""
    unit = _whole_tiles(whole)
    if width % unit:
        return width
    return max([unit] + [bc for bc in range(unit, widest + 1, unit)
                         if width % bc == 0])


def _row_block(seq_len: int, lanes: int, elems: int = _BLOCK_ELEMS) -> int:
    """Rows a block: about ``elems / lanes``, in sixteens (a
    packed bf16 tile); the whole of a shorter sequence; a divisor of the
    sequence where one lies within a factor of two below."""
    rows = max(16, elems // lanes // 16 * 16)
    if seq_len <= rows:
        return seq_len
    return next((bs for bs in range(rows, rows // 2, -16)
                 if seq_len % bs == 0), rows)


def _row_chunk(bs: int, chunk: int) -> int:
    """``chunk`` rows a pass, or the largest of its halves down to a
    packed bf16 tile that divides the block; a block no sixteen divides
    (a short sequence taken whole) in one pass."""
    while chunk > 16 and bs % chunk:
        chunk //= 2
    return chunk if bs % chunk == 0 else bs


def _conv_forward(x, taps, bias, blocks: Tuple[int, int], interpret: bool):
    b, s, c = x.shape
    bs, bc = blocks
    k = taps.shape[0]
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, chunk=_row_chunk(bs, _CONV_CHUNK),
                          gated=False),
        grid=(b, c // bc, pl.cdiv(s, bs)),
        in_specs=[
            pl.BlockSpec((1, bs, bc), lambda b, c, s: (b, s, c)),
            pl.BlockSpec((k, bc), lambda b, c, s: (0, c)),
            pl.BlockSpec((1, bc), lambda b, c, s: (0, c)),
        ],
        out_specs=pl.BlockSpec((1, bs, bc), lambda b, c, s: (b, s, c)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + bs, bc), jnp.float32)],
        interpret=interpret, name="ssm_conv_fwd",
    )(x, taps, bias.reshape(1, c))


def _conv_backward(x, taps, bias, dy, blocks: Tuple[int, int],
                   interpret: bool):
    b, s, c = x.shape
    bs, bc = blocks
    k = taps.shape[0]
    nb = pl.cdiv(s, bs)
    halo = nb > 1
    tile = 32 // x.dtype.itemsize       # rows of x's sublane tile

    def at(s):
        return nb - 1 - s

    rows = pl.BlockSpec((1, bs, bc), lambda c, b, s: (b, at(s), c))
    # the tile of rows that ends where the block starts
    before = pl.BlockSpec(
        (1, tile, bc),
        lambda c, b, s: (b, jnp.maximum(at(s) * (bs // tile) - 1, 0), c))
    tap_rows = pl.BlockSpec((k, bc), lambda c, b, s: (0, c))
    lanes = pl.BlockSpec((1, bc), lambda c, b, s: (0, c))
    f32 = jnp.float32
    dx, dtaps, dbias = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, chunk=_row_chunk(bs, _CONV_CHUNK),
                          seq_len=s, halo=halo, gated=False),
        grid=(c // bc, b, nb),
        in_specs=[rows] + ([before] if halo else []) + [rows, tap_rows, lanes],
        out_specs=[rows, tap_rows, lanes],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((k, c), f32),
            jax.ShapeDtypeStruct((1, c), f32),
        ],
        scratch_shapes=[pltpu.VMEM((_HALO + bs, bc), f32),
                        pltpu.VMEM((bs + _HALO, bc), f32)],
        interpret=interpret, name="ssm_conv_bwd",
    )(*([x, x] if halo else [x]), dy, taps, bias.reshape(1, c))
    return dx, dtaps.astype(taps.dtype), dbias.reshape(c).astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, taps, bias, blocks, interpret):
    return _conv_forward(x, taps, bias, blocks, interpret)


def _conv_fwd_rule(x, taps, bias, blocks, interpret):
    return _conv_forward(x, taps, bias, blocks, interpret), (x, taps, bias)


def _conv_bwd_rule(blocks, interpret, residuals, dy):
    return _conv_backward(*residuals, dy, blocks, interpret)


_conv.defvjp(_conv_fwd_rule, _conv_bwd_rule)


def _refuse_lanes(what: str, width: int, interpret: bool) -> None:
    if not interpret and width % _LANES:
        raise ValueError(
            f"{what}: {width} channels are no multiple of {_LANES} lanes")


def conv_silu(x, taps, bias):
    """``silu(bias + Σ_j taps[j] ⊙ x[t − (K−1) + j])`` of the module's
    docstring: ``x [B, S, C]``, ``taps [K, C]``, ``bias [C]`` ->
    ``[B, S, C]`` in ``x``'s dtype, differentiable in all three. The
    blocks are chosen from the shape; the result does not depend on
    them (``tests/test_ssm_pointwise.py`` runs ``_conv`` at others)."""
    if (x.ndim != 3 or taps.ndim != 2 or taps.shape[1] != x.shape[2]
            or bias.shape != x.shape[2:]):
        raise ValueError(
            f"conv_silu: x{tuple(x.shape)} taps{tuple(taps.shape)} "
            f"bias{tuple(bias.shape)} do not fit")
    if not 1 <= taps.shape[0] <= _HALO + 1:
        raise ValueError(
            f"conv_silu: {taps.shape[0]} taps; the halo holds {_HALO} rows")
    interpret = _interpret()
    _refuse_lanes("conv_silu", x.shape[2], interpret)
    bc = _lane_block(x.shape[2])
    return _conv(x, taps, bias, (_row_block(x.shape[1], bc), bc), interpret)


# ------------------------------------------- gated short convolution
def _gconv_forward(bcx, taps, blocks: Tuple[int, int], interpret: bool):
    b, s, c3 = bcx.shape
    c = c3 // 3
    bs, bc = blocks
    k, nc = taps.shape[0], c // bc

    def third(n):
        return pl.BlockSpec((1, bs, bc), lambda b, c, s: (b, s, n * nc + c))

    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, chunk=_row_chunk(bs, _CONV_CHUNK),
                          gated=True),
        grid=(b, nc, pl.cdiv(s, bs)),
        in_specs=[third(0), third(1), third(2),
                  pl.BlockSpec((k, bc), lambda b, c, s: (0, c))],
        out_specs=pl.BlockSpec((1, bs, bc), lambda b, c, s: (b, s, c)),
        out_shape=jax.ShapeDtypeStruct((b, s, c), bcx.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + bs, bc), jnp.float32)],
        interpret=interpret, name="sconv_fwd",
    )(bcx, bcx, bcx, taps)


def _gconv_backward(bcx, taps, dy, blocks: Tuple[int, int], interpret: bool):
    b, s, c3 = bcx.shape
    c = c3 // 3
    bs, bc = blocks
    k, nc = taps.shape[0], c // bc
    nb = pl.cdiv(s, bs)
    halo = nb > 1
    tile = 32 // bcx.dtype.itemsize     # rows of bcx's sublane tile

    def at(s):
        return nb - 1 - s

    def third(n):
        return pl.BlockSpec(
            (1, bs, bc), lambda c, b, s, p: (b, at(s), n * nc + c))

    def before(n):      # the tile of rows that ends where the block starts
        return pl.BlockSpec(
            (1, tile, bc), lambda c, b, s, p: (
                b, jnp.maximum(at(s) * (bs // tile) - 1, 0), n * nc + c))

    tap_rows = pl.BlockSpec((k, bc), lambda c, b, s, p: (0, c))
    f32 = jnp.float32
    d_bcx, dtaps = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, chunk=_row_chunk(bs, _CONV_CHUNK),
                          seq_len=s, halo=halo, gated=True),
        grid=(nc, b, nb, 3),
        in_specs=[third(0), third(1), third(2)]
        + ([before(0), before(2)] if halo else [])
        + [pl.BlockSpec((1, bs, bc), lambda c, b, s, p: (b, at(s), c)),
           tap_rows],
        out_specs=[
            # part 0 writes dB's block, 1 dC's, 2 dX's
            pl.BlockSpec((1, bs, bc),
                         lambda c, b, s, p: (b, at(s), p * nc + c)),
            tap_rows],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((k, c), f32)],
        scratch_shapes=[pltpu.VMEM((_HALO + bs, bc), f32),
                        pltpu.VMEM((bs + _HALO, bc), f32),
                        pltpu.VMEM((bs, bc), bcx.dtype),
                        pltpu.VMEM((bs, bc), bcx.dtype)],
        interpret=interpret, name="sconv_bwd",
    )(*([bcx] * (5 if halo else 3)), dy, taps)
    return d_bcx, dtaps.astype(taps.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gconv(bcx, taps, blocks, interpret):
    return _gconv_forward(bcx, taps, blocks, interpret)


def _gconv_fwd_rule(bcx, taps, blocks, interpret):
    return _gconv_forward(bcx, taps, blocks, interpret), (bcx, taps)


def _gconv_bwd_rule(blocks, interpret, residuals, dy):
    return _gconv_backward(*residuals, dy, blocks, interpret)


_gconv.defvjp(_gconv_fwd_rule, _gconv_bwd_rule)


def gated_conv(bcx, taps):
    """``C ⊙ conv(B ⊙ X)`` of the module's docstring: ``bcx [B, S, 3C]``
    (``[B ; C ; X]`` along the channels, as one projection writes them),
    ``taps [K, C]`` -> ``[B, S, C]`` in ``bcx``'s dtype, differentiable
    in both. The blocks are chosen from the shape; the result does not
    depend on them (``tests/test_ssm_pointwise.py`` runs ``_gconv`` at
    others)."""
    if (bcx.ndim != 3 or taps.ndim != 2 or bcx.shape[2] != 3 * taps.shape[1]):
        raise ValueError(
            f"gated_conv: bcx{tuple(bcx.shape)} taps{tuple(taps.shape)} "
            f"do not fit")
    if not 1 <= taps.shape[0] <= _HALO + 1:
        raise ValueError(
            f"gated_conv: {taps.shape[0]} taps; the halo holds {_HALO} rows")
    interpret = _interpret()
    c = taps.shape[1]
    _refuse_lanes("gated_conv", c, interpret)
    bc = _lane_block(c)
    return _gconv(bcx, taps, (_row_block(bcx.shape[1], bc), bc), interpret)


# ------------------------------------------------------ gate + grouped norm
def _gate_fwd_kernel(y_ref, z_ref, s_ref, o_ref, *, chunk: int, group: int,
                     eps: float):
    """One (batch row, sequence block, channel block of whole groups)."""
    bs, bc = y_ref.shape[1:]

    def rows(i, carry):
        at = pl.ds(_row0(i, chunk), chunk)
        for g in range(bc // group):
            lanes = pl.ds(g * group, group)
            zv = _f32(z_ref[0, at, lanes])
            gated = _f32(y_ref[0, at, lanes]) * (zv * jax.nn.sigmoid(zv))
            r = jax.lax.rsqrt(
                jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
            o_ref[0, at, lanes] = (
                (gated * r) * _f32(s_ref[:, lanes])).astype(o_ref.dtype)
        return carry

    _for_chunks(bs // chunk, rows, 0)


def _gate_bwd_kernel(y_ref, z_ref, s_ref, do_ref, dy_ref, dz_ref, ds_ref, *,
                     chunk: int, group: int, eps: float, seq_len: int):
    """One (channel block, batch row, sequence block); ``ds_ref`` stays
    resident over a channel block's batch rows and sequence blocks."""
    bi, si = pl.program_id(1), pl.program_id(2)
    bs, bc = y_ref.shape[1:]
    ragged = seq_len % bs != 0
    f32 = jnp.float32
    groups = bc // group

    @pl.when((bi == 0) & (si == 0))
    def _zero():
        ds_ref[...] = jnp.zeros(ds_ref.shape, f32)

    def rows(i, sums):
        r0 = _row0(i, chunk)
        at = pl.ds(r0, chunk)
        out = []
        gone = _past_the_end(si * bs + r0, chunk, seq_len) if ragged else None
        for g in range(groups):
            lanes = pl.ds(g * group, group)
            yv, zv, dov = (_f32(ref[0, at, lanes])
                           for ref in (y_ref, z_ref, do_ref))
            if ragged:
                yv, zv, dov = (jnp.where(gone, 0.0, v) for v in (yv, zv, dov))
            act, slope = _silu_and_slope(zv)
            gated = yv * act
            r = jax.lax.rsqrt(
                jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
            n = gated * r
            dn = dov * _f32(s_ref[:, lanes])
            dg = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
            dy_ref[0, at, lanes] = (dg * act).astype(dy_ref.dtype)
            dz_ref[0, at, lanes] = (dg * yv * slope).astype(dz_ref.dtype)
            out.append(sums[g] + _fold(dov * n))
        return tuple(out)

    sums = _for_chunks(
        bs // chunk, rows,
        tuple(jnp.zeros((_fold_rows(chunk), group), f32)
              for _ in range(groups)))
    for g in range(groups):
        ds_ref[:, g * group:(g + 1) * group] += _row_sum(sums[g])


def _gate_forward(y, z, scale, group: int, eps: float,
                  blocks: Tuple[int, int], interpret: bool):
    b, s, width = y.shape
    bs, bc = blocks
    rows = pl.BlockSpec((1, bs, bc), lambda b, s, c: (b, s, c))
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, chunk=_row_chunk(bs, _GATE_CHUNK),
                          group=group, eps=eps),
        grid=(b, pl.cdiv(s, bs), width // bc),
        in_specs=[rows, rows, pl.BlockSpec((1, bc), lambda b, s, c: (0, c))],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        interpret=interpret, name="ssm_gate_fwd",
    )(y, z, scale.reshape(1, width))


def _gate_backward(y, z, scale, dout, group: int, eps: float,
                   blocks: Tuple[int, int], interpret: bool):
    b, s, width = y.shape
    bs, bc = blocks
    rows = pl.BlockSpec((1, bs, bc), lambda c, b, s: (b, s, c))
    lanes = pl.BlockSpec((1, bc), lambda c, b, s: (0, c))
    dy, dz, dscale = pl.pallas_call(
        functools.partial(_gate_bwd_kernel, chunk=_row_chunk(bs, _GATE_CHUNK),
                          group=group, eps=eps, seq_len=s),
        grid=(width // bc, b, pl.cdiv(s, bs)),
        in_specs=[rows, rows, lanes, rows],
        out_specs=[rows, rows, lanes],
        out_shape=[
            jax.ShapeDtypeStruct(y.shape, y.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((1, width), jnp.float32),
        ],
        interpret=interpret, name="ssm_gate_bwd",
    )(y, z, scale.reshape(1, width), dout)
    return dy, dz, dscale.reshape(width).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gate(y, z, scale, group, eps, blocks, interpret):
    return _gate_forward(y, z, scale, group, eps, blocks, interpret)


def _gate_fwd_rule(y, z, scale, group, eps, blocks, interpret):
    return (_gate_forward(y, z, scale, group, eps, blocks, interpret),
            (y, z, scale))


def _gate_bwd_rule(group, eps, blocks, interpret, residuals, dout):
    return _gate_backward(*residuals, dout, group, eps, blocks, interpret)


_gate.defvjp(_gate_fwd_rule, _gate_bwd_rule)


def gated_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm_group(y ⊙ silu(z)) ⊙ scale`` of the module's docstring:
    ``y, z [B, S, I]``, ``scale [I]``, each of the ``groups`` runs of
    ``I / groups`` channels normalised alone -> ``[B, S, I]`` in ``y``'s
    dtype, differentiable in ``y``, ``z`` and ``scale``."""
    width = y.shape[-1]
    if (y.ndim != 3 or y.shape != z.shape or scale.shape != (width,)
            or groups < 1 or width % groups):
        raise ValueError(
            f"gated_norm: y{tuple(y.shape)} z{tuple(z.shape)} "
            f"scale{tuple(scale.shape)} in {groups} groups do not fit")
    interpret = _interpret()
    group = width // groups
    _refuse_lanes("gated_norm: a group's", group, interpret)
    bc = _lane_block(width, group)
    return _gate(y, z, scale, group, float(eps),
                 (_row_block(y.shape[1], bc), bc), interpret)


# ------------------------------- around the delta rule: norms and decay
def _softplus_and_slope(v):
    """``softplus(v)`` and ``softplus'(v) = σ(v)``."""
    return (jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v))),
            jax.nn.sigmoid(v))


def _qkg_fwd_kernel(q_ref, k_ref, v_ref, f_ref, b_ref, a_ref, qo_ref, ko_ref,
                    vo_ref, g_ref, *, chunk: int, head: int, normed):
    """One (batch row, sequence block, channel block of whole heads);
    ``v``'s block goes through as it is (its bytes ride under the
    norms' lane reductions, which bound the step)."""
    bs, bc = f_ref.shape[1:]
    bias, rate = _f32(b_ref[...]), -jnp.exp(_f32(a_ref[...]))
    vo_ref[0] = v_ref[0]

    def rows(i, carry):
        at = pl.ds(_row0(i, chunk), chunk)
        for h in range(bc // head):
            lanes = pl.ds(h * head, head)
            qo_ref[0, at, lanes] = (
                normed(_f32(q_ref[0, at, lanes])) * head ** -0.5
            ).astype(qo_ref.dtype)
            ko_ref[0, at, lanes] = normed(
                _f32(k_ref[0, at, lanes])).astype(ko_ref.dtype)
        g_ref[0, at, :] = rate * _softplus_and_slope(
            _f32(f_ref[0, at, :]) + bias)[0]
        return carry

    _for_chunks(bs // chunk, rows, 0)


def _qkg_bwd_kernel(q_ref, k_ref, f_ref, b_ref, a_ref, dq_ref, dk_ref,
                    dg_ref, dqo_ref, dko_ref, df_ref, db_ref, da_ref, *,
                    chunk: int, head: int, seq_len: int, normed):
    """One (channel block, batch row, sequence block); ``db_ref`` and
    ``da_ref`` stay resident over a channel block's batch rows and
    sequence blocks."""
    bi, si = pl.program_id(1), pl.program_id(2)
    bs, bc = f_ref.shape[1:]
    ragged = seq_len % bs != 0
    f32 = jnp.float32

    @pl.when((bi == 0) & (si == 0))
    def _zero():
        db_ref[...] = jnp.zeros(db_ref.shape, f32)
        da_ref[...] = jnp.zeros(da_ref.shape, f32)

    bias, rate = _f32(b_ref[...]), -jnp.exp(_f32(a_ref[...]))

    def rows(i, sums):
        r0 = _row0(i, chunk)
        at = pl.ds(r0, chunk)
        # rows past the end: what they give dq̃ and dk̃ is thrown away
        for h in range(bc // head):
            lanes = pl.ds(h * head, head)
            dqo_ref[0, at, lanes] = jax.vjp(
                normed, _f32(q_ref[0, at, lanes]))[1](
                _f32(dq_ref[0, at, lanes]) * head ** -0.5
            )[0].astype(dqo_ref.dtype)
            dko_ref[0, at, lanes] = jax.vjp(
                normed, _f32(k_ref[0, at, lanes]))[1](
                _f32(dk_ref[0, at, lanes]))[0].astype(dko_ref.dtype)
        fv, dgv = _f32(f_ref[0, at, :]), _f32(dg_ref[0, at, :])
        if ragged:
            gone = _past_the_end(si * bs + r0, chunk, seq_len)
            fv, dgv = (jnp.where(gone, 0.0, v) for v in (fv, dgv))
        soft, slope = _softplus_and_slope(fv + bias)
        dfv = dgv * rate * slope
        df_ref[0, at, :] = dfv.astype(df_ref.dtype)
        return sums[0] + _fold(dfv), sums[1] + _fold(dgv * soft)

    sums = _for_chunks(
        bs // chunk, rows,
        tuple(jnp.zeros((_fold_rows(chunk), bc), f32) for _ in range(2)))
    db_ref[...] += _row_sum(sums[0])
    da_ref[...] += rate * _row_sum(sums[1])


# The four functions that build these kernels are jitted: a model calls
# each once a layer, again under remat and for every program it makes, and
# tracing a kernel's body (the seams' jnp code and its ``jax.vjp``, a head
# at a time) and lowering it for Mosaic is Python that every run pays,
# compile cache or not. Under an inner jit a process traces each body
# once a shape (PERF.md, PR 44: kimi's step program traced and lowered in
# 14.3 s without and 11.3 s with, the parent's in 10.0 s, on the chip's
# host) — and reads ``_GATE_CHUNK`` once a shape: whoever sets another
# clears jax's caches (``scripts/ssm_pointwise_micro.py``).
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _qkg_forward(qkv, f, dt_bias, a_chan, head: int, normed,
                 blocks: Tuple[int, int], interpret: bool):
    b, s, width = f.shape
    bs, bc = blocks
    nc = width // bc
    rows = pl.BlockSpec((1, bs, bc), lambda b, s, c: (b, s, c))
    lanes = pl.BlockSpec((1, bc), lambda b, s, c: (0, c))

    def third(n):
        return pl.BlockSpec((1, bs, bc), lambda b, s, c: (b, s, n * nc + c))

    return tuple(pl.pallas_call(
        functools.partial(_qkg_fwd_kernel, chunk=_row_chunk(bs, _GATE_CHUNK),
                          head=head, normed=normed),
        grid=(b, pl.cdiv(s, bs), nc),
        in_specs=[third(0), third(1), third(2), rows, lanes, lanes],
        out_specs=[rows, rows, rows, rows],
        out_shape=[jax.ShapeDtypeStruct(f.shape, qkv.dtype)] * 3
        + [jax.ShapeDtypeStruct(f.shape, jnp.float32)],
        interpret=interpret, name="kda_qkg_fwd",
    )(qkv, qkv, qkv, f, dt_bias.reshape(1, width), a_chan.reshape(1, width)))


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _qkg_backward(qkv, f, dt_bias, a_chan, dq, dk, dv, dg, head: int, normed,
                  blocks: Tuple[int, int], interpret: bool):
    b, s, width = f.shape
    bs, bc = blocks
    nc = width // bc
    rows = pl.BlockSpec((1, bs, bc), lambda c, b, s: (b, s, c))
    lanes = pl.BlockSpec((1, bc), lambda c, b, s: (0, c))

    def third(n):
        return pl.BlockSpec((1, bs, bc), lambda c, b, s: (b, s, n * nc + c))

    f32 = jnp.float32
    dq, dk, df, d_bias, d_a = pl.pallas_call(
        functools.partial(_qkg_bwd_kernel, chunk=_row_chunk(bs, _GATE_CHUNK),
                          head=head, seq_len=s, normed=normed),
        grid=(nc, b, pl.cdiv(s, bs)),
        in_specs=[third(0), third(1), rows, lanes, lanes, rows, rows, rows],
        out_specs=[rows, rows, rows, lanes, lanes],
        out_shape=[jax.ShapeDtypeStruct(f.shape, qkv.dtype),
                   jax.ShapeDtypeStruct(f.shape, qkv.dtype),
                   jax.ShapeDtypeStruct(f.shape, f.dtype),
                   jax.ShapeDtypeStruct((1, width), f32),
                   jax.ShapeDtypeStruct((1, width), f32)],
        interpret=interpret, name="kda_qkg_bwd",
    )(qkv, qkv, f, dt_bias.reshape(1, width), a_chan.reshape(1, width),
      dq, dk, dg)
    # the convolution's cotangent: its v third is the scan's dv as it is
    return (jnp.concatenate([dq, dk, dv], axis=-1), df,
            d_bias.reshape(width).astype(dt_bias.dtype),
            d_a.reshape(width).astype(a_chan.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _qkg(qkv, f, dt_bias, a_chan, head, normed, blocks, interpret):
    return _qkg_forward(qkv, f, dt_bias, a_chan, head, normed, blocks,
                        interpret)


def _qkg_fwd_rule(qkv, f, dt_bias, a_chan, head, normed, blocks, interpret):
    return (_qkg_forward(qkv, f, dt_bias, a_chan, head, normed, blocks,
                         interpret), (qkv, f, dt_bias, a_chan))


def _qkg_bwd_rule(head, normed, blocks, interpret, residuals, cotangents):
    return _qkg_backward(*residuals, *cotangents, head, normed, blocks,
                         interpret)


_qkg.defvjp(_qkg_fwd_rule, _qkg_bwd_rule)


def kda_qkg(qkv, f, dt_bias, a_log, normed):
    """What stands between the delta-rule mixer's convolution and its
    scan (the module's docstring): ``qkv [B, S, 3·H·D]`` (``[q̃ ; k̃ ; v]``
    along the channels, as the one convolution writes them), ``f [B, S,
    H·D]``, ``dt_bias [H·D]``, ``a_log [H]``, and ``normed`` the
    caller's normalisation of a head (jnp code over the last axis of a
    ``[rows, D]`` f32 block) -> ``q = normed(q̃)·D^{-1/2}``, ``k =
    normed(k̃)`` and ``v`` in ``qkv``'s dtype and ``g = −exp(a_log_h) ·
    softplus(f + dt_bias)`` in f32, each ``[B, S, H·D]``; differentiable
    in the four arrays."""
    width, heads = f.shape[-1], a_log.shape[0]
    if (qkv.ndim != 3 or qkv.shape != f.shape[:2] + (3 * width,)
            or dt_bias.shape != (width,) or a_log.ndim != 1
            or width % heads):
        raise ValueError(
            f"kda_qkg: qkv{tuple(qkv.shape)} f{tuple(f.shape)} "
            f"dt_bias{tuple(dt_bias.shape)} a_log{tuple(a_log.shape)} "
            f"do not fit")
    interpret = _interpret()
    head = width // heads
    _refuse_lanes("kda_qkg: a head's", head, interpret)
    bc = _lane_block(width, head)
    return _qkg(qkv, f, dt_bias, jnp.repeat(a_log, head), head, normed,
                (_row_block(f.shape[1], bc), bc), interpret)


# ------------------------------------- behind the delta rule: norm, then gate
def _ogate_fwd_kernel(o_ref, z_ref, s_ref, y_ref, *, chunk: int, head: int,
                      eps: float, gated):
    """One (batch row, sequence block, channel block of whole heads)."""
    bs, bc = o_ref.shape[1:]
    scale = _f32(s_ref[...])

    def rows(i, carry):
        at = pl.ds(_row0(i, chunk), chunk)
        for h in range(bc // head):
            lanes = pl.ds(h * head, head)
            y_ref[0, at, lanes] = gated(
                _f32(o_ref[0, at, lanes]), scale, _f32(z_ref[0, at, lanes]),
                eps).astype(y_ref.dtype)
        return carry

    _for_chunks(bs // chunk, rows, 0)


def _ogate_bwd_kernel(o_ref, z_ref, s_ref, dy_ref, do_ref, dz_ref, ds_ref, *,
                      chunk: int, head: int, eps: float, seq_len: int, gated):
    """One (channel block, batch row, sequence block); ``ds_ref`` (a sum a
    channel: the heads' are added up outside) stays resident over a
    channel block's batch rows and sequence blocks."""
    bi, si = pl.program_id(1), pl.program_id(2)
    bs, bc = o_ref.shape[1:]
    ragged = seq_len % bs != 0
    f32 = jnp.float32
    heads = bc // head

    @pl.when((bi == 0) & (si == 0))
    def _zero():
        ds_ref[...] = jnp.zeros(ds_ref.shape, f32)

    scale = _f32(s_ref[...])

    def rows(i, sums):
        r0 = _row0(i, chunk)
        at = pl.ds(r0, chunk)
        gone = _past_the_end(si * bs + r0, chunk, seq_len) if ragged else None
        out = []
        for h in range(heads):
            lanes = pl.ds(h * head, head)
            ov, zv, dyv = (_f32(ref[0, at, lanes])
                           for ref in (o_ref, z_ref, dy_ref))
            if ragged:
                ov, zv, dyv = (jnp.where(gone, 0.0, v) for v in (ov, zv, dyv))
            # the weight a row, so that its cotangent comes a row too
            do, ds, dz = jax.vjp(
                lambda o, s, z: gated(o, s, z, eps),
                ov, jnp.broadcast_to(scale, ov.shape), zv)[1](dyv)
            do_ref[0, at, lanes] = do.astype(do_ref.dtype)
            dz_ref[0, at, lanes] = dz.astype(dz_ref.dtype)
            out.append(sums[h] + _fold(ds))
        return tuple(out)

    sums = _for_chunks(
        bs // chunk, rows,
        tuple(jnp.zeros((_fold_rows(chunk), head), f32)
              for _ in range(heads)))
    for h in range(heads):
        ds_ref[:, h * head:(h + 1) * head] += _row_sum(sums[h])


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _ogate_forward(o, gate, scale, head: int, eps: float, gated,
                   blocks: Tuple[int, int], interpret: bool):
    b, s, width = o.shape
    bs, bc = blocks
    rows = pl.BlockSpec((1, bs, bc), lambda b, s, c: (b, s, c))
    return pl.pallas_call(
        functools.partial(_ogate_fwd_kernel,
                          chunk=_row_chunk(bs, _GATE_CHUNK), head=head,
                          eps=eps, gated=gated),
        grid=(b, pl.cdiv(s, bs), width // bc),
        in_specs=[rows, rows, pl.BlockSpec((1, head), lambda b, s, c: (0, 0))],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        interpret=interpret, name="kda_ogate_fwd",
    )(o, gate, scale.reshape(1, head))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _ogate_backward(o, gate, scale, dy, head: int, eps: float, gated,
                    blocks: Tuple[int, int], interpret: bool):
    b, s, width = o.shape
    bs, bc = blocks
    rows = pl.BlockSpec((1, bs, bc), lambda c, b, s: (b, s, c))
    do, dgate, dscale = pl.pallas_call(
        functools.partial(_ogate_bwd_kernel,
                          chunk=_row_chunk(bs, _GATE_CHUNK), head=head,
                          eps=eps, seq_len=s, gated=gated),
        grid=(width // bc, b, pl.cdiv(s, bs)),
        in_specs=[rows, rows, pl.BlockSpec((1, head), lambda c, b, s: (0, 0)),
                  rows],
        out_specs=[rows, rows, pl.BlockSpec((1, bc), lambda c, b, s: (0, c))],
        out_shape=[
            jax.ShapeDtypeStruct(o.shape, o.dtype),
            jax.ShapeDtypeStruct(gate.shape, gate.dtype),
            jax.ShapeDtypeStruct((1, width), jnp.float32),
        ],
        interpret=interpret, name="kda_ogate_bwd",
    )(o, gate, scale.reshape(1, head), dy)
    return do, dgate, jnp.sum(
        dscale.reshape(width // head, head), axis=0).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ogate(o, gate, scale, head, eps, gated, blocks, interpret):
    return _ogate_forward(o, gate, scale, head, eps, gated, blocks, interpret)


def _ogate_fwd_rule(o, gate, scale, head, eps, gated, blocks, interpret):
    return (_ogate_forward(o, gate, scale, head, eps, gated, blocks,
                           interpret), (o, gate, scale))


def _ogate_bwd_rule(head, eps, gated, blocks, interpret, residuals, dy):
    return _ogate_backward(*residuals, dy, head, eps, gated, blocks,
                           interpret)


_ogate.defvjp(_ogate_fwd_rule, _ogate_bwd_rule)


def kda_ogate(o, gate, scale, eps: float, gated):
    """What stands between a delta-rule mixer's scan and its output
    projection (the module's docstring): ``o, gate [B, S, H·D]``, ``scale
    [D]`` (the one weight every head shares: ITS width is a head's) and
    ``gated`` the caller's ``(o, scale, gate, eps) -> y`` of a head (jnp
    code over the last axis of ``[rows, D]`` f32 blocks, ``scale`` as
    ``[1, D]``: the norm and the gate's activation are the caller's) ->
    ``y [B, S, H·D]`` in ``o``'s dtype, differentiable in ``o``,
    ``gate`` and ``scale``. On the TPU ``H·D`` must be whole blocks of
    ``lcm(D, 128)`` lanes (a 128-wide head is one lane tile:
    ``models/qwen3_next.py``'s, with ``models/olmo_hybrid.py``'s body)."""
    width, head = o.shape[-1], scale.shape[-1]
    if (o.ndim != 3 or o.shape != gate.shape or scale.ndim != 1
            or width % head):
        raise ValueError(
            f"kda_ogate: o{tuple(o.shape)} gate{tuple(gate.shape)} "
            f"scale{tuple(scale.shape)} do not fit")
    interpret = _interpret()
    if not interpret and width % _whole_tiles(head):
        raise ValueError(
            f"kda_ogate: {width} channels in heads of {head} are no whole "
            f"blocks of {_whole_tiles(head)} lanes (whole heads and whole "
            f"{_LANES}-lane tiles)")
    bc = _lane_block(width, head)
    return _ogate(o, gate, scale, head, float(eps), gated,
                  (_row_block(o.shape[1], bc), bc), interpret)


# ------------------------------- around the attention call: the rotation
def rotary_tables(freqs, seq_len: int, head_dim: int, factor: float = 1.0):
    """``(cos, sin)``, each ``[seq_len, head_dim]`` f32, for
    :func:`rotary`: the table of ``models/common.py::rotary`` — the same
    expressions, so the same values — laid out a LANE of the head. With
    ``r = len(freqs)``: lanes ``i`` and ``r + i`` hold ``cos(t·f_i)``,
    lane ``i`` holds ``−sin(t·f_i)`` and lane ``r + i`` ``+sin(t·f_i)``
    (both times ``factor``), the lanes from ``2r`` on hold 1 and 0 (they
    pass). Built once a step, outside the layers."""
    half = freqs.shape[0]
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    rest = (seq_len, head_dim - 2 * half)
    return (jnp.concatenate([cos, cos, jnp.ones(rest, jnp.float32)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros(rest, jnp.float32)],
                            axis=-1))


def _partner(x, half: int):
    """Lane ``i + half`` at lane ``i < half`` and lane ``i − half`` at
    ``half <= i < 2·half`` of a head ``[rows, D]``: one roll where the
    two halves are the head, two and a select where they are its first
    lanes (what stands beyond ``2·half`` meets a zero of the table)."""
    head = x.shape[-1]
    down = pltpu.roll(x, shift=half, axis=1)
    if 2 * half == head:
        return down
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < half,
                     pltpu.roll(x, shift=head - half, axis=1), down)


def _head_lanes(h, head: int):
    """The lanes of head ``h`` of a block of whole heads; ``h`` may be a
    loop's index (Mosaic takes a dynamic lane offset that is whole
    tiles)."""
    return pl.ds(h * head if isinstance(h, int)
                 else pl.multiple_of(h * head, head), head)


def _rotary_kernel(x_ref, cos_ref, sin_ref, o_ref, *, chunk: int, half: int,
                   back: bool):
    """One (sequence block, batch row, channel block of whole heads); the
    table's block stays while the grid runs over batch rows and channel
    blocks. Forward: ``x_ref [1, rows, heads·D]`` -> ``o_ref [1, heads,
    rows, D]``, ``x ⊙ cos + partner(x) ⊙ sin``. ``back``: the transposed
    rotation ``dy ⊙ cos − partner(dy) ⊙ sin`` from ``[1, heads, rows, D]``
    to ``[1, rows, heads·D]``. The heads are a loop that the LOWERING
    unrolls: the body is traced a head long whatever the block holds
    (PERF.md, PR 62: on the chip's host a traced equation costs 1.4 ms,
    once a shape and a process, in ``setup_s``; eight heads written out
    were 0.3 s a body), and Mosaic still sees them in a straight line
    with static offsets (left as a loop they read 3.2 ms a call for
    1.7)."""
    heads = (x_ref if back else o_ref).shape[1]
    bs, head = cos_ref.shape

    def rows(i, carry):
        at = pl.ds(_row0(i, chunk), chunk)
        cos, sin = cos_ref[at, :], sin_ref[at, :]

        def a_head(h, carry):
            lanes = _head_lanes(h, head)
            if back:
                dy = _f32(x_ref[0, h, at, :])
                o_ref[0, at, lanes] = (
                    dy * cos - _partner(dy, half) * sin).astype(o_ref.dtype)
            else:
                x = _f32(x_ref[0, at, lanes])
                o_ref[0, h, at, :] = (
                    x * cos + _partner(x, half) * sin).astype(o_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, heads, a_head, carry, unroll=True)

    _for_chunks(bs // chunk, rows, 0)


def _whole_row_blocks(what: str, seq_len: int, bs: int) -> None:
    if seq_len % bs:
        raise ValueError(
            f"{what}: a sequence of {seq_len} is no whole blocks of {bs} "
            f"rows")


# Jitted as the builders above are: five layers call each of these three
# times a step, and a body is traced once a shape.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _rotary_call(x, cos, sin, half: int, blocks: Tuple[int, int],
                 interpret: bool, back: bool):
    """Forward ``x [B, S, H·D]`` -> ``[B, H, S, D]``; ``back`` the other
    way with the transposed rotation."""
    head = cos.shape[-1]
    bs, bc = blocks
    if back:
        b, heads, s, _ = x.shape
    else:
        b, s, width = x.shape
        heads = width // head
    _whole_row_blocks("rotary", s, bs)
    wide = pl.BlockSpec((1, bs, bc), lambda s, b, c: (b, s, c))
    tall = pl.BlockSpec((1, bc // head, bs, head),
                        lambda s, b, c: (b, c, s, 0))
    table = pl.BlockSpec((bs, head), lambda s, b, c: (s, 0))
    return pl.pallas_call(
        functools.partial(_rotary_kernel, chunk=_row_chunk(bs, _ROTARY_CHUNK),
                          half=half, back=back),
        grid=(s // bs, b, heads * head // bc),
        in_specs=[tall if back else wide, table, table],
        out_specs=wide if back else tall,
        out_shape=jax.ShapeDtypeStruct(
            (b, s, heads * head) if back else (b, heads, s, head), x.dtype),
        interpret=interpret, name="rotary_bwd" if back else "rotary_fwd",
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rotary(x, cos, sin, half, blocks, interpret):
    return _rotary_call(x, cos, sin, half, blocks, interpret, False)


def _rotary_fwd_rule(x, cos, sin, half, blocks, interpret):
    return _rotary_call(x, cos, sin, half, blocks, interpret, False), (
        cos, sin)


def _rotary_bwd_rule(half, blocks, interpret, tables, dy):
    # the table is no function of anything that learns
    return _rotary_call(dy, *tables, half, blocks, interpret, True), None, None


_rotary.defvjp(_rotary_fwd_rule, _rotary_bwd_rule)


def rotary(x, cos, sin, half: int):
    """The ``rotate_half`` rotation of ``models/common.py::rotary`` over
    the first ``2·half`` lanes of every head: ``x [B, S, H·D]`` (as a
    projection writes it) and :func:`rotary_tables`' ``cos, sin [S, D]``
    -> ``[B, H, S, D]`` in ``x``'s dtype, THE HEADS FIRST: the order the
    flash call merges to, so that its ``[B, S, H, D]`` argument is this
    array's transposed view and nothing is copied. Differentiable in
    ``x`` (the transposed rotation, from the same table). Two products
    and a sum a lane in f32, one rounding: the values of the jnp form."""
    if (x.ndim != 3 or cos.ndim != 2 or cos.shape != sin.shape
            or cos.shape[0] != x.shape[1] or x.shape[2] % cos.shape[1]
            or not 0 < 2 * half <= cos.shape[1]):
        raise ValueError(
            f"rotary: x{tuple(x.shape)} cos{tuple(cos.shape)} "
            f"sin{tuple(sin.shape)} turned over {2 * half} lanes do not fit")
    interpret = _interpret()
    head = cos.shape[1]
    _refuse_lanes("rotary: a head's", head, interpret)
    bc = _lane_block(x.shape[2], head, _ROTARY_LANES)
    TRACED.incr("rotary_kernel_calls")
    return _rotary(x, cos, sin, half,
                   (_row_block(x.shape[1], bc, _ATTN_BLOCK_ELEMS), bc),
                   interpret)


# ------------------------------ behind the attention call: a gate a head
def _head_groups(heads: int) -> int:
    """Heads a trip of the gate's loop: up to ``_GATE_GROUP`` that divide
    the heads."""
    return max(n for n in range(1, _GATE_GROUP + 1) if heads % n == 0)


def _gate_group(g, j, group: int, head: int):
    """Trip ``j`` of the gate's loop over groups of ``group`` heads: the
    gate's lanes rolled so that the group's columns are lanes ``0 ..
    group − 1`` (a dynamic lane offset into a VALUE is a rotate by a
    dynamic amount: one a group), the group's first head, and the lanes
    of its ``k``-th head in a ``[rows, H·D]`` block (a dynamic offset
    that is whole tiles, then a static one)."""
    lanes = g.shape[1]
    first = j * group

    def at(k):
        return pl.ds(pl.multiple_of(first * head, group * head) + k * head,
                     head)

    return pltpu.roll(g, shift=(lanes - first) % lanes, axis=1), first, at


def _hgate_fwd_kernel(o_ref, g_ref, y_ref, *, chunk: int):
    """One (batch row, sequence block) of ALL the heads: ``o_ref [1, H,
    rows, D]`` as the flash call leaves it, ``g_ref [1, rows, lanes >=
    H]`` f32 -> ``y_ref [1, rows, H·D]``. The heads go by in a loop of
    groups, a group written out: the body is traced a group long (64
    heads written out were 0.3 – 0.5 s a body forward and 1.0 – 1.2
    backward on the chip's host, once a shape, in ``setup_s``; a loop a
    HEAD long read 5.8 ms a call for 1.7: PERF.md, PR 62)."""
    _, heads, bs, head = o_ref.shape
    group = _head_groups(heads)

    def rows(i, carry):
        rows_at = pl.ds(_row0(i, chunk), chunk)
        g = g_ref[0, rows_at, :]

        def a_group(j, carry):
            gj, first, at = _gate_group(g, j, group, head)
            for k in range(group):
                y_ref[0, rows_at, at(k)] = (
                    _f32(o_ref[0, first + k, rows_at, :]) * gj[:, k:k + 1]
                ).astype(y_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, heads // group, a_group, carry)

    _for_chunks(bs // chunk, rows, 0)


def _hgate_bwd_kernel(o_ref, g_ref, dy_ref, do_ref, dg_ref, *, chunk: int):
    """``do = dy · γ`` (heads first, as the flash call's backward takes
    it) and ``dγ = Σ_D dy ⊙ o`` in f32, from one read of ``dy`` and
    ``o``; a head's sum lands in its lane of a block-wide value that is
    stored once."""
    _, heads, bs, head = o_ref.shape
    group = _head_groups(heads)

    def rows(i, carry):
        rows_at = pl.ds(_row0(i, chunk), chunk)
        g = g_ref[0, rows_at, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)

        def a_group(j, dg):
            gj, first, at = _gate_group(g, j, group, head)
            for k in range(group):
                dy = _f32(dy_ref[0, rows_at, at(k)])
                do_ref[0, first + k, rows_at, :] = (
                    dy * gj[:, k:k + 1]).astype(do_ref.dtype)
                dg = jnp.where(lane == first + k, jnp.sum(
                    dy * _f32(o_ref[0, first + k, rows_at, :]), axis=-1,
                    keepdims=True), dg)
            return dg

        dg_ref[0, rows_at, :] = jax.lax.fori_loop(
            0, heads // group, a_group, jnp.zeros(g.shape, jnp.float32))
        return carry

    _for_chunks(bs // chunk, rows, 0)


def _gate_lanes(gate):
    """``gate [B, S, H]`` with zeros up to whole lane tiles on the TPU (a
    value's lanes are rolled: :func:`_gate_group`); 16 MB at the cell's."""
    short = -gate.shape[2] % _LANES
    return jnp.pad(gate, ((0, 0), (0, 0), (0, short))) if short else gate


@functools.partial(jax.jit, static_argnums=(2, 3))
def _hgate_forward(o, gate, bs: int, interpret: bool):
    b, heads, s, head = o.shape
    _whole_row_blocks("gate_heads", s, bs)
    if not interpret:
        gate = _gate_lanes(gate)
    return pl.pallas_call(
        functools.partial(_hgate_fwd_kernel,
                          chunk=_row_chunk(bs, _GATE_CHUNK)),
        grid=(b, s // bs),
        in_specs=[pl.BlockSpec((1, heads, bs, head),
                               lambda b, s: (b, 0, s, 0)),
                  pl.BlockSpec((1, bs, gate.shape[2]),
                               lambda b, s: (b, s, 0))],
        out_specs=pl.BlockSpec((1, bs, heads * head), lambda b, s: (b, s, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, heads * head), o.dtype),
        interpret=interpret, name="head_gate_fwd",
    )(o, gate)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _hgate_backward(o, gate, dy, bs: int, interpret: bool):
    b, heads, s, head = o.shape
    if not interpret:
        gate = _gate_lanes(gate)
    tall = pl.BlockSpec((1, heads, bs, head), lambda b, s: (b, 0, s, 0))
    a_head = pl.BlockSpec((1, bs, gate.shape[2]), lambda b, s: (b, s, 0))
    do, dgate = pl.pallas_call(
        functools.partial(_hgate_bwd_kernel,
                          chunk=_row_chunk(bs, _GATE_CHUNK)),
        grid=(b, s // bs),
        in_specs=[tall, a_head,
                  pl.BlockSpec((1, bs, heads * head), lambda b, s: (b, s, 0))],
        out_specs=[tall, a_head],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(gate.shape, gate.dtype)],
        interpret=interpret, name="head_gate_bwd",
    )(o, gate, dy)
    return do, dgate[..., :heads]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _hgate(o, gate, bs, interpret):
    return _hgate_forward(o, gate, bs, interpret)


def _hgate_fwd_rule(o, gate, bs, interpret):
    return _hgate_forward(o, gate, bs, interpret), (o, gate)


def _hgate_bwd_rule(bs, interpret, residuals, dy):
    return _hgate_backward(*residuals, dy, bs, interpret)


_hgate.defvjp(_hgate_fwd_rule, _hgate_bwd_rule)


def gate_heads(o, gate):
    """A gate a head a position on the attention's output: ``o [B, H, S,
    D]`` — THE HEADS FIRST, the flash call's own order: its ``[B, S, H,
    D]`` result's transposed view, so nothing is copied — and ``gate [B,
    S, H]`` f32 -> ``[B, S, H·D]`` in ``o``'s dtype, as the output
    projection reads it: ``(o · gate)`` with one f32 multiply and one
    rounding, the values of the jnp form. Differentiable in both: ``do =
    dy · gate`` likewise and ``dgate = Σ_D dy ⊙ o`` in f32. What the
    gate IS stays the caller's (``models/laguna.py::head_gate``)."""
    if (o.ndim != 4 or gate.shape != (o.shape[0], o.shape[2], o.shape[1])
            or gate.dtype != jnp.float32):
        raise ValueError(
            f"gate_heads: o{tuple(o.shape)} and a {gate.dtype} "
            f"gate{tuple(gate.shape)} do not fit")
    interpret = _interpret()
    _refuse_lanes("gate_heads: a head's", o.shape[3], interpret)
    TRACED.incr("head_gate_kernel_calls")
    return _hgate(o, gate, _row_block(o.shape[2], o.shape[1] * o.shape[3],
                                      _ATTN_BLOCK_ELEMS), interpret)
