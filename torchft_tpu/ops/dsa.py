"""Attention whose keys are chosen by the data (DeepSeek sparse
attention, as ``models/keye.py`` runs it): an INDEXER scores every causal
pair, each query keeps its ``topk`` best keys, attention runs over those
keys alone, and the indexer learns from the attention it selected for
(a KL term). Three calls, all on head-major arrays (``q [B, H, S, D]``,
``k``, ``v [B, KV, S, D]``, ``H % KV == 0``, query head ``i`` reads
key/value head ``i // (H / KV)`` where it lies; the indexer's ``qi [B,
HI, S, DI]`` on ONE key head ``ki [B, S, DI]``, weights ``w [B, S, HI]``
f32):

    sel, lse_i = select(qi, ki, w, topk)
    o, lse     = attend(q, k, v, sel)
    kl         = index_kl(q, k, lse, qi, ki, w, sel, lse_i)

``select``: ``I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])`` for ``s
<= t`` (f32 from the operands' products, the relu, the weighting and the
sum over heads f32), ``S_t`` = the ``min(t + 1, topk)`` largest of row
``t``, TIES TO THE LOWER ``s``; never differentiated (its arguments are
detached). The ``[S, S]`` scores exist a block of query rows at a time,
in VMEM: the kernel (``dsa_select``) writes a block's row of scores as
SORTABLE integers (an f32's bits, the negative ones flipped, order as
signed integers what the floats' order is), finds the ``K``-th largest
of each row by bisection on those 32 bits — a count of the row's keys at
or above a candidate a bit: a threshold by counting, no sort —, then the
position of the last tie it may keep by a second bisection, and leaves
the row's set PACKED: ``sel [B, S, S / 32]`` int32, bit ``b`` of word
``c`` of row ``t`` is key ``s = b * (S / 32) + c``. So the ``b``-th k
tile of ``S / 32`` keys is bit ``b`` of every word of the row: a kernel
that sweeps k tiles of that width takes tile ``b``'s mask by one shift
and one compare, no lane moves, and the set of a 16 384-token sequence is
33.5 MB (the scores would be 1.07 GB). ``lse_i [B, S]`` is the
log-sum-exp of ``I`` over ``S_t``, what the KL term normalises with.

``attend``: softmax attention over ``S_t``, ``o [B, H, S, D]`` and the
rows' log-sum-exp ``lse [B, H, S]`` (f32, not differentiated).
``dsa_fwd``, ``dsa_dq`` and ``dsa_dkv`` are flash kernels in the streamed
regime of ``ops/flash.py`` (its tile arithmetic, its statistics' rows and
columns, its transposed recompute in dkv, its group-wide dk / dv
accumulators) whose mask is the packed set and nothing else — a key after
the query is in no set, so no iota: every causal TILE is computed (the
work of full causal attention) and a key outside ``S_t`` gives nothing to
``o``, ``dq``, ``dk``, ``dv``. A grid step of each holds MORE THAN ONE
tile, and meets what it holds in straight-line code (``_straight``: as
``flash._flash_streamed_kernel`` does — inside a group Mosaic schedules a
tile's matmuls beside its neighbour's pointwise work, which neither a grid
step nor a loop trip lets it do), the count a pure function of the shape
(the longest rung of a ladder whose VMEM estimate fits ``_PARAMS``' limit;
1, the one-tile body, where a tile is narrower than a lane tile: the sizes
the interpreter runs) that a ``TRACED`` gauge reports. ``dsa_fwd`` and
``dsa_dq`` (row sweeps) take a CHUNK of consecutive k tiles
(``_choose_chunk``, ``_choose_backward``: all 32, the whole row of keys, at
the cell's 16 384; ``TRACED["dsa_fwd_chunk_tiles"]``,
``["dsa_dq_chunk_tiles"]``) and sweep the chunk's live tiles in ascending
k, a PAIR of tiles a matmul under one concatenated mask; the forward
updates its softmax statistics once a pair (f32, as every sum of the
kernels; ``lse`` is the value the three other kernels read): 83.6 -> 35.4
ms a call (PR 69); ``dsa_dq`` has no statistics to update and gains by the
chunk alone: 56.9 -> 37.5 (PR 72). ``dsa_dkv`` (a column sweep) takes two k
tiles, one matmul wide, against a chunk of consecutive q blocks
(``["dsa_dkv_chunk_tiles"]``: their product, 2 x 8 there), the group's
heads innermost: 74.9 -> 56.5. A tile above the diagonal that a step holds
beside a live one is computed under its all-false mask (3 % of the tiles at
two k tiles a step); steps that hold no live tile compute and fetch
nothing.

``index_kl``: ``sum_t KL(pbar[t] || softmax_{S_t}(I[t]))`` with ``pbar[t,
s] = mean_h P[t, h, s]`` (from ``q``, ``k``, ``lse``, all detached).
``dsa_kl`` recomputes every head's ``P`` tile and the indexer's scores
tile by tile, one tile a grid step, its 32 + 16 + 16 heads in LOOPS of
straight-line groups (``_choose_kl``: 16 heads a group at the cell;
``TRACED["dsa_kl_group_heads"]``): 65.7 -> 42.1 ms a call (PR 72; two k
tiles a step gained nothing there). DIFFERENTIATED it still runs once: the forward rule's call
carries ``dI = softmax - pbar`` on to ``dqi``, ``dw`` (accumulated over a
row's tiles) and ``dki`` (one partial a query block, summed outside) in
the sweep that makes the value — every operand exists where the loss is
evaluated, the backward pass brings one scalar — and the three cross to
the backward rule, which scales them (``TRACED["dsa_kl_grad_calls"]``
counts those calls as traced). An evaluation asks for no rule and runs
the kernel without them.

Off the TPU each call is its plain ``jnp`` form over whole ``[S, S]``
arrays (the CPU tests' sizes), differentiated by ``jax``; ``interpret=
True`` runs the kernels in the interpreter instead.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.flash import (
    _LANES,
    _NEG_INF,
    _f32,
    _rows_to_cols,
    _wide_to_row,
)
from torchft_tpu.utils.metrics import TRACED

__all__ = ["WORD", "KEY_CHOICE", "select", "attend", "index_kl", "pack",
           "unpack", "index_scores"]

WORD = 32                       # keys a word of the packed set holds
# the name under which a choice of keys, and the attention over it, cross
# a layer's ``jax.checkpoint`` (``models/common.py::checkpoint_layer``):
# ``select``'s two results, and what ``attend``'s backward reads of its
# forward (``_attend_fwd``: the output and the rows' log-sum-exp), so that
# a layer run again does not run ``dsa_fwd`` again — it computes every
# causal tile to attend to a quarter of the pairs, and at that price a
# second forward a step (83 ms a layer at 2 x 16 384 on the v5e, PERF.md,
# PR 66; 35 since PR 69) costs more than 272 MB a layer; and
# ``index_kl``'s gradients
# (``_kl_fwd``: ``dqi``, ``dki``, ``dw``, 73 MB a layer there), so that
# neither the layer run again nor the backward pass runs ``dsa_kl`` — kept
# only where the backward pass reads THEM: ``models/keye.py`` pulls them
# back to the indexer's parameters in the forward pass and keeps those
KEY_CHOICE = "key_choice"
_INDEX_ROWS = 128               # query rows a step of ``dsa_select``
_ATTEND_ROWS = 512              # of the attention kernels and ``dsa_kl``
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)
_NT = (((1,), (1,)), ((), ()))  # a . b^T
_NN = (((1,), (0,)), ((), ()))  # a . b
_TN = (((0,), (0,)), ((), ()))  # a^T . b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _relu(s):
    """The indexer's activation; ``_index_heads`` of every form calls it
    by this name (a seam: ``benchmark/tests/keye_faults.py`` puts the
    identity here, and clears jax's caches: the calls are jitted). Its
    slope AT zero is zero, in every form (``jnp.maximum`` would halve it
    there)."""
    return jnp.where(s > 0.0, s, 0.0)


def _use_kernels(interpret: Optional[bool]) -> Tuple[bool, bool]:
    """``(kernels, interpret)``: the kernels on a TPU, or in the
    interpreter where the caller asks for that; else the ``jnp`` form."""
    if interpret:
        return True, True
    return jax.default_backend() == "tpu", False


def _rows(seq_len: int, want: int, block_q: Optional[int]) -> int:
    rows = min(want, seq_len) if block_q is None else block_q
    if seq_len % rows:
        raise ValueError(f"dsa: {seq_len} positions are no multiple of "
                         f"the {rows} query rows a step takes")
    return rows


def _width(seq_len: int) -> int:
    if seq_len % WORD:
        raise ValueError(f"dsa: {seq_len} positions are no multiple of "
                         f"the {WORD} keys a word of the set holds")
    return seq_len // WORD


# ------------------------------------------------------- the packed set
def pack(keep):
    """``[.., S, S]`` bool -> ``[.., S, S / 32]`` int32 (module
    docstring: bit ``b`` of word ``c`` is key ``b * (S / 32) + c``)."""
    *lead, s = keep.shape
    bits = keep.reshape(*lead, WORD, s // WORD).astype(jnp.uint32)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)[:, None]
    return jax.lax.bitcast_convert_type(
        jnp.sum(bits << shifts, axis=-2, dtype=jnp.uint32), jnp.int32)


def unpack(sel):
    """:func:`pack` back: ``[.., S, S / 32]`` int32 -> ``[.., S, S]``
    bool."""
    *lead, w = sel.shape
    shifts = jnp.arange(WORD, dtype=jnp.int32)[:, None]
    return (((sel[..., None, :] >> shifts) & 1) != 0).reshape(*lead, WORD * w)


def _bit(words, tile):
    """K tile ``tile``'s mask ``[rows, S / 32]`` of a block of words."""
    return ((words >> tile) & 1) != 0


# --------------------------------------------------------- the jnp forms
def index_scores(qi, ki, w):
    """``I [B, S, S]`` f32, whole (the small sizes' form)."""
    s = jnp.einsum("bhtd,bsd->bhts", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bhts,bth->bts", _relu(s), w.astype(jnp.float32))


def _dense_select(scores, topk: int):
    seq_len = scores.shape[-1]
    t = jnp.arange(seq_len)
    causal = t[None, :] <= t[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    kr = jnp.minimum(t + 1, topk)
    kth = jnp.take_along_axis(-jnp.sort(-scores, axis=-1),
                              (kr - 1)[None, :, None], axis=-1)
    above, ties = scores > kth, (scores == kth) & causal
    need = kr[None, :, None] - jnp.sum(above, axis=-1, keepdims=True)
    keep = above | (ties & (jnp.cumsum(ties, axis=-1) <= need))
    top = jnp.max(scores, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(
        jnp.where(keep, jnp.exp(scores - top), 0.0), axis=-1))
    return pack(keep), lse


def _grouped_scores(q, k, scale: float):
    b, h, s, d = q.shape
    kv = k.shape[1]
    return jnp.einsum("bngtd,bnsd->bngts",
                      q.astype(jnp.float32).reshape(b, kv, h // kv, s, d),
                      k.astype(jnp.float32)) * scale


def _dense_attend(q, k, v, sel, scale: float):
    b, h, s, d = q.shape
    scores = jnp.where(unpack(sel)[:, None, None],
                       _grouped_scores(q, k, scale), -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    o = jnp.einsum("bngts,bnsd->bngtd", jnp.exp(scores - lse[..., None]),
                   v.astype(jnp.float32))
    return (o.reshape(b, h, s, v.shape[-1]).astype(q.dtype),
            jax.lax.stop_gradient(lse.reshape(b, h, s)))


def _dense_kl(q, k, lse, qi, ki, w, sel, scale: float):
    b, h, s, _ = q.shape
    keep = unpack(sel)
    p = jnp.exp(jnp.where(keep[:, None, None], _grouped_scores(q, k, scale),
                          -jnp.inf)
                - lse.reshape(b, k.shape[1], -1, s)[..., None])
    pbar = jax.lax.stop_gradient(jnp.mean(p, axis=(1, 2)))
    scores = jnp.where(keep, index_scores(qi, ki, w), -jnp.inf)
    log_q = scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)
    on = keep & (pbar > 0)
    return jnp.sum(jnp.where(
        on, pbar * (jnp.log(jnp.where(on, pbar, 1.0))
                    - jnp.where(on, log_q, 0.0)), 0.0))


# ------------------------------------------------------------ dsa_select
_SIGN = -2 ** 31


def _sortable(x):
    """An f32's bits as an int32 whose signed order is the floats'."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _unsortable(key):
    return jax.lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key), jnp.float32)


def _index_kernel(qi_ref, ki_ref, w_ref, sel_ref, lse_ref, keys, *,
                  topk: int, block_q: int, width: int, seq_len: int):
    """One block of query rows: their scores against every key at or
    before the block's last row into ``keys [rows, S]`` (sortable ints,
    -inf after a row's own position), the rows' thresholds, the packed
    set and its log-sum-exp."""
    i = pl.program_id(1)
    heads = qi_ref.shape[1]
    live = ((i + 1) * block_q - 1) // width + 1   # chunks with a causal key
    slab = min(width, _LANES)
    row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    w = w_ref[0]

    def chunk(c, size=width):
        return pl.ds(pl.multiple_of(c * size, size), size)

    def score(c, top):
        kc = ki_ref[0, chunk(c), :]
        acc = jnp.zeros((block_q, width), jnp.float32)   # + 0.0: no -0.0
        for j in range(heads):
            acc = acc + w[:, j:j + 1] * _relu(_dot(qi_ref[0, j], kc, _NT))
        pos = c * width + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        acc = jnp.where(pos <= row, acc, -jnp.inf)
        keys[:, chunk(c)] = _sortable(acc)
        return jnp.maximum(top, jnp.max(acc, axis=1, keepdims=True))

    top = jax.lax.fori_loop(0, live, score,
                            jnp.full((block_q, 1), -jnp.inf, jnp.float32))

    def count(pred):
        """How many of a row's keys ``pred(keys, positions)`` holds for,
        ``[rows, 1]`` f32 (exact: at most ``S``)."""
        def body(u, acc):
            pos = u * slab + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, slab), 1)
            return acc + jnp.where(pred(keys[:, chunk(u, slab)], pos),
                                   1.0, 0.0)
        acc = jax.lax.fori_loop(0, live * (width // slab), body,
                                jnp.zeros((block_q, slab), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    kr = jnp.minimum(row + 1, topk).astype(jnp.float32)

    def value_bit(n, found):
        cand = found | jnp.left_shift(jnp.int32(1), 31 - n)
        signed = cand ^ jnp.int32(_SIGN)
        at_or_above = count(lambda kc, pos: kc >= signed)
        return jnp.where(at_or_above >= kr, cand, found)

    # the K-th largest key of each row: the largest value with K keys at
    # or above it, built from its top bit down in the unsigned order
    thr = jax.lax.fori_loop(
        0, 32, value_bit, jnp.zeros((block_q, 1), jnp.int32)
    ) ^ jnp.int32(_SIGN)
    need = kr - count(lambda kc, pos: kc > thr)      # ties to keep: >= 1

    bits = max(1, (seq_len - 1).bit_length())

    def position_bit(n, found):
        cand = found | jnp.left_shift(jnp.int32(1), bits - 1 - n)
        before = count(lambda kc, pos: (kc == thr) & (pos < cand))
        return jnp.where(before < need, cand, found)

    # the position of the last tie kept: the largest with fewer than
    # ``need`` ties before it
    cut = jax.lax.fori_loop(0, bits, position_bit,
                            jnp.zeros((block_q, 1), jnp.int32))

    def emit(c, carry):
        words, total = carry
        kc = keys[:, chunk(c)]
        pos = c * width + jax.lax.broadcasted_iota(jnp.int32, kc.shape, 1)
        on = (kc > thr) | ((kc == thr) & (pos <= cut))
        return (words | jnp.left_shift(on.astype(jnp.int32), c),
                total + jnp.where(on, jnp.exp(_unsortable(kc) - top), 0.0))

    words, total = jax.lax.fori_loop(
        0, live, emit, (jnp.zeros((block_q, width), jnp.int32),
                        jnp.zeros((block_q, width), jnp.float32)))
    sel_ref[0] = words
    lse_ref[0] = _wide_to_row(jnp.broadcast_to(
        top + jnp.log(jnp.sum(total, axis=1, keepdims=True)),
        (block_q, _LANES)))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _index_call(qi, ki, w, topk: int, block_q: int, interpret: bool):
    b, heads, seq_len, d = qi.shape
    width = _width(seq_len)
    sel, lse = pl.pallas_call(
        functools.partial(_index_kernel, topk=topk, block_q=block_q,
                          width=width, seq_len=seq_len),
        grid=(b, seq_len // block_q),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, d), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, heads), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=(jax.ShapeDtypeStruct((b, seq_len, width), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, seq_len), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((block_q, seq_len), jnp.int32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="dsa_select",
    )(qi, ki, w)
    return sel, lse[:, 0]


def select(qi, ki, w, topk: int, *, block_q: Optional[int] = None,
           interpret: Optional[bool] = None):
    """``(sel [B, S, S / 32] int32, lse_i [B, S] f32)``: module
    docstring. Constants to ``jax.grad``."""
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    seq_len = qi.shape[2]
    _width(seq_len)
    kernels, interpret = _use_kernels(interpret)
    TRACED.incr("dsa_calls")
    if not kernels:
        chose = _dense_select(index_scores(qi, ki, w), topk)
    else:
        chose = _index_call(qi, ki, w.astype(jnp.float32), int(topk),
                            _rows(seq_len, _INDEX_ROWS, block_q), interpret)
    return tuple(checkpoint_name(a, KEY_CHOICE) for a in chose)


# ------------------------------------------- attention over the chosen keys
def _last_k(qi, block_q: int, width: int):
    """The last k tile that holds a key at or before q block ``qi``'s
    last row."""
    return ((qi + 1) * block_q - 1) // width


def _first_q(ki, block_q: int, width: int):
    """The first q block with a row at or after k tile ``ki``'s first
    key."""
    return (ki * width) // block_q


# K tiles a grid step of ``dsa_fwd`` and ``dsa_dq`` (a tile is a bit of the
# packed word: ``S / 32`` keys), the longest first, every rung a divisor of
# the 32; the straight-line groups a step's tiles are met in
# (``ops/flash.py``'s, PR 60: inside a group Mosaic schedules an update's
# matmuls beside its neighbour's pointwise work, which neither a grid step
# nor a loop trip lets it do); and the tiles ONE matmul takes side by side
# (in the forward one update of the softmax statistics: one max-reduce,
# one alpha, one rescale of acc: 1 024 keys at 16k, that file's streamed
# tile; a lone tile updates alone). On the v5e (``scripts/dsa_micro.py``;
# ``[64 | 8, 16384, 128]``, 2 048 keys a query, ms a call at 1 / 2 / 4 / 8 /
# 16 / 32 tiles a step; one tile a step is the body the kernels had, bit
# for bit):
#   dsa_fwd, an update a tile  83.6  75.2  71.2  69.9  65.9  62.7  (PR 69)
#   dsa_fwd, an update a pair  83.6  49.9  43.9  42.7  39.2  35.4
#   dsa_dq, a tile a matmul    56.9  51.5  47.3  45.6  41.7  37.5  (PR 72)
#   dsa_dq, a pair a matmul    56.9  51.5  47.3  45.7  41.7  37.5
# At 32 the k axis is one step: no dead steps (31 744 of dq's 65 536 were),
# and K and V of a key head are fetched once for all its query heads'
# rows. The forward at four tiles an update read 34.7, groups of (8, 4, 2,
# 1) 33.7 - 34.2 for twice the body; groups of (2, 1) 39.0. ``dsa_dq`` has
# no statistics, and the pair's width is worth nothing to it: at 37.5 ms
# it runs its three matmuls a tile at 92 % of the MXU's peak.
_CHUNK_LADDER = (32, 16, 8, 4, 2, 1)
_STRAIGHT = (4, 2, 1)
_SPAN = 2


def _forward_vmem_estimate(head_dim: int, v_dim: int, itemsize: int,
                           block_q: int, width: int, chunk: int) -> int:
    """Bytes ``dsa_fwd`` keeps in VMEM at ``chunk`` k tiles a grid step:
    its pipelined operands twice (K and V of the chunk, q, the block of
    packed words, o and the lse row), the three scratch accumulators, the
    f32 copy of q and, for every tile of the longest straight-line group,
    S and P. Held against the compiler for a described v5e (PR 69: the
    least ``vmem_limit_bytes`` at which the kernel lowers at the cell's
    ``[64 | 8, 16384, 128]`` bf16, 512 rows; MiB, estimate -> allocation):
    1 tile 6.0 -> 4.69, 2 tiles 8.5 -> 6.91, 4 tiles 13.5 -> 8.88, 8 tiles
    15.5 -> 10.84, 16 tiles 19.5 -> 14.78, 32 tiles 27.5 -> 22.90 (an
    update a tile allocates 7.40 / 11.83 / 13.80 / 17.73 / 25.61 from 2
    tiles on): over at every rung, by 1.3 - 4.7."""
    pair = head_dim + v_dim
    operands = ((chunk * width + block_q) * pair * itemsize
                + block_q * width * 4 + block_q * 4)
    scratch = block_q * (v_dim + 2 * _LANES) * 4
    straight = min(chunk, _STRAIGHT[0])
    return (2 * operands + scratch + block_q * head_dim * 4
            + straight * 2 * block_q * width * 4)


def _fits(estimate: int) -> bool:
    return estimate <= _PARAMS.vmem_limit_bytes


def _choose_chunk(seq_len: int, head_dim: int, v_dim: int, itemsize: int,
                  block_q: int) -> int:
    """K tiles a grid step of ``dsa_fwd`` sweeps, a pure function of the
    shape: 1 where a tile is narrower than a lane tile (its slice of a
    chunk's K and V and its scores' lanes would be no whole tiles of the
    chip's layout: the sizes the interpreter runs), else the longest rung
    of ``_CHUNK_LADDER`` whose :func:`_forward_vmem_estimate` fits
    ``_PARAMS``' limit."""
    width = _width(seq_len)
    if width % _LANES:
        return 1
    return next(n for n in _CHUNK_LADDER if n == 1 or _fits(
        _forward_vmem_estimate(head_dim, v_dim, itemsize, block_q, width, n)))


def _backward_vmem_estimate(head_dim: int, v_dim: int, itemsize: int,
                            block_q: int, width: int, chunk: int) -> int:
    """Bytes ``dsa_dq`` keeps in VMEM at ``chunk`` k tiles a grid step:
    ``_forward_vmem_estimate``'s operands with dO beside q, dq for o, the
    two statistics' rows for the one, a scratch accumulator and the
    statistics' columns, the f32 copies of q and dO and, for every tile of
    the longest straight-line group, S, P and dS. Held against the compiler
    for a described v5e as that estimate is (PR 72, the cell's ``[64 | 8,
    16384, 128]`` bf16, 512 rows; MiB, estimate -> allocation): 1 tile 7.3
    -> 5.5, 2 tiles 10.8 -> 8.25, 4 tiles 17.8 -> 9.5, 8 tiles 19.8 -> 11.5,
    16 tiles 23.8 -> 15.5, 32 tiles 31.8 -> 23.5: over at every rung, by 1.8
    - 8.3."""
    pair = head_dim + v_dim
    operands = ((chunk * width + block_q) * pair * itemsize
                + block_q * head_dim * itemsize
                + block_q * width * 4 + 2 * block_q * 4)
    scratch = block_q * (head_dim + _LANES) * 4
    straight = min(chunk, _STRAIGHT[0])
    return (2 * operands + scratch + block_q * pair * 4
            + straight * 3 * block_q * width * 4)


def _column_vmem_estimate(head_dim: int, v_dim: int, itemsize: int,
                          block_q: int, width: int, tiles: int,
                          chunk: int) -> int:
    """Bytes ``dsa_dkv`` keeps in VMEM at ``tiles`` k tiles against a
    ``chunk`` of q blocks a grid step: its pipelined operands twice (K, V,
    dk and dv of the tiles; q, dO, the statistics' rows and the packed
    words of the chunk), the two accumulators and the turned mask, the f32
    copies of K and V and of a span's q and dO and, for every q block of
    the longest straight-line group, two of S^T, P^T and dS^T (the compiler
    reuses the third's room). Held against the compiler for a described
    v5e (PR 72, the cell's shape; MiB, estimate -> allocation, k tiles x q
    blocks): 1 x 1 8.0 -> 6.75, 2 x 1 13.0 -> 11.25, 2 x 2 22.0 -> 20.0, 2 x 4
    39.0 -> 30.0, 2 x 8 57.1 -> 48.0 (1 x 8 39.1 -> 34.0, 4 x 4 67.0 -> 50.0):
    over at every rung, by 1.3 - 9.1."""
    pair = head_dim + v_dim
    keys, rows = tiles * width, chunk * block_q
    operands = ((2 * keys + rows) * pair * itemsize
                + rows * width * 4 + 2 * rows * 4)
    scratch = keys * pair * 4 + keys * rows * 4
    straight = min(chunk, _STRAIGHT[0])
    return (2 * operands + scratch
            + (keys + min(chunk, _SPAN) * block_q) * pair * 4
            + straight * 2 * keys * block_q * 4)


def _kl_vmem_estimate(heads: int, kv_heads: int, head_dim: int,
                      index_heads: int, index_dim: int, itemsize: int,
                      block_q: int, width: int, straight: int) -> int:
    """Bytes ``dsa_kl`` (with its gradients) keeps in VMEM at ``straight``
    heads a group: its pipelined operands twice (every head's q and
    statistics, the key heads' K and the indexer's of the tile, the words;
    dqi, dw and the tile's dki), the scratch accumulators, and tiles of
    ``[S / 32, rows]`` f32: pbar, the indexer's scores, dI, the mask, a
    head's S and P and what the compiler keeps beside them — thirteen, and
    one more for every four heads of a group. Held against the compiler for
    a described v5e (PR 72, the cell's ``[2, 32 | 4, 16384, 128]`` and 16 x
    64 bf16, 512 rows; MiB, estimate -> allocation): 1 head 30.7 -> 29.75, 4
    heads 31.7 -> 30.0, 8 heads 32.7 -> 30.0, 16 heads 34.7 -> 31.25: over at
    every rung, by 0.9 - 3.4."""
    operands = (heads * block_q * (head_dim * itemsize + 4)
                + kv_heads * width * head_dim * itemsize
                + 2 * index_heads * block_q * (index_dim * itemsize + 4)
                + width * index_dim * (itemsize + 4)
                + block_q * width * 4 + 2 * block_q * 4)
    scratch = index_heads * block_q * (index_dim + 1) * 4 + block_q * 4
    return (2 * operands + scratch
            + (13 + straight // 4) * width * block_q * 4)


# ``dsa_dkv``: ``_COLUMN_TILES`` k tiles a grid step, one matmul wide
# (``[2 * S / 32, rows]`` transposed tiles, the mask turned once a q step for
# both bits), against a chunk of consecutive q blocks on ``_COLUMN_LADDER``,
# swept in ``_STRAIGHT``'s groups. What bounds the chunk is VMEM: the packed
# words are the large operand of a column sweep, 1 MiB a q block of 512
# rows at 16k, twice for the pipeline, and the turned mask as much again a
# k tile — 48 MiB of the limit's 64 at 2 x 8. ``dsa_kl``: heads a
# straight-line group on ``_KL_LADDER``; a group of 32 is the 48 heads
# written out that compiled for 15 - 25 s a call site. On the v5e (PR 72,
# ``scripts/dsa_micro.py``, ms a call, the parent 74.9 and 65.7):
#   dsa_dkv, k tiles x q blocks   2x1 62.4  4x1 59.1  8x1 61.7  1x2 68.2
#       1x4 64.9  1x8 63.2  2x2 59.0  4x2 57.5  2x4 57.3  2x8 56.5  4x4 56.5
#       (two q blocks a matmul or one: the same to 0.2)
#   dsa_kl, heads a group         2 54.0  4 48.3  8 45.5  16 42.1
#       (two k tiles a step 63.7 / 53.9 / 49.1 / 47.2 at 1 / 2 / 4 / 8
#       heads, four 66.3: no gain, so a step stays one tile)
_COLUMN_TILES = 2
_COLUMN_LADDER = (8, 4, 2, 1)
_KL_LADDER = (16, 8, 4, 2, 1)


def _choose_backward(seq_len: int, head_dim: int, v_dim: int, itemsize: int,
                     block_q: int) -> Tuple[int, int, int]:
    """``(k tiles a grid step of dsa_dq, k tiles and q blocks a grid step
    of dsa_dkv)``, a pure function of the shape: all 1 where a tile is
    narrower than a lane tile (:func:`_choose_chunk`), else the longest
    rungs whose estimates fit ``_PARAMS``' limit."""
    width = _width(seq_len)
    if width % _LANES:
        return 1, 1, 1
    blocks = seq_len // block_q
    dq = next(n for n in _CHUNK_LADDER if n == 1 or _fits(
        _backward_vmem_estimate(head_dim, v_dim, itemsize, block_q, width,
                                n)))
    tiles, chunk = next(
        ((_COLUMN_TILES, n) for n in _COLUMN_LADDER
         if blocks % n == 0 and _fits(_column_vmem_estimate(
             head_dim, v_dim, itemsize, block_q, width, _COLUMN_TILES, n))),
        (1, 1))
    return dq, tiles, chunk


def _choose_kl(seq_len: int, heads: int, kv_heads: int, head_dim: int,
               index_heads: int, index_dim: int, itemsize: int,
               block_q: int) -> int:
    """Heads a straight-line group of ``dsa_kl``, a pure function of the
    shape: 1 where a tile is narrower than a lane tile (the loop of one
    head the kernel had), else the longest rung of ``_KL_LADDER`` whose
    :func:`_kl_vmem_estimate` fits ``_PARAMS``' limit."""
    width = _width(seq_len)
    if width % _LANES:
        return 1
    return next(n for n in _KL_LADDER if n == 1 or _fits(_kl_vmem_estimate(
        heads, kv_heads, head_dim, index_heads, index_dim, itemsize, block_q,
        width, n)))


def _tiles(ref, first, span: int, width: int, chunk: int):
    """Tiles ``first .. first + span`` (of ``width`` rows each) of the
    ``chunk`` a ref's block holds, f32 as loaded."""
    if chunk == 1:
        return _f32(ref[0])
    return _f32(ref[0, pl.ds(pl.multiple_of(first * width, width),
                             span * width), :])


def _bits(words, tile, span: int, transposed: bool = False):
    """The masks of k tiles ``tile .. tile + span`` of a block of words
    side by side: ``[rows, span * S / 32]`` bool, or ``transposed`` the
    bits themselves as ``[span * S / 32, rows]`` int32 (a kernel that
    computes the tile transposed keeps them in scratch)."""
    if transposed:
        keep = [((words >> (tile + j)) & 1).T for j in range(span)]
    else:
        keep = [_bit(words, tile + j) for j in range(span)]
    return keep[0] if span == 1 else jnp.concatenate(
        keep, axis=0 if transposed else 1)


def _straight(lo, hi, longest: int, update, carried, keep):
    """Tiles ``lo .. hi`` (traced, at most ``longest``) in ascending order
    in straight-line groups: as many groups of ``_STRAIGHT``'s longest
    size as they hold, then of the next; a group is traced as a loop of
    ONE ``update(first tile, span, *carry)`` (over ``_SPAN`` tiles side by
    side) that is laid out whole when it is lowered, the carry a value
    inside a group and through ``keep`` / ``carried`` (the scratch)
    between groups. ``longest == 1``: the one update, no loop."""
    if longest == 1:
        keep(*update(lo, 1, *carried()))
        return

    def group(size, start):
        span = min(size, _SPAN)

        def body(i, _):
            first = start + i * size
            keep(*jax.lax.fori_loop(
                0, size // span,
                lambda j, carry: update(first + j * span, span, *carry),
                carried(), unroll=True))
            return 0
        return body

    done = lo
    for size in _STRAIGHT:
        if size > longest:
            continue
        count = (hi - done) // size
        jax.lax.fori_loop(0, count, group(size, done), 0)
        done = done + count * size


def _fwd_kernel(q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, scale: float, block_q: int, width: int,
                chunk: int):
    """Grid ``(B * H, q blocks, 32 / chunk)``: a step holds ``chunk``
    consecutive k tiles of K and V and sweeps those at or before the q
    block's last row (:func:`_last_k`) in ascending k, in straight-line
    groups of ``_STRAIGHT`` tiles — each traced as a loop of ONE update
    (of ``_SPAN`` tiles) that is laid out whole when it is lowered —,
    ``(acc, m, l)`` carried as values inside a group and through the
    scratch between groups. At one tile a step there is no loop."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    last = _last_k(qi, block_q, width)
    lo = ki * chunk                 # ki counts chunks; the tiles are lo on

    @pl.when(ki == 0)
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

    def _update(q, kt, span, acc, m, l):
        # ``flash._fwd_tile`` over tiles ``kt .. kt + span`` under the
        # set's mask: a row with no chosen key yet accumulates at m =
        # _NEG_INF and its first chosen key clears that (alpha = 0), as
        # under that file's window
        k, v = (_tiles(ref, kt - lo, span, width, chunk)
                for ref in (k_ref, v_ref))
        s = jnp.where(_bits(sel_ref[0], kt, span), _dot(q, k, _NT), _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        return (acc * alpha + _dot(p, v, _NN),
                m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True))

    @pl.when(lo <= last)
    def _chunk():
        _straight(lo, jnp.minimum(last + 1, lo + chunk), chunk,
                  functools.partial(_update, _f32(q_ref[0]) * scale),
                  _carried, _keep)

    @pl.when(ki == last // chunk)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = _wide_to_row(m_ref[...] + jnp.log(l))


def _row_maps(heads: int, group: int, block_q: int, width: int,
              chunk: int = 1):
    """Index maps of a row sweep's grid ``(B * H, q blocks, k steps)``:
    q rows, k rows in blocks of ``chunk`` tiles (clamped to the last live
    one: a dead step fetches nothing), q positions along the lanes, the
    batch row's words."""
    def by_k(bh, i, j):
        return (bh // group,
                jnp.minimum(j, _last_k(i, block_q, width) // chunk), 0)

    return (lambda bh, i, j: (bh, i, 0), by_k,
            lambda bh, i, j: (bh, 0, i),
            lambda bh, i, j: (bh // heads, i, 0))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _forward(q, k, v, sel, heads: int, scale: float, block_q: int,
             chunk: int, interpret: bool):
    """``q [B * H, S, D]``, ``k``, ``v [B * KV, S, D]``, ``sel [B, S, S /
    32]`` -> ``(o [B * H, S, D], lse [B * H, 1, S])``, ``chunk`` k tiles a
    grid step (:func:`_choose_chunk`'s: ``TRACED["dsa_fwd_chunk_tiles"]``
    says what the last call traced took)."""
    bh, seq_len, d = q.shape
    width, dv = _width(seq_len), v.shape[-1]
    TRACED.gauge("dsa_fwd_chunk_tiles", chunk)
    by_q, by_k, q_lanes, words = _row_maps(heads, bh // k.shape[0], block_q,
                                           width, chunk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                          width=width, chunk=chunk),
        grid=(bh, seq_len // block_q, WORD // chunk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), by_q),
            pl.BlockSpec((1, chunk * width, d), by_k),
            pl.BlockSpec((1, chunk * width, dv), by_k),
            pl.BlockSpec((1, block_q, width), words),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), by_q),
            pl.BlockSpec((1, 1, block_q), q_lanes),
        ],
        out_shape=(jax.ShapeDtypeStruct((bh, seq_len, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="dsa_fwd",
    )(q, k, v, sel)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref,
               dq_ref, dq_acc, cols, *, scale: float, block_q: int,
               width: int, chunk: int):
    """``_fwd_kernel``'s grid and sweep: a step holds ``chunk`` k tiles
    and meets the live ones in straight-line groups, each matmul over
    ``_SPAN`` tiles side by side under one concatenated mask (``S``,
    ``P``, ``dS`` ``[rows, span * S / 32]``; ``dS . K`` over the span's
    keys); ``dq_acc`` a value inside a group, the scratch between."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    last = _last_k(qi, block_q, width)
    lo = ki * chunk

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        cols[...] = _rows_to_cols(lse_ref[0], delta_ref[0])

    def _keep(dq):
        dq_acc[...] = dq

    def _update(q, do, kt, span, dq):
        k, v = (_tiles(ref, kt - lo, span, width, chunk)
                for ref in (k_ref, v_ref))
        s = jnp.where(_bits(sel_ref[0], kt, span), _dot(q, k, _NT), _NEG_INF)
        p = jnp.exp(s - cols[:, :1])
        ds = p * (_dot(do, v, _NT) - cols[:, 1:2])
        return (dq + _dot(ds, k, _NN),)

    @pl.when(lo <= last)
    def _chunk():
        _straight(lo, jnp.minimum(last + 1, lo + chunk), chunk,
                  functools.partial(_update, _f32(q_ref[0]) * scale,
                                    _f32(do_ref[0])),
                  lambda: (dq_acc[...],), _keep)

    @pl.when(ki == last // chunk)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, keep_t, *, scale: float,
                block_q: int, width: int, tiles: int, chunk: int):
    """Grid ``(B * KV, k steps, q steps, group)``: a step holds ``tiles``
    consecutive k tiles (one matmul wide) against a ``chunk`` of
    consecutive q blocks, the group's heads innermost, so the tiles' mask
    is turned once a q step (``keep_t``, ``[tiles * S / 32, chunk *
    rows]``: a tile is recomputed TRANSPOSED as in ``flash._dkv_tile``)
    and dk, dv are summed over the group in f32 and rounded once. The
    chunk's q blocks at or after the first tile's first key
    (:func:`_first_q`) are met in ascending q in straight-line groups
    (:func:`_straight`), ``_SPAN`` q blocks side by side a matmul (``dS^T
    . Q`` and ``P^T . dO`` over the span's rows). Of the step's later
    tiles the q blocks before their first key hold no chosen key."""
    ki, qi, g = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first = _first_q(ki * tiles, block_q, width)
    lo = qi * chunk

    @pl.when((qi == first // chunk) & (g == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _keep(dk, dv):
        dk_acc[...] = dk
        dv_acc[...] = dv

    def _update(qb, span, dk, dv):
        # the span's q blocks: rows of q and dO, lanes of the statistics'
        # rows and of the turned mask
        at = slice(None) if chunk == 1 else pl.ds(
            pl.multiple_of((qb - lo) * block_q, block_q), span * block_q)
        q, do = _f32(q_ref[0, at, :]) * scale, _f32(do_ref[0, at, :])
        st = jnp.where(keep_t[:, at] != 0, _dot(_f32(k_ref[0]), q, _NT),
                       _NEG_INF)
        pt = jnp.exp(st - lse_ref[0, :, at])
        dst = pt * (_dot(_f32(v_ref[0]), do, _NT) - delta_ref[0, :, at])
        return dk + _dot(dst, q, _NN), dv + _dot(pt, do, _NN)

    @pl.when(qi >= first // chunk)
    def _tile():
        @pl.when(g == 0)
        def _turn():
            keep_t[...] = _bits(sel_ref[0], ki * tiles, tiles,
                                transposed=True)

        _straight(jnp.maximum(lo, first), lo + chunk, chunk, _update,
                  lambda: (dk_acc[...], dv_acc[...]), _keep)

    @pl.when((qi == pl.num_programs(2) - 1) & (g == pl.num_programs(3) - 1))
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11))
def _backward(q, k, v, do, lse, delta, sel, heads: int, scale: float,
              block_q: int, tiles: Tuple[int, int, int], interpret: bool):
    """``lse``, ``delta``: ``[B * H, 1, S]`` f32. ``tiles``:
    :func:`_choose_backward`'s (``TRACED["dsa_dq_chunk_tiles"]`` and
    ``["dsa_dkv_chunk_tiles"]`` say what the last call traced took)."""
    bh, seq_len, d = q.shape
    bkv, dv = k.shape[0], v.shape[-1]
    group, kv_heads = bh // bkv, heads // (bh // bkv)
    width = _width(seq_len)
    dq_chunk, dkv_tiles, dkv_chunk = tiles
    TRACED.gauge("dsa_dq_chunk_tiles", dq_chunk)
    TRACED.gauge("dsa_dkv_chunk_tiles", dkv_tiles * dkv_chunk)
    by_q, by_k, q_lanes, words = _row_maps(heads, group, block_q, width,
                                           dq_chunk)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          width=width, chunk=dq_chunk),
        grid=(bh, seq_len // block_q, WORD // dq_chunk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), by_q),
            pl.BlockSpec((1, dq_chunk * width, d), by_k),
            pl.BlockSpec((1, dq_chunk * width, dv), by_k),
            pl.BlockSpec((1, block_q, dv), by_q),
            pl.BlockSpec((1, 1, block_q), q_lanes),
            pl.BlockSpec((1, 1, block_q), q_lanes),
            pl.BlockSpec((1, block_q, width), words),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), by_q),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="dsa_dq",
    )(q, k, v, do, lse, delta, sel)

    rows, keys = dkv_chunk * block_q, dkv_tiles * width

    def q_step(j, i):           # a dead step stays on the first live one
        return jnp.maximum(
            i, _first_q(j * dkv_tiles, block_q, width) // dkv_chunk)

    def col_q(n, j, i, g):
        return (n * group + g, q_step(j, i), 0)

    def col_lanes(n, j, i, g):
        return (n * group + g, 0, q_step(j, i))

    def col_k(n, j, i, g):
        return (n, j, 0)

    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          width=width, tiles=dkv_tiles, chunk=dkv_chunk),
        grid=(bkv, WORD // dkv_tiles, seq_len // rows, group),
        in_specs=[
            pl.BlockSpec((1, rows, d), col_q),
            pl.BlockSpec((1, keys, d), col_k),
            pl.BlockSpec((1, keys, dv), col_k),
            pl.BlockSpec((1, rows, dv), col_q),
            pl.BlockSpec((1, 1, rows), col_lanes),
            pl.BlockSpec((1, 1, rows), col_lanes),
            pl.BlockSpec((1, rows, width),
                         lambda n, j, i, g: (n // kv_heads, q_step(j, i), 0)),
        ],
        out_specs=[pl.BlockSpec((1, keys, d), col_k),
                   pl.BlockSpec((1, keys, dv), col_k)],
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((keys, d), jnp.float32),
                        pltpu.VMEM((keys, dv), jnp.float32),
                        pltpu.VMEM((keys, rows), jnp.int32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="dsa_dkv",
    )(q, k, v, do, lse, delta, sel)
    return dq, dk, dv_


def _merge(x):
    return x.reshape(-1, *x.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attend(q, k, v, sel, scale, block_q, interpret):
    return _attend_fwd(q, k, v, sel, scale, block_q, interpret)[0]


def _attend_fwd(q, k, v, sel, scale, block_q, interpret):
    chunk = _choose_chunk(q.shape[2], q.shape[3], v.shape[3],
                          q.dtype.itemsize, block_q)
    o, lse = (checkpoint_name(a, KEY_CHOICE) for a in _forward(
        _merge(q), _merge(k), _merge(v), sel, q.shape[1], scale, block_q,
        chunk, interpret))
    return ((o.reshape(*q.shape[:3], -1), lse.reshape(q.shape[:3])),
            (q, k, v, sel, o, lse))


def _attend_bwd(scale, block_q, interpret, res, cotangents):
    q, k, v, sel, o, lse = res
    do = _merge(cotangents[0])
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    tiles = _choose_backward(q.shape[2], q.shape[3], v.shape[3],
                             q.dtype.itemsize, block_q)
    dq, dk, dv = _backward(_merge(q), _merge(k), _merge(v), do, lse, delta,
                           sel, q.shape[1], scale, block_q, tiles, interpret)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), None


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend(q, k, v, sel, scale: Optional[float] = None, *,
           block_q: Optional[int] = None, interpret: Optional[bool] = None):
    """``(o [B, H, S, Dv], lse [B, H, S] f32)``: softmax attention of
    each query over the keys its row of ``sel`` holds (module docstring).
    Differentiable in ``q``, ``k``, ``v``; ``lse`` is a constant."""
    b, h, seq_len, d = q.shape
    if k.shape[1] != v.shape[1] or h % k.shape[1]:
        raise ValueError(
            f"dsa: the {h} heads of q{tuple(q.shape)} must be a multiple of "
            f"the key/value heads of k{tuple(k.shape)} and v{tuple(v.shape)}")
    if sel.shape != (b, seq_len, _width(seq_len)):
        raise ValueError(f"dsa: sel{tuple(sel.shape)} is not the packed set "
                         f"of q{tuple(q.shape)}")
    scale = float(d ** -0.5 if scale is None else scale)
    kernels, interpret = _use_kernels(interpret)
    if not kernels:
        return _dense_attend(q, k, v, sel, scale)
    o, lse = _attend(q, k, v, sel, scale,
                     _rows(seq_len, _ATTEND_ROWS, block_q), interpret)
    return o, jax.lax.stop_gradient(lse)


# ---------------------------------------------------------------- dsa_kl
def _kl_kernel(*refs, scale: float, block_q: int, width: int, group: int,
               grads: bool, straight: int):
    """Grid ``(B, q blocks, k tiles)``; a step holds ALL the heads of a
    tile: ``pbar`` is their mean. The tile is computed TRANSPOSED (``[S /
    32, rows]``, as ``flash._dkv_tile``): the heads' and the rows'
    statistics and the indexer's weights are then rows ``[1, rows]`` read
    where they lie, both sets of heads are LOOPS of straight-line groups
    of ``straight`` heads (a group is traced as a loop of ONE head that is
    laid out when it is lowered: inside it Mosaic schedules a head's
    matmul beside its neighbour's mask, subtraction and exponential, which
    a loop trip does not let it do; 48 heads written out compiled for 15 -
    25 s a call site), and the mask is turned once a tile. ``grads``:
    besides the rows' KL, ``dI = softmax_S(I) - pbar`` onto ``dqi`` and
    ``dw`` (over a row's tiles, in scratch) and ``dki`` (this q block's
    partial of the tile)."""
    if grads:
        (q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, sel_ref, lsei_ref,
         kl_ref, dqi_ref, dw_ref, dki_ref, kl_acc, dqi_acc, dw_acc) = refs
    else:
        (q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, sel_ref, lsei_ref,
         kl_ref, kl_acc) = refs
    qi, ki = pl.program_id(1), pl.program_id(2)
    last = _last_k(qi, block_q, width)
    heads, index_heads = q_ref.shape[1], qi_ref.shape[1]
    tile = (width, block_q)

    @pl.when(ki == 0)
    def _init():
        kl_acc[...] = jnp.zeros_like(kl_acc)
        if grads:
            dqi_acc[...] = jnp.zeros_like(dqi_acc)
            dw_acc[...] = jnp.zeros_like(dw_acc)

    def row(ref, h):
        return ref[0, pl.ds(h, 1), :]

    def over(count, head, init):
        """``head(h, carry)`` over the heads in ascending ``h``: a loop of
        groups of ``straight`` heads, then the heads left over; a group is
        traced as a loop of ONE head that is laid out when it is lowered."""
        if straight == 1:
            return jax.lax.fori_loop(0, count, head, init)

        def group(size):
            return lambda i, carry: jax.lax.fori_loop(
                0, size, lambda j, c: head(i * straight + j, c), carry,
                unroll=True)

        groups, rest = divmod(count, straight)
        carry = jax.lax.fori_loop(0, groups, group(straight), init)
        return group(rest)(groups, carry) if rest else carry

    @pl.when(ki <= last)
    def _tile():
        keep = ((sel_ref[0] >> ki) & 1).T != 0

        def head(h, pbar):
            s = _dot(_f32(k_ref[0, h // group]), _f32(q_ref[0, h]) * scale,
                     _NT)
            return pbar + jnp.exp(jnp.where(keep, s, _NEG_INF)
                                  - row(lse_ref, h))

        pbar = over(heads, head, jnp.zeros(tile, jnp.float32)) * (
            1.0 / heads)
        kt = ki_ref[0]

        def index_head(j, index):
            return index + row(w_ref, j) * _relu(
                _dot(kt, qi_ref[0, j], _NT))

        log_q = over(index_heads, index_head,
                     jnp.zeros(tile, jnp.float32)) - lsei_ref[0]
        on = keep & (pbar > 0.0)
        kl = jnp.where(on, pbar * (jnp.log(jnp.where(on, pbar, 1.0))
                                   - log_q), 0.0)
        kl_acc[...] = kl_acc[...] + jnp.sum(kl, axis=0, keepdims=True)
        if grads:
            d_index = jnp.where(keep, jnp.exp(log_q), 0.0) - pbar
            ktf = _f32(kt)

            def index_grads(j, dki):
                qj = qi_ref[0, j]
                s = _dot(kt, qj, _NT)
                dw_acc[pl.ds(j, 1), :] = dw_acc[pl.ds(j, 1), :] + jnp.sum(
                    d_index * _relu(s), axis=0, keepdims=True)
                g = jnp.where(s > 0.0, d_index * row(w_ref, j), 0.0)
                dqi_acc[j] = dqi_acc[j] + _dot(g, ktf, _TN)
                return dki + _dot(g, _f32(qj), _NN)

            dki_ref[0, 0] = over(index_heads, index_grads,
                                 jnp.zeros(dki_ref.shape[2:], jnp.float32))

    if grads:
        @pl.when(ki > last)
        def _dead():
            dki_ref[0, 0] = jnp.zeros(dki_ref.shape[2:], jnp.float32)

    @pl.when(ki == last)
    def _finalize():
        kl_ref[0] = kl_acc[...]
        if grads:
            dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
            dw_ref[0] = dw_acc[...]


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _kl_call(q, k, lse, qi, ki, w, sel, lse_i, scale: float, block_q: int,
             grads: bool, straight: int, interpret: bool):
    """The rows' KL ``[B, S]`` and, with ``grads``, ``(dqi, dki, dw)`` of
    their SUM. ``w [B, S, HI]`` goes in, and ``dw`` comes out, through its
    ``[B, HI, S]`` form (a head's weights along the lanes). ``straight``:
    :func:`_choose_kl`'s heads a straight-line group
    (``TRACED["dsa_kl_group_heads"]`` says what the last call traced
    took)."""
    b, heads, seq_len, d = q.shape
    kv, index_heads, di = k.shape[1], qi.shape[1], qi.shape[-1]
    width, blocks = _width(seq_len), seq_len // block_q
    TRACED.gauge("dsa_kl_group_heads", straight)

    def by_k(n, i, j):
        return jnp.minimum(j, _last_k(i, block_q, width))

    def lanes(rows):
        return pl.BlockSpec((1, rows, block_q), lambda n, i, j: (n, 0, i))

    in_specs = [
        pl.BlockSpec((1, heads, block_q, d), lambda n, i, j: (n, 0, i, 0)),
        pl.BlockSpec((1, kv, width, d),
                     lambda n, i, j: (n, 0, by_k(n, i, j), 0)),
        lanes(heads),
        pl.BlockSpec((1, index_heads, block_q, di),
                     lambda n, i, j: (n, 0, i, 0)),
        pl.BlockSpec((1, width, di), lambda n, i, j: (n, by_k(n, i, j), 0)),
        lanes(index_heads),
        pl.BlockSpec((1, block_q, width), lambda n, i, j: (n, i, 0)),
        lanes(1),
    ]
    out_specs = [lanes(1)]
    out_shape = [jax.ShapeDtypeStruct((b, 1, seq_len), jnp.float32)]
    scratch = [pltpu.VMEM((1, block_q), jnp.float32)]
    if grads:
        out_specs += [
            pl.BlockSpec((1, index_heads, block_q, di),
                         lambda n, i, j: (n, 0, i, 0)),
            lanes(index_heads),
            pl.BlockSpec((1, 1, width, di), lambda n, i, j: (n, i, j, 0)),
        ]
        out_shape += [
            jax.ShapeDtypeStruct(qi.shape, qi.dtype),
            jax.ShapeDtypeStruct((b, index_heads, seq_len), jnp.float32),
            jax.ShapeDtypeStruct((b, blocks, seq_len, di), jnp.float32),
        ]
        scratch += [pltpu.VMEM((index_heads, block_q, di), jnp.float32),
                    pltpu.VMEM((index_heads, block_q), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_kl_kernel, scale=scale, block_q=block_q,
                          width=width, group=heads // kv, grads=grads,
                          straight=straight),
        grid=(b, blocks, WORD),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_PARAMS,
        interpret=interpret,
        name="dsa_kl",
    )(q, k, lse, qi, ki, w.transpose(0, 2, 1), sel, lse_i[:, None, :])
    if not grads:
        return out[0][:, 0]
    kl, dqi, dw, dki = out
    return (kl[:, 0], dqi, jnp.sum(dki, axis=1).astype(ki.dtype),
            dw.transpose(0, 2, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _kl(q, k, lse, qi, ki, w, sel, lse_i, scale, block_q, straight,
        interpret):
    return jnp.sum(_kl_call(q, k, lse, qi, ki, w, sel, lse_i, scale, block_q,
                            False, straight, interpret))


def _kl_fwd(q, k, lse, qi, ki, w, sel, lse_i, scale, block_q, straight,
            interpret):
    # asked for by ``jax`` only where ``_kl`` is differentiated: the
    # gradients of the rows' SUM come out of the call that makes the value
    # (every operand is here; the backward pass brings the cotangent, one
    # scalar) and are all the backward rule reads. Named HERE: a name on
    # the op's result does not reach a rule's own residuals, and under
    # ``common.checkpoint_layer`` these are what the forward pass keeps of
    # a call whose caller keeps nothing smaller
    TRACED.incr("dsa_kl_grad_calls")
    kl, *grads = _kl_call(q, k, lse, qi, ki, w, sel, lse_i, scale, block_q,
                          True, straight, interpret)
    return jnp.sum(kl), tuple(checkpoint_name(a, KEY_CHOICE) for a in grads)


def _kl_bwd(scale, block_q, straight, interpret, grads, g):
    dqi, dki, dw = ((g * a).astype(a.dtype) for a in grads)
    return None, None, None, dqi, dki, dw, None, None


_kl.defvjp(_kl_fwd, _kl_bwd)


def index_kl(q, k, lse, qi, ki, w, sel, lse_i, scale: Optional[float] = None,
             *, block_q: Optional[int] = None,
             interpret: Optional[bool] = None):
    """``sum_{b, t} KL(pbar[b, t] || softmax_{S_t}(I[b, t]))``, a scalar
    f32 (module docstring). Differentiable in ``qi``, ``ki``, ``w``
    alone: ``q``, ``k`` and ``lse`` make the target and are detached."""
    q, k, lse = (jax.lax.stop_gradient(a) for a in (q, k, lse))
    seq_len = q.shape[2]
    scale = float(q.shape[-1] ** -0.5 if scale is None else scale)
    kernels, interpret = _use_kernels(interpret)
    if not kernels:
        return _dense_kl(q, k, lse, qi, ki, w, sel, scale)
    block_q = _rows(seq_len, _ATTEND_ROWS, block_q)
    straight = _choose_kl(seq_len, q.shape[1], k.shape[1], q.shape[3],
                          qi.shape[1], qi.shape[3], q.dtype.itemsize, block_q)
    return _kl(q, k, lse.astype(jnp.float32), qi, ki, w.astype(jnp.float32),
               sel, jax.lax.stop_gradient(lse_i), scale, block_q, straight,
               interpret)
