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
``o``, ``dq``, ``dk``, ``dv``. In ``dsa_dq`` and ``dsa_dkv`` a grid step
is one tile, and tiles above the diagonal are grid steps that compute and
fetch nothing. ``dsa_fwd`` takes a CHUNK of consecutive k tiles a grid
step (``_choose_chunk``: a pure function of the shape, the longest rung of
``_CHUNK_LADDER`` that fits VMEM — all 32, the whole row of keys, at the
cell's 16 384 —; ``TRACED["dsa_fwd_chunk_tiles"]`` says what a trace took)
and sweeps the chunk's live tiles in ascending k in straight-line groups,
as ``flash._flash_streamed_kernel`` does, the softmax statistics updated
once a PAIR of tiles (f32, as every sum of the kernel; ``lse`` is the
value the three other kernels read): 83.6 -> 35.4 ms a call there (PR 69).

``index_kl``: ``sum_t KL(pbar[t] || softmax_{S_t}(I[t]))`` with ``pbar[t,
s] = mean_h P[t, h, s]`` (from ``q``, ``k``, ``lse``, all detached).
``dsa_kl`` recomputes every head's ``P`` tile and the indexer's scores
tile by tile. DIFFERENTIATED it still runs once: the forward rule's call
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


# K tiles a grid step of ``dsa_fwd`` (a tile is a bit of the packed word:
# ``S / 32`` keys), the longest first, every rung a divisor of the 32; the
# straight-line groups a chunk's live tiles are met in (``ops/flash.py``'s,
# PR 60: inside a group Mosaic schedules an update's matmuls beside its
# neighbour's softmax, which neither a grid step nor a loop trip lets it
# do); and the tiles ONE update of the softmax statistics takes (one
# max-reduce, one alpha, one rescale of acc: 1 024 keys at 16k, that
# file's streamed tile; a lone tile updates alone). On the v5e (PR 69,
# ``scripts/dsa_micro.py``; ``[64 | 8, 16384, 128]``, 2 048 keys a query, ms
# a call at 1 / 2 / 4 / 8 / 16 / 32 tiles a step):
#   an update a tile       83.6  75.2  71.2  69.9  65.9  62.7
#   an update a pair       83.6  49.9  43.9  42.7  39.2  35.4
# (one tile a step is the body the kernel had, bit for bit). At 32 the k
# axis is one step: no dead steps, and K and V of a key head are fetched
# once for all its query heads' rows. Four tiles an update read 34.7,
# groups of (8, 4, 2, 1) 33.7 - 34.2 for twice the body; groups of (2, 1)
# 39.0.
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
    limit = _PARAMS.vmem_limit_bytes
    return next(n for n in _CHUNK_LADDER if n == 1 or _forward_vmem_estimate(
        head_dim, v_dim, itemsize, block_q, width, n) <= limit)


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
        if chunk == 1:
            k, v = k_ref[0], v_ref[0]
        else:
            at = pl.ds(pl.multiple_of((kt - lo) * width, width),
                       span * width)
            k, v = k_ref[0, at, :], v_ref[0, at, :]
        words = sel_ref[0]
        keep = [_bit(words, kt + j) for j in range(span)]
        s = jnp.where(keep[0] if span == 1 else jnp.concatenate(keep, axis=1),
                      _dot(q, _f32(k), _NT), _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        return (acc * alpha + _dot(p, _f32(v), _NN),
                m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True))

    @pl.when(lo <= last)
    def _chunk():
        q = _f32(q_ref[0]) * scale
        if chunk == 1:
            _keep(*_update(q, ki, 1, *_carried()))
            return

        def group(size, start):
            span = min(size, _SPAN)

            def body(i, _):
                first = start + i * size
                _keep(*jax.lax.fori_loop(
                    0, size // span,
                    lambda j, carry: _update(q, first + j * span, span,
                                             *carry),
                    _carried(), unroll=True))
                return 0
            return body

        # the chunk's live tiles in ascending k: as many groups of the
        # longest size as they hold, then of the next
        done, live_hi = lo, jnp.minimum(last + 1, lo + chunk)
        for size in _STRAIGHT:
            if size > chunk:
                continue
            count = (live_hi - done) // size
            jax.lax.fori_loop(0, count, group(size, done), 0)
            done = done + count * size

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
               width: int):
    qi, ki = pl.program_id(1), pl.program_id(2)
    last = _last_k(qi, block_q, width)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        cols[...] = _rows_to_cols(lse_ref[0], delta_ref[0])

    @pl.when(ki <= last)
    def _tile():
        k, v = _f32(k_ref[0]), _f32(v_ref[0])
        s = jnp.where(_bit(sel_ref[0], ki),
                      _dot(_f32(q_ref[0]) * scale, k, _NT), _NEG_INF)
        p = jnp.exp(s - cols[:, :1])
        ds = p * (_dot(_f32(do_ref[0]), v, _NT) - cols[:, 1:2])
        dq_acc[...] = dq_acc[...] + _dot(ds, k, _NN)

    @pl.when(ki == last)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, keep_t, *, scale: float,
                block_q: int, width: int):
    """Grid ``(B * KV, k tiles, q blocks, group)``: a k tile's column of q
    blocks, the group's heads innermost, so the tile's mask is turned
    once a q block (``keep_t``, ``[S / 32, rows]``: the tile is recomputed
    TRANSPOSED as in ``flash._dkv_tile``) and dk, dv are summed over the
    group in f32 and rounded once."""
    ki, qi, g = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first = _first_q(ki, block_q, width)

    @pl.when((qi == first) & (g == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(qi >= first)
    def _tile():
        @pl.when(g == 0)
        def _turn():
            keep_t[...] = ((sel_ref[0] >> ki) & 1).T

        q, do = _f32(q_ref[0]) * scale, _f32(do_ref[0])
        st = jnp.where(keep_t[...] != 0, _dot(_f32(k_ref[0]), q, _NT),
                       _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])
        dst = pt * (_dot(_f32(v_ref[0]), do, _NT) - delta_ref[0])
        dk_acc[...] = dk_acc[...] + _dot(dst, q, _NN)
        dv_acc[...] = dv_acc[...] + _dot(pt, do, _NN)

    @pl.when((qi == pl.num_programs(2) - 1) & (g == pl.num_programs(3) - 1))
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _backward(q, k, v, do, lse, delta, sel, heads: int, scale: float,
              block_q: int, interpret: bool):
    """``lse``, ``delta``: ``[B * H, 1, S]`` f32."""
    bh, seq_len, d = q.shape
    bkv, dv = k.shape[0], v.shape[-1]
    group, kv_heads = bh // bkv, heads // (bh // bkv)
    width = _width(seq_len)
    by_q, by_k, q_lanes, words = _row_maps(heads, group, block_q, width)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          width=width),
        grid=(bh, seq_len // block_q, WORD),
        in_specs=[
            pl.BlockSpec((1, block_q, d), by_q),
            pl.BlockSpec((1, width, d), by_k),
            pl.BlockSpec((1, width, dv), by_k),
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

    def q_block(j, i):          # a dead step stays on the first live block
        return jnp.maximum(i, _first_q(j, block_q, width))

    def col_q(n, j, i, g):
        return (n * group + g, q_block(j, i), 0)

    def col_lanes(n, j, i, g):
        return (n * group + g, 0, q_block(j, i))

    def col_k(n, j, i, g):
        return (n, j, 0)

    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          width=width),
        grid=(bkv, WORD, seq_len // block_q, group),
        in_specs=[
            pl.BlockSpec((1, block_q, d), col_q),
            pl.BlockSpec((1, width, d), col_k),
            pl.BlockSpec((1, width, dv), col_k),
            pl.BlockSpec((1, block_q, dv), col_q),
            pl.BlockSpec((1, 1, block_q), col_lanes),
            pl.BlockSpec((1, 1, block_q), col_lanes),
            pl.BlockSpec((1, block_q, width),
                         lambda n, j, i, g: (n // kv_heads, q_block(j, i), 0)),
        ],
        out_specs=[pl.BlockSpec((1, width, d), col_k),
                   pl.BlockSpec((1, width, dv), col_k)],
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((width, d), jnp.float32),
                        pltpu.VMEM((width, dv), jnp.float32),
                        pltpu.VMEM((width, block_q), jnp.int32)],
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
    dq, dk, dv = _backward(_merge(q), _merge(k), _merge(v), do, lse, delta,
                           sel, q.shape[1], scale, block_q, interpret)
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
               grads: bool):
    """Grid ``(B, q blocks, k tiles)``; a step holds ALL the heads of a
    tile: ``pbar`` is their mean. The tile is computed TRANSPOSED (``[S /
    32, rows]``, as ``flash._dkv_tile``): the heads' and the rows'
    statistics and the indexer's weights are then rows ``[1, rows]`` read
    where they lie, both sets of heads are LOOPS (a body is traced and
    compiled once: 48 heads written out compiled for 15 - 25 s a call
    site), and the mask is turned once a tile. ``grads``: besides the
    rows' KL, ``dI = softmax_S(I) - pbar`` onto ``dqi`` and ``dw`` (over a
    row's tiles, in scratch) and ``dki`` (this q block's partial of the
    tile)."""
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

    @pl.when(ki <= last)
    def _tile():
        keep = ((sel_ref[0] >> ki) & 1).T != 0

        def head(h, pbar):
            s = _dot(_f32(k_ref[0, h // group]), _f32(q_ref[0, h]) * scale,
                     _NT)
            return pbar + jnp.exp(jnp.where(keep, s, _NEG_INF)
                                  - row(lse_ref, h))

        pbar = jax.lax.fori_loop(
            0, heads, head, jnp.zeros(tile, jnp.float32)) * (1.0 / heads)
        kt = ki_ref[0]

        def index_head(j, index):
            return index + row(w_ref, j) * _relu(
                _dot(kt, qi_ref[0, j], _NT))

        log_q = jax.lax.fori_loop(
            0, index_heads, index_head, jnp.zeros(tile, jnp.float32)
        ) - lsei_ref[0]
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

            dki_ref[0, 0] = jax.lax.fori_loop(
                0, index_heads, index_grads,
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


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _kl_call(q, k, lse, qi, ki, w, sel, lse_i, scale: float, block_q: int,
             grads: bool, interpret: bool):
    """The rows' KL ``[B, S]`` and, with ``grads``, ``(dqi, dki, dw)`` of
    their SUM. ``w [B, S, HI]`` goes in, and ``dw`` comes out, through its
    ``[B, HI, S]`` form (a head's weights along the lanes)."""
    b, heads, seq_len, d = q.shape
    kv, index_heads, di = k.shape[1], qi.shape[1], qi.shape[-1]
    width, blocks = _width(seq_len), seq_len // block_q

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
                          width=width, group=heads // kv, grads=grads),
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _kl(q, k, lse, qi, ki, w, sel, lse_i, scale, block_q, interpret):
    return jnp.sum(_kl_call(q, k, lse, qi, ki, w, sel, lse_i, scale, block_q,
                            False, interpret))


def _kl_fwd(q, k, lse, qi, ki, w, sel, lse_i, scale, block_q, interpret):
    # asked for by ``jax`` only where ``_kl`` is differentiated: the
    # gradients of the rows' SUM come out of the call that makes the value
    # (every operand is here; the backward pass brings the cotangent, one
    # scalar) and are all the backward rule reads. Named HERE: a name on
    # the op's result does not reach a rule's own residuals, and under
    # ``common.checkpoint_layer`` these are what the forward pass keeps of
    # a call whose caller keeps nothing smaller
    TRACED.incr("dsa_kl_grad_calls")
    kl, *grads = _kl_call(q, k, lse, qi, ki, w, sel, lse_i, scale, block_q,
                          True, interpret)
    return jnp.sum(kl), tuple(checkpoint_name(a, KEY_CHOICE) for a in grads)


def _kl_bwd(scale, block_q, interpret, grads, g):
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
    return _kl(q, k, lse.astype(jnp.float32), qi, ki, w.astype(jnp.float32),
               sel, jax.lax.stop_gradient(lse_i), scale,
               _rows(seq_len, _ATTEND_ROWS, block_q), interpret)
