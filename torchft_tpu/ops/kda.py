"""Pallas kernels for the gated delta rule in its chunked form, forward
and backward under one ``jax.custom_vjp`` a kind of decay: a decay PER
KEY CHANNEL (:func:`kda_scan`: Kimi Delta Attention, KDA; Kimi Linear,
arXiv:2510.26692), which this docstring describes first, and ONE decay a
head (:func:`gdn_scan`: Gated DeltaNet, arXiv:2412.06464; Olmo Hybrid),
whose kernels ``gdn_fwd`` / ``gdn_bwd`` share the triangular inverse, the
state pass, the backward's replay and the head-group grid and are
described where they stand, at the end of the file. A head has ``K`` key
and ``V`` value channels, equal or not; the step size ``β`` is whatever
the caller hands over (Kimi's model bounds it by 1, Olmo Hybrid's by 2:
the rule contracts for ``β`` in (0, 2), and no op here bounds it).

Per head, with a state ``S ∈ R^{K×V}``, ``S_0 = 0``, log-decays ``g_t ∈
R^K`` (<= 0), ``α_t = exp(g_t)`` and a step size ``β_t``:

    S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t-1} + β_t k_t v_tᵀ,
    o_t = S_tᵀ q_t.

Written with the delta-corrected value ``u_t = β_t (v_t − (Diag(α_t)
S_{t-1})ᵀ k_t)`` the state is ``S_t = Diag(α_t) S_{t-1} + k_t u_tᵀ``. In
chunks of ``C`` positions, ``G_i`` the sum of ``g`` from the chunk's
first position to ``i`` (a vector of ``K`` channels), ``S`` the state
that enters the chunk:

    A_ij  = Σ_c k_ic k_jc exp(G_ic − G_jc)   (i > j)
    B_ij  = Σ_c q_ic k_jc exp(G_ic − G_jc)   (i >= j)
    U     = (I + Diag(β) A)^{-1} Diag(β) (V − (K ∘ exp G) S)
    O     = (Q ∘ exp G) S + B U
    S'    = Diag(exp G_C) S + (K ∘ exp(G_C − G))ᵀ U

which is the recurrence whatever ``C`` is. Unlike ``ops/ssd.py``'s
scalar-a-head decay, ``exp(G_i − G_j)`` is a vector: ``A`` and ``B`` are
no product of two matrices, and ``exp(−G_j)`` alone may overflow. **Every
exponent is a difference of cumulative log-decays taken so that it is <=
0** — a channel whose decay underflows gives 0, never inf or nan — by
splitting the pairs ``(i, j)`` of a chunk by the highest bit in which
``i`` and ``j`` differ:

- bit ``b >= 3`` (``h = 2^b``: ``i`` in the upper and ``j`` in the lower
  half of one ``2h``-block): with ``m`` the first position of ``i``'s
  half, ``exp(G_i − G_j) = exp(G_i − G_{m-1}) · exp(G_{m-1} − G_j)``,
  both <= 0, the first a function of the row and the second of the
  column alone: one masked matmul a level (``log2(C) − 3`` levels);
- bits 0-2 (``i`` and ``j`` in one tile of 8 rows): for each distance
  ``d = 1..7`` the band ``exp(G_i − G_{i-d})`` is formed on the whole
  chunk at once from a sublane roll, and the sum over the channels is a
  lane reduction.

The triangular inverse ``T = (I + Diag(β) A)^{-1}`` is built by block
doubling, ``T_{2h} = T_h − T_h X_h T_h`` with ``X_h`` the entries of
``Diag(β) A`` between the halves of each ``2h``-block: every factor is
an inverse of a diagonal block, bounded because the delta rule
contracts. (The Neumann product ``(I − L)(I + L²)(I + L⁴)…`` is the same
matrix on paper and cancels catastrophically: with correlated keys its
terms reach ``e^{‖L‖}``.)

The kernels. One grid step is one chunk of SEVERAL heads of one batch
row (:func:`_heads_a_step`: four of the cell's 32), the chunks innermost
and in order. A head's chunk is one chain — the cumulative sum, the pair
sums, the fourteen matmuls of the inverse each of which needs the one
before, ``R``, ``U``, ``o`` — and a step that held one head ran it on one
of the chip's four MXUs with the vector unit waiting beside it: 2.5 µs a
step, about what its matmuls take one after another. Heads share nothing,
so a step's heads are written side by side (:func:`_side_by_side`) and
each fills the others' waits; a head is a 128-lane slice of the step's
blocks, and what it computes does not change by a bit. The states are
carried from step to step in a VMEM scratch, TRANSPOSED (``[G, V, K]``:
the decay of a channel is then a lane's factor), in f32. No per-position
state exists anywhere: nothing ``[B, S, H, K, V]`` is formed. ``kda_bwd``
walks the chunks LAST TO FIRST with the states' cotangents in the same
scratch. **What the backward keeps**:
the scan's inputs and the chunk-boundary states ``[B, H, S/C, V, K]``
f32 (written by the forward kernel only when it runs as the vjp's
forward rule; 537 MB a layer at 32 768 tokens, 32 heads and C 128; under
``jax.checkpoint`` it lives for that layer's backward alone). ``A``,
``B``, ``T``, ``U`` are recomputed. With ``R = V − K̄ S``, ``K̄ = K ∘
exp G``, ``Q̄ = Q ∘ exp G``, ``K̃ = K ∘ exp(G_C − G)``:

    dU  = Bᵀ dO + K̃ dS'                dB = tril(dO Uᵀ)
    dRβ = Tᵀ dU                          dL = −stril(dRβ Uᵀ)   (L = βA)
    dA  = Diag(β) dL                     dβ = Σ_j dL ∘ A + Σ_v dRβ ∘ R
    dV  = β dRβ = dR                     dK̄ = −dR Sᵀ, dQ̄ = dO Sᵀ, dK̃ = U dS'ᵀ
    dS  = Q̄ᵀ dO + Diag(exp G_C) dS' − K̄ᵀ dR

and the pair sums' own cotangents by the same levels and bands (the
transposed matmuls and the rolls back). ``G`` stands in every factor, so
``dG = q ∘ dq + k ∘ dk_row − k ∘ dk_col`` (a pair adds at its row and
subtracts at its column) ``+ dQ̄ ∘ Q̄ + dK̄ ∘ K̄ − dK̃ ∘ K̃``, the chunk's
last row takes what ``G_C`` carries, and ``dg`` is the sum of ``dG``
from a position to the chunk's end.

What is which dtype: ``q, k, v`` arrive and ``o, dq, dk, dv`` leave in
the input dtype (bf16 in the models); ``g`` and ``β`` are f32, as are
the cumulative sums, every decay, the state and every accumulator. The
two cumulative sums are matmuls against a triangle of ones at
``Precision.HIGHEST`` (an f32 ``g`` rounded to bf16 on the MXU would
move an exponent by 2^-9 of its size).

An exponent is a difference of two of the chunk's cumulative sums, so
its absolute error is 2^-24 of the chunk's total log-decay: 1e-5 at the
model's decays, 1e-3 where a chunk forgets by ``e^-10000``.

The chunk is ``_CHUNK`` (a shorter sequence takes the power of two that
holds it, at least 16); a sequence that is no multiple is padded with
``g = 0, β = 0`` positions at its end (decay 1, nothing written: they
change nothing before them). Off the TPU the same kernels run in
Pallas's interpreter (the CPU tests), chosen from the backend alone. On
the TPU the channel-wise kernels take ``K`` and ``V`` that are multiples
of 128 lanes and refuse others with a message (a head there is an
aligned lane slice of the step's block); the scalar-decay kernels take
any ``K`` and ``V`` of which some group of heads fills whole lane tiles
(four heads of 96 are three tiles, of 192 six), refuse others with a
message, and take any head count (:func:`_gdn_heads_a_step`) — and q and
k at FEWER heads than v where the one divides the other: value head ``h``
reads key head ``h // r`` where it lies (KEY HEADS, at the end of the
file; ``TRACED["gdn_value_group_copies"]`` counts the calls, as traced,
that had to copy q and k to the value heads after all).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.utils.metrics import TRACED

__all__ = ["kda_scan", "gdn_scan"]

_NEG = -1e30     # exp(_NEG) == 0: a pair outside its band
_LANES = 128
_TILE = 8        # rows of an f32 tile: pairs closer than this go by bands
# positions a chunk; see the module docstring
_CHUNK = 128
_HIGHEST = jax.lax.Precision.HIGHEST
# heads a grid step, largest first: :func:`_heads_a_step`. Eight gain 0.6
# ms a forward and 0.9 a backward call on four (of 14.2 / 24.0: PERF.md
# section 6, PR 48) and cost a body twice as long to trace, once a call
# shape in every process: 5 s of the cell's ``setup_s``, most of its bound
_LADDER = (4, 2, 1)
# what a kernel may take of the chip's 128 MiB of VMEM; ``kda_bwd`` at four
# heads of 128 takes 15.23 MiB, 0.8 under what Mosaic allows unasked
_VMEM_LIMIT = 32 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _f32(a):
    return a.astype(jnp.float32)


def _dot(a, b, contract=((1,), (0,)), precision=None):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


_NT = ((1,), (1,))     # the contraction of ``a·bᵀ``


def _dot_nt(a, b):
    """``a·bᵀ``."""
    return _dot(a, b, _NT)


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _cross(row, col, lg: int):
    """Pairs whose row lies in the upper and whose column in the lower
    half of one block of ``2^(lg+1)`` positions."""
    rb, cb = row >> lg, col >> lg
    return (rb == cb + 1) & ((cb & 1) == 0)


def _side_by_side(chains):
    """Runs the generators ``chains`` in lockstep, each to its next
    ``yield`` in turn, until all have ended. A kernel's body is
    traced in that order, and Mosaic's scheduler keeps close to the order
    it is given: four heads one AFTER the other in the body gain 2.8 ms a
    forward call of 25.5, side by side 11.3 (PERF.md section 6, PR 48).
    So a head's chain yields where its next matmul needs the last — at
    every matmul of the inverse, between the stages elsewhere; finer
    than that gained nothing — and the step's other heads stand there."""
    live = list(chains)
    while live:
        live = [chain for chain in live if next(chain, False) is None]


def _decays(g):
    """``g [C, K]`` -> the cumulative sum ``G`` and what every pair's
    decay is made of: per level ``(lg, exp(G_i − G_{m-1}) by row,
    exp(G_{m-1} − G_j) by column)`` and per band ``(d, exp(G_i −
    G_{i-d}))``, zero where ``i`` and ``i − d`` lie in two tiles."""
    C, K = g.shape
    tri = _iota((C, C), 0) >= _iota((C, C), 1)
    G = _dot(jnp.where(tri, 1.0, 0.0), g, precision=_HIGHEST)
    pos = _iota((C, K), 0)
    levels = []
    h = _TILE
    while h < C:
        ends = jnp.broadcast_to(
            G.reshape(C // h, h, K)[:, h - 1:h, :], (C // h, h, K)
        ).reshape(C, K)                                  # G at the block's end
        before = jnp.where(pos >= h, pltpu.roll(ends, h, 0), 0.0)
        levels.append((h.bit_length() - 1, jnp.exp(G - before),
                       jnp.exp(ends - G)))
        h *= 2
    tile = pos & (_TILE - 1)
    bands = [(d, jnp.exp(jnp.where(tile >= d, G - pltpu.roll(G, d, 0), _NEG)))
             for d in range(1, min(_TILE, C))]
    return G, levels, bands


def _pairs(xs, k, levels, bands):
    """For each ``x`` of ``xs``: ``M[i, j] = Σ_c x_ic k_jc exp(G_ic −
    G_jc)`` over the pairs ``i > j``."""
    C = k.shape[0]
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    out = [jnp.zeros((C, C), jnp.float32) for _ in xs]
    for lg, by_row, by_col in levels:
        prod = _dot_nt(jnp.concatenate([x * by_row for x in xs], axis=0),
                       k * by_col)                       # [n·C, C]
        live = _cross(row, col, lg)
        for t in range(len(xs)):
            out[t] = out[t] + jnp.where(live, prod[t * C:(t + 1) * C], 0.0)
    for d, decay in bands:
        kd = pltpu.roll(k, d, 0) * decay
        live = col == row - d
        for t, x in enumerate(xs):
            out[t] = out[t] + jnp.where(
                live, jnp.sum(x * kd, axis=1, keepdims=True), 0.0)
    return out


def _pairs_bwd(dms, xs, k, levels, bands):
    """:func:`_pairs`' transpose: ``(dxs, dk_col)`` — the cotangents of
    each ``x`` and of ``k`` in its column's place."""
    C, K = k.shape
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    dxs = [jnp.zeros((C, K), jnp.float32) for _ in xs]
    dk_col = jnp.zeros((C, K), jnp.float32)
    for lg, by_row, by_col in levels:
        live = _cross(row, col, lg)
        stack = jnp.concatenate(
            [jnp.where(live, dm, 0.0) for dm in dms], axis=0)    # [n·C, C]
        by_rows = _dot(stack, k * by_col)                        # [n·C, K]
        for t in range(len(xs)):
            dxs[t] = dxs[t] + by_row * by_rows[t * C:(t + 1) * C]
        dk_col = dk_col + by_col * _dot(
            stack.T, jnp.concatenate([x * by_row for x in xs], axis=0))
    for d, decay in bands:
        kd = pltpu.roll(k, d, 0) * decay
        live = col == row - d
        at_row = jnp.zeros((C, K), jnp.float32)
        for t, (dm, x) in enumerate(zip(dms, xs)):
            band = jnp.sum(jnp.where(live, dm, 0.0), axis=1, keepdims=True)
            dxs[t] = dxs[t] + band * kd
            at_row = at_row + band * x
        # what row i gives to k_{i-d}: rolled back (rows whose partner is
        # in another tile carry a zero decay, so nothing wraps)
        dk_col = dk_col + pltpu.roll(at_row * decay, C - d, 0)
    return dxs, dk_col


def _solve(lower, dot=None):
    """``(I + lower)^{-1}`` of a strictly lower-triangular ``[C, C]`` by
    block doubling (the module docstring): two matmuls a doubling, each
    of which needs the one before (a generator: :func:`_side_by_side`);
    ``dot`` the matmul (:func:`_dot`; the scalar-decay kernels hand in
    theirs)."""
    dot = dot or _dot
    C = lower.shape[0]
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    T = jnp.where(row == col, 1.0, 0.0)
    lg = 0
    while (1 << lg) < C:
        half = dot(T, jnp.where(_cross(row, col, lg), lower, 0.0))
        yield
        T = T - dot(half, T)
        yield
        lg += 1
    return T


def _chunk(q, k, v, g, beta, st):
    """What both kernels compute of one chunk of one head (f32 operands;
    ``beta [C, 1]``, ``st [V, K]`` the transposed state that enters; a
    generator: :func:`_side_by_side`)."""
    C = q.shape[0]
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    G, levels, bands = _decays(g)
    yield
    A, B = _pairs([k, q], k, levels, bands)
    yield
    B = B + jnp.where(row == col, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    T = yield from _solve(beta * A)
    last = G[C - 1:C, :]                                 # [1, K]
    gam, to_end = jnp.exp(G), jnp.exp(last - G)
    kbar, qbar, ktil = k * gam, q * gam, k * to_end
    R = v - _dot_nt(kbar, st)                            # [C, V]
    yield
    U = _dot(T, beta * R)
    yield
    return dict(levels=levels, bands=bands, A=A, B=B, T=T, last=last,
                gam=gam, to_end=to_end, kbar=kbar, qbar=qbar, ktil=ktil,
                R=R, U=U)


def _head(j: int, heads: int, kd: int, vd: int, q_ref, k_ref, v_ref, g_ref,
          beta_ref):
    """Head ``j`` of a step's ``heads``: its key and its value lanes in
    the step's ``[1, C, G·width]`` blocks (aligned slices: free), ``q, k,
    v, g`` in f32 and its ``β``, a column of the ``[C, H]`` block as ``[C,
    1]`` (a select and a lane sum: exact, and no one-lane slice)."""
    keys, values = slice(j * kd, (j + 1) * kd), slice(j * vd, (j + 1) * vd)
    block = beta_ref[0, 0]
    mine = _iota(block.shape, 1) == pl.program_id(1) * heads + j
    beta = jnp.sum(jnp.where(mine, block, 0.0), axis=1, keepdims=True)
    return keys, values, (_f32(q_ref[0, :, keys]), _f32(k_ref[0, :, keys]),
                          _f32(v_ref[0, :, values]), g_ref[0, :, keys], beta)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                    heads: int, save_states: bool):
    """One (batch, group of ``heads`` heads, chunk): ``o`` of the chunk
    and the states it leaves, the states it entered with written out for
    the backward where asked."""
    state = rest[-1]                                     # [G, V, K]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    _, vd, kd = state.shape

    def head(j):
        st = state[j]
        if save_states:
            rest[0][0, j, 0] = st
        _, values, ins = _head(j, heads, kd, vd, q_ref, k_ref, v_ref, g_ref,
                               beta_ref)
        c = yield from _chunk(*ins, st)
        o = _dot_nt(c["qbar"], st) + _dot(c["B"], c["U"])
        o_ref[0, :, values] = o.astype(o_ref.dtype)
        yield
        state[j] = st * jnp.exp(c["last"]) + _dot(c["U"].T, c["ktil"])

    _side_by_side(head(j) for j in range(heads))


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *,
                    heads: int):
    """One (batch, group of ``heads`` heads, chunk), chunks last to
    first; ``dstate`` carries the cotangents of the (transposed) states
    the chunk leaves."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    _, vd, kd = dstate.shape
    C = q_ref.shape[1]
    row, col = _iota((C, C), 0), _iota((C, C), 1)

    def head(j):
        keys, values, ins = _head(j, heads, kd, vd, q_ref, k_ref, v_ref,
                                  g_ref, beta_ref)
        q, k, _, _, beta = ins
        st, dst, do = st_ref[0, j, 0], dstate[j], _f32(do_ref[0, :, values])
        c = yield from _chunk(*ins, st)
        U, R, T = c["U"], c["R"], c["T"]
        dU = _dot(c["B"].T, do) + _dot_nt(c["ktil"], dst)    # [C, V]
        dB = jnp.where(row >= col, _dot_nt(do, U), 0.0)
        dqbar, dktil = _dot(do, st), _dot(U, dst)            # [C, K]
        yield
        dRb = _dot(T.T, dU)
        yield
        dL = jnp.where(row > col, -_dot_nt(dRb, U), 0.0)
        yield
        dbeta = (jnp.sum(dL * c["A"], axis=1, keepdims=True)
                 + jnp.sum(dRb * R, axis=1, keepdims=True))  # [C, 1]
        dR = beta * dRb
        dkbar = -_dot(dR, st)
        decay = jnp.exp(c["last"])                           # [1, K]
        dstate[j] = (dst * decay + _dot(do.T, c["qbar"])
                     - _dot(dR.T, c["kbar"]))
        yield
        (dk_row, dq), dk_col = _pairs_bwd(
            [beta * dL, dB], [k, q], k, c["levels"], c["bands"])
        yield
        diag = jnp.sum(jnp.where(row == col, dB, 0.0), axis=1, keepdims=True)
        dG = (k * (dk_row - dk_col) + q * dq + dqbar * c["qbar"]
              + dkbar * c["kbar"] - dktil * c["ktil"])
        # what the chunk's total decay carries, on its last row
        carried = (jnp.sum(dktil * c["ktil"], axis=0, keepdims=True)
                   + decay * jnp.sum(st * dst, axis=0, keepdims=True))
        dG = dG + jnp.where(_iota((C, kd), 0) == C - 1, carried, 0.0)
        dq_ref[0, :, keys] = (dq + diag * k
                              + dqbar * c["gam"]).astype(dq_ref.dtype)
        dk_ref[0, :, keys] = (dk_row + dk_col + diag * q + dkbar * c["gam"]
                              + dktil * c["to_end"]).astype(dk_ref.dtype)
        dv_ref[0, :, values] = dR.astype(dv_ref.dtype)
        yield
        dg_ref[0, :, keys] = _dot(jnp.where(col >= row, 1.0, 0.0), dG,
                                  precision=_HIGHEST).astype(dg_ref.dtype)
        # the column as a row: lane-dense in HBM
        dbeta_ref[0, j, 0] = jnp.sum(jnp.where(row == col, dbeta, 0.0),
                                     axis=0, keepdims=True)

    _side_by_side(head(j) for j in range(heads))


def _layouts(q, k, v, g, beta, chunk: int):
    """The kernels' operands from the public ones (padded to whole
    chunks): ``q, k, g [B, S, H·K]``, ``v [B, S, H·V]``, ``β [B, S/C, C,
    H]``."""
    b, s, h, kd = q.shape
    pad = (-s) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
            for z in (q, k, v, g, beta))
    sp = s + pad
    return (q.reshape(b, sp, h * kd), k.reshape(b, sp, h * kd),
            v.reshape(b, sp, h * v.shape[3]), g.reshape(b, sp, h * kd),
            beta.reshape(b, sp // chunk, chunk, h))


def _specs(chunk: int, heads: int, h: int, kd: int, vd: int, at):
    """Block specs of the five operands both kernels read, ``heads``
    heads wide; ``at`` maps the grid's chunk index to the chunk (the
    backward's runs down)."""
    def wide(width):
        return pl.BlockSpec((1, chunk, heads * width),
                            lambda b, h, c: (b, at(c), h))

    return [wide(kd), wide(kd), wide(vd), wide(kd),
            pl.BlockSpec((1, 1, chunk, h), lambda b, h, c: (b, at(c), 0, 0))]


def _heads_a_step(h: int, chunk: int, kd: int, vd: int) -> int:
    """Heads a grid step holds: the largest rung of ``_LADDER`` that
    divides ``h`` and whose step fits ``_VMEM_LIMIT``. A head of
    ``kda_bwd``, the larger kernel, keeps 61 f32 tiles of ``[128, 128]``
    at the cell's chunk and widths (Mosaic's plan for a described v5e:
    15.23 MiB at four heads a step, 30.21 at eight), reckoned here as 64
    tiles of ``[C, max(C, K, V)]``."""
    tile = 4 * chunk * max(chunk, kd, vd)
    return next(n for n in _LADDER
                if h % n == 0 and (n == 1 or n * 64 * tile <= _VMEM_LIMIT))


_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _forward(q, k, v, g, beta, chunk: int, interpret: bool,
             save_states: bool, dot=None):
    # under jit a body is traced once a shape and process, not at each of
    # the cell's twelve call sites (both bodies of four heads: ~1 s of
    # Python and lowering). ``dot`` is the module's ``_dot`` as a key of
    # that cache and nothing else: ``benchmark/tests/kda_micro.py`` swaps
    # it between two traces (whoever sets another ``_LADDER`` clears jax's
    # caches)
    del dot
    b, s, h, kd = q.shape
    vd = v.shape[3]
    heads = _heads_a_step(h, chunk, kd, vd)
    ops = _layouts(q, k, v, g, beta, chunk)
    sp = ops[0].shape[1]
    nc = sp // chunk
    out_shape = [jax.ShapeDtypeStruct((b, sp, h * vd), v.dtype)]
    out_specs = [pl.BlockSpec((1, chunk, heads * vd),
                              lambda b, h, c: (b, c, h))]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, vd, kd),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, heads, 1, vd, kd),
                                      lambda b, h, c: (b, h, c, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, heads=heads,
                          save_states=save_states),
        grid=(b, h // heads, nc),
        in_specs=_specs(chunk, heads, h, kd, vd, lambda c: c),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, vd, kd), jnp.float32)],
        interpret=interpret, name="kda_fwd", compiler_params=_PARAMS,
    )(*ops)
    o = out[0][:, :s].reshape(b, s, h, vd)
    return o, (out[1] if save_states else None)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _backward(q, k, v, g, beta, states, do, chunk: int, interpret: bool,
              dot=None):
    del dot
    b, s, h, kd = q.shape
    vd = v.shape[3]
    heads = _heads_a_step(h, chunk, kd, vd)
    ops = _layouts(q, k, v, g, beta, chunk)
    sp = ops[0].shape[1]
    nc = sp // chunk
    do = jnp.pad(do, ((0, 0), (0, sp - s), (0, 0), (0, 0))).reshape(
        b, sp, h * vd)

    def at(c):
        return nc - 1 - c

    specs = _specs(chunk, heads, h, kd, vd, at)
    keys, _, values, _, _ = specs
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, heads=heads),
        grid=(b, h // heads, nc),
        in_specs=specs + [
            pl.BlockSpec((1, heads, 1, vd, kd),
                         lambda b, h, c: (b, h, at(c), 0, 0)),
            values,
        ],
        out_specs=[
            keys, keys, values, keys,
            pl.BlockSpec((1, heads, 1, 1, chunk),
                         lambda b, h, c: (b, h, at(c), 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, h * kd), q.dtype),
            jax.ShapeDtypeStruct((b, sp, h * kd), k.dtype),
            jax.ShapeDtypeStruct((b, sp, h * vd), v.dtype),
            jax.ShapeDtypeStruct((b, sp, h * kd), g.dtype),
            jax.ShapeDtypeStruct((b, h, nc, 1, chunk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, vd, kd), jnp.float32)],
        interpret=interpret, name="kda_bwd", compiler_params=_PARAMS,
    )(*ops, states, do)
    dbeta = dbeta.reshape(b, h, sp).transpose(0, 2, 1)
    return (dq[:, :s].reshape(q.shape), dk[:, :s].reshape(k.shape),
            dv[:, :s].reshape(v.shape), dg[:, :s].reshape(g.shape),
            dbeta[:, :s].astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, chunk, interpret):
    return _forward(q, k, v, g, beta, chunk, interpret, False, _dot)[0]


def _kda_fwd(q, k, v, g, beta, chunk, interpret):
    o, states = _forward(q, k, v, g, beta, chunk, interpret, True, _dot)
    return o, (q, k, v, g, beta, states)


def _kda_bwd(chunk, interpret, residuals, do):
    return _backward(*residuals, do, chunk, interpret, _dot)


_kda.defvjp(_kda_fwd, _kda_bwd)


def _choose_chunk(seq_len: int) -> int:
    """``_CHUNK``, or for a shorter sequence the power of two that holds
    it (at least the 16 rows of a packed bf16 tile)."""
    return min(_CHUNK, max(16, 1 << (seq_len - 1).bit_length()))


def kda_scan(q, k, v, g, beta):
    """The gated delta rule of the module's docstring.

    ``q, k [B, S, H, K]`` (the caller's normalisation and scale already
    in them), ``v [B, S, H, V]``, ``g [B, S, H, K]`` (log-decays, <= 0,
    f32), ``beta [B, S, H]`` (f32) -> ``o [B, S, H, V]`` in ``v``'s
    dtype, differentiable in all five. The chunk is chosen from the
    sequence length (:func:`_choose_chunk`); the result does not depend
    on it beyond rounding (``tests/test_kda.py`` runs ``_kda`` at
    others)."""
    if (k.shape != q.shape or g.shape != q.shape
            or v.shape[:3] != q.shape[:3] or beta.shape != q.shape[:3]):
        raise ValueError(
            f"kda_scan: q{tuple(q.shape)} k{tuple(k.shape)} "
            f"v{tuple(v.shape)} g{tuple(g.shape)} beta{tuple(beta.shape)} "
            "do not fit")
    interpret = _interpret()
    if not interpret and (q.shape[3] % _LANES or v.shape[3] % _LANES):
        raise ValueError(
            f"kda_scan: heads of {q.shape[3]} key and {v.shape[3]} value "
            f"channels are no multiples of {_LANES} lanes")
    return _kda(q, k, v, _f32(g), _f32(beta), _choose_chunk(q.shape[1]),
                interpret)


# ------------------------------------------------ a decay that is ONE SCALAR
# a head a position (Gated DeltaNet, arXiv:2412.06464): ``g [B, S, H]``.
# With ``G_i`` the chunk's cumulative log-decay (a number a position),
# every pair's weight is one ``[C, C]`` matrix ``D_ij = exp(G_i − G_j)``
# (``i >= j``: the exponent is <= 0, masked BEFORE the exponential), so
#
#     A = stril((K Kᵀ) ∘ D)        B = tril((Q Kᵀ) ∘ D)
#
# are two matmuls and a product: the levels, the bands and their lane
# reductions of the channel-wise rule have no counterpart, and ``g``
# never exists a channel anywhere. The triangular inverse
# (:func:`_solve`), the transposed f32 state carried in VMEM, the
# backward's replay from the chunk-boundary states, the head-group grid
# and the heads side by side are the channel-wise kernels' own. The
# cumulative sums are taken OUTSIDE the kernels, on ``[B, S, H]`` (a
# thousandth of the stream): ``G`` comes in twice, a column a head (``[C,
# H]`` blocks, as ``β``) and a row a head (``[1, C]``, lane-dense), and
# its cotangent leaves as a row; ``dg`` is the sum of ``dG`` from a
# position to its chunk's end, outside too. Backward, beyond the
# channel-wise formulas (``dU, dB, dRβ, dL, dβ, dR, dK̄, dQ̄, dK̃, dS``
# are the same): with ``dA = β dL``,
#
#     dK = (dA∘D + (dA∘D)ᵀ) K + (dB∘D)ᵀ Q + dK̄ γ + dK̃ e^{G_C − G}
#     dQ = (dB∘D) K + dQ̄ γ
#     dG_i = Σ_j (P_ij − P_ji) + Σ_c (dQ̄∘Q̄ + dK̄∘K̄ − dK̃∘K̃)_ic,
#     P = dA∘A + dB∘B
#
# and the chunk's last row takes what ``G_C`` carries.
#
# LANES. ``Dk`` and ``Dv`` need not be equal nor multiples of 128, and
# nothing is padded: the operands stay ``[B, S, H·Dk]`` / ``[B, S, H·Dv]``
# as the model hands them over, a grid step takes a group of heads whose
# lanes are whole 128-lane tiles (four heads of 96 key channels are three
# tiles, of 192 value channels six), and a head is a lane slice of the
# step's block that may start between tiles — Mosaic shifts it, and a
# ``[C, 96]`` operand is padded in VMEM, never in HBM. The group need not
# divide the heads: 30 heads are seven groups of four and one of two,
# whose two missing heads are the part of an edge block that lies outside
# the arrays — read as whatever the buffer holds, computed beside the
# others (heads share nothing) and never written. Measured against every
# head padded to whole tiles in HBM (96 -> 128, 192 -> 256, five heads a
# step), at [1, 8192, 30, 96 | 192] on the v5e with the XLA ops around
# the kernels: 5.19 ms forward and 12.49 forward +
# backward against 6.12 and 13.32, every leaf equal to the bit. In the
# cell the kernels themselves are 6 - 10 % slower (the shifts), XLA's
# pads and slices are gone, and what it now spends relaying ``[B, S, H,
# D]`` tiles to the flat operands takes most of that back: + 0.1 % in
# rate, 0.2 GiB less in the step's plan
# (PERF.md section 6, PR 56). ``β`` is whatever the caller hands over: the
# rule contracts for ``β`` in (0, 2) (``I − β k kᵀ`` has the eigenvalue
# ``1 − β``), and nothing here bounds it.
#
# KEY HEADS. ``q, k [B, S, H_k, K]`` may have fewer heads than ``v [B, S,
# H, V]``, ``r = H / H_k`` value heads reading one key head (Qwen3-Next:
# 16 and 32): value head ``h`` reads key head ``h // r``, ``r`` read off
# the shapes. q and k stay ``[B, S, H_k·K]``; a grid step of ``n`` VALUE
# heads (``n % r == 0``) takes the block of its ``n / r`` key heads at the
# same head-block index. Inside the step a key head's f32 ``q``, ``k`` and
# ``[k ; q]·kᵀ`` are formed once, by the first of its value heads in the
# body's order, and read by all ``r`` chains; everything else of a value
# head's chain (``D``, ``A``, ``B``, ``T``, ``R``, ``U``, ``o``, the state)
# is what it is at equal heads, so ``o``, ``dv``, ``dg`` and ``dβ`` are the
# bits of the same kernels on copied q and k. ``gdn_bwd`` adds a key
# head's ``r`` shares of ``dq`` and of ``dk`` in f32, in the heads' order,
# and rounds the sum once into ``[B, S, H_k·K]`` (on copies each share is
# rounded and XLA's sum of them rounded again); the vjp's residuals hold
# q and k at ``H_k``. At ``r = 1`` the traced program is the one it was
# (``tests/test_kda.py`` pins Olmo Hybrid's call). Measured at the cell's
# ``[4, 8192, 16 | 32, 128]`` on the v5e against the same kernels on
# ``jnp.repeat``-ed q and k with XLA's copy and pair-sum around them:
# 14.4 ms forward and 34.2 forward + backward against 17.9 and 42.0
# (PERF.md section 6, PR 65).
_GDN_LADDER = (6, 5, 4, 3, 2, 1)


def _gdot(exact: bool, a, b, contract=((1,), (0,))):
    """The scalar-decay kernels' matmul of two f32 operands in THREE
    bf16 passes of the MXU, where the channel-wise kernels' :func:`_dot`
    takes one. ``T``, ``U``, ``R``, the state and every cotangent are
    f32 values that one pass rounds to bf16 each time they meet the MXU,
    a chunk after a chunk; measured on the v5e
    (``benchmark/tests/gdn_micro.py``; PERF.md section 6, PR 56) the
    one-pass kernels read 0.005 – 0.010 in every leaf against the
    recurrence where a RECURRENCE whose state is rounded to bf16 at
    every chunk boundary reads 0.001 – 0.003: the kernels would be less
    exact than a fault ``correct`` has to catch. Each operand is split
    into its bf16 rounding and the bf16 rounding of what is left (``x =
    hi + lo`` to 2^-17), and ``hi·hi + (hi·lo + lo·hi)`` is summed in f32
    (``lo·lo``, 2^-18 of the product, is dropped) — ``Precision.HIGH``,
    which Mosaic does not lower; the six passes of ``Precision.HIGHEST``
    agree no better with the recurrence and take 1.7 x the time.
    ``exact`` (the interpreter, where the CPU tests hold the kernels'
    mathematics to f32's rounding) multiplies in f32;
    ``tests/test_kda.py`` holds the three passes by themselves."""
    if exact:
        return _dot(a, b, contract, _HIGHEST)
    bf16 = jnp.bfloat16
    a_hi, b_hi = a.astype(bf16), b.astype(bf16)
    a_lo, b_lo = (a - _f32(a_hi)).astype(bf16), (b - _f32(b_hi)).astype(bf16)
    return _dot(a_hi, b_hi, contract) + (
        _dot(a_hi, b_lo, contract) + _dot(a_lo, b_hi, contract))


def _up(n: int, to: int = _LANES) -> int:
    return -(-n // to) * to


def _gdn_rungs(chunk: int, kd: int, vd: int, interpret: bool,
               r: int = 1) -> list:
    """The rungs of ``_GDN_LADDER`` a grid step may hold, in VALUE heads
    of which ``r`` read one key head: whole key heads (``n % r == 0``),
    blocks that are whole lane tiles (``(n / r)·K`` and ``n·V`` multiples
    of 128; the interpreter takes any) and a step that fits
    ``_VMEM_LIMIT`` — a head of ``gdn_bwd`` reckoned as 48 f32 tiles of
    ``[C, max(C, K, V)]``, the lanes rounded up."""
    tile = 4 * chunk * _up(max(chunk, kd, vd))
    return [n for n in _GDN_LADDER
            if n % r == 0
            and (interpret
                 or (n // r * kd % _LANES == 0 and n * vd % _LANES == 0))
            and (n == 1 or n * 48 * tile <= _VMEM_LIMIT)]


def _gdn_heads_a_step(h: int, chunk: int, kd: int, vd: int,
                      interpret: bool, r: int = 1) -> int:
    """Value heads a grid step holds: of :func:`_gdn_rungs` the one that
    leaves the fewest heads of the last group outside the arrays, the
    largest of those. (With ``r`` value heads a key head a rung is whole
    key heads, so the same head-block index finds a step's ``n / r`` key
    heads: at the cell's ``[4, 8192, 16 | 32, 128]`` four value heads are
    two key heads, 256 lanes of q and k beside 512 of v. Where no rung
    fits an ``r > 1``, :func:`gdn_scan` copies q and k and calls at
    equal heads.)"""
    rungs = _gdn_rungs(chunk, kd, vd, interpret, r)
    if not rungs:
        raise ValueError(
            f"gdn_scan: no group of {_GDN_LADDER} heads of {kd} key and "
            f"{vd} value channels fills whole {_LANES}-lane tiles")
    return min(rungs, key=lambda n: (-(-h // n) * n, -n))


def _column(block, j):
    """Column ``j`` of a ``[C, H]`` block as ``[C, 1]`` (a select and a
    lane sum: exact, and no one-lane slice)."""
    return jnp.sum(jnp.where(_iota(block.shape, 1) == j, block, 0.0),
                   axis=1, keepdims=True)


def _as_row(column):
    """``[C, 1]`` -> ``[1, C]`` through the diagonal (exact)."""
    C = column.shape[0]
    return jnp.sum(jnp.where(_iota((C, C), 0) == _iota((C, C), 1),
                             column, 0.0), axis=0, keepdims=True)


def _gdn_chunk(dot, key, v, gcol, grow, beta, st):
    """What both scalar-decay kernels compute of one chunk of one value
    head (f32 operands; ``key`` its key head's ``q``, ``k`` and, formed
    by the first of the value heads that read it, their product ``[k ;
    q]·kᵀ`` (:func:`_gdn_head`); ``gcol [C, 1]`` and ``grow [1, C]`` the
    chunk's cumulative log-decay, ``beta [C, 1]``, ``st [V, K]`` the
    transposed state that enters; ``dot`` the matmul, :func:`_gdot`; a
    generator: :func:`_side_by_side`)."""
    q, k = key["q"], key["k"]
    C = q.shape[0]
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    D = jnp.exp(jnp.where(row >= col, gcol - grow, _NEG))
    if "qk" not in key:
        key["qk"] = dot(jnp.concatenate([k, q], axis=0), k, _NT)  # [2C, C]
    qk = key["qk"]
    yield
    A = jnp.where(row > col, qk[:C] * D, 0.0)
    B = qk[C:] * D
    T = yield from _solve(beta * A, dot)
    last = jnp.sum(jnp.where(_iota((C, 1), 0) == C - 1, gcol, 0.0),
                   axis=0, keepdims=True)                # [1, 1]
    gam, to_end = jnp.exp(gcol), jnp.exp(last - gcol)    # [C, 1]
    kbar, qbar, ktil = k * gam, q * gam, k * to_end
    R = v - dot(kbar, st, _NT)           # [C, V]
    yield
    U = dot(T, beta * R)
    yield
    return dict(D=D, A=A, B=B, T=T, last=last, gam=gam, to_end=to_end,
                kbar=kbar, qbar=qbar, ktil=ktil, R=R, U=U)


def _gdn_head(j: int, heads: int, kd: int, vd: int, q_ref, k_ref, v_ref,
              gc_ref, gr_ref, beta_ref, key_heads: dict):
    """Value head ``j`` of a step's ``heads``: its lanes and its operands
    in f32 (:func:`_head`), the decay's column and row. The step's q and
    k blocks hold ``heads / r`` KEY heads, ``r`` read off the blocks'
    widths; value head ``j`` reads key head ``j // r``, whose f32 ``q``
    and ``k`` the first of its ``r`` value heads forms in ``key_heads``
    (one dict a grid step) for all of them."""
    at = j // (heads * kd // q_ref.shape[2])
    keys, values = slice(at * kd, (at + 1) * kd), slice(j * vd, (j + 1) * vd)
    mine = pl.program_id(1) * heads + j
    if at not in key_heads:
        key_heads[at] = dict(q=_f32(q_ref[0, :, keys]),
                             k=_f32(k_ref[0, :, keys]))
    return keys, values, (
        key_heads[at], _f32(v_ref[0, :, values]),
        _column(gc_ref[0, 0], mine), gr_ref[0, j, 0],
        _column(beta_ref[0, 0], mine))


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, beta_ref, o_ref,
                    *rest, heads: int, save_states: bool, exact: bool):
    """One (batch, group of ``heads`` heads, chunk), as
    :func:`_kda_fwd_kernel`."""
    state = rest[-1]                                     # [G, V, K]
    dot = functools.partial(_gdot, exact)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    _, vd, kd = state.shape
    key_heads = {}

    def head(j):
        st = state[j]
        if save_states:
            rest[0][0, j, 0] = st
        _, values, ins = _gdn_head(j, heads, kd, vd, q_ref, k_ref, v_ref,
                                   gc_ref, gr_ref, beta_ref, key_heads)
        c = yield from _gdn_chunk(dot, *ins, st)
        o = dot(c["qbar"], st, _NT) + dot(c["B"], c["U"])
        o_ref[0, :, values] = o.astype(o_ref.dtype)
        yield
        state[j] = st * jnp.exp(c["last"]) + dot(c["U"].T, c["ktil"])

    _side_by_side(head(j) for j in range(heads))


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, beta_ref, st_ref,
                    do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                    dstate, *, heads: int, exact: bool):
    """One (batch, group of ``heads`` heads, chunk), chunks last to
    first, as :func:`_kda_bwd_kernel`; ``dg_ref`` takes the cotangent of
    the CUMULATIVE log-decay, a row a head. ``dq`` and ``dk`` leave at
    the KEY heads: what a key head's ``r`` value heads give is added in
    f32, in the heads' order, and rounded once by the last of them."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    _, vd, kd = dstate.shape
    C = q_ref.shape[1]
    r = heads * kd // q_ref.shape[2]
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    dot = functools.partial(_gdot, exact)
    key_heads = {}

    def head(j):
        keys, values, ins = _gdn_head(j, heads, kd, vd, q_ref, k_ref, v_ref,
                                      gc_ref, gr_ref, beta_ref, key_heads)
        key, beta = ins[0], ins[-1]
        q, k = key["q"], key["k"]
        st, dst, do = st_ref[0, j, 0], dstate[j], _f32(do_ref[0, :, values])
        c = yield from _gdn_chunk(dot, *ins, st)
        U, R, T, D = c["U"], c["R"], c["T"], c["D"]
        dU = dot(c["B"].T, do) + dot(
            c["ktil"], dst, _NT)                    # [C, V]
        dB = jnp.where(row >= col, dot(do, U, _NT), 0.0)
        dqbar, dktil = dot(do, st), dot(U, dst)    # [C, K]
        yield
        dRb = dot(T.T, dU)
        yield
        dL = jnp.where(row > col, -dot(dRb, U, _NT), 0.0)
        yield
        dbeta = (jnp.sum(dL * c["A"], axis=1, keepdims=True)
                 + jnp.sum(dRb * R, axis=1, keepdims=True))  # [C, 1]
        dR = beta * dRb
        dkbar = -dot(dR, st)
        decay = jnp.exp(c["last"])                           # [1, 1]
        dstate[j] = (dst * decay + dot(do.T, c["qbar"])
                     - dot(dR.T, c["kbar"]))
        yield
        dA = beta * dL
        dkk, dqk = dA * D, dB * D
        dq = dot(dqk, k) + dqbar * c["gam"]
        dk = (dot(dkk + dkk.T, k) + dot(dqk.T, q) + dkbar * c["gam"]
              + dktil * c["to_end"])
        if j % r:
            dq, dk = key["dq"] + dq, key["dk"] + dk
        if (j + 1) % r:
            key["dq"], key["dk"] = dq, dk
        else:
            dq_ref[0, :, keys] = dq.astype(dq_ref.dtype)
            dk_ref[0, :, keys] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, values] = dR.astype(dv_ref.dtype)
        yield
        pairs = dA * c["A"] + dB * c["B"]
        moved = dktil * c["ktil"]
        mine = jnp.sum(dqbar * c["qbar"] + dkbar * c["kbar"] - moved,
                       axis=1, keepdims=True)                # [C, 1]
        # what the chunk's total decay carries, on its last position
        carried = (jnp.sum(jnp.sum(moved, axis=1, keepdims=True),
                           axis=0, keepdims=True)
                   + decay * jnp.sum(jnp.sum(st * dst, axis=1, keepdims=True),
                                     axis=0, keepdims=True))  # [1, 1]
        dG = jnp.sum(pairs.T - pairs + jnp.where(row == col, mine, 0.0),
                     axis=0, keepdims=True)                  # [1, C]
        dg_ref[0, j, 0] = dG + jnp.where(
            _iota((1, C), 1) == C - 1, carried, 0.0)
        dbeta_ref[0, j, 0] = _as_row(dbeta)

    _side_by_side(head(j) for j in range(heads))


def _gdn_layouts(q, k, v, g, beta, chunk: int):
    """The scalar-decay kernels' operands from the public ones, padded
    to whole chunks (``g = 0, β = 0``): ``q, k [B, S, H_k·K]`` at the
    heads they came with, ``v [B, S, H·V]``, the chunk's cumulative
    log-decay as columns ``[B, S/C, C, H]`` and as rows ``[B, H, S/C, 1,
    C]``, ``β [B, S/C, C, H]`` (``H`` the value heads)."""
    b, s, h, _ = v.shape
    sp = _up(s, chunk)

    def wide(z):
        return jnp.pad(z, ((0, 0), (0, sp - s), (0, 0), (0, 0))).reshape(
            b, sp, -1)

    g, beta = (jnp.pad(z, ((0, 0), (0, sp - s), (0, 0))).reshape(
        b, sp // chunk, chunk, h) for z in (g, beta))
    G = jnp.cumsum(g, axis=2)
    return (wide(q), wide(k), wide(v), G,
            G.transpose(0, 3, 1, 2)[:, :, :, None, :], beta)


def _gdn_specs(chunk: int, heads: int, key_heads: int, h: int, kd: int,
               vd: int, at):
    """Block specs of the six operands both scalar-decay kernels read
    (:func:`_specs`): a step of ``heads`` value heads takes its
    ``key_heads`` key heads of q and k at the same head-block index."""
    def wide(n, width):
        return pl.BlockSpec((1, chunk, n * width),
                            lambda b, h, c: (b, at(c), h))

    keys = wide(key_heads, kd)
    column = pl.BlockSpec((1, 1, chunk, h), lambda b, h, c: (b, at(c), 0, 0))
    rows = pl.BlockSpec((1, heads, 1, 1, chunk),
                        lambda b, h, c: (b, h, at(c), 0, 0))
    return [keys, keys, wide(heads, vd), column, rows, column]


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _gdn_forward(q, k, v, g, beta, chunk: int, interpret: bool,
                 save_states: bool):
    (b, s, h, vd), (hk, kd) = v.shape, q.shape[2:]
    heads = _gdn_heads_a_step(h, chunk, kd, vd, interpret, h // hk)
    ops = _gdn_layouts(q, k, v, g, beta, chunk)
    sp = ops[0].shape[1]
    nc = sp // chunk
    out_shape = [jax.ShapeDtypeStruct((b, sp, h * vd), v.dtype)]
    out_specs = [pl.BlockSpec((1, chunk, heads * vd),
                              lambda b, h, c: (b, c, h))]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, vd, kd),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, heads, 1, vd, kd),
                                      lambda b, h, c: (b, h, c, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, heads=heads,
                          save_states=save_states, exact=interpret),
        grid=(b, pl.cdiv(h, heads), nc),
        in_specs=_gdn_specs(chunk, heads, heads * hk // h, h, kd, vd,
                            lambda c: c),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, vd, kd), jnp.float32)],
        interpret=interpret, name="gdn_fwd", compiler_params=_PARAMS,
    )(*ops)
    o = out[0].reshape(b, sp, h, vd)[:, :s]
    return o, (out[1] if save_states else None)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _gdn_backward(q, k, v, g, beta, states, do, chunk: int, interpret: bool):
    (b, s, h, vd), (hk, kd) = v.shape, q.shape[2:]
    heads = _gdn_heads_a_step(h, chunk, kd, vd, interpret, h // hk)
    ops = _gdn_layouts(q, k, v, g, beta, chunk)
    sp = ops[0].shape[1]
    nc = sp // chunk
    do = jnp.pad(do, ((0, 0), (0, sp - s), (0, 0), (0, 0))).reshape(
        b, sp, h * vd)

    def at(c):
        return nc - 1 - c

    specs = _gdn_specs(chunk, heads, heads * hk // h, h, kd, vd, at)
    keys, _, values, _, rows, _ = specs
    dq, dk, dv, dG, dbeta = pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, heads=heads, exact=interpret),
        grid=(b, pl.cdiv(h, heads), nc),
        in_specs=specs + [
            pl.BlockSpec((1, heads, 1, vd, kd),
                         lambda b, h, c: (b, h, at(c), 0, 0)),
            values,
        ],
        out_specs=[keys, keys, values, rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, hk * kd), q.dtype),
            jax.ShapeDtypeStruct((b, sp, hk * kd), k.dtype),
            jax.ShapeDtypeStruct((b, sp, h * vd), v.dtype),
            jax.ShapeDtypeStruct((b, h, nc, 1, chunk), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nc, 1, chunk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, vd, kd), jnp.float32)],
        interpret=interpret, name="gdn_bwd", compiler_params=_PARAMS,
    )(*ops, states, do)
    # dg: the sum of dG from a position to its chunk's end
    dg = jnp.flip(jnp.cumsum(jnp.flip(dG, axis=-1), axis=-1), axis=-1)
    dg, dbeta = (z.reshape(b, h, sp).transpose(0, 2, 1)[:, :s]
                 for z in (dg, dbeta))

    def narrow(z, like):
        return z.reshape(b, sp, like.shape[2], -1)[:, :s].astype(like.dtype)

    return (narrow(dq, q), narrow(dk, k), narrow(dv, v),
            dg.astype(g.dtype), dbeta.astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn(q, k, v, g, beta, chunk, interpret):
    return _gdn_forward(q, k, v, g, beta, chunk, interpret, False)[0]


def _gdn_fwd(q, k, v, g, beta, chunk, interpret):
    o, states = _gdn_forward(q, k, v, g, beta, chunk, interpret, True)
    return o, (q, k, v, g, beta, states)


def _gdn_bwd(chunk, interpret, residuals, do):
    return _gdn_backward(*residuals, do, chunk, interpret)


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gdn_scan(q, k, v, g, beta):
    """The gated delta rule with ONE decay a head a position:

        S_t = exp(g_t) (I − β_t k_t k_tᵀ) S_{t-1} + β_t k_t v_tᵀ,
        o_t = S_tᵀ q_t,       S_0 = 0.

    ``q, k [B, S, H_k, K]`` (the caller's normalisation and scale already
    in them), ``v [B, S, H, V]`` with ``H_k`` dividing ``H``: value head
    ``h`` reads key head ``h // (H / H_k)`` where it lies, nothing is
    copied, and ``dq``, ``dk`` come back at the key heads, their value
    heads' shares added in f32 — ``K`` and ``V`` equal or not; on the TPU
    widths of which some group of heads fills whole lane tiles
    (:func:`_gdn_heads_a_step`), others are refused —, ``g [B, S, H]``
    (log-decays, <= 0, f32), ``beta [B, S, H]`` (f32; the rule contracts
    for ``β`` in (0, 2), the op bounds nothing) -> ``o [B, S, H, V]`` in
    ``v``'s dtype, differentiable in all five. It is :func:`kda_scan`
    fed ``g`` broadcast over the key channels (``tests/test_kda.py``),
    in kernels of its own (``gdn_fwd``, ``gdn_bwd``). The chunk is
    chosen from the sequence length; a sequence that is no multiple is
    padded at its end. Where no rung of the ladder is whole key heads
    that fill whole lane tiles (:func:`_gdn_rungs`), q and k are copied
    to the value heads here and the kernels run at equal heads:
    ``TRACED["gdn_value_group_copies"]`` counts those calls as traced."""
    if (q.ndim != 4 or k.shape != q.shape or v.ndim != 4
            or v.shape[:2] != q.shape[:2] or v.shape[2] % q.shape[2]
            or g.shape != v.shape[:3] or beta.shape != v.shape[:3]):
        raise ValueError(
            f"gdn_scan: q{tuple(q.shape)} k{tuple(k.shape)} "
            f"v{tuple(v.shape)} g{tuple(g.shape)} beta{tuple(beta.shape)} "
            "do not fit")
    chunk, interpret = _choose_chunk(q.shape[1]), _interpret()
    r = v.shape[2] // q.shape[2]
    if r > 1 and not _gdn_rungs(chunk, q.shape[3], v.shape[3], interpret, r):
        TRACED.incr("gdn_value_group_copies")
        q, k = (jnp.repeat(z, r, axis=2) for z in (q, k))
    return _gdn(q, k, v, _f32(g), _f32(beta), chunk, interpret)
