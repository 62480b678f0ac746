"""Pallas kernels for the selective scan of Mamba-1 (arXiv:2312.00752):
forward and backward under one ``jax.custom_vjp``.

Per channel ``c`` a state of ``N`` numbers, ``S_0 = 0``:

    S_t[c, n] = exp(Δ_t[c]·A[c, n])·S_{t-1}[c, n] + Δ_t[c]·x_t[c]·B_t[n]
    y_t[c]    = Σ_n C_t[n]·S_t[c, n] + D[c]·x_t[c]

The decay differs by channel AND by state index, so nothing here is a
matmul: ``ops/ssd.py`` (one scalar decay a head) and ``ops/kda.py`` (a
decay a key channel, delta rule) factor theirs into chunk-sized products
and this recurrence does not — per channel the kernel matrix of a chunk
of ``Q`` positions would be its own ``[Q, Q]``. It is vector-unit work,
position by position, ``B·S·C·N`` state updates a call, and written in
``jnp`` it materialises ``[B, S, C, N]`` f32 (2.7 GB a row of 8192 at
5120 channels): the kernel is what makes the layer fit.

The kernels. One grid step is one (batch row, block of ``_LANE_BLOCK``
channels, chunk of ``_CHUNK`` positions), the chunks innermost and in
order, the state ``[N, channels]`` f32 — the state index along the
sublanes, the channels along the lanes — carried from step to step in a
VMEM scratch. Inside, a loop over groups of ``_GROUP`` positions (one
packed bf16 tile of rows, loaded and stored aligned) whose positions are
laid out straight-line: ``Δ_t`` and ``Δ_t x_t`` are rows ``[1,
channels]`` broadcast down the sublanes; ``B_t`` and ``C_t`` must be
COLUMNS ``[N, 1]`` broadcast along the lanes. An array with a singleton
minor dimension is padded 128 times over in HBM (``[4, 8192·16, 1]`` f32:
268 MB), so a group's ``_GROUP·N`` numbers travel as rows of 128 lanes —
``[B, S/_GROUP, 8, 128]`` f32, the rows a group does not fill zero — and
the kernel turns a group's tile into columns with one product on the
otherwise idle MXU, ``I₁₂₈·Xᵀ`` (``_columns``; exact: ``highest``).
``y_t`` is a sum down the sublanes.

``s6_bwd`` walks the chunks LAST TO FIRST with the state's cotangent
``G`` in a scratch. **What the backward keeps**: the scan's inputs and
the state that ENTERS each chunk, ``[B, S/Q, N, C]`` f32, written by the
forward kernel only when it runs as the vjp's forward rule (168 MB a
layer at [4, 8192, 5120], Q 64; under ``jax.checkpoint`` it lives for
that layer's backward alone). A chunk's backward first runs the chunk
forward again from that state and keeps every position's state in VMEM
(``[(Q + 1)·N, channels]`` f32), then walks back: with ``a_t = exp(Δ_t
A)``, ``G_t = C_t·dy_t + a_{t+1} G_{t+1}`` and ``w_t = G_t ⊙ S_{t−1} ⊙
a_t`` (the cotangent of the exponent ``Δ_t A``),

    dx_t = Δ_t Σ_n G_t B_t + D dy_t      dΔ_t = x_t Σ_n G_t B_t + Σ_n w_t A
    dB_t = Σ_c G_t Δ_t x_t               dC_t = Σ_c dy_t S_t
    dA   = Σ_t w_t Δ_t                   dD   = Σ_t dy_t x_t

``dB`` and ``dC`` are sums ALONG the lanes: each position adds its lane
tiles into a ``[N, 128]`` partial, and a group's partials are summed
across the lanes AND laid back along them by the same kind of product,
``1·Pᵀ`` (``_lane_sums``). They leave as a block of channels' parts
``[B, C/block, S/_GROUP, 8, 128]`` and ``dA``, ``dD`` as a batch row's
``[B, N, C]`` / ``[B, 1, C]`` (accumulated over the chunks in the output
block); the sums over blocks and rows are XLA's, on arrays of megabytes.

What is which dtype: ``x, B, C`` arrive and ``y, dx, dB, dC`` leave in
the input dtype (bf16 in the models); ``Δ`` (already through its
softplus), ``A``, ``D``, ``dΔ``, ``dA``, ``dD`` are f32; the decays, the
state, its cotangent and every sum are f32.

A sequence that is no multiple of the chunk is padded with ``Δ = 0``
positions at its end (decay 1, input 0: they change nothing before
them). ``N`` is a multiple of 8 that divides 128. Off the TPU the same
kernels run in Pallas's interpreter (the CPU tests), chosen from the
backend alone. On the TPU the channels must be a multiple of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["s6_scan"]

_LANES = 128
# channels a grid step: [N 16, 512] f32 is 8 vector registers a quantity
_LANE_BLOCK = 512
# positions a chunk: what the backward keeps in VMEM grows with it (a
# state a position), the boundary states in HBM with its inverse
_CHUNK = 64
# positions laid out straight-line inside the loop: a packed bf16 tile
_GROUP = 16


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _f32(a):
    return a.astype(jnp.float32)


def _group(g):
    """Rows ``g·_GROUP … (g + 1)·_GROUP`` of a block: an aligned slice."""
    return pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)


def _rows(ref, g):
    """Group ``g``'s positions of a block's ``[1, rows, lanes]``."""
    return ref[0, _group(g), :]


def _down(prod):
    """``[N, lanes] -> [1, lanes]``: the sum over the state index."""
    return jnp.sum(prod, axis=0, keepdims=True)


def _dot_nt(a, b):
    """``a·bᵀ`` in true float32: the MXU as a transpose unit."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _identity():
    """``I₁₂₈`` in float32, made once a kernel instance."""
    return (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
            ).astype(jnp.float32)


def _columns(eye, tile, n: int):
    """A group's ``[8, 128]`` tile of ``B`` or ``C`` (position-major, ``N``
    numbers a position, 128 a row) as ``_GROUP`` columns ``[N, 1]``:
    ``I·Xᵀ`` is ``[128, 8]``, a row of the tile down each lane."""
    cols = _dot_nt(eye, tile)
    per = _LANES // n                         # positions a row of the tile
    return [cols[(j % per) * n:(j % per + 1) * n, j // per:j // per + 1]
            for j in range(_GROUP)]


def _lane_sums(parts, n: int):
    """``_GROUP`` partials ``[N, lanes]`` (a position each) -> the group's
    ``[8, 128]`` tile of their sums along the lanes, laid out as
    :func:`_columns` reads it: ``1·Pᵀ`` of 128 rows of partials is their
    row sums along the lanes."""
    flat = jnp.concatenate(parts, axis=0)     # [_GROUP·N, lanes]
    ones = jnp.ones((8, flat.shape[1]), jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)
    out = jnp.zeros((8, _LANES), jnp.float32)
    for r in range(_GROUP * n // _LANES):
        sums = _dot_nt(ones, flat[r * _LANES:(r + 1) * _LANES])
        out = jnp.where(row == r, sums, out)
    return out


def _s6_fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                   n: int, save_states: bool):
    s_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    if save_states:
        rest[0][0, 0] = s_ref[...]            # the state that enters
    a, d, eye = a_ref[...], d_ref[...], _identity()

    def group(g, s):
        x, dt = _f32(_rows(x_ref, g)), _rows(dt_ref, g)
        u = dt * x
        bcol = _columns(eye, b_ref[0, g], n)
        ccol = _columns(eye, c_ref[0, g], n)
        ys = []
        for j in range(_GROUP):
            s = jnp.exp(dt[j:j + 1] * a) * s + bcol[j] * u[j:j + 1]
            ys.append(_down(ccol[j] * s))
        y = jnp.concatenate(ys, axis=0) + d * x
        y_ref[0, _group(g), :] = y.astype(y_ref.dtype)
        return s

    s_ref[...] = jax.lax.fori_loop(
        0, x_ref.shape[1] // _GROUP, group, s_ref[...])


def _lane_tiles(prod):
    """``[N, lanes] -> [N, 128]`` (the whole of a narrower block): the
    block's lane tiles added up; :func:`_lane_sums` sums across them."""
    width = prod.shape[1]
    if width % _LANES:
        return prod
    out = prod[:, :_LANES]
    for i in range(1, width // _LANES):
        out = out + prod[:, i * _LANES:(i + 1) * _LANES]
    return out


def _s6_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, st_ref, dy_ref,
                   dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                   hist_ref, g_ref, *, n: int):
    groups = x_ref.shape[1] // _GROUP
    a, d, eye = a_ref[...], d_ref[...], _identity()

    @pl.when(pl.program_id(2) == 0)           # the LAST chunk: walk's start
    def _start():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    def state_at(t):
        """Rows of ``hist``: the state BEFORE position ``t`` (after ``t −
        1``; the entering state at 0)."""
        return pl.ds(pl.multiple_of(t * n, n), n)

    # -- the chunk forward again, every position's state kept
    hist_ref[state_at(0), :] = st_ref[0, 0]

    def replay(g, s):
        x, dt = _f32(_rows(x_ref, g)), _rows(dt_ref, g)
        u = dt * x
        bcol = _columns(eye, b_ref[0, g], n)
        for j in range(_GROUP):
            s = jnp.exp(dt[j:j + 1] * a) * s + bcol[j] * u[j:j + 1]
            hist_ref[state_at(g * _GROUP + j + 1), :] = s
        return s

    jax.lax.fori_loop(0, groups, replay, st_ref[0, 0])

    # -- and back
    def walk(i, carry):
        gs, da = carry
        g = groups - 1 - i
        x, dt = _f32(_rows(x_ref, g)), _rows(dt_ref, g)
        dy = _f32(_rows(dy_ref, g))
        u = dt * x
        bcol = _columns(eye, b_ref[0, g], n)
        ccol = _columns(eye, c_ref[0, g], n)
        into_u, into_e, for_b, for_c = ([None] * _GROUP for _ in range(4))
        for j in reversed(range(_GROUP)):
            t = g * _GROUP + j
            s_prev, s = hist_ref[state_at(t), :], hist_ref[state_at(t + 1), :]
            decay = jnp.exp(dt[j:j + 1] * a)
            gs = gs + ccol[j] * dy[j:j + 1]               # G_t
            for_c[j] = _lane_tiles(dy[j:j + 1] * s)
            for_b[j] = _lane_tiles(gs * u[j:j + 1])
            w = gs * s_prev * decay
            into_u[j] = _down(gs * bcol[j])
            into_e[j] = _down(w * a)
            da = da + w * dt[j:j + 1]
            gs = decay * gs
        into_u = jnp.concatenate(into_u, axis=0)
        dx_ref[0, _group(g), :] = (dt * into_u + d * dy).astype(dx_ref.dtype)
        ddt_ref[0, _group(g), :] = x * into_u + jnp.concatenate(into_e,
                                                               axis=0)
        db_ref[0, 0, g] = _lane_sums(for_b, n)
        dc_ref[0, 0, g] = _lane_sums(for_c, n)
        dd_ref[0] += jnp.sum(dy * x, axis=0, keepdims=True)
        return gs, da

    gs, da = jax.lax.fori_loop(
        0, groups, walk, (g_ref[...], jnp.zeros_like(g_ref)))
    g_ref[...] = gs
    da_ref[0] += da


def _lane_block(channels: int) -> int:
    """Channels a grid step: the largest of 512 / 256 / 128 that divides
    them, or all of them."""
    for block in (_LANE_BLOCK, 256, _LANES):
        if channels % block == 0:
            return block
    return channels


def _group_tiles(z, groups: int, n: int):
    """``[B, S, N] -> [B, S/_GROUP, 8, 128]`` f32: a group's numbers as
    rows of 128 lanes, the tile's other rows zero."""
    b = z.shape[0]
    z = _f32(z).reshape(b, groups, _GROUP * n // _LANES, _LANES)
    return jnp.pad(z, ((0, 0), (0, 0), (0, 8 - z.shape[2]), (0, 0)))


def _from_group_tiles(z, n: int):
    """:func:`_group_tiles` back: ``[B, groups, 8, 128] -> [B, S, N]``."""
    b, groups = z.shape[:2]
    return z[:, :, :_GROUP * n // _LANES].reshape(b, groups * _GROUP, n)


def _layouts(x, dt, a, bm, cm, d, chunk: int):
    """The kernels' operands from the scan's: the sequence padded to
    whole chunks, ``A`` as ``[N, C]``, ``B`` and ``C`` as
    :func:`_group_tiles`, ``D`` as a row."""
    s, c = x.shape[1:]
    n = a.shape[1]
    pad = -s % chunk

    def padded(z):
        return jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z

    groups = (s + pad) // _GROUP
    return (padded(x), padded(_f32(dt)), _f32(a).T,
            _group_tiles(padded(bm), groups, n),
            _group_tiles(padded(cm), groups, n), _f32(d).reshape(1, c))


def _specs(chunk: int, block: int, n: int, at):
    """Block specs of the six operands; ``at`` maps the grid's chunk index
    to the chunk (the backward walks them in reverse)."""
    tiles = (1, chunk // _GROUP, 8, _LANES)
    return [
        pl.BlockSpec((1, chunk, block), lambda b, c, k: (b, at(k), c)),
        pl.BlockSpec((1, chunk, block), lambda b, c, k: (b, at(k), c)),
        pl.BlockSpec((n, block), lambda b, c, k: (0, c)),
        pl.BlockSpec(tiles, lambda b, c, k: (b, at(k), 0, 0)),
        pl.BlockSpec(tiles, lambda b, c, k: (b, at(k), 0, 0)),
        pl.BlockSpec((1, block), lambda b, c, k: (0, c)),
    ]


def _forward(x, dt, a, bm, cm, d, chunk: int, interpret: bool,
             save_states: bool):
    b, s, c = x.shape
    n = a.shape[1]
    ops = _layouts(x, dt, a, bm, cm, d, chunk)
    sp = ops[0].shape[1]
    nc, block = sp // chunk, _lane_block(c)
    out_shape = [jax.ShapeDtypeStruct((b, sp, c), x.dtype)]
    out_specs = [pl.BlockSpec((1, chunk, block), lambda b, c, k: (b, k, c))]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, n, c), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, n, block),
                                      lambda b, c, k: (b, k, 0, c)))
    out = pl.pallas_call(
        functools.partial(_s6_fwd_kernel, n=n, save_states=save_states),
        grid=(b, c // block, nc),
        in_specs=_specs(chunk, block, n, lambda k: k),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, block), jnp.float32)],
        interpret=interpret, name="s6_fwd",
    )(*ops)
    return out[0][:, :s], (out[1] if save_states else None)


def _backward(x, dt, a, bm, cm, d, states, dy, chunk: int, interpret: bool):
    b, s, c = x.shape
    n = a.shape[1]
    ops = _layouts(x, dt, a, bm, cm, d, chunk)
    sp = ops[0].shape[1]
    nc, block = sp // chunk, _lane_block(c)
    blocks = c // block
    if sp != s:
        dy = jnp.pad(dy, ((0, 0), (0, sp - s), (0, 0)))
    f32 = jnp.float32

    def back(k):
        return nc - 1 - k

    def tile(b, c, k):
        return (b, back(k), c)

    def parts(b, c, k):
        return (b, c, back(k), 0, 0)

    dx, ddt, db, dc, da, dd = pl.pallas_call(
        functools.partial(_s6_bwd_kernel, n=n),
        grid=(b, blocks, nc),
        in_specs=_specs(chunk, block, n, back) + [
            pl.BlockSpec((1, 1, n, block), lambda b, c, k: (b, back(k), 0, c)),
            pl.BlockSpec((1, chunk, block), tile),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block), tile),
            pl.BlockSpec((1, chunk, block), tile),
            pl.BlockSpec((1, 1, chunk // _GROUP, 8, _LANES), parts),
            pl.BlockSpec((1, 1, chunk // _GROUP, 8, _LANES), parts),
            pl.BlockSpec((1, n, block), lambda b, c, k: (b, 0, c)),
            pl.BlockSpec((1, 1, block), lambda b, c, k: (b, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, c), x.dtype),
            jax.ShapeDtypeStruct((b, sp, c), f32),
            jax.ShapeDtypeStruct((b, blocks, sp // _GROUP, 8, _LANES), f32),
            jax.ShapeDtypeStruct((b, blocks, sp // _GROUP, 8, _LANES), f32),
            jax.ShapeDtypeStruct((b, n, c), f32),
            jax.ShapeDtypeStruct((b, 1, c), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM(((chunk + 1) * n, block), f32),   # a state a position
            pltpu.VMEM((n, block), f32),                 # G
        ],
        interpret=interpret, name="s6_bwd",
    )(*ops, states, dy)

    def rows(z, like):          # the blocks' parts -> [B, S, N]
        return _from_group_tiles(
            jnp.sum(z, axis=1), n)[:, :s].astype(like.dtype)

    return (dx[:, :s], ddt[:, :s].astype(dt.dtype),
            jnp.sum(da, axis=0).T.astype(a.dtype), rows(db, bm), rows(dc, cm),
            jnp.sum(dd, axis=(0, 1)).astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _s6(x, dt, a, bm, cm, d, chunk, interpret):
    return _forward(x, dt, a, bm, cm, d, chunk, interpret, False)[0]


def _s6_fwd(x, dt, a, bm, cm, d, chunk, interpret):
    y, states = _forward(x, dt, a, bm, cm, d, chunk, interpret, True)
    return y, (x, dt, a, bm, cm, d, states)


def _s6_bwd(chunk, interpret, residuals, dy):
    return _backward(*residuals, dy, chunk, interpret)


_s6.defvjp(_s6_fwd, _s6_bwd)


def _choose_chunk(seq_len: int) -> int:
    """``_CHUNK``, or the whole of a shorter sequence rounded up to the
    ``_GROUP`` rows of a packed bf16 tile."""
    return min(_CHUNK, -(-seq_len // _GROUP) * _GROUP)


def s6_scan(x, dt, A, B, C, D):
    """The selective scan of the module's docstring.

    ``x [B, S, C]``, ``dt [B, S, C]`` (``Δ``: positive, f32), ``A [C, N]``
    (negative, f32), ``B, C [B, S, N]``, ``D [C]`` -> ``y [B, S, C]`` in
    ``x``'s dtype, differentiable in all six. The chunk is chosen from
    the sequence length (:func:`_choose_chunk`); the result does not
    depend on it beyond rounding (``tests/test_s6.py`` runs ``_s6`` at
    others)."""
    if (dt.shape != x.shape or B.shape != C.shape
            or A.shape != (x.shape[2], B.shape[2]) or D.shape != x.shape[2:]
            or B.shape[:2] != x.shape[:2]):
        raise ValueError(
            f"s6_scan: x{tuple(x.shape)} dt{tuple(dt.shape)} "
            f"A{tuple(A.shape)} B{tuple(B.shape)} C{tuple(C.shape)} "
            f"D{tuple(D.shape)} do not fit")
    interpret = _interpret()
    if A.shape[1] % 8 or _LANES % A.shape[1]:
        raise ValueError(
            f"s6_scan: {A.shape[1]} states are no multiple of 8 that "
            f"divides 128")
    if not interpret and x.shape[2] % _LANES:
        raise ValueError(
            f"s6_scan on the TPU: {x.shape[2]} channels are no multiple of "
            f"128")
    return _s6(x, dt, A, B, C, D, _choose_chunk(x.shape[1]), interpret)
