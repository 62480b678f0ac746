"""Dropless sparse-expert path: top-k routing, sort by expert, grouped
matmuls over ragged groups, unsort, weight, sum.

Every (token, expert) assignment is computed: there is no capacity and
nothing is dropped, so the result equals "every expert on every token,
weighted by a matrix that is zero outside the top k" (the benchmark's
plain reference is written that way). All shapes are static and the
group sizes are data, so one compiled program serves every routing; an
empty group and a group that holds every row are ordinary inputs.

A layer that holds every expert moves ``[N*k]`` rows whatever the
routing. The data movement is gathers in both directions: a
permutation's transpose is the inverse permutation, so the backward
pass of the sort gathers through ``inverse`` instead of scattering, and
the gradient of the ``k`` copies of a token is a gather and a sum over
``k``.

A layer that holds a share of the experts (one chip of an
expert-parallel deployment) moves the rows it holds: held rows sort
first, and a pass takes :func:`held_capacity` rows of the expert order
— a static size, the rows an even router sends times a slack — gathers
them from their tokens, runs them through the experts and sums them
into their tokens row by row (a scatter-add in float32; its transpose
is the gather). How many rows are held is data, and so is the number of
passes (:func:`_share_mlp`): one for a router as even as the deployment
expects, none where nothing is held, as many as it takes where every
assignment falls on held experts — dropless in one program, and nothing
of ``[N*k, d]`` exists. Per row the scatter-add costs twice the
gathers, so a share that holds nearly every row pays more than a layer
that holds every expert; it is the price of one code path.

The grouped matmul is jax's megablox Pallas kernel
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and for
the gradient of the rows, ``tgmm`` for the gradient of the weights;
the instructions are named ``gmm*`` / ``*tgmm*`` in a trace). Chosen
over ``jax.lax.ragged_dot`` by a chip measurement at the OLMoE cell's
shapes (PERF.md, PR 26: the expert MLP forward and backward in 45.9 ms
against 59.5). Off the TPU the same kernel runs in Pallas's interpreter.
Nothing of ``parallel/moe.py`` (GShard top-2 with a capacity) is used.
"""

from __future__ import annotations

import functools
from fractions import Fraction
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["Dispatch", "take_chosen", "top_k_routing", "sort_by_expert",
           "gather_tokens", "grouped_matmul", "swiglu_experts",
           "reglu_experts", "relu2_experts", "combine", "held_capacity",
           "moe_mlp"]


class Dispatch(NamedTuple):
    """The sort of ``N*k`` assignments by expert. Assignment ``a`` is
    token ``a // k``'s ``a % k``-th choice; row ``i`` of the expert order
    is assignment ``order[i]`` and ``inverse[order[i]] == i``."""
    order: Any         # [N*k] int32
    inverse: Any       # [N*k] int32
    group_sizes: Any   # [E] int32, rows per expert, sums to N*k


def take_chosen(values: Any, experts: Any) -> Any:
    """``values[n, experts[n, j]]`` as ``[N, k]``, of ``values`` ``[N, E]``
    (or ``[1, E]``: the same row for every token), by compare and select:
    a sum over ``E`` with one term that is not zero, so the bits are a
    gather's (``jnp.take_along_axis``; a chosen ``-0.0`` alone reads
    ``+0.0``), and its transpose — the ``[N, k]`` cotangents put back at
    their columns of ``[N, E]`` — is a sum over ``k``, not a scatter.
    XLA's TPU gather and scatter move an element at a time (3.3 – 3.5 ms
    for ``32768 x 10`` of 512 columns or of 10); the fused select is a
    pass over ``N·k·E`` (0.5 and 0.25 ms there: PERF.md, PR 64)."""
    columns = jnp.arange(values.shape[-1], dtype=experts.dtype)
    return jnp.sum(jnp.where(experts[..., None] == columns,
                             values[:, None, :], 0), axis=-1)


def top_k_routing(scores: Any, k: int, bias: Any = None,
                  renormalise: bool = False,
                  scale: float = 1.0, eps: float = 1e-20,
                  softmax: bool = False) -> Tuple[Any, Any]:
    """``(weights [N, k], experts [N, k] int32)``. Without ``bias``: the
    ``k`` largest router scores of each token, as they are (OLMoE's
    softmax probabilities, not renormalised). With a ``bias [E]``
    (DeepSeek-V3's ``noaux_tc``): the ``k`` largest of ``scores + bias``
    choose, and the weights are the chosen experts' ``scores`` — the bias
    selects and never weights, and no gradient reaches it.
    ``renormalise`` divides a token's weights by their sum + ``eps``
    (1e-20: DeepSeek-V3's; LFM2 publishes 1e-6); ``softmax`` takes
    ``scores`` for logits and weighs by the softmax over the CHOSEN ones
    (SmallThinker's: the softmax over all, renormalised); ``scale``
    multiplies them."""
    if bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias).astype(scores.dtype), k)
        weights = take_chosen(scores, experts)
    if softmax:
        weights = jax.nn.softmax(weights, axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return (weights * scale if scale != 1.0 else weights), experts


def sort_by_expert(experts: Any, n_experts: int) -> Dispatch:
    """Stable sort of the flattened ``[N, k]`` expert ids."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    n = flat.shape[0]
    inverse = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True
    )
    # an id past the last expert (a share's sentinel) is dropped: the
    # default of a scatter
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    return Dispatch(order, inverse, sizes)


@jax.custom_vjp
def _permute(x: Any, idx: Any, inv: Any) -> Any:
    """``x[idx]`` for a permutation ``idx`` with inverse ``inv``."""
    return x[idx]


def _permute_fwd(x, idx, inv):
    return x[idx], (idx, inv)


def _permute_bwd(res, g):
    idx, inv = res
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def _gather_copies(h: Any, order: Any, inverse: Any) -> Any:
    """Rows of ``h [N, d]`` in expert order: ``h[order // k]``."""
    return h[order // (order.shape[0] // h.shape[0])]


def _gather_copies_fwd(h, order, inverse):
    return _gather_copies(h, order, inverse), (inverse, h.shape[0])


def _gather_copies_bwd(res, g):
    inverse, n = res
    # back in assignment order a token's k copies are adjacent rows
    return g[inverse].reshape(n, -1, g.shape[-1]).sum(axis=1), None, None


_gather_copies.defvjp(_gather_copies_fwd, _gather_copies_bwd)


def gather_tokens(h: Any, dispatch: Dispatch) -> Any:
    """``[N, d]`` tokens -> ``[N*k, d]`` rows grouped by expert."""
    return _gather_copies(h, dispatch.order, dispatch.inverse)


# (rows, contraction, columns) of one tile: the fastest of the six that
# fit VMEM at [131072, 2048] x [64, 2048, 1024], forward and backward
# (PERF.md, PR 26); clamped to a smaller problem.
_TILING = (512, 1024, 1024)


def _interpret() -> bool:
    """Off the TPU the kernel runs in Pallas's interpreter (the CPU
    tests); chosen from the backend alone, as ``ops/attention.py``."""
    return jax.default_backend() != "tpu"


def grouped_matmul(x: Any, w: Any, group_sizes: Any) -> Any:
    """``x [M, K]`` whose rows lie in ``len(group_sizes)`` consecutive
    groups, times ``w [G, K, N]`` group by group -> ``[M, N]`` in ``x``'s
    dtype, accumulated in float32. ``M`` is a multiple of the row tile
    (512) or below it."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    tiling = tuple(min(t, n) for t, n in
                   zip(_TILING, (x.shape[0], x.shape[1], w.shape[2])))
    # positional: custom_vjp's nondiff_argnums are positions
    return megablox.gmm(x, w, group_sizes, x.dtype, tiling, None, None,
                        False, _interpret())


def _gated_experts(act, x: Any, gate: Any, up: Any, down: Any,
                   group_sizes: Any) -> Any:
    dt = x.dtype
    g = grouped_matmul(x, gate.astype(dt), group_sizes)
    u = grouped_matmul(x, up.astype(dt), group_sizes)
    a = (act(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(dt)
    return grouped_matmul(a, down.astype(dt), group_sizes)


def swiglu_experts(x: Any, gate: Any, up: Any, down: Any,
                   group_sizes: Any) -> Any:
    """``(silu(x·gate[e]) * (x·up[e])) · down[e]`` for the rows of each
    expert ``e``; the weights are cast to ``x``'s dtype, the activation
    is computed in float32."""
    return _gated_experts(jax.nn.silu, x, gate, up, down, group_sizes)


def reglu_experts(x: Any, gate: Any, up: Any, down: Any,
                  group_sizes: Any) -> Any:
    """``(relu(x·gate[e]) * (x·up[e])) · down[e]``: the three-matrix
    expert whose gate is a relu (SmallThinker's ReGLU); weights and
    activation as :func:`swiglu_experts`'."""
    return _gated_experts(jax.nn.relu, x, gate, up, down, group_sizes)


def relu2_experts(x: Any, up: Any, down: Any, group_sizes: Any) -> Any:
    """``relu(x·up[e])² · down[e]`` for the rows of each expert ``e``:
    the two-matrix expert without a gate (Nemotron-H's ``relu2``); the
    weights are cast to ``x``'s dtype, the activation is computed in
    float32."""
    dt = x.dtype
    u = grouped_matmul(x, up.astype(dt), group_sizes).astype(jnp.float32)
    a = jnp.square(jax.nn.relu(u)).astype(dt)
    return grouped_matmul(a, down.astype(dt), group_sizes)


def combine(y: Any, weights: Any, dispatch: Dispatch) -> Any:
    """Rows in expert order ``[N*k, d]`` -> ``[N, d]``: unsort, weight
    each copy by its router probability, sum a token's ``k`` copies in
    float32."""
    n, k = weights.shape
    back = _permute(y, dispatch.inverse, dispatch.order).reshape(n, k, -1)
    out = jnp.sum(
        back.astype(jnp.float32) * weights.astype(jnp.float32)[..., None],
        axis=1,
    )
    return out.astype(y.dtype)


# the row buffer of a held share: the rows expected of an even router
# times this (PERF.md, PR 39, says why no more)
_SLACK = Fraction(5, 4)


def held_capacity(n_assignments: Any, n_held: int, n_routed: int) -> Any:
    """Rows of the buffer in which a share of ``n_held`` of ``n_routed``
    experts moves the rows it holds: the rows expected of
    ``n_assignments`` spread evenly, times ``_SLACK``, rounded up to the
    grouped matmul's row tile; never above ``n_assignments`` (a share
    that is the whole layer, or a small problem, moves every row).
    ``n_assignments`` may be a traced integer (``optim.routing_gauges``
    counts it from a router's loads)."""
    tile = _TILING[0]
    rows = -(-n_assignments * n_held * _SLACK.numerator
             // (n_routed * _SLACK.denominator * tile)) * tile
    if isinstance(n_assignments, int):
        return min(n_assignments, rows)
    return jnp.minimum(n_assignments, rows)


def _experts(x: Any, gate: Any, up: Any, down: Any, group_sizes: Any,
             activation: Optional[str] = None) -> Any:
    # ``activation``: the gated expert's where it is not SwiGLU's silu, a
    # static choice of the caller's
    if gate is None:
        return relu2_experts(x, up, down, group_sizes)
    gated = reglu_experts if activation == "reglu" else swiglu_experts
    return gated(x, gate, up, down, group_sizes)


def _all_rows(h: Any, weights: Any, experts: Any, gate: Any, up: Any,
              down: Any, share: bool,
              activation: Optional[str] = None) -> Any:
    """Every one of the ``N*k`` assignments a row. With a ``share`` held
    (``experts``: local ids, the sentinel ``len(up)`` for an absent
    expert) the absent ones sort last and ``group_sizes`` count held
    rows only: megablox's grid ends at the groups' sum and the rows past
    it are never written, so they are cut off by a select on the way in
    (for the gradient's sake) and on the way out."""
    with jax.named_scope("moe_dispatch"):
        dispatch = sort_by_expert(experts, up.shape[0])
        x = gather_tokens(h, dispatch)
        if share:
            live = (jnp.arange(x.shape[0], dtype=jnp.int32)
                    < jnp.sum(dispatch.group_sizes))[:, None]  # [N*k, 1]
            x = jnp.where(live, x, 0)
    with jax.named_scope("moe_experts"):
        y = _experts(x, gate, up, down, dispatch.group_sizes, activation)
    with jax.named_scope("moe_combine"):
        return combine(jnp.where(live, y, 0) if share else y, weights,
                       dispatch)


def _add_rows(into: Any, v: Any, token: Any) -> Any:
    """``into [N, d]`` with the rows ``v [C, d]`` summed into their
    tokens: the transpose of the gather ``h[token]``."""
    return into.at[token].add(v)


class _Passes(NamedTuple):
    """A share's held rows, cut into passes of ``capacity`` rows of the
    expert order (held rows sort first, so the passes that hold any are
    the first ``count``)."""
    order: Any      # [passes * capacity] int32, ``N*k`` past the end
    starts: Any     # [n_held] int32: first row of each expert's group
    sizes: Any      # [n_held] int32
    count: Any      # int32: passes that hold a row; a data value


def _passes(capacity: int, experts: Any, n_held: int) -> _Passes:
    """The sort, without :func:`sort_by_expert`'s inverse (nothing here
    gathers through it) and with the group sizes counted by comparison:
    1.8 ms a call cheaper on the chip than the two scatters (PERF.md,
    PR 39)."""
    flat = experts.reshape(-1)
    n = flat.shape[0]
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sizes = jnp.sum(
        flat[None, :] == jnp.arange(n_held, dtype=flat.dtype)[:, None],
        axis=1, dtype=jnp.int32)
    order = jnp.pad(order, (0, -n % capacity), constant_values=n)
    total = jnp.sum(sizes)
    return _Passes(order, jnp.cumsum(sizes) - sizes, sizes,
                   (total + capacity - 1) // capacity)


def _pass_rows(passes: _Passes, i: Any, capacity: int, n: int, k: int):
    """Pass ``i``: ``(rows [C], token [C], live [C, 1], group_sizes)`` —
    the assignment in each row (``N*k`` where there is none), its token,
    whether a held assignment stands there, and how many rows of the
    pass each expert has."""
    lo = i * capacity
    rows = jax.lax.dynamic_slice(passes.order, (lo,), (capacity,))
    at = lo + jnp.arange(capacity, dtype=jnp.int32)
    live = (at < passes.starts[-1] + passes.sizes[-1])[:, None]
    inside = (jnp.clip(passes.starts + passes.sizes - lo, 0, capacity)
              - jnp.clip(passes.starts - lo, 0, capacity))
    return rows, jnp.minimum(rows, n * k - 1) // k, live, inside


def _pass(x: Any, w: Any, live: Any, group_sizes: Any, gate: Any, up: Any,
          down: Any, activation: Optional[str] = None) -> Any:
    """One pass's rows through the experts: gathered rows ``x [C, d]``
    and their router weights ``w [C]`` -> weighted rows, float32. Rows
    past the held ones are never written by megablox, whose grid ends at
    the groups' sum: cut off by a select on the way in (for the
    gradient's sake) and on the way out."""
    with jax.named_scope("moe_dispatch"):
        x = jnp.where(live, x, 0)
    with jax.named_scope("moe_experts"):
        y = _experts(x, gate, up, down, group_sizes, activation)
    with jax.named_scope("moe_combine"):
        return jnp.where(live, y, 0).astype(jnp.float32) * w[:, None]


def _cast(dtype: Any, *weights: Any):
    return tuple(None if w is None else w.astype(dtype) for w in weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _share_mlp(capacity: int, activation: Optional[str], h: Any,
               weights: Any, experts: Any, gate: Any, up: Any,
               down: Any) -> Any:
    """A held share's layer over a row buffer of ``capacity`` rows.
    ``experts`` holds local ids, the sentinel ``len(up)`` for an absent
    expert. Held rows sort first; a pass gathers ``capacity`` of them
    from their tokens, runs them through the experts, weights them in
    float32 and sums them into their tokens row by row. How many rows
    are held is data, and so is the number of passes: one where the
    held rows fit the buffer (an even router's always do), none where
    nothing is held, ``N*k / capacity`` where every assignment is —
    dropless, in one program, and no ``[N*k, d]`` array in either
    direction.

    Differentiated by hand (a loop whose length is data has no
    transpose): the residuals are the inputs, and the backward pass
    runs each pass forward again and differentiates it there. The
    experts so run forward twice a step — as they do under the blocks'
    remat, whose own recomputation of this layer is then dead code (the
    sparse sublayer ends its block)."""
    (n, d), k = h.shape, weights.shape[1]
    with jax.named_scope("moe_dispatch"):
        passes = _passes(capacity, experts, up.shape[0])
    with jax.named_scope("moe_experts"):
        cast = _cast(h.dtype, gate, up, down)
    flat = weights.reshape(-1).astype(jnp.float32)

    def one(i, out):
        with jax.named_scope("moe_dispatch"):
            rows, token, live, inside = _pass_rows(passes, i, capacity, n, k)
            x = h[token]
        y = _pass(x, flat[jnp.minimum(rows, n * k - 1)], live, inside, *cast,
                  activation=activation)
        with jax.named_scope("moe_combine"):
            return _add_rows(out, y, token)

    out = jax.lax.fori_loop(0, passes.count, one,
                            jnp.zeros((n, d), jnp.float32))
    return out.astype(h.dtype)


def _share_mlp_fwd(capacity, activation, h, weights, experts, gate, up, down):
    return (_share_mlp(capacity, activation, h, weights, experts, gate, up,
                       down),
            (h, weights, experts, gate, up, down))


def _share_mlp_bwd(capacity, activation, res, g):
    h, weights, experts, gate, up, down = res
    (n, d), k = h.shape, weights.shape[1]
    with jax.named_scope("moe_dispatch"):
        passes = _passes(capacity, experts, up.shape[0])
    with jax.named_scope("moe_experts"):
        cast = _cast(h.dtype, gate, up, down)
    flat = weights.reshape(-1).astype(jnp.float32)

    def one(i, carry):
        dh, dflat, dcast = carry
        with jax.named_scope("moe_dispatch"):
            rows, token, live, inside = _pass_rows(passes, i, capacity, n, k)
            x = h[token]
        with jax.named_scope("moe_combine"):
            dy = g[token].astype(jnp.float32)
        _, pull = jax.vjp(
            lambda x, w, *cast: _pass(x, w, live, inside, *cast,
                                      activation=activation),
            x, flat[jnp.minimum(rows, n * k - 1)], *cast)
        dx, dw, *dpass = pull(dy)
        with jax.named_scope("moe_dispatch"):
            dh = _add_rows(dh, dx.astype(jnp.float32), token)
        with jax.named_scope("moe_combine"):
            # a row with no assignment in it has index N*k: dropped
            dflat = dflat.at[rows].set(jnp.where(live[:, 0], dw, 0),
                                       mode="drop", unique_indices=True)
        with jax.named_scope("moe_experts"):
            dcast = tuple(None if a is None else a + b.astype(jnp.float32)
                          for a, b in zip(dcast, dpass))
        return dh, dflat, dcast

    zeros = functools.partial(jnp.zeros, dtype=jnp.float32)
    dh, dflat, dcast = jax.lax.fori_loop(0, passes.count, one, (
        zeros((n, d)), zeros((n * k,)),
        tuple(None if w is None else zeros(w.shape) for w in cast)))
    dgate, dup, ddown = (None if w is None else dw.astype(w.dtype)
                         for w, dw in zip((gate, up, down), dcast))
    return (dh.astype(h.dtype), dflat.reshape(n, k).astype(weights.dtype),
            None, dgate, dup, ddown)


_share_mlp.defvjp(_share_mlp_fwd, _share_mlp_bwd)


def moe_mlp(h: Any, weights: Any, experts: Any, gate: Any, up: Any,
            down: Any, n_routed: Optional[int] = None,
            first_expert: int = 0, activation: Optional[str] = None) -> Any:
    """The whole sparse sublayer after the router, under the trace's
    scopes ``moe_dispatch`` / ``moe_experts`` / ``moe_combine``: tokens
    ``h [N, d]`` with their ``[N, k]`` routing -> ``[N, d]``.

    The expert's shape is the caller's: with ``gate`` [E, d, f] it is
    :func:`swiglu_experts`' three matrices, with ``gate=None``
    :func:`relu2_experts`' two, and ``activation="reglu"`` takes the
    three through :func:`reglu_experts` (a static choice); dispatch,
    share, sentinel and the ``live`` select are one code for all three.

    The layer is told which experts it holds: the router chose among
    ``n_routed`` experts (default: as many as ``up`` holds) and the
    weights here are those of experts ``first_expert ..
    first_expert + up.shape[0]``. What comes back is the held experts'
    part of the result; assignments to absent experts are computed
    nowhere and nothing stands in for the chips that hold them.

    A held share moves the rows it holds: absent assignments get a
    sentinel and sort behind the held ones, and the rows gathered,
    activated and combined are those of a buffer of
    :func:`held_capacity` rows — a static size read off the call
    (``N*k``, ``len(up)``, ``n_routed``) — pass after pass until the
    held rows, whose number is data, are done (:func:`_share_mlp`: one
    pass for an even router). A share whose buffer would be all ``N*k``
    rows (the whole layer, or a small problem) moves them as a layer
    that holds every expert does, the absent rows cut off by a select.
    Every assignment on a held expert is computed either way."""
    assert activation in (None, "reglu"), activation
    n_held = up.shape[0]
    share = n_routed is not None and (n_routed, first_expert) != (n_held, 0)
    if share:
        local = experts - first_expert
        held = (local >= 0) & (local < n_held)             # [N, k]
        # the sentinel ``n_held`` sorts last and counts nowhere
        experts = jnp.where(held, local, n_held)
        weights = jnp.where(held, weights, 0)
        capacity = held_capacity(experts.size, n_held, n_routed)
        if capacity < experts.size:
            return _share_mlp(capacity, activation, h, weights, experts, gate,
                              up, down)
    return _all_rows(h, weights, experts, gate, up, down, share, activation)
