"""Dropless sparse-expert path: top-k routing, sort by expert, grouped
matmuls over ragged groups, unsort, weight, sum.

Every (token, expert) assignment is computed: there is no capacity and
nothing is dropped, so the result equals "every expert on every token,
weighted by a matrix that is zero outside the top k" (the benchmark's
plain reference is written that way). All shapes are static — ``[N*k]``
assignments whatever the routing — and the group sizes are data, so one
compiled program serves every routing; an empty group and a group that
holds every row are ordinary inputs.

The data movement is gathers in both directions: a permutation's
transpose is the inverse permutation, so the backward pass of the sort
gathers through ``inverse`` instead of scattering, and the gradient of
the ``k`` copies of a token is a gather and a sum over ``k``.

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

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["Dispatch", "top_k_routing", "sort_by_expert", "gather_tokens",
           "grouped_matmul", "swiglu_experts", "relu2_experts", "combine",
           "moe_mlp"]


class Dispatch(NamedTuple):
    """The sort of ``N*k`` assignments by expert. Assignment ``a`` is
    token ``a // k``'s ``a % k``-th choice; row ``i`` of the expert order
    is assignment ``order[i]`` and ``inverse[order[i]] == i``."""
    order: Any         # [N*k] int32
    inverse: Any       # [N*k] int32
    group_sizes: Any   # [E] int32, rows per expert, sums to N*k


def top_k_routing(scores: Any, k: int, bias: Any = None,
                  renormalise: bool = False,
                  scale: float = 1.0, eps: float = 1e-20) -> Tuple[Any, Any]:
    """``(weights [N, k], experts [N, k] int32)``. Without ``bias``: the
    ``k`` largest router scores of each token, as they are (OLMoE's
    softmax probabilities, not renormalised). With a ``bias [E]``
    (DeepSeek-V3's ``noaux_tc``): the ``k`` largest of ``scores + bias``
    choose, and the weights are the chosen experts' ``scores`` — the bias
    selects and never weights, and no gradient reaches it.
    ``renormalise`` divides a token's weights by their sum + ``eps``
    (1e-20: DeepSeek-V3's; LFM2 publishes 1e-6); ``scale`` multiplies
    them."""
    if bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias).astype(scores.dtype), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
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


def swiglu_experts(x: Any, gate: Any, up: Any, down: Any,
                   group_sizes: Any) -> Any:
    """``(silu(x·gate[e]) * (x·up[e])) · down[e]`` for the rows of each
    expert ``e``; the weights are cast to ``x``'s dtype, the activation
    is computed in float32."""
    dt = x.dtype
    g = grouped_matmul(x, gate.astype(dt), group_sizes)
    u = grouped_matmul(x, up.astype(dt), group_sizes)
    a = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(dt)
    return grouped_matmul(a, down.astype(dt), group_sizes)


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


def moe_mlp(h: Any, weights: Any, experts: Any, gate: Any, up: Any,
            down: Any, n_routed: Optional[int] = None,
            first_expert: int = 0) -> Any:
    """The whole sparse sublayer after the router, under the trace's
    scopes ``moe_dispatch`` / ``moe_experts`` / ``moe_combine``: tokens
    ``h [N, d]`` with their ``[N, k]`` routing -> ``[N, d]``.

    The expert's shape is the caller's: with ``gate`` [E, d, f] it is
    :func:`swiglu_experts`' three matrices, with ``gate=None``
    :func:`relu2_experts`' two; dispatch, share, sentinel and the
    ``live`` select are one code for both.

    The layer is told which experts it holds: the router chose among
    ``n_routed`` experts (default: as many as ``up`` holds) and the
    weights here are those of experts ``first_expert ..
    first_expert + up.shape[0]``. What comes back is the held experts'
    part of the result; assignments to absent experts are computed
    nowhere and nothing stands in for the chips that hold them.

    With a share held, the shapes stay those of all ``N*k`` assignments
    (every assignment on a held expert is an ordinary input): absent
    assignments sort behind the held ones, the group sizes count held
    rows only, and megablox's grid ends at the groups' sum — the grouped
    matmuls' time follows the rows held, and the rows past the sum are
    never written, so they are cut off by a select on the way in (for
    the gradient's sake) and on the way out."""
    n_held = up.shape[0]
    share = n_routed is not None and (n_routed, first_expert) != (n_held, 0)
    with jax.named_scope("moe_dispatch"):
        if share:
            local = experts - first_expert
            held = (local >= 0) & (local < n_held)             # [N, k]
            # the sentinel ``n_held`` sorts last and counts nowhere
            experts = jnp.where(held, local, n_held)
            weights = jnp.where(held, weights, 0)
        dispatch = sort_by_expert(experts, n_held)
        x = gather_tokens(h, dispatch)
        if share:
            live = (jnp.arange(experts.size, dtype=jnp.int32)
                    < jnp.sum(dispatch.group_sizes))[:, None]  # [N*k, 1]
            x = jnp.where(live, x, 0)
    with jax.named_scope("moe_experts"):
        if gate is None:
            y = relu2_experts(x, up, down, dispatch.group_sizes)
        else:
            y = swiglu_experts(x, gate, up, down, dispatch.group_sizes)
    with jax.named_scope("moe_combine"):
        return combine(jnp.where(live, y, 0) if share else y, weights,
                       dispatch)
