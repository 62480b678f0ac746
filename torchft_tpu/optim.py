"""Fault-tolerant optimizer wrapper over optax.

The reference wraps torch optimizers (ref /root/reference/torchft/optim.py:
24-63): ``zero_grad()`` starts the quorum, ``step()`` only applies when the
group votes to commit. JAX optimizers (optax) are pure transformations, so
the TPU-native wrapper owns the (params, opt_state) pair functionally:

    opt = OptimizerWrapper(manager, optax.adamw(3e-4))
    opt_state = opt.init(params)
    for batch in data:
        opt.begin_step()                      # zero_grad analog: quorum
        grads = grad_fn(params, batch)        # user's jitted compute
        avg = ddp.average_gradients(grads)    # cross-replica DCN reduce
        params, opt_state, committed = opt.step(params, opt_state, avg)

The optax update itself is jitted once (static tree structure) — the commit
decision happens OUTSIDE the compiled function, so quorum changes never
recompile anything.
"""

from __future__ import annotations

import functools
import logging
from contextlib import nullcontext
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.utils.device import land_like
from torchft_tpu.utils.profiling import span, step_program

logger = logging.getLogger(__name__)

__all__ = [
    "BalanceBiasState",
    "balance_bias_rule",
    "with_balance_bias",
    "routing_gauges",
    "StepStatsState",
    "with_step_stats",
    "OptimizerWrapper",
    "PartitionedOuterOptimizer",
    "ShardedOptState",
    "ShardedOptimizerWrapper",
]


class BalanceBiasState(NamedTuple):
    """What :func:`balance_bias_rule` keeps: the loads it last saw (the
    step's assignments per expert, one vector a router), so that whoever
    holds the optimizer state can read a step's routing without a second
    forward pass. No moments, no count."""
    loads: Any


def balance_bias_rule(rate: float):
    """The optax transformation of a router's balance bias (DeepSeek-V3's
    auxiliary-loss-free balancing, ``topk_method: noaux_tc``): what
    arrives as the leaf's gradient is the step's assignments per expert
    (the model's doing, as ``models/common.py::loads_as_gradient``;
    averaged over replica groups like any gradient), and the update is
    ``rate · sign(mean(load) - load_e)``: an expert with fewer than its share is
    made likelier, one with more less likely. No moments, no decay; the
    state is the loads themselves (:class:`BalanceBiasState`), which the
    update never reads."""
    import jax
    import jax.numpy as jnp
    import optax

    def init(params):
        return BalanceBiasState(jax.tree_util.tree_map(jnp.zeros_like, params))

    def update(loads, state, params=None):
        del state, params
        return jax.tree_util.tree_map(
            lambda x: rate * jnp.sign(jnp.mean(x) - x), loads
        ), BalanceBiasState(loads)

    return optax.GradientTransformation(init, update)


@functools.lru_cache(maxsize=None)
def _balanced_transformation():
    import optax

    class BalancedTransformation(optax.GradientTransformation):
        """:func:`with_balance_bias`'s result: an optax transformation
        that also says which of a router's experts this replica holds
        (``held_experts``: ``(first, count)`` or None)."""
        held_experts: Optional[Tuple[int, int]] = None

    return BalancedTransformation


def with_balance_bias(tx, rate: float, is_bias,
                      held: Optional[Tuple[int, int]] = None):
    """``tx`` for every leaf but those whose path in the parameter tree
    ``is_bias`` accepts (the model file's predicate: this module knows no
    model's leaf names), which take :func:`balance_bias_rule`: one optax
    transformation, so the fused step, the classic update behind the
    commit gate and the heal treat the bias like any other leaf.

    ``held`` = ``(first_expert, n_held)`` where the replica holds a share
    of every router's experts: :class:`OptimizerWrapper` then reports the
    share of a step's assignments that fell on them (``moe_held_share``)
    and the share of routers whose held rows fit ``ops/moe.py``'s row
    buffer (``moe_row_buffer_share``) beside ``moe_load_max_over_mean``,
    which it reports for every transformation made here."""
    import jax
    import optax

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _x: "bias" if is_bias(path) else "rest", params)

    both = optax.multi_transform(
        {"rest": tx, "bias": balance_bias_rule(rate)}, labels)
    out = _balanced_transformation()(both.init, both.update)
    out.held_experts = None if held is None else (int(held[0]), int(held[1]))
    return out


def _states_of(opt_state, kind) -> List[Any]:
    """Every state of type ``kind`` (a rule's NamedTuple) inside
    ``opt_state``, in the tree's order."""
    import jax

    return [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: isinstance(x, kind))
        if isinstance(s, kind)]


def routing_gauges(opt_state, held: Optional[Tuple[int, int]] = None):
    """``[moe_load_max_over_mean, moe_held_share, moe_row_buffer_share]``
    (float32) from the loads every :class:`BalanceBiasState` inside
    ``opt_state`` holds: the largest, over routers, of an expert's load
    over the mean load; the mean, over routers, of the share of all
    assignments that fell on experts ``held[0] .. held[0] + held[1]``;
    and the share of routers whose held rows fit the row buffer
    ``ops/moe.py::moe_mlp`` moves them in (:func:`~torchft_tpu.ops.moe.
    held_capacity` of the router's own count: its loads sum to ``N*k``
    and their length is the number routed among) — both NaN without
    ``held``. The loads are the groups' mean where gradients were
    averaged, so the third is exact for a replica alone and otherwise
    says whether the mean routing fits. None where ``opt_state`` holds
    no such state. Traceable."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.moe import held_capacity

    loads = [x.astype(jnp.float32)
             for s in _states_of(opt_state, BalanceBiasState)
             for x in jax.tree_util.tree_leaves(s.loads)]
    if not loads:
        return None
    skew = jnp.max(jnp.stack(
        [jnp.max(x) / jnp.maximum(jnp.mean(x), 1e-30) for x in loads]))
    share = fits = jnp.float32(jnp.nan)
    if held is not None:
        first, count = held
        rows = [jnp.sum(x[first:first + count]) for x in loads]
        share = jnp.mean(jnp.stack(
            [r / jnp.maximum(jnp.sum(x), 1e-30) for r, x in zip(rows, loads)]))
        fits = jnp.mean(jnp.stack(
            [r <= held_capacity(jnp.round(jnp.sum(x)).astype(jnp.int32),
                                count, x.shape[0])
             for r, x in zip(rows, loads)]).astype(jnp.float32))
    return jnp.stack([skew, share, fits])


class StepStatsState(NamedTuple):
    """What :func:`with_step_stats`' rule keeps: the statistics of the
    last step it saw, one vector a carrying leaf."""
    stats: Any


@functools.lru_cache(maxsize=None)
def _stats_transformation():
    import optax

    class StatsTransformation(optax.GradientTransformation):
        """:func:`with_step_stats`' result: an optax transformation that
        also says how its statistics become gauges
        (``publish_step_stats(metrics, values)``)."""
        publish_step_stats: Optional[Any] = None

    return StatsTransformation


def with_step_stats(tx, is_stats, publish):
    """``tx`` for every leaf but those whose path ``is_stats`` accepts (the
    model file's predicate), whose "gradient" is a vector of statistics of
    the step the model put there
    (``models/common.py::loads_as_gradient``; averaged over replica groups
    like any gradient): the leaf is never moved and the statistics are
    kept in the optimizer state (:class:`StepStatsState`), so the fused
    step, the classic update behind the commit gate and the heal carry
    them like any other leaf and no second forward pass reads them.
    :class:`OptimizerWrapper` hands them, joined into one vector of
    floats, to ``publish(metrics, values)`` (the model file's: it names
    the gauges; this module knows no model's) by the route of the routing
    gauges: read a commit later, never waited for."""
    import jax
    import jax.numpy as jnp
    import optax

    def keep(stats, state, params=None):
        del state, params
        return (jax.tree_util.tree_map(jnp.zeros_like, stats),
                StepStatsState(stats))

    rule = optax.GradientTransformation(
        lambda params: StepStatsState(
            jax.tree_util.tree_map(jnp.zeros_like, params)), keep)

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _x: "stats" if is_stats(path) else "rest", params)

    both = optax.multi_transform({"rest": tx, "stats": rule}, labels)
    out = _stats_transformation()(both.init, both.update)
    out.publish_step_stats = publish
    return out


def step_stats(opt_state):
    """The statistics every :class:`StepStatsState` inside ``opt_state``
    holds, joined into one float32 vector. Traceable."""
    import jax
    import jax.numpy as jnp

    return jnp.concatenate([
        x.astype(jnp.float32).reshape(-1)
        for s in _states_of(opt_state, StepStatsState)
        for x in jax.tree_util.tree_leaves(s.stats)])


class PartitionedOuterOptimizer:
    """A per-fragment partition of one optax transformation.

    The streaming outer sync (torchft_tpu/local_sgd.py) lands each
    fragment's outer update the moment that fragment's averaged
    pseudogradient comes off the wire — while later fragments are still
    riding it — so the outer state must be addressable PER FRAGMENT, not
    as one monolithic tree. Each fragment owns an independent optax
    state over its leaf list; for the elementwise transformations outer
    optimizers use in practice (sgd, momentum/nesterov, adam...) the
    concatenation of per-fragment updates is exactly the monolithic
    update, fragment count merely re-slices the state.

    Commit discipline: :meth:`update_fragment` is PURE — it returns the
    staged (new_params, new_state) pair without mutating anything, and
    the round adopts states via :meth:`adopt` only after the commit
    barrier votes yes, so an aborted round leaves every fragment's outer
    state untouched (the rollback invariant). ``adopt`` replaces the
    state list rather than mutating it, so a snapshot taken before a
    sync (``states``) is never silently updated under the caller."""

    def __init__(self, tx) -> None:
        self._tx = tx
        self._states: "Optional[List[Any]]" = None

    def init(self, fragments: "Sequence[Sequence[Any]]") -> None:
        """One optax state per fragment, over that fragment's leaf list."""
        self._states = [self._tx.init(list(f)) for f in fragments]

    def init_fragment(self, leaves: "Sequence[Any]") -> Any:
        """A fresh state for ONE fragment's leaf list — the sharded
        outer plane (re)initializes a fragment that moved onto this
        rank without touching its siblings."""
        return self._tx.init(list(leaves))

    @property
    def states(self) -> "Optional[List[Any]]":
        return self._states

    def load_states(self, states: "Sequence[Any]") -> None:
        self._states = list(states)

    def update_fragment(
        self, f: int, grads: "Sequence[Any]", params: "Sequence[Any]"
    ) -> "Tuple[List[Any], Any]":
        """Staged outer step for fragment ``f``: returns
        ``(new_params_leaves, new_state)`` WITHOUT adopting the state —
        the round adopts on commit, discards on abort."""
        import optax

        assert self._states is not None, "init() was never called"
        if self._states[f] is None:
            raise RuntimeError(
                f"fragment {f} has no outer state on this rank — with "
                "sharded_outer only the fragment's OWNER holds state "
                "and runs its update (owner map: f % wire_world)"
            )
        updates, new_state = self._tx.update(
            list(grads), self._states[f], list(params)
        )
        return list(optax.apply_updates(list(params), updates)), new_state

    def adopt(self, f: int, new_state: Any) -> None:
        assert self._states is not None, "init() was never called"
        states = list(self._states)
        states[f] = new_state
        self._states = states


class ShardedOptState:
    """Cross-replica sharded optimizer state (ZeRO-style): one optax
    state PER PARAM LEAF, held only for the leaves this rank's shard
    owns. Per-leaf granularity is what makes resharding tractable — a
    world-size change moves whole leaf states between ranks, and a heal
    at a *different* world size intersects leaf index ranges against
    donor manifests instead of re-slicing packed buffers.

    ``ranges``/``rank``/``world_size`` record the grid the held states
    were built for; ``wire_gen`` records the transport incarnation the
    grid was adopted under — the reshard trigger (every membership
    change bumps it on every wire member at the same quorum boundary,
    which is what keeps the reshard exchange a matched collective)."""

    __slots__ = ("world_size", "rank", "ranges", "leaf_states", "wire_gen")

    def __init__(self, n_leaves: int, world_size: int = 0, rank: int = 0,
                 ranges: "Sequence[Tuple[int, int]]" = (),
                 leaf_states: "Optional[List[Any]]" = None,
                 wire_gen: "Optional[int]" = None) -> None:
        self.world_size = int(world_size)
        self.rank = int(rank)
        self.ranges = tuple(tuple(r) for r in ranges)
        self.leaf_states: "List[Any]" = (
            list(leaf_states) if leaf_states is not None
            else [None] * int(n_leaves)
        )
        self.wire_gen = wire_gen

    def held(self) -> "List[int]":
        return [i for i, s in enumerate(self.leaf_states) if s is not None]

    def state_bytes(self) -> int:
        import jax

        total = 0
        for s in self.leaf_states:
            if s is None:
                continue
            for a in jax.tree_util.tree_leaves(s):
                total += int(np.asarray(a).nbytes) if not hasattr(
                    a, "nbytes"
                ) else int(a.nbytes)
        return total


class ShardedOptimizerWrapper:
    """ZeRO-style cross-replica sharded weight update (ROADMAP item 3,
    per "Automatic Cross-Replica Sharding of Weight Update"):

        reduce-scatter(grads) → 1/N sharded optax update → allgather(params)

    Each wire rank receives only its byte-balanced contiguous leaf-shard
    of the averaged gradient (``ddp.ShardedGradReducer``), runs the
    optax update ONLY on those leaves against a per-leaf sharded state
    (:class:`ShardedOptState`), and the committed step allgathers the
    updated shards back into full replicated params. Per-step update
    FLOPs, optimizer-state memory, and optimizer-state heal bytes all
    divide by the wire world size.

    ``sharded=False`` is the live A/B lever and bitwise oracle: the SAME
    shard-aligned buckets ride a plain allreduce and every rank updates
    every leaf — allgather(sharded arm) must equal the replicated arm
    bit for bit (pinned by tests/test_sharded_update.py), because the
    transport's reduce_scatter delivers allreduce-identical bytes on
    owned shards, the per-leaf update is the same jitted function, and
    the params allgather forwards raw bytes verbatim. The flag must
    match across replicas (it changes the collective sequence).
    Exception: over an xla ``algorithm='psum'`` wire with a lossy codec
    the gradient hop rides the QUANTIZED psum_scatter (encoded
    all_to_all — comm/xla_backend.py) with zero changes here, and the
    oracle is numeric (the quantization envelope), not bitwise; the
    params allgather still moves raw bytes, so ranks agree bit-for-bit
    with EACH OTHER (pinned by tests/test_quantized_psum.py).

    Constraints: ``tx`` must be an ELEMENTWISE optax transformation with
    value-independent init (sgd, momentum/nesterov, adam, adamw — the
    standard DP optimizers; anything coupling elements across leaves,
    e.g. global-norm clipping, needs the full gradient and belongs in
    the replicated wrapper). Unlike :class:`OptimizerWrapper`, ``grads``
    passed to :meth:`step` are the RAW per-replica gradients — the
    wrapper owns the cross-replica reduction.

    Resharding: every transport incarnation change (quorum membership
    change) triggers ONE reshard exchange, compiled by the
    redistribution engine (comm/redistribute.py): the cohort allgathers
    holdings METADATA only (tiny), every rank derives the same
    (src spec → new grid) transfer plan — cached per spec pair, so
    repeated world-size oscillation replans zero times — and exactly
    the leaf states whose owner changed move point-to-point over the
    raw-bytes heal plane to their ONE new owner
    (``redist_moved_bytes == redist_lower_bound_bytes``, counter-pinned).
    ``redistribute="allgather"`` keeps the legacy exchange — each rank
    allgathers every departing leaf state to the WHOLE cohort — as the
    live A/B arm whose wire bytes measurably exceed the bound
    (``tests/test_redistribute.py``). Like ``sharded``, ``redistribute``
    MUST match across replicas: it changes the collective sequence at
    every membership change (the planned arm runs address/ack
    allgathers the legacy arm never posts — mixed arms wedge the wire
    until the transport timeout latches). Leaf states no surviving rank holds
    are REINITIALIZED (a momentum reset for that 1/N slice, made
    visible by the ``reshard`` event's ``reinit_leaves`` count; donors'
    checkpoints + ``checkpointing.fetch_opt_shard`` cover the heal path
    bitwise). A healer's fetched donor shard enters the same exchange,
    so an up-to-date-world heal moves only ~1/N of the optimizer state
    and still converges to the exact per-rank shard.

    Failure-after-vote window: the params allgather runs after the
    commit barrier (the update is not final before the vote the way
    OptimizerWrapper's is). If the allgather fails on a committed step,
    this replica cannot materialize the step the cohort committed —
    :meth:`step` RAISES, and the standard restart+heal path recovers
    (the same window :meth:`OptimizerWrapper.fused_step` documents)."""

    def __init__(self, manager, tx, state_fn=None, sharded: bool = True,
                 error_feedback: "bool | str" = "auto",
                 redistribute: str = "plan",
                 planner=None,
                 model_shards: "int | str" = "auto") -> None:
        import optax

        from torchft_tpu.comm.redistribute import RedistPlanner
        from torchft_tpu.ddp import ShardedGradReducer

        if redistribute not in ("plan", "allgather"):
            raise ValueError(
                f"redistribute must be 'plan' (minimal transfer plans "
                f"over the heal plane) or 'allgather' (the legacy "
                f"full-departing-leaf broadcast A/B arm), "
                f"got {redistribute!r}; the choice must match across "
                "replicas — it changes the reshard collective sequence"
            )
        self.manager = manager
        self.tx = tx
        self._state_fn = state_fn
        self._sharded = bool(sharded)
        self._redistribute = redistribute
        # 2-D (replica × model) layout: each leaf state is priced as
        # model_shards sub-units so the planner bounds a reshard at a
        # changed world size or mesh shape EXACTLY ("auto" follows the
        # Manager's mesh). Must match across replicas, like `sharded`.
        if model_shards == "auto":
            model_shards = getattr(manager, "model_shards", 1)
        self._model_shards = max(1, int(model_shards))
        # Plan cache (hit/miss-counted): per-wrapper unless a shared
        # planner is injected (bench/smoke harnesses pin cache behavior
        # across arms/transitions through one instance).
        self._planner = planner if planner is not None else RedistPlanner()
        self._reducer = ShardedGradReducer(
            manager, error_feedback=error_feedback
        )
        self._state_def = None  # treedef of one leaf's optax state
        self._state_slots = 0   # arrays per leaf state (flattened)
        # opt_state_bytes cache: held-state byte totals only change at
        # grid changes (reshard / heal adoption) — recomputing the
        # tree-leaves walk per step would be pure hot-path overhead.
        self._state_bytes: "Optional[float]" = None

        def _leaf_update(grad, state, param):
            updates, new_state = tx.update(grad, state, param)
            return optax.apply_updates(param, updates), new_state

        # One jitted per-leaf update, cached by jax per (shape, dtype) —
        # identical in both arms, which is half the bitwise oracle; one a
        # process for this ``tx``, like every step program.
        self._jit_update = step_program(_leaf_update, (tx,))[0]

    # ------------------------------------------------------------ lifecycle

    @property
    def sharded(self) -> bool:
        return self._sharded

    def init(self, params) -> ShardedOptState:
        """Fresh unsharded state: per-leaf states materialize lazily at
        the first step (once the wire world is known) — optax init for
        the supported transformations is value-independent (zeros), so
        deferred init is bitwise-identical to init at t0."""
        import jax

        n = len(jax.tree_util.tree_leaves(params))
        return ShardedOptState(n)

    def begin_step(self, **kwargs) -> None:
        self.manager.start_quorum(**kwargs)

    zero_grad = begin_step

    def _metrics(self):
        return getattr(self.manager, "metrics", None)

    def _ensure_state_def(self) -> None:
        if self._state_def is not None:
            return
        import jax
        import jax.numpy as jnp

        leaves, treedef = jax.tree_util.tree_flatten(
            self.tx.init(jnp.zeros((1,), jnp.float32))
        )
        self._state_def = treedef
        self._state_slots = len(leaves)

    def _leaf_init(self, param_leaf) -> Any:
        import jax.numpy as jnp

        return self.tx.init(jnp.asarray(param_leaf))

    def _flatten_state(self, state) -> "List[np.ndarray]":
        import jax

        return [np.asarray(a) for a in jax.tree_util.tree_leaves(state)]

    def _unflatten_state(self, arrays: "Sequence[np.ndarray]",
                         param_leaf: Any = None) -> Any:
        """Rebuild one leaf's optax state from its wire arrays. With
        ``param_leaf`` (a device array), param-shaped slots land on its
        device(s) with its sharding — a moved state arrives where the
        update that consumes it runs, not on the default device. Other
        slots (step counts) and a heal's states (no param at hand) stay
        uncommitted and follow the first update."""
        import jax
        import jax.numpy as jnp

        self._ensure_state_def()
        if len(arrays) != self._state_slots:
            raise ValueError(
                f"leaf state has {len(arrays)} arrays, transformation "
                f"expects {self._state_slots} — optimizer configs "
                "diverged across replicas"
            )
        shape = (
            tuple(param_leaf.shape) if hasattr(param_leaf, "sharding")
            else None
        )
        return jax.tree_util.tree_unflatten(
            self._state_def,
            [
                land_like(a, param_leaf, dtype=np.asarray(a).dtype)
                if np.shape(a) == shape else jnp.asarray(a)
                for a in arrays
            ],
        )

    # -------------------------------------------------------------- reshard

    def _maybe_reshard(self, param_leaves, opt_state: ShardedOptState,
                       plan, my_rank: int) -> ShardedOptState:
        """Redistribute per-leaf states at the quorum boundary when the
        transport incarnation changed (membership change / heal /
        first step). Default path: the redistribution engine — one tiny
        holdings-metadata allgather, a cached (src spec → new grid)
        transfer plan, and point-to-point fetches of exactly the leaf
        states whose owner changed (comm/redistribute.py; nothing
        fanned out to non-owners). ``redistribute='allgather'`` keeps
        the legacy exchange — every departing leaf state allgathered to
        the whole cohort, every new owner picking what it needs (lowest
        contributing rank wins ties — all copies are bitwise identical
        anyway) — as the A/B arm. Either way it runs on every wire
        member at the same step — the generation bump is
        cohort-synchronized — so the collectives are always matched."""
        mgr = self.manager
        gen_fn = getattr(mgr, "wire_generation", None)
        gen = int(gen_fn()) if callable(gen_fn) else 0
        world = plan.world_size
        ranges = tuple(tuple(r) for r in plan.ranges)
        if not self._sharded:
            # Replicated arm: every rank owns every leaf, no exchange.
            missing = [
                i for i, s in enumerate(opt_state.leaf_states) if s is None
            ]
            for i in missing:
                opt_state.leaf_states[i] = self._leaf_init(param_leaves[i])
            opt_state.world_size, opt_state.rank = 1, 0
            opt_state.ranges = ((0, len(param_leaves)),)
            opt_state.wire_gen = gen
            if missing or self._state_bytes is None:
                self._state_bytes = float(opt_state.state_bytes())
            return opt_state
        if (
            opt_state.wire_gen == gen
            and opt_state.ranges == ranges
            and opt_state.rank == my_rank
        ):
            return opt_state

        self._ensure_state_def()
        n_leaves = len(opt_state.leaf_states)
        owned = set(plan.owned_leaves(my_rank))
        held = set(opt_state.held())
        # available: adoptable leaf states that arrived off the wire;
        # wire_bytes: what the exchange actually RECEIVED (the A/B
        # surface — the planned arm receives exactly the lower bound,
        # the legacy arm receives every other rank's departures);
        # lower_bound: bytes of owned-but-missing leaves some survivor
        # holds — the set-theoretic minimum any correct exchange moves.
        available: "Dict[int, List[np.ndarray]]" = {}
        wire_bytes = 0
        lower_bound = 0
        if world > 1 and self._redistribute == "plan":
            import jax

            from torchft_tpu.checkpointing import (
                join_leaf_payload,
                redistribute_exchange,
                split_leaf_payload,
            )

            M = self._model_shards
            if M > 1:
                # 2-D mesh: each leaf state splits into M contiguous
                # sub-unit payloads (unit = leaf * M + shard) so the
                # planner prices a mesh-shape change exactly. Sub-unit
                # payloads are host slices (views), staged per fetch
                # like the 1-D arm.
                holdings = {
                    i * M + m: pieces
                    for i in sorted(held)
                    for m, pieces in enumerate(split_leaf_payload(
                        self._flatten_state(opt_state.leaf_states[i]), M
                    ))
                }
            else:
                # Holdings stay DEVICE arrays: the exchange reads only
                # nbytes metadata from them, and the serve side stages
                # lazily — a leaf pays its device-to-host copy exactly
                # when a receiver actually fetches it (the legacy arm's
                # outgoing-only materialization, generalized).
                holdings = {
                    i: jax.tree_util.tree_leaves(opt_state.leaf_states[i])
                    for i in sorted(held)
                }
            result = redistribute_exchange(
                mgr, my_rank, world, plan.shard_spec(M), holdings,
                self._planner, source="reshard",
            )
            if result is None:
                # Latched wire / transfer failed whole: keep the old
                # grid — this step discards, and the next healthy
                # quorum's generation bump retries the exchange.
                return opt_state
            wire_bytes = result.moved_bytes
            lower_bound = result.lower_bound_bytes
            if M > 1:
                # Reassemble each needed leaf from its M sub-units;
                # any gap (or byte mismatch) demotes the leaf to the
                # reinit path — the standard adoption contract.
                for i in sorted(owned - held):
                    subs = [result.fetched.get(i * M + m)
                            for m in range(M)]
                    if any(s is None for s in subs):
                        continue
                    shapes = [
                        a.shape for a in self._flatten_state(
                            self._leaf_init(param_leaves[i])
                        )
                    ]
                    try:
                        available[i] = join_leaf_payload(subs, shapes)
                    except ValueError:
                        logger.warning(
                            "reshard: leaf %d sub-units did not "
                            "reassemble; reinitializing", i,
                        )
            else:
                available = result.fetched
        elif world > 1:
            # Legacy allgather exchange (the A/B arm): contribution is
            # [outgoing indices (i64)] + each outgoing leaf's flattened
            # state arrays, in index order. Variable layouts per rank
            # are allgather's normal use.
            outgoing = sorted(held - owned)
            contrib: "List[np.ndarray]" = [
                np.asarray(outgoing, dtype=np.int64)
            ]
            for i in outgoing:
                contrib.extend(
                    self._flatten_state(opt_state.leaf_states[i])
                )
            work = mgr.allgather_arrays(contrib)
            gathered = work.future().result()
            errored = getattr(mgr, "errored", None)
            if callable(errored) and errored() is not None:
                return opt_state
            # Index every contributed leaf state (lowest rank wins);
            # foreign payload bytes are what this arm put on the wire
            # FOR this rank regardless of need — the waste the planner
            # exists to avoid.
            k = self._state_slots
            for r, rank_arrays in enumerate(gathered):
                if not rank_arrays:
                    continue
                idx = np.asarray(rank_arrays[0]).astype(np.int64).reshape(-1)
                pos = 1
                for i in idx.tolist():
                    slot = [
                        np.asarray(a) for a in rank_arrays[pos: pos + k]
                    ]
                    pos += k
                    if r != my_rank:
                        wire_bytes += sum(int(a.nbytes) for a in slot)
                    if int(i) not in available:
                        available[int(i)] = slot
            lower_bound = sum(
                sum(int(a.nbytes) for a in available[i])
                for i in owned - held if i in available
            )
            metrics = self._metrics()
            if metrics is not None:
                metrics.incr("redist_moved_bytes", float(wire_bytes))
                metrics.incr("redist_lower_bound_bytes", float(lower_bound))
        new_states: "List[Any]" = [None] * n_leaves
        moved_bytes = 0
        kept = 0
        reinit: "List[int]" = []
        # A fresh wrapper's first grid build materializes every owned
        # state (deferred zero-init — not a loss); only a rebuild of an
        # EXISTING grid can lose states to a dead owner.
        had_grid = opt_state.world_size > 0
        for i in sorted(owned):
            if opt_state.leaf_states[i] is not None:
                new_states[i] = opt_state.leaf_states[i]
                kept += 1
            elif i in available:
                new_states[i] = self._unflatten_state(
                    available[i], param_leaves[i]
                )
                moved_bytes += sum(int(a.nbytes) for a in available[i])
            else:
                new_states[i] = self._leaf_init(param_leaves[i])
                if had_grid:
                    reinit.append(i)
        if reinit:
            logger.warning(
                "reshard reinitialized %d leaf optimizer states (old "
                "owner left the quorum with them): momentum restarts "
                "for that slice", len(reinit),
            )
        out = ShardedOptState(
            n_leaves, world_size=world, rank=my_rank, ranges=ranges,
            leaf_states=new_states, wire_gen=gen,
        )
        self._state_bytes = float(out.state_bytes())
        metrics = self._metrics()
        if metrics is not None:
            metrics.incr("reshard_count")
            metrics.incr("reshard_moved_bytes", float(moved_bytes))
        ev = getattr(mgr, "events", None)
        if ev:
            ev.emit(
                "reshard",
                old_world=opt_state.world_size or None,
                new_world=world, rank=my_rank,
                moved_bytes=moved_bytes,
                wire_bytes=wire_bytes,
                lower_bound_bytes=lower_bound,
                kept_leaves=kept,
                reinit_leaves=len(reinit),
                owned_leaves=len(owned),
                mesh_shape=f"{world}x{self._model_shards}",
            )
        return out

    # ----------------------------------------------------------------- step

    def step(
        self, params: Any, opt_state: ShardedOptState, grads: Any
    ) -> "Tuple[Any, ShardedOptState, bool]":
        """One sharded step: reduce-scatter grads, update this rank's
        leaf-shard, commit-barrier, allgather updated params. Returns
        ``(params, opt_state, committed)``; on a discarded step params
        are the caller's references and no state is adopted (rollback =
        no-op), though a reshard triggered this step persists (it moves
        state between ranks, never along the trajectory)."""
        import time as _time

        from concurrent.futures import Future as _Future

        import jax
        import jax.numpy as jnp

        if isinstance(grads, _Future):
            grads = grads.result()
        mgr = self.manager
        metrics = self._metrics()

        plan, my_rank, red = self._reducer.reduce(
            grads, sharded=self._sharded
        )
        sca = getattr(mgr, "should_commit_async", None)
        if callable(sca):
            decision = sca()
            local_ok = bool(getattr(decision, "local_should_commit", True))
            resolve = decision.result
        else:  # stub managers: synchronous barrier
            errored = getattr(mgr, "errored", None)
            local_ok = not callable(errored) or errored() is None

            def resolve():
                return bool(mgr.should_commit())
        did_heal = getattr(mgr, "did_heal", None)
        if callable(did_heal) and did_heal() and self._state_fn is not None:
            # the commit prologue just applied a donor checkpoint; the
            # caller's (params, opt_state) predate it
            params, opt_state = self._state_fn()

        param_leaves, treedef = jax.tree_util.tree_flatten(params)
        errored_fn = getattr(mgr, "errored", None)
        wire_ok = not callable(errored_fn) or errored_fn() is None
        if wire_ok:
            # Never reshard off a failed step's degraded view (a latched
            # quorum/wire error reports a world-1 plan): the step is
            # discarding anyway, and the next healthy quorum's
            # generation bump re-triggers the exchange. A GENUINE solo
            # wire (lone survivor) still reshards-to-full here — it must
            # own every leaf to keep training.
            opt_state = self._maybe_reshard(
                param_leaves, opt_state, plan, my_rank
            )
        owned = (
            plan.owned_leaves(my_rank) if self._sharded
            else list(range(len(param_leaves)))
        )

        staged: "Optional[Dict[int, Tuple[Any, Any]]]" = None
        # The last two conjuncts guard the window where the reshard
        # exchange itself latched AFTER the prologue cast a True local
        # vote: the old grid's held states may not cover the new plan's
        # owned set — skip the staged update (never feed optax a None
        # state) and let the step resolve as uncommitted; peers that
        # committed fail their params allgather and recover through the
        # documented restart+heal window.
        if local_ok and set(owned) <= set(red.keys()) and all(
            opt_state.leaf_states[i] is not None for i in owned
        ):
            t0 = _time.perf_counter()
            staged = {}
            for i in owned:
                grad_i = (
                    red[i] if hasattr(red[i], "devices")
                    else land_like(red[i], param_leaves[i])
                )
                staged[i] = self._jit_update(
                    grad_i, opt_state.leaf_states[i], param_leaves[i]
                )
            if metrics is not None:
                metrics.observe("opt_update", _time.perf_counter() - t0)
                metrics.gauge(
                    "opt_update_elems",
                    float(sum(plan.sizes[i] for i in owned)),
                )
        committed = bool(resolve())
        if metrics is not None and self._state_bytes is not None:
            # cached at grid changes (_maybe_reshard) — the byte total
            # is a function of the grid, not of the step
            metrics.gauge("opt_state_bytes", self._state_bytes)
        if not committed or staged is None:
            return params, opt_state, False

        # Adopt the staged shard, then assemble full params: the sharded
        # arm allgathers updated shards (raw bytes, never compressed —
        # bitwise); the replicated arm updated everything locally.
        for i, (new_leaf, new_state) in staged.items():
            opt_state.leaf_states[i] = new_state
        if not self._sharded or plan.world_size == 1:
            new_leaves = list(param_leaves)
            for i, (new_leaf, _) in staged.items():
                new_leaves[i] = new_leaf
            return (
                jax.tree_util.tree_unflatten(treedef, new_leaves),
                opt_state, True,
            )

        contrib = [
            np.asarray(jax.device_get(staged[i][0])) for i in owned
        ]
        gathered = mgr.allgather_arrays(contrib).future().result()
        errored = getattr(mgr, "errored", None)
        if callable(errored) and errored() is not None:
            raise RuntimeError(
                "sharded step committed but the params allgather failed "
                f"({errored()}): this replica cannot materialize the "
                "committed step — restart and heal from a peer"
            )
        new_leaves = [None] * len(param_leaves)
        for i, (new_leaf, _) in staged.items():
            new_leaves[i] = new_leaf
        for shard, (start, stop) in enumerate(plan.ranges):
            if shard == my_rank:
                continue
            got = gathered[shard]
            if len(got) != stop - start:
                raise RuntimeError(
                    f"sharded step committed but shard {shard} "
                    f"contributed {len(got)} of {stop - start} leaves — "
                    "restart and heal from a peer"
                )
            for j, i in enumerate(range(start, stop)):
                new_leaves[i] = land_like(
                    np.asarray(got[j]).reshape(plan.shapes[i]),
                    param_leaves[i],
                )
        return (
            jax.tree_util.tree_unflatten(treedef, new_leaves),
            opt_state, True,
        )

    # -------------------------------------------------------- heal surface
    # The wrapper's sharded state enters the user state_dict through
    # these: a donor checkpoint carries ONLY its 1/N shard (the
    # (N−1)/N heal-bytes saving), in a FIXED tree structure (empty
    # placeholder arrays for non-held leaves) so every donor's
    # checkpoint manifests align leaf-for-leaf — which is what lets a
    # healer at a different world size intersect shard specs across
    # donor manifests (checkpointing.fetch_opt_shard) and fetch exactly
    # the missing pieces.

    def opt_state_dict(self, opt_state: ShardedOptState) -> dict:
        self._ensure_state_def()
        slots: "List[List[np.ndarray]]" = []
        for s in opt_state.leaf_states:
            if s is None:
                slots.append(
                    [np.zeros(0, np.float32)] * self._state_slots
                )
            else:
                slots.append(self._flatten_state(s))
        return {
            "spec": {
                "world_size": opt_state.world_size,
                "rank": opt_state.rank,
                "ranges": [list(r) for r in opt_state.ranges],
            },
            "slots": slots,
        }

    def load_opt_state_dict(self, state: dict) -> ShardedOptState:
        """Adopt a donor's shard as this replica's held states (grid =
        the donor's; ``wire_gen=None`` so the next step's reshard
        exchange redistributes onto the live grid). Gauges
        ``heal_opt_bytes`` — the optimizer-state bytes this heal
        actually moved (~1/N of the full state)."""
        self._ensure_state_def()
        spec = state["spec"]
        slots = state["slots"]
        leaf_states: "List[Any]" = [None] * len(slots)
        heal_bytes = 0
        rank = int(spec.get("rank", 0))
        ranges = [tuple(r) for r in spec.get("ranges", [])]
        held = (
            set(range(*ranges[rank])) if rank < len(ranges) else set()
        )
        for i, arrays in enumerate(slots):
            if i not in held:
                continue
            leaf_states[i] = self._unflatten_state(arrays)
            heal_bytes += sum(int(np.asarray(a).nbytes) for a in arrays)
        metrics = self._metrics()
        if metrics is not None:
            metrics.gauge("heal_opt_bytes", float(heal_bytes))
            metrics.incr("heal_opt_bytes_total", float(heal_bytes))
        return ShardedOptState(
            len(slots),
            world_size=int(spec.get("world_size", 0)),
            rank=rank, ranges=ranges,
            leaf_states=leaf_states, wire_gen=None,
        )


class OptimizerWrapper:
    """Gates optax updates on the manager's two-phase commit
    (ref optim.py:24-63).

    ``state_fn`` (optional) returns the CURRENT (params, opt_state) pair
    from the same holder the Manager's ``load_state_dict`` writes into.
    Pass it whenever heals are possible: ``should_commit`` applies a
    fetched donor checkpoint *during* ``step()``, after the caller already
    captured its (pre-heal) arguments — without ``state_fn`` the update
    would be applied to the stale pair and the heal silently discarded.
    With it, a healed step applies the received average on top of the
    donor snapshot, ending bitwise-identical to the donor."""

    def __init__(self, manager, tx, state_fn=None,
                 fence_depth: int = 1, fence_stride: int = 8,
                 donate_update: bool = False) -> None:
        import jax
        import optax

        self.manager = manager
        self.tx = tx
        self._state_fn = state_fn
        # Bounded dispatch pipeline. JAX dispatch is async: a host loop
        # can race many steps ahead of the chip, which makes wall-clock
        # windows lie and lets should_commit count steps whose device work
        # hasn't run.
        # fence_depth=1 blocks on the update from ``fence_depth`` steps
        # ago before committing the current one — full host/device overlap
        # of one step, but never more. 0 disables.
        #
        # HBM cost: the fence keeps the last ``fence_depth`` committed
        # params pytrees referenced until their turn to be waited on —
        # one extra full parameter tree of HBM at the default depth. The
        # list is drained on every non-committing step (below) so a stale
        # reference can never outlive the step that created it by more
        # than the fence window.
        self._fence_depth = fence_depth
        # Fused-path readback batching: a scalar device_get is a
        # synchronous round trip to the device whatever its payload, so
        # ready fence scalars are drained ``fence_stride`` at a time in
        # ONE transfer — one round trip per stride instead of per step.
        # Host lead is bounded by fence_depth + fence_stride steps (with
        # the window's final sync still accounting every dispatched step).
        self._fence_stride = max(1, fence_stride)
        self._in_flight: list = []
        # Path counters (observability: the bench reports how many steps
        # rode each path so an artifact can't silently claim fused-path
        # throughput for a wire that was never solo, or vice versa).
        self.fused_steps = 0
        self.classic_steps = 0
        # Per-phase rolling timers of recent fused steps (the same
        # Metrics facility the Manager uses, so one reset protocol covers
        # a measurement window): where the FT tax goes — the commit
        # barrier RPC, the program dispatch, and the fence readback. The
        # fence entry is the interesting one on a remote-dispatch
        # backend: it absorbs whatever device time step N-1 still needs,
        # so fence >> barrier+dispatch means the host is NOT the
        # bottleneck (the tax is device/transport time), while large
        # dispatch means per-program host overhead.
        from torchft_tpu.utils.metrics import Metrics

        self.metrics = Metrics(window=512)
        # spans of this sink carry the Manager's replica id too (a test
        # double of the manager may not have one)
        replica_id = getattr(manager, "replica_id", None)
        if callable(replica_id):
            self.metrics.label("replica_id", replica_id())

        # the functions' names are the programs' on a trace's XLA Modules
        # line, the scope is what their operations are filed under
        def tft_opt_update(grads, opt_state, params):
            with jax.named_scope("opt_update"):
                updates, new_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), new_state

        # One pair of programs a process for this ``tx``: a wrapper built
        # after a fault beside its peers runs the executables they loaded.
        self._update, reused = step_program(tft_opt_update, (tx,))

        # Decide-then-apply variant for HBM-constrained multi-peer wires:
        # donating (opt_state, params) means the update program allocates
        # NO second params+opt footprint — but a donated input cannot be
        # rolled back, so the commit decision must precede the dispatch
        # (the same soundness rule as fused_step), which exposes the
        # barrier RPC on the critical path. The default overlapped path
        # makes the opposite trade: transient 2x params+opt, RPC hidden
        # behind device time. Pick per job via ``donate_update``.
        #
        # The extra ``probe`` output is the fence anchor: a COPIED scalar
        # element of the new params. Fencing any leaf of new_params
        # itself would crash one step later — the next committing step
        # donates new_params back in, deleting the fenced buffer before
        # its deferred device_get runs. The probe is a fresh 1-element
        # buffer no later step ever consumes (the same role the loss aux
        # plays for the fused path).
        def tft_opt_update_donated(grads, opt_state, params):
            new_params, new_state = tft_opt_update(grads, opt_state, params)
            probe = jax.tree_util.tree_leaves(new_params)[0].ravel()[0]
            return new_params, new_state, probe

        self._donate_update = bool(donate_update)
        # Donate (opt_state, params) only: per parameter leaf the outputs
        # are one new-params + the new opt leaves, so donating grads TOO
        # would leave one param-shaped donation unusable every step (XLA
        # warns per dispatch, and the grads donation buys no HBM — the
        # peak already excludes a second params+opt footprint).
        self._update_donated = step_program(
            tft_opt_update_donated, (tx,), donate_argnums=(1, 2)
        )[0]
        if reused:
            self.metrics.incr("update_program_reused")
        # What the optimizer state says of a step beside its update, as
        # gauges: of a :func:`with_balance_bias` transformation the
        # routing's, of a :func:`with_step_stats` one the statistics as its
        # ``publish_step_stats`` names them (nothing for any other). Each a
        # program of a few operations over the state, its ``publish`` and
        # the one result of it whose host copy is under way.
        self._late_gauges: List[List[Any]] = []
        if hasattr(tx, "held_experts"):
            held = tx.held_experts

            def tft_routing_gauges(opt_state):
                return routing_gauges(opt_state, held)

            self._late_gauges.append([
                step_program(tft_routing_gauges, (tx,))[0],
                self._publish_routing, None])
        if hasattr(tx, "publish_step_stats"):
            def tft_step_stats(opt_state):
                return step_stats(opt_state)

            self._late_gauges.append([
                step_program(tft_step_stats, (tx,))[0],
                functools.partial(tx.publish_step_stats, self.metrics),
                None])

    def init(self, params) -> Any:
        return self.tx.init(params)

    def _publish_routing(self, values: Sequence[float]) -> None:
        skew, share, fits = values
        self.metrics.gauge("moe_load_max_over_mean", skew)
        if share == share:      # NaN: the share held was not said
            self.metrics.gauge("moe_held_share", share)
            self.metrics.gauge("moe_row_buffer_share", fits)

    def _observe_routing(self, opt_state: Any) -> None:
        """Gauges ``moe_load_max_over_mean`` and (where the
        transformation was told the share held) ``moe_held_share`` and
        ``moe_row_buffer_share`` of a committed step, and those a
        :func:`with_step_stats` transformation publishes, without a wait: a
        gauges' program is
        dispatched behind the step's and its host copy started; what a
        LATER commit finds ready it reads, and starts the next. A result
        the device has not reached yet stays pending and this step's is
        not asked for (the loop's host runs steps ahead of the chip)."""
        for late in self._late_gauges:
            program, publish, pending = late
            if pending is not None:
                if not pending.is_ready():
                    continue
                publish([float(v) for v in np.asarray(pending)])
            late[2] = program(opt_state)
            late[2].copy_to_host_async()

    def _span(self, name: str) -> span:
        """One phase of this step: a timing in ``self.metrics`` and a
        ``tft.<name>`` span on the profiler timeline."""
        return span(self.metrics, name, step=self.manager.current_step())

    def begin_step(self, **kwargs) -> None:
        """Start the (async) quorum — call before the forward pass
        (the reference binds this to zero_grad, ref optim.py:49-51)."""
        self.manager.start_quorum(**kwargs)

    # Alias for API familiarity with the reference.
    zero_grad = begin_step

    def step(
        self, params: Any, opt_state: Any, grads: Any
    ) -> Tuple[Any, Any, bool]:
        """Apply the update iff the replica group commits this step
        (ref optim.py:53-55). Returns (params, opt_state, committed).

        Low-tax multi-peer design: the commit barrier's prologue
        (``Manager.should_commit_async``) drains the transport futures,
        applies any pending heal, and casts the local vote on this
        thread; the barrier RPC then rides a background thread WHILE the
        update program is dispatched — the decision never depends on the
        update's output (it is a function of the allreduce outcome, which
        is final before the dispatch), so the RPC round trip hides behind
        device time instead of serializing ahead of it. On a
        non-commit the freshly computed pair is simply dropped — the
        inputs were NOT donated, so rollback is the no-op of returning
        the caller's references (unit-tested in
        tests/test_train_integration.py). A False local vote forces a
        False global decision, so the dispatch is skipped entirely then.

        With ``donate_update=True`` the order flips to decide-then-apply
        with a fully donated update program (no transient second
        params+opt footprint — the 1b multi-peer configuration), paying
        the exposed barrier RPC instead; see __init__.

        ``grads`` may also be the FUTURE returned by
        ``DistributedDataParallel.average_gradients_async`` — it is
        resolved here, right before the commit prologue (which drains
        the same transport work anyway). That lets a training loop
        submit the average, do more host work (next-batch prefetch,
        logging) while the buckets ride the wire, and hand the
        unresolved future straight to ``step()`` — the cross-step
        comm/compute overlap the DDP staging-arena generations exist
        for.
        """
        self.classic_steps += 1
        from concurrent.futures import Future as _Future

        if isinstance(grads, _Future):
            # every average_gradients_async path returns exactly a
            # concurrent.futures.Future — an isinstance check can't
            # misfire on a user pytree that happens to expose .result()
            blocked = getattr(self.manager, "blocked_on_wire", nullcontext)
            with blocked():
                grads = grads.result()
        if self._donate_update:
            return self._step_donated(params, opt_state, grads)
        with self._span("prologue"):
            decision = self.manager.should_commit_async()
        dispatched = False
        if getattr(decision, "local_should_commit", True):
            if self.manager.did_heal() and self._state_fn is not None:
                # the prologue just loaded the donor snapshot into the
                # user's holder; the caller's args predate it. Re-read so
                # the (received-average) update lands on healed state.
                params, opt_state = self._state_fn()
            with self._span("dispatch"):
                new_params, new_opt = self._update(grads, opt_state, params)
            dispatched = True
        # Exposed barrier time only: whatever the RPC costs BEYOND the
        # dispatch it overlapped — the honest per-step FT tax.
        try:
            with self._span("barrier"):
                committed = bool(decision.result())
        except BaseException:
            # Barrier RPC failed (manager wedged, timeout): the caller's
            # retry loop treats the step as discarded, but the optimistic
            # dispatch is already queued on the device — await it (and
            # the fence) before re-raising, or every failed step would
            # leak one unawaited params+opt program.
            if dispatched:
                self._wait_batch([("block", new_params)])
            self._drain_fence()
            raise
        if committed and dispatched:
            # block_until_ready, not a device_get readback: these updates
            # are not donated, so the params tree stays valid to wait on,
            # and waiting moves no bytes to the host.
            with self._span("fence"):
                self._push_fence("block", new_params)
            self._observe_routing(new_opt)
            return new_params, new_opt, True
        # Non-committing step (error latched, insufficient quorum, heal
        # retry): drain the fence by WAITING, not dropping — dropping
        # would let the first commit after a non-commit stretch dispatch
        # without blocking on the prior update (two unawaited steps
        # outstanding, exactly what the fence exists to prevent), and a
        # discarded step has no latency to protect anyway. Waiting also
        # releases the references, bounding stale HBM retention.
        self._drain_fence()
        if dispatched:
            # The optimistically dispatched program was not adopted, but
            # it is still queued on the device: block on it here, or a
            # run of global-False decisions (a flapping peer) would
            # enqueue one unawaited params+opt program per step — the
            # host outrunning the device without bound, precisely what
            # the fence exists to prevent. A discarded step has no
            # latency to protect, so the wait costs nothing real.
            self._wait_batch([("block", new_params)])
        return params, opt_state, False

    def _step_donated(
        self, params: Any, opt_state: Any, grads: Any
    ) -> Tuple[Any, Any, bool]:
        """Decide-then-apply with buffer donation (donate_update=True):
        barrier first — a discarded step dispatches nothing, so donation
        never needs rollback — then ONE donated update program whose peak
        HBM adds no second params+opt footprint. The caller's (params,
        opt_state) references are CONSUMED on a committing step (grads
        stay valid; donating them buys nothing — see __init__)."""
        with self._span("barrier"):
            committed = self.manager.should_commit()
        if committed:
            if self.manager.did_heal() and self._state_fn is not None:
                params, opt_state = self._state_fn()
            with self._span("dispatch"):
                new_params, new_opt, probe = self._update_donated(
                    grads, opt_state, params
                )
            with self._span("fence"):
                # Donated chain: fence via a readback of the probe
                # scalar — completion of any output of an XLA execution
                # implies the whole execution (the donated update
                # included) ran. See __init__ for why the probe, not a
                # leaf of new_params.
                self._push_fence("readback", probe)
            self._observe_routing(new_opt)
            return new_params, new_opt, True
        self._drain_fence()
        return params, opt_state, False

    def _push_fence(self, kind: str, value: Any) -> None:
        """Enqueue a fence entry and wait out the one from ``fence_depth``
        steps ago. kind "block" waits with block_until_ready (a
        non-donated pytree); kind "readback" does a scalar device_get (a
        scalar from a DONATED chain, whose params the next step consumes —
        completion of one output of an XLA execution implies the whole
        execution ran)."""
        if self._fence_depth <= 0:
            return
        self._in_flight.append((kind, value))
        if kind == "block":
            # drain to depth (not one-per-push): a fused->classic
            # transition can inherit up to fence_depth + fence_stride - 1
            # readback entries, and a single-pop policy would pin that
            # widened window — fence_stride params trees in HBM and a
            # fence_stride-step host lead — onto the classic path forever
            if len(self._in_flight) > self._fence_depth:
                self._wait_batch([
                    self._in_flight.pop(0)
                    for _ in range(
                        len(self._in_flight) - self._fence_depth
                    )
                ])
            return
        # readback entries batch: drain fence_stride ready scalars in one
        # device_get (see fence_stride rationale in __init__)
        excess = len(self._in_flight) - self._fence_depth
        if excess >= self._fence_stride:
            self._wait_batch(
                [self._in_flight.pop(0) for _ in range(excess)]
            )

    def _drain_fence(self) -> None:
        entries, self._in_flight = self._in_flight, []
        self._wait_batch(entries)

    @staticmethod
    def _wait_batch(entries) -> None:
        if not entries:
            return
        import jax

        blocks = [v for k, v in entries if k == "block"]
        reads = [v for k, v in entries if k != "block"]
        if blocks:
            jax.block_until_ready(blocks)
        if reads:
            jax.device_get(reads)  # one batched D2H for all scalars

    def can_fuse(self) -> bool:
        """True when THIS step's wire is solo: no data-plane peer means
        the cross-replica average is an identity, so the whole step can
        run as one fused grad+update program via :meth:`fused_step`. The
        quorum and commit barrier still run — they are what detect
        rejoining peers and membership changes.

        Waits the in-flight quorum itself; on quorum failure the error is
        LATCHED (so the step is discarded by the commit gate) and False
        is returned — callers just branch on the result, no try/except
        needed. This keeps the "only after wait_quorum" contract
        unbreakable instead of conventional."""
        try:
            self.manager.wait_quorum()
        except Exception as e:  # noqa: BLE001 — timeout, malformed
            # response, donor staging error: all mean "no fused step"
            self.manager.report_error(e)
            return False
        return self.manager.is_solo_wire()

    def fused_step(
        self, fused_fn, params: Any, opt_state: Any, *args
    ) -> Tuple[Any, Any, Any, bool]:
        """Solo-wire fast path: commit barrier FIRST, then dispatch ONE
        fused grad+update program. Returns (params, opt_state, aux,
        committed); aux is ``fused_fn``'s third output (the loss) or None
        on a discarded step.

        Why barrier-before-dispatch is sound: the local vote never
        depends on gradient VALUES — it is "no transport error latched
        and enough participants" (ref manager.py:545-598) — and a solo
        wire has no transport ops that could fail between the vote and
        the update. Deciding first makes buffer DONATION safe (a
        discarded step dispatches nothing, so there is nothing to roll
        back), which halves peak params+opt HBM vs the non-donated
        two-program path — the difference that closes the 1b FT row.

        The fence differs from :meth:`step`: the params of a donated
        chain are consumed by the next step, so the fence here is a
        ``device_get`` of delayed loss scalars — batched ``fence_stride``
        at a time (one transfer per stride; host lead bounded by
        fence_depth + fence_stride), and completion of any output of an
        XLA execution implies the whole execution (the donated params
        update included) ran.

        Failure-after-vote window: the barrier advances step and
        batches_committed BEFORE the fused compute is dispatched, so a
        dispatch failure (e.g. RESOURCE_EXHAUSTED at first compile)
        leaves the counters one ahead of the applied updates. This is the
        REFERENCE's semantics too — should_commit increments step and the
        torch optimizer.step() runs after it and can fail the same way
        (ref manager.py:594-596, optim.py:53-55); the fused path only
        widens the window to the whole step. Recovery is identical:
        the raise crashes the step, the replica restarts and heals from a
        peer (or resumes a durable checkpoint, which snapshots counters
        and params atomically). Warm the fused executable before the FT
        loop (as the bench's T0 does) to keep first-compile failures out
        of the window.

        Callers MUST branch on :meth:`can_fuse` each step (it waits the
        quorum itself) and use the grad/average/:meth:`step` path when it
        returns False."""
        self.fused_steps += 1
        with self._span("barrier"):
            committed = self.manager.should_commit()
        if committed:
            if self.manager.did_heal() and self._state_fn is not None:
                # the barrier just loaded the donor snapshot; recompute on
                # the healed pair, not the caller's stale references
                params, opt_state = self._state_fn()
            if any(kind == "block" for kind, _ in self._in_flight):
                # classic->fused transition: a "block" entry IS the params
                # tree we are about to donate; wait it out while its
                # buffers are still valid (block_until_ready on a donated
                # buffer raises). Transition steps only — steady-state
                # fused entries are loss scalars. Timed separately so a
                # transition's device-scale wait can't masquerade as
                # per-program dispatch overhead in the breakdown.
                with self._span("transition_drain"):
                    self._drain_fence()
            with self._span("dispatch"):
                params, opt_state, aux = fused_fn(params, opt_state, *args)
            with self._span("fence"):
                self._push_fence("readback", aux)
            self._observe_routing(opt_state)
            return params, opt_state, aux, True
        self._drain_fence()
        return params, opt_state, None, False
