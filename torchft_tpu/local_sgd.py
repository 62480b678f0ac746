"""Fault-tolerant LocalSGD and DiLoCo with a streaming fragment scheduler.

Reference: /root/reference/torchft/local_sgd.py:26-239 for the blocking
algorithms. Both run ``sync_every`` local optimizer steps between
cross-replica syncs, keep a host-side backup of the params to roll back
failed syncs, and compute the quorum once per sync ROUND.

JAX rendering: params are pytrees owned by the training loop, so instead
of optimizer hooks these are step-driven objects:

    local = LocalSGD(manager, sync_every=8)
    params = local.register(params)
    for batch in data:
        params, opt_state = inner_step(params, opt_state, batch)
        params = local.step(params)     # round machinery inside

DiLoCo (https://arxiv.org/pdf/2311.08105) additionally applies an *outer*
optax transformation to the averaged pseudogradient. NOTE on sign: the
pseudogradient here is ``backup - params`` (θ_old − θ_new, the paper's
outer gradient). The reference snapshot computes the negation
(p.data − backup, ref local_sgd.py:211-215) and would therefore *ascend*
with a plain SGD outer optimizer — we implement the paper-correct sign.

Streaming fragment scheduler
----------------------------

The outer sync is no longer one monolithic stall. The registered param
tree is partitioned into ``num_fragments`` byte-balanced, leaf-granular
fragments (``comm.wire.split_weighted`` — deterministic from shapes
alone, so every rank computes the identical grid), and each fragment's
outer sync is staggered across the inner-step window: fragment ``f``
ships at inner step ``sync_every*(f+1)//num_fragments`` of the round.
At its boundary a fragment

1. snapshots its outer value into a persistent per-fragment float32
   staging arena (params for LocalSGD, ``backup − params`` for DiLoCo —
   no per-sync host allocation, and the transport reduces the arena in
   place under the comm donation contract),
2. optionally folds in its error-feedback residual and ships through the
   transport's wire codec (bf16/int8 — the PR 2 ``wire_roundtrip``/EF
   machinery; residuals reset on every transport incarnation, and EF is
   role-aware via ``wire_compensable`` exactly like the DDP arena),
3. rides the comm data plane as a NON-blocking op while the inner
   loop keeps stepping — backend-agnostic: the fragment arena goes
   through ``manager.allreduce_arrays`` under the donation contract,
   which the host socket transport and the on-device xla backend
   (comm/xla_backend.py) implement identically, with bit-identical
   wire codecs (a full outer round over ``comm_backend="xla"`` matches
   the host plane exactly; tests/test_xla_backend.py) — and
4. lands its outer update (per-fragment outer optax state —
   ``optim.PartitionedOuterOptimizer``) on a bounded worker the moment
   its wire future resolves — while later fragments are still riding
   the wire.

Commit semantics stay per-round: the quorum is computed async AHEAD of
the first fragment boundary and fenced at round start
(``Manager.quorum_fence`` — which also eagerly applies a pending heal,
lifting the old ``use_async_quorum=False`` requirement), a
``futures.FutureGroup`` resolves the round once every fragment has
landed and every EF task has finished, ``should_commit`` gates the WHOLE
round, and an aborted round rolls every fragment back to its backup —
landed updates are STAGED, never merged into live state before the
commit vote, so abort is exact.

``streaming=False`` keeps the same schedule and the same math but blocks
at every fragment boundary — the A/B lever and the bitwise oracle
(tests/test_localsgd_streaming.py pins streaming ≡ blocking per round
for every codec × topology at the same fragment grid), mirroring the
PR 3 ``streamed=False`` pattern. ``num_fragments=1`` reproduces the
legacy monolithic schedule (one fragment, boundary at ``sync_every``).

Fragment staleness: with F > 1, fragment ``f``'s snapshot is taken
``sync_every − boundary_f`` inner steps before the round ends — the
Streaming-DiLoCo staleness the outer optimizer tolerates by design. The
grid is part of the algorithm (both A/B arms share it); changing F
changes the trajectory, changing ``streaming`` does not.

Metrics (into ``manager.metrics``): per-fragment ``outer_d2h`` /
``outer_ef`` / ``outer_wire`` / ``outer_land`` stage timers, plus
per-round gauges ``outer_wire_ms`` (summed fragment wire time),
``outer_wire_exposed_ms`` (wall time the round actually blocked on the
wire), ``outer_overlap`` (1 − exposed/total — the bench's
``t1_outer_overlap``), ``outer_wire_bytes`` (encoded payload bytes) and
``outer_inflight_at_drain`` (fragments still riding the wire when the
round ran out of inner steps).
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, List, Optional, Tuple

import numpy as np

from torchft_tpu.comm.wire import split_weighted
from torchft_tpu.futures import FutureGroup
from torchft_tpu.optim import PartitionedOuterOptimizer
from torchft_tpu.utils.device import land, land_like
from torchft_tpu.utils.profiling import span

logger = logging.getLogger(__name__)

__all__ = ["LocalSGD", "DiLoCo", "fragment_boundaries"]


def fragment_boundaries(sync_every: int, num_fragments: int) -> List[int]:
    """Inner-step boundary for each fragment: fragment ``f`` snapshots
    and ships at step ``sync_every*(f+1)//num_fragments`` of the round —
    evenly staggered, last fragment exactly at the round end. Strictly
    increasing whenever ``sync_every >= num_fragments`` (enforced by the
    ctor)."""
    return [
        sync_every * (f + 1) // num_fragments for f in range(num_fragments)
    ]


# Process-wide bounded workers for the off-critical-path outer stages,
# mirroring the DDP pipeline pools: many wrapper instances (tests,
# multi-group benches) share two threads per stage instead of
# accumulating idle ones. Landings ("land") and EF quantizer roundtrips
# ("ef") get SEPARATE pools for the same reason ddp.py splits them: a
# multi-MB quantizer task must never queue a fragment landing whose wire
# future already resolved — that delay lands squarely in
# outer_wire_exposed_ms. Tasks never block on other tasks (both stages
# are pure compute), so the bounded pools cannot deadlock.
_OUTER_LOCK = threading.Lock()
_OUTER_EXECUTORS: "dict[str, ThreadPoolExecutor]" = {}


def _outer_executor(kind: str) -> ThreadPoolExecutor:
    with _OUTER_LOCK:
        ex = _OUTER_EXECUTORS.get(kind)
        if ex is None:
            ex = ThreadPoolExecutor(
                max_workers=2,
                thread_name_prefix=f"torchft_tpu_outer_{kind}",
            )
            _OUTER_EXECUTORS[kind] = ex
        return ex


_REMOTE = object()  # staged-slot sentinel: fragment landed on its owner


class _SyncRound:
    """One in-flight sync round: the completion group, per-fragment
    staged landings (adopted only on commit), and the wire timestamps
    the overlap gauges are derived from. ``world``/``rank`` are the wire
    membership captured at the round-start fence — the sharded outer
    plane's fragment→owner map (fragment f is owned by rank
    ``f % world``) derives from them."""

    __slots__ = ("group", "staged", "shipped", "fenced",
                 "submit_t", "wire_t", "exposed_s", "wire_bytes",
                 "world", "rank")

    def __init__(self, num_fragments: int) -> None:
        self.group = FutureGroup()
        self.staged: List[Any] = [None] * num_fragments
        self.shipped = [False] * num_fragments
        self.fenced = False
        self.submit_t = [0.0] * num_fragments
        self.wire_t = [0.0] * num_fragments
        self.exposed_s = 0.0
        self.wire_bytes = 0
        self.world = 1
        self.rank = 0


class LocalSGD:
    """Infrequent-sync data parallelism with rollback
    (ref local_sgd.py:26-174), scheduled as streaming fragments (module
    docstring). LocalSGD ships the params themselves; the committed
    round adopts the cross-replica average per fragment."""

    def __init__(self, manager, sync_every: int,
                 params_fn: Optional[Any] = None,
                 num_fragments: int = 1,
                 streaming: bool = True,
                 error_feedback: "bool | str" = "auto",
                 sharded_outer: bool = False,
                 topology: "Optional[str]" = None) -> None:
        """``params_fn``: zero-arg callable returning the CURRENT params —
        the same state the Manager's user ``load_state_dict`` writes into.
        Needed for heal: params here are caller-owned values, so after a
        round-start heal the wrapper must re-read them. Without it, a
        rejoined replica would average its stale params into the group.

        ``num_fragments``: outer-sync fragments (1 = the legacy
        monolithic schedule). ``streaming``: non-blocking staggered wire
        (True, default) vs block-at-every-boundary (the A/B lever and
        bitwise oracle). ``error_feedback``: "auto" runs the residual
        arena exactly when this rank's contribution crosses a lossy wire
        codec (``manager.wire_compensable``); True forces it on; False
        disables it (raw quantization).

        ``sharded_outer``: the fragments BECOME the sharded weight
        update's shard unit — each fragment's pseudogradient
        reduce-scatters to its owner rank (``f % wire_world``), ONLY the
        owner runs that fragment's outer optax step (per-fragment outer
        state held owner-side only, 1/N outer-state memory and update
        FLOPs), and the committed round allgathers the updated fragment
        params back (raw native-dtype bytes, so the committed values
        stay bitwise identical to the replicated arm). Must match
        across replicas (it changes the collective sequence); an owner
        map changed by membership churn — heals included, since a
        donor ships only its own fragments — EXCHANGES the moved
        fragments' outer state at the next round fence through the
        redistribution engine (fetched from a surviving holder over
        the raw-bytes heal plane; reinitialized only when no holder
        survives), made visible by a ``reshard`` event (see
        ``_on_owner_map``)."""
        assert sync_every >= 1, "sync_every must be >= 1"
        if num_fragments < 1:
            raise ValueError("num_fragments must be >= 1")
        if sync_every < num_fragments:
            raise ValueError(
                f"sync_every ({sync_every}) must be >= num_fragments "
                f"({num_fragments}): fragments ship at inner steps "
                f"sync_every*(f+1)//num_fragments, which collide when the "
                "round has fewer steps than fragments — raise sync_every "
                "or lower num_fragments"
            )
        if error_feedback not in (True, False, "auto"):
            raise ValueError(
                f"error_feedback must be True/False/'auto', "
                f"got {error_feedback!r}"
            )
        self._manager = manager
        # Outer-sync data-path selector ("flat"/"hier"; None = the comm
        # context's default, and the kwarg is then not passed at all so
        # stub/legacy managers keep working). The hierarchical tier is
        # the natural outer-sync wire: pseudogradients are exactly the
        # heavy, lossy-codec-friendly cross-DCN traffic DynamiQ tiers.
        self._topology = topology
        self._ar_kwargs = {} if topology is None else {
            "topology": topology
        }
        self._sync_every = sync_every
        self._params_fn = params_fn
        self._num_fragments = int(num_fragments)
        self._streaming = bool(streaming)
        self._error_feedback = error_feedback
        self._sharded_outer = bool(sharded_outer)
        self._outer_world: "Optional[Tuple[int, int]]" = None
        # Transport incarnation of the last sharded-outer reshard — the
        # cohort-synchronized trigger (every membership change bumps it
        # on every wire member at the same quorum boundary, which is
        # what keeps the exchange's collectives matched).
        self._outer_gen: "Optional[int]" = None
        self._local_step = 0
        self._healed_backup = False
        # Frozen leaf layout (built at register / first step) — the
        # fragment grid must be identical across ranks and across steps,
        # the same freeze discipline as the DDP bucket plan.
        self._treedef = None
        self._shapes: Optional[List[Tuple[int, ...]]] = None
        self._dtypes: Optional[List[np.dtype]] = None
        self._sizes: Optional[List[int]] = None
        # Where each param leaf lives (its jax sharding; None for host
        # leaves): everything the outer sync hands back to the device
        # returns there, not to the process's default device.
        self._shardings: Optional[List[Any]] = None
        self._fragments: Optional[List[Tuple[int, int]]] = None
        self._boundaries: Optional[List[int]] = None
        # Persistent arenas (satellite: no per-sync host allocation):
        self._backup: Optional[List[np.ndarray]] = None
        self._pg_arena: Optional[List[Optional[np.ndarray]]] = None
        self._ef_residuals: Optional[List[np.ndarray]] = None
        self._ef_scratch: Optional[List[Optional[np.ndarray]]] = None
        self._ef_generation: Optional[int] = None
        self._round: Optional[_SyncRound] = None
        self._round_starting = False

    # -- introspection -------------------------------------------------------

    @property
    def local_step(self) -> int:
        return self._local_step

    @property
    def num_fragments(self) -> int:
        """Actual fragment count (clamped to the leaf count at layout
        build; the requested value before register)."""
        if self._fragments is not None:
            return len(self._fragments)
        return self._num_fragments

    @property
    def streaming(self) -> bool:
        return self._streaming

    def _metrics(self):
        return getattr(self._manager, "metrics", None)

    def _wire_healthy(self) -> bool:
        """Gauge gate (the DDP rule): after a latched transport error
        every allreduce resolves inline and its ~0ms 'wire' time would
        corrupt the overlap gauges the bench grades — skip observations
        instead (the round never commits anyway)."""
        errored = getattr(self._manager, "errored", None)
        return not callable(errored) or errored() is None

    # -- lifecycle -----------------------------------------------------------

    def register(self, params: Any) -> Any:
        """Freeze the leaf/fragment layout and save the initial backup
        (ref local_sgd.py:95 saves in ctor)."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(params)
        self._treedef = treedef
        self._build_layout(leaves)
        self._save_backup_leaves(leaves)
        return params

    # NOTE: no context-manager protocol. The torch reference restores the
    # model in place on __exit__ (ref local_sgd.py:104-119); params here are
    # caller-owned JAX values, so an __exit__ could not reach them — callers
    # roll back explicitly with restore() instead:
    #
    #     try:
    #         params, opt_state = inner_step(...)
    #         params = local.step(params)
    #     except Exception:
    #         params = local.restore()

    def _build_layout(self, leaves: List[Any]) -> None:
        self._shapes = [tuple(x.shape) for x in leaves]
        self._dtypes = [np.dtype(x.dtype) for x in leaves]
        self._sizes = [int(np.prod(s, dtype=np.int64)) for s in self._shapes]
        self._shardings = [getattr(x, "sharding", None) for x in leaves]
        if any(np.issubdtype(dt, np.integer) for dt in self._dtypes):
            logger.warning(
                "param tree contains integer leaves: the outer wire "
                "plane is float32, so integer values survive the sync "
                "exactly only below 2**24 — larger values drift by f32 "
                "rounding every round (keep counters out of the synced "
                "tree, or carry them as float64 outside it)"
            )
        # Byte-balanced leaf-granular fragments; the wire plane is f32,
        # so weight by element count * 4 == the actual staged bytes.
        self._fragments = split_weighted(
            [sz * 4 for sz in self._sizes], self._num_fragments
        )
        if len(self._fragments) != self._num_fragments:
            logger.info(
                "num_fragments clamped %d -> %d (param tree has only %d "
                "leaves)", self._num_fragments, len(self._fragments),
                len(leaves),
            )
        self._boundaries = fragment_boundaries(
            self._sync_every, len(self._fragments)
        )

    def _check_layout(self, leaves: List[Any]) -> None:
        if len(leaves) != len(self._shapes):
            raise ValueError(
                "param pytree changed between steps; the outer-sync "
                "fragment layout is frozen by design"
            )

    def _save_backup_leaves(self, leaves: List[Any]) -> None:
        """Persistent backup arena: allocated once, refreshed in place —
        no fresh host tree per sync (the old ``_to_host_copy``)."""
        import jax

        if self._backup is None:
            self._backup = [
                np.array(jax.device_get(x), copy=True) for x in leaves
            ]
            return
        for dst, x in zip(self._backup, leaves):
            np.copyto(dst, np.asarray(jax.device_get(x)), casting="unsafe")

    # -- checkpoint surface --------------------------------------------------
    # The wrapper's backup IS part of the training state: a healing replica
    # must receive the donor's sync point, not re-derive one, or the first
    # post-heal sync diverges (the reference checkpoints backup_params the
    # same way, ref manager_integ_test.py:278-290). Include these in the
    # state_dict/load_state_dict functions given to the Manager.

    def state_dict(self) -> dict:
        import jax

        backup = None
        if self._backup is not None and self._treedef is not None:
            # COPIES, not the arena itself: the heal plane stages leaves
            # lazily, and a commit's in-place backup refresh racing a
            # donor's deferred read would serve a torn sync point.
            backup = jax.tree_util.tree_unflatten(
                self._treedef,
                [np.array(b, copy=True) for b in self._backup],
            )
        return {"backup": backup, "local_step": self._local_step}

    def load_state_dict(self, state: dict) -> None:
        import jax

        backup = state["backup"]
        if backup is None:
            self._backup = None
        else:
            leaves, treedef = jax.tree_util.tree_flatten(backup)
            if self._treedef is None:
                self._treedef = treedef
                self._build_layout(leaves)
            elif len(leaves) != len(self._shapes):
                # zip() below would silently truncate, mixing donor and
                # stale local leaves into one corrupt sync point — the
                # same drift class _check_layout guards in step().
                raise ValueError(
                    f"donor backup has {len(leaves)} leaves but this "
                    f"replica's frozen layout has {len(self._shapes)}: "
                    "replica configs diverged — align model/wrapper "
                    "construction across replica groups"
                )
            if self._backup is None:
                self._backup = [
                    np.array(np.asarray(l), copy=True) for l in leaves
                ]
            else:
                for dst, src in zip(self._backup, leaves):
                    np.copyto(dst, np.asarray(src), casting="unsafe")
        if self._round is None and not self._round_starting:
            # Mid-round (a round-start heal) the schedule owns the
            # counter; the donor's value describes ITS mid-round position
            # and both reset to 0 at the round end anyway. The
            # _round_starting flag covers the sync-quorum manager, whose
            # eager heal runs INSIDE start_quorum — before self._round
            # exists — where adopting the donor's counter would rewind
            # this round's fragment schedule and strand the peers'
            # allreduces waiting for fragments that never ship.
            self._local_step = int(state["local_step"])
        self._healed_backup = True

    def restore(self) -> Any:
        """The last committed (synced) params, as device arrays where
        the registered params live (copies of the backup arena — see
        :meth:`_to_device`)."""
        import jax

        assert self._backup is not None, "register() was never called"
        return jax.tree_util.tree_unflatten(
            self._treedef,
            [self._to_device(i, b) for i, b in enumerate(self._backup)],
        )

    # -- stepping ------------------------------------------------------------

    def _kick_step(self) -> int:
        """Inner step at which the round's quorum is kicked off. With an
        async-quorum manager, one step AHEAD of the first fragment
        boundary so the RPC overlaps inner compute and the round-start
        fence finds it resolved; with a sync-quorum manager start_quorum
        blocks (and heals eagerly), so kicking early would stall an
        inner step for nothing — kick at the boundary itself."""
        b0 = self._boundaries[0]
        if getattr(self._manager, "_use_async_quorum", False):
            return max(1, b0 - 1)
        return b0

    def _ensure_registered(self, params: Any) -> None:
        """Lazy register() for callers that never called it explicitly:
        freeze the layout and seed the backup from the first params
        seen. Both step() and sync() route through this — the
        pre-streaming sync() worked on an unregistered wrapper and the
        catch-up path must keep doing so."""
        import jax

        if self._treedef is None:
            leaves, treedef = jax.tree_util.tree_flatten(params)
            self._treedef = treedef
            self._build_layout(leaves)
        elif all(s is None for s in self._shardings):
            # layout frozen from a heal's host leaves: the first params
            # seen say where the leaves live
            self._shardings = [
                getattr(x, "sharding", None)
                for x in jax.tree_util.tree_flatten(params)[0]
            ]
        if self._backup is None:
            self._save_backup_leaves(jax.tree_util.tree_flatten(params)[0])

    def _to_device(self, i: int, host: np.ndarray) -> Any:
        """A COPY of ``host`` as a device array where param leaf ``i``
        lives. A copy because callers pass views of persistent arenas
        (the backup, the staged fragments) that the next round refreshes
        in place — on the CPU backend an aliased result would be mutated
        under the caller."""
        return land(host, self._shardings[i])

    def step(self, params: Any) -> Any:
        """Count one inner optimizer step; drive the round machinery
        (quorum kick, round-start fence, fragment boundaries, round
        commit) as boundaries come due (ref local_sgd.py:133-149)."""
        self._ensure_registered(params)
        self._local_step += 1
        if self._round is None and self._local_step >= self._kick_step():
            self._begin_round()
        if self._round is not None:
            params = self._advance_round(params, self._local_step)
        return params

    def sync(self, params: Any) -> Any:
        """Force a full sync round NOW (catch-up path): every fragment
        ships this step and the round commits or rolls back before
        returning. ``step()`` uses the same machinery incrementally."""
        self._ensure_registered(params)
        self._local_step = max(self._local_step, self._sync_every)
        if self._round is None:
            self._begin_round()
        return self._advance_round(params, self._local_step)

    def _begin_round(self) -> None:
        # _round_starting marks that the schedule already owns
        # _local_step: a sync-quorum manager applies a pending heal
        # INSIDE start_quorum — before self._round exists — and without
        # the flag load_state_dict would adopt the donor's mid-round
        # counter (see load_state_dict).
        self._round_starting = True
        try:
            self._manager.start_quorum()
        finally:
            self._round_starting = False
        self._round = _SyncRound(len(self._fragments))

    def _advance_round(self, params: Any, s: int) -> Any:
        rnd = self._round
        if not rnd.fenced and s >= self._boundaries[0]:
            rnd.fenced = True
            params = self._fence(params)
        due = [
            f for f, b in enumerate(self._boundaries)
            if not rnd.shipped[f] and b <= s
        ]
        if due:
            import jax

            leaves = jax.tree_util.tree_flatten(params)[0]
            self._check_layout(leaves)
            for f in due:
                start, stop = self._fragments[f]
                for i in range(start, stop):  # async D2H ahead of the pack
                    if hasattr(leaves[i], "copy_to_host_async"):
                        leaves[i].copy_to_host_async()
            for f in due:
                self._ship_fragment(rnd, f, leaves)
                rnd.shipped[f] = True
        if s >= self._sync_every:
            params = self._finish_round(rnd, params)
        return params

    def _fence(self, params: Any) -> Any:
        """Round-start fence: resolve the quorum kicked ahead of the
        first boundary and eagerly apply a pending heal, so every
        fragment snapshot of this round derives from healed state."""
        mgr = self._manager
        try:
            fence = getattr(mgr, "quorum_fence", None)
            if callable(fence):
                fence()
            else:  # pre-fence manager/stub: plain wait
                mgr.wait_quorum()
        except Exception as e:  # noqa: BLE001 — latch; the round aborts
            # at its commit barrier instead of crashing the inner loop
            logger.exception("round-start quorum fence failed: %s", e)
            mgr.report_error(e)
            return params
        if mgr.did_heal():
            # The fence applied a peer's checkpoint via the user
            # load_state_dict; this round must snapshot THAT state, not
            # the caller's stale params (see ctor docstring).
            if self._params_fn is not None:
                import jax

                params = self._params_fn()
                if self._healed_backup:
                    # the donor's backup came through load_state_dict —
                    # keep it; it is the true sync point
                    self._healed_backup = False
                else:
                    self._save_backup_leaves(
                        jax.tree_util.tree_flatten(params)[0]
                    )
            else:
                logger.warning(
                    "healed without params_fn: caller params may be stale "
                    "— pass params_fn to LocalSGD/DiLoCo for correct heal"
                )
        rnd = self._round
        if rnd is not None:
            world_fn = getattr(mgr, "transport_world_size", None)
            rank_fn = getattr(mgr, "transport_rank", None)
            rnd.world = max(
                1, int(world_fn()) if callable(world_fn) else 1
            )
            rnd.rank = int(rank_fn()) if callable(rank_fn) else 0
            if self._sharded_outer:
                self._on_owner_map(rnd, params)
        return params

    def _frag_owner(self, rnd: _SyncRound, f: int) -> int:
        return f % rnd.world

    def _frag_owned(self, rnd: _SyncRound, f: int) -> bool:
        return (not self._sharded_outer) or rnd.world == 1 or (
            self._frag_owner(rnd, f) == rnd.rank
        )

    def _on_owner_map(self, rnd: _SyncRound, params: Any) -> None:
        """Sharded-outer hook, called once per round after the fence
        resolved the wire membership: DiLoCo reshards its per-fragment
        outer states onto the new owner map. Base LocalSGD carries no
        outer state — nothing to move."""

    def _exchange_fragments(
        self, rnd: _SyncRound,
        contrib: "dict[int, List[np.ndarray]]",
    ) -> "dict[int, List[np.ndarray]]":
        """Commit-time allgather of updated fragment params: each rank
        contributes its OWNED fragments' leaves (native dtypes — raw
        bytes forward verbatim, keeping the committed values bitwise
        identical to the replicated arm) and receives everyone else's.
        Returns per-fragment leaf arrays for EVERY fragment. Runs only
        on a committed round, which is a globally consistent decision —
        the collective is always matched across the cohort. A failure
        here means this replica cannot materialize a round the cohort
        committed: raise so the standard restart+heal path recovers."""
        F = len(self._fragments)
        flat: "List[np.ndarray]" = []
        for f in sorted(contrib):
            flat.extend(contrib[f])
        gathered = (
            self._manager.allgather_arrays(flat).future().result()
        )
        errored = getattr(self._manager, "errored", None)
        if callable(errored) and errored() is not None:
            raise RuntimeError(
                "sharded outer round committed but the fragment "
                f"allgather failed ({errored()}): restart and heal"
            )
        out: "dict[int, List[np.ndarray]]" = {}
        for owner in range(rnd.world):
            ofrags = [
                f for f in range(F) if self._frag_owner(rnd, f) == owner
            ]
            arrays = gathered[owner] if owner < len(gathered) else []
            cursor = 0
            for f in ofrags:
                start, stop = self._fragments[f]
                n_leaves = stop - start
                got = arrays[cursor: cursor + n_leaves]
                cursor += n_leaves
                if len(got) != n_leaves:
                    raise RuntimeError(
                        f"sharded outer commit: owner {owner} shipped "
                        f"{len(got)} of {n_leaves} leaves for fragment "
                        f"{f} — restart and heal"
                    )
                out[f] = [np.asarray(a) for a in got]
        return out

    # -- fragment pipeline ---------------------------------------------------

    def _frag_elems(self, f: int) -> int:
        start, stop = self._fragments[f]
        return sum(self._sizes[start:stop])

    def _frag_arena(self, f: int) -> np.ndarray:
        if self._pg_arena is None:
            self._pg_arena = [None] * len(self._fragments)
        if self._pg_arena[f] is None:
            self._pg_arena[f] = np.empty(self._frag_elems(f), np.float32)
        return self._pg_arena[f]

    def _fragment_value_into(self, f: int, leaves: List[Any],
                             out: np.ndarray) -> None:
        """LocalSGD ships the params themselves (weight averaging; the
        outer update adopts the average — outer SGD at lr=1 in
        pseudogradient terms). In-place pack into the f32 arena."""
        import jax

        start, stop = self._fragments[f]
        off = 0
        for i in range(start, stop):
            n = self._sizes[i]
            np.copyto(
                out[off:off + n],
                np.asarray(jax.device_get(leaves[i])).reshape(-1),
                casting="unsafe",
            )
            off += n

    def _ef_enabled(self) -> bool:
        """THE DDP error-feedback gate, applied to the outer stream:
        enabled AND this rank's contribution actually crosses a lossy
        wire (role-aware) AND this replica ships real values this round.
        Delegates to ddp._ef_gate — this used to be a hand-rolled
        mirror, which is exactly the drift the one-definition lint now
        forbids (scripts/check.py)."""
        from torchft_tpu.ddp import _ef_gate

        return _ef_gate(self._manager, self._error_feedback)

    def _ef_prepare(self) -> None:
        """(Re)allocate zeroed residuals on first use and on every
        transport incarnation change — membership changed, so the
        previous round's quantization error no longer belongs to this
        cohort's stream (the DDP residual lifecycle)."""
        gen_fn = getattr(self._manager, "wire_generation", None)
        gen = int(gen_fn()) if callable(gen_fn) else 0
        if self._ef_residuals is None or gen != self._ef_generation:
            self._ef_residuals = [
                np.zeros(self._frag_elems(f), np.float32)
                for f in range(len(self._fragments))
            ]
            self._ef_generation = gen

    def _ef_scratch_for(self, f: int) -> np.ndarray:
        if self._ef_scratch is None:
            self._ef_scratch = [None] * len(self._fragments)
        if self._ef_scratch[f] is None:
            self._ef_scratch[f] = np.empty(self._frag_elems(f), np.float32)
        return self._ef_scratch[f]

    def _ef_residual(self, transmitted: np.ndarray, res: np.ndarray,
                     metrics) -> None:
        """e_t = v' − C(v') against the wire's own chunk grid.
        ``transmitted`` is v' (or a snapshot of it — the donated arena is
        reduced in place the moment the wire takes it)."""
        with span(metrics, "outer_ef"):
            self._manager.wire_roundtrip(transmitted, res)  # res = C(v')
            np.subtract(transmitted, res, out=res)
            if not np.all(np.isfinite(res)):
                # A non-finite value poisons its wire image; the round is
                # discarded by the commit gate, but the residual persists
                # — left NaN it would re-inject the spike into every
                # later round. Drop that error instead.
                np.nan_to_num(res, copy=False,
                              nan=0.0, posinf=0.0, neginf=0.0)

    def _ship_fragment(self, rnd: _SyncRound, f: int,
                       leaves: List[Any]) -> None:
        mgr = self._manager
        metrics = self._metrics()
        arena = self._frag_arena(f)
        with span(metrics, "outer_d2h", fragment=f):
            self._fragment_value_into(f, leaves, arena)
        if self._ef_enabled():
            self._ef_prepare()
            res = self._ef_residuals[f]
            # v' = v + e_prev stays inline (one vector add); the
            # quantizer roundtrip rides the worker in streaming mode,
            # reading a SNAPSHOT because the donated arena is reduced in
            # place once the wire takes it. Blocking mode computes it
            # inline BEFORE submit (arena still intact) — identical
            # values, which is what keeps the two arms bitwise.
            np.add(arena, res, out=arena)
            if self._streaming:
                scratch = self._ef_scratch_for(f)
                np.copyto(scratch, arena)
                rnd.group.add(_outer_executor("ef").submit(
                    self._ef_residual, scratch, res, metrics
                ))
            else:
                self._ef_residual(arena, res, metrics)
        nbytes_fn = getattr(mgr, "wire_nbytes", None)
        if callable(nbytes_fn):
            try:
                rnd.wire_bytes += int(nbytes_fn(arena))
            except Exception:  # noqa: BLE001 — gauge only, never fatal
                pass
        rnd.submit_t[f] = time.perf_counter()
        owned = self._frag_owned(rnd, f)
        if self._sharded_outer and rnd.world > 1:
            # The fragment IS the shard unit: its averaged value is
            # delivered only to its owner (same bytes the allreduce
            # would deliver there — transport reduce_scatter contract);
            # everyone else skips the landing compute entirely and
            # receives the owner's UPDATED params at commit.
            work = mgr.reduce_scatter_arrays(
                [arena], owners=[self._frag_owner(rnd, f)]
            )
        else:
            work = mgr.allreduce_arrays([arena], **self._ar_kwargs)
        landed: Future = Future()
        landed.set_running_or_notify_cancel()
        rnd.group.add(landed)

        def _land(wf: Future, f: int = f, owned: bool = owned) -> None:
            try:
                reduced = wf.result()[0]
                if owned:
                    self._land_fragment(rnd, f, reduced)
                else:
                    rnd.staged[f] = _REMOTE
                landed.set_result(None)
            except Exception as e:  # noqa: BLE001 — fails the group →
                landed.set_exception(e)  # the round aborts at commit

        if self._streaming:
            def _on_wire(wf: Future, f: int = f) -> None:
                # Lane-thread continuation: timestamp + enqueue only (the
                # transport's O(enqueue) contract) — the landing compute
                # belongs on the bounded worker.
                rnd.wire_t[f] = time.perf_counter()
                if metrics is not None and self._wire_healthy():
                    metrics.observe(
                        "outer_wire", rnd.wire_t[f] - rnd.submit_t[f]
                    )
                _outer_executor("land").submit(_land, wf)

            work.add_done_callback(_on_wire)
        else:
            t0 = time.perf_counter()
            wf = work.future()
            try:
                wf.result()  # manager futures never raise (wrap_future);
            except Exception:  # noqa: BLE001 — stubs may: _land re-reads
                pass  # the exception and fails the group
            rnd.wire_t[f] = time.perf_counter()
            rnd.exposed_s += rnd.wire_t[f] - t0
            if metrics is not None and self._wire_healthy():
                metrics.observe("outer_wire", rnd.wire_t[f] - rnd.submit_t[f])
            _land(wf)

    def _land_fragment(self, rnd: _SyncRound, f: int,
                       reduced: np.ndarray) -> None:
        """Stage fragment ``f``'s landed outer result (adopted only on
        commit). LocalSGD: the averaged flat values themselves."""
        with span(self._metrics(), "outer_land", fragment=f):
            rnd.staged[f] = reduced

    # -- round completion ----------------------------------------------------

    def _finish_round(self, rnd: _SyncRound, params: Any) -> Any:
        mgr = self._manager
        metrics = self._metrics()
        if metrics is not None:
            metrics.gauge("outer_inflight_at_drain", rnd.group.outstanding)
        t0 = time.perf_counter()
        done = rnd.group.seal(lambda: None)
        error: Optional[BaseException] = None
        try:
            done.result()  # the exposed drain — everything the inner
        except Exception as e:  # noqa: BLE001 — steps failed to hide
            error = e
        rnd.exposed_s += time.perf_counter() - t0
        if error is not None:
            logger.exception("sync round fragment failed: %s", error)
            mgr.report_error(error)
        total = sum(
            rnd.wire_t[f] - rnd.submit_t[f]
            for f in range(len(self._fragments))
            if rnd.shipped[f] and rnd.wire_t[f] > 0.0
        )
        if metrics is not None and self._wire_healthy() and total > 0.0:
            exposed = min(rnd.exposed_s, total)
            metrics.gauge("outer_wire_ms", total * 1000.0)
            metrics.gauge("outer_wire_exposed_ms", exposed * 1000.0)
            metrics.gauge(
                "outer_overlap",
                max(0.0, min(1.0, 1.0 - exposed / total)),
            )
            metrics.gauge("outer_wire_bytes", rnd.wire_bytes)
        # Round state is consumed BEFORE the commit barrier: if the
        # barrier itself raises (manager wedged), the caller's retry loop
        # finds local_step >= sync_every with no round active and the
        # next step() catches up with a fresh quorum.
        self._round = None
        committed = bool(mgr.should_commit())
        self._local_step = 0
        if committed:
            return self._commit_round(rnd)
        logger.warning(
            "sync round aborted; rolling back %d local steps",
            self._sync_every,
        )
        ev = getattr(mgr, "events", None)
        if ev:
            # the outer-plane lifecycle event: a whole sync round (every
            # fragment, sync_every inner steps) rolled back to backup
            ev.emit(
                "round_abort", source="outer_sync",
                fragments=len(self._fragments),
                inner_steps=self._sync_every,
                error=None if error is None else repr(error)[:200],
            )
        return self.restore()

    def _frag_native_leaves(self, f: int,
                            flat: np.ndarray) -> "List[np.ndarray]":
        """One fragment's averaged f32 arena decoded to native-dtype
        leaf arrays (ints rounded, not truncated — exact only below
        2**24; _build_layout warns once). THE f32→native conversion,
        shared by the local adopt and the sharded exchange so both
        paths commit identical bytes."""
        start, stop = self._fragments[f]
        out: "List[np.ndarray]" = []
        off = 0
        for i in range(start, stop):
            n = self._sizes[i]
            view = flat[off:off + n].reshape(self._shapes[i])
            if np.issubdtype(self._dtypes[i], np.integer):
                # participant-scaled float average of identical ints
                # can sit an ulp off the integer — round, don't
                # truncate.
                leaf = np.rint(view).astype(self._dtypes[i])
            else:
                leaf = np.asarray(view).astype(self._dtypes[i])
            out.append(leaf)
            off += n
        return out

    def _commit_round(self, rnd: _SyncRound) -> Any:
        """Adopt every fragment's staged average: refresh the backup
        arena in place and return fresh device params. Sharded outer:
        owned fragments adopt locally AND ship through the commit
        allgather; remote fragments adopt the owner's bytes."""
        import jax

        new_leaves: List[Any] = [None] * len(self._shapes)
        if self._sharded_outer and rnd.world > 1:
            contrib = {
                f: self._frag_native_leaves(f, rnd.staged[f])
                for f in range(len(self._fragments))
                if rnd.staged[f] is not _REMOTE
            }
            frag_leaves = self._exchange_fragments(rnd, contrib)
            for f, (start, stop) in enumerate(self._fragments):
                for j, i in enumerate(range(start, stop)):
                    np.copyto(self._backup[i], frag_leaves[f][j],
                              casting="unsafe")
                    new_leaves[i] = self._to_device(i, self._backup[i])
            return jax.tree_util.tree_unflatten(self._treedef, new_leaves)
        # Replicated arm: decode straight into the persistent backup
        # arena — zero per-sync allocation, the PR 5 contract (the
        # allocating _frag_native_leaves path is reserved for sharded
        # contributions, which need standalone wire buffers).
        for f, (start, stop) in enumerate(self._fragments):
            flat = rnd.staged[f]
            off = 0
            for i in range(start, stop):
                n = self._sizes[i]
                view = flat[off:off + n].reshape(self._shapes[i])
                if np.issubdtype(self._dtypes[i], np.integer):
                    # participant-scaled float average of identical ints
                    # can sit an ulp off the integer — round, don't
                    # truncate. Exact only below 2**24 (f32 wire plane;
                    # _build_layout warns once).
                    np.copyto(self._backup[i], np.rint(view),
                              casting="unsafe")
                else:
                    np.copyto(self._backup[i], view, casting="unsafe")
                new_leaves[i] = self._to_device(i, self._backup[i])
                off += n
        return jax.tree_util.tree_unflatten(self._treedef, new_leaves)


class DiLoCo(LocalSGD):
    """Outer/inner-optimizer DP: average pseudogradients per fragment,
    land per-fragment outer optax steps (ref local_sgd.py:177-239 for the
    blocking semantics; module docstring for the streaming schedule).

    The reference forbade async quorum outright (ref local_sgd.py:
    195-199); here the round-start fence (``Manager.quorum_fence``)
    resolves the quorum AND eagerly applies a pending heal before the
    first fragment snapshots, so async-quorum managers overlap the
    quorum RPC with inner compute instead of being rejected."""

    def __init__(self, manager, outer_tx, sync_every: int,
                 params_fn: Optional[Any] = None,
                 num_fragments: int = 1,
                 streaming: bool = True,
                 error_feedback: "bool | str" = "auto",
                 sharded_outer: bool = False,
                 topology: "Optional[str]" = None) -> None:
        super().__init__(
            manager, sync_every, params_fn=params_fn,
            num_fragments=num_fragments, streaming=streaming,
            error_feedback=error_feedback, sharded_outer=sharded_outer,
            topology=topology,
        )
        from torchft_tpu.comm.redistribute import RedistPlanner

        self._outer = PartitionedOuterOptimizer(outer_tx)
        # Sharded-outer reshard plans, cached per (holdings, owner-map)
        # spec pair — kill→reform oscillation replans zero times.
        self._redist_planner = RedistPlanner()

    def register(self, params: Any) -> Any:
        params = super().register(params)
        self._init_outer(params)
        return params

    def _ensure_registered(self, params: Any) -> None:
        super()._ensure_registered(params)
        if self._outer.states is None:
            self._init_outer(params)

    def _init_outer(self, params: Any) -> None:
        import jax
        import jax.numpy as jnp

        leaves = jax.tree_util.tree_flatten(params)[0]
        self._outer.init([
            [jnp.asarray(leaves[i]) for i in range(start, stop)]
            for start, stop in self._fragments
        ])

    @property
    def outer_state(self) -> Any:
        """Per-fragment outer optax states (a list — one per fragment)."""
        return self._outer.states

    def load_outer_state(self, state: Any) -> None:
        self._outer.load_states(state)

    def state_dict(self) -> dict:
        out = super().state_dict()
        out["outer_state"] = self._outer.states
        return out

    def load_state_dict(self, state: dict) -> None:
        super().load_state_dict(state)
        self._outer.load_states(state["outer_state"])

    def _fragment_value_into(self, f: int, leaves: List[Any],
                             out: np.ndarray) -> None:
        """Outer gradient Δ = θ_old − θ_new (paper sign; see module
        note), computed in place into the fragment's f32 arena — no
        fresh pseudogradient tree per sync."""
        import jax

        start, stop = self._fragments[f]
        off = 0
        for i in range(start, stop):
            n = self._sizes[i]
            np.subtract(
                self._backup[i].reshape(-1),
                np.asarray(jax.device_get(leaves[i])).reshape(-1),
                out=out[off:off + n],
                casting="unsafe",
            )
            off += n

    def _land_fragment(self, rnd: _SyncRound, f: int,
                       reduced: np.ndarray) -> None:
        """Fragment landing = the outer optax step for this fragment,
        STAGED (params and state adopted only on commit). Runs on the
        bounded worker in streaming mode — while later fragments are
        still riding the wire."""
        with span(self._metrics(), "outer_land", fragment=f):
            start, stop = self._fragments[f]
            grads: List[Any] = []
            off = 0
            for i in range(start, stop):
                n = self._sizes[i]
                grads.append(self._to_device(
                    i, reduced[off:off + n].reshape(self._shapes[i])
                ))
                off += n
            # The outer step moves from the last synced point
            # (ref local_sgd.py:216-225) — the backup, untouched for the
            # whole round.
            frag_params = [self._to_device(i, self._backup[i])
                           for i in range(start, stop)]
            rnd.staged[f] = self._outer.update_fragment(
                f, grads, frag_params
            )

    def _adopt_fragment_state(self, f: int, leaves: "List[Any]",
                              arrays: "List[np.ndarray]") -> Any:
        """A fetched fragment outer state, rebuilt from its flattened
        wire arrays: the tree STRUCTURE comes from a fresh
        ``init_fragment`` template over this rank's own leaves (optax
        states are pure functions of the leaf list's shapes), the
        VALUES are the donor's bytes verbatim — outer momentum survives
        the move bitwise."""
        import jax
        import jax.numpy as jnp

        start, stop = self._fragments[f]
        template = self._outer.init_fragment(
            [jnp.asarray(leaves[i]) for i in range(start, stop)]
        )
        t_leaves, treedef = jax.tree_util.tree_flatten(template)
        if len(arrays) != len(t_leaves):
            raise ValueError(
                f"fragment {f}: donor shipped {len(arrays)} outer-state "
                f"arrays, the transformation expects {len(t_leaves)} — "
                "outer optimizer configs diverged across replicas"
            )
        # each donor array takes the place of its template slot; slots
        # optax left uncommitted (step counts) stay so and follow the
        # update that consumes them
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                land_like(a, t) if getattr(t, "committed", False)
                else jnp.asarray(a)
                for a, t in zip(arrays, t_leaves)
            ],
        )

    def _on_owner_map(self, rnd: _SyncRound, params: Any) -> None:
        """Sharded outer reshard — EXCHANGE-ON-HEAL (closing the PR 8
        reinit gap): fragments are the shard unit, owners are
        ``f % wire_world``. On an owner-map change (membership churn,
        heals included — a donor's checkpoint carries only the DONOR's
        owned fragments and a healer's wire rank differs), the cohort
        runs one redistribution exchange (comm/redistribute.py over the
        raw-bytes heal plane): holdings metadata allgathered, a cached
        (held → owner-map) transfer plan compiled, and each ARRIVING
        fragment's outer state fetched from a surviving holder — outer
        momentum moves with the fragment instead of resetting. Only
        fragments NO live rank holds reinitialize (``reinit_fragments``
        in the ``reshard`` event — 0 whenever a covering donor
        survives). Runs once per round, at the fence; the trigger
        (generation bump / first sight) is cohort-synchronized so the
        embedded collectives stay matched."""
        import jax

        gen_fn = getattr(self._manager, "wire_generation", None)
        gen = int(gen_fn()) if callable(gen_fn) else 0
        key = (rnd.world, rnd.rank)
        states = self._outer.states
        if states is None or (
            key == self._outer_world and gen == self._outer_gen
        ):
            self._outer_world = key
            self._outer_gen = gen
            return
        F = len(self._fragments)
        owned = {
            f for f in range(F)
            if self._frag_owner(rnd, f) == rnd.rank or rnd.world == 1
        }
        leaves = jax.tree_util.tree_flatten(params)[0]
        self._check_layout(leaves)
        held = [f for f in range(F) if states[f] is not None]
        fetched: "dict[int, List[np.ndarray]]" = {}
        wire_bytes = lower_bound = 0
        if rnd.world > 1:
            from torchft_tpu.checkpointing import redistribute_exchange
            from torchft_tpu.comm.redistribute import ShardSpec

            # Device arrays stay device-side: the exchange reads nbytes
            # metadata only, and served fragments stage lazily (D2H
            # exactly when a receiver fetches).
            holdings = {
                f: list(jax.tree_util.tree_leaves(states[f]))
                for f in held
            }
            dst = ShardSpec.from_owner_map(
                F, rnd.world, lambda f: self._frag_owner(rnd, f)
            )
            result = redistribute_exchange(
                self._manager, rnd.rank, rnd.world, dst, holdings,
                self._redist_planner, source="outer_sync",
            )
            if result is None:
                # Latched mid-exchange / transfer failed whole: keep
                # the old states and do NOT advance the (gen, key)
                # marker — this round aborts at its commit barrier and
                # the next round's fence retries the exchange.
                return
            fetched = result.fetched
            wire_bytes = result.moved_bytes
            lower_bound = result.lower_bound_bytes
        reinit = dropped = adopted = 0
        new_states: List[Any] = [None] * F
        for f in range(F):
            if f in owned:
                if states[f] is not None:
                    new_states[f] = states[f]
                elif f in fetched:
                    new_states[f] = self._adopt_fragment_state(
                        f, leaves, fetched[f]
                    )
                    adopted += 1
                else:
                    start, stop = self._fragments[f]
                    import jax.numpy as jnp

                    new_states[f] = self._outer.init_fragment(
                        [jnp.asarray(leaves[i])
                         for i in range(start, stop)]
                    )
                    reinit += 1
            elif states[f] is not None:
                dropped += 1
        if reinit:
            logger.warning(
                "sharded_outer reshard reinitialized %d fragment outer "
                "states (no surviving holder): outer momentum restarts "
                "for those fragments", reinit,
            )
        self._outer.load_states(new_states)
        old = self._outer_world
        self._outer_world = key
        self._outer_gen = gen
        ev = getattr(self._manager, "events", None)
        if ev:
            ev.emit(
                "reshard", source="outer_sync",
                old_world=None if old is None else old[0],
                new_world=rnd.world, rank=rnd.rank,
                owned_fragments=len(owned),
                adopted_fragments=adopted,
                wire_bytes=wire_bytes,
                lower_bound_bytes=lower_bound,
                reinit_fragments=reinit, dropped_fragments=dropped,
            )

    def _commit_round(self, rnd: _SyncRound) -> Any:
        import jax

        sharded = self._sharded_outer and rnd.world > 1
        new_leaves: List[Any] = [None] * len(self._shapes)
        if sharded:
            contrib: "dict[int, List[np.ndarray]]" = {}
            for f, (start, stop) in enumerate(self._fragments):
                if rnd.staged[f] is _REMOTE:
                    continue
                frag_leaves, new_state = rnd.staged[f]
                self._outer.adopt(f, new_state)
                contrib[f] = [
                    np.asarray(jax.device_get(l)) for l in frag_leaves
                ]
            gathered = self._exchange_fragments(rnd, contrib)
            for f, (start, stop) in enumerate(self._fragments):
                for j, i in enumerate(range(start, stop)):
                    np.copyto(
                        self._backup[i], gathered[f][j], casting="unsafe"
                    )
                    new_leaves[i] = self._to_device(i, self._backup[i])
            return jax.tree_util.tree_unflatten(self._treedef, new_leaves)
        for f, (start, stop) in enumerate(self._fragments):
            frag_leaves, new_state = rnd.staged[f]
            self._outer.adopt(f, new_state)
            for j, i in enumerate(range(start, stop)):
                dev = frag_leaves[j]
                np.copyto(
                    self._backup[i],
                    np.asarray(jax.device_get(dev)),
                    casting="unsafe",
                )
                new_leaves[i] = dev
        return jax.tree_util.tree_unflatten(self._treedef, new_leaves)
