"""Cross-replica gradient averaging (the DDP analog for JAX train steps).

The reference subclasses torch DDP and routes every gradient bucket through
``manager.allreduce`` via a comm hook, freezing bucket order so recovering
replicas reduce identical buckets (ref /root/reference/torchft/ddp.py:32-97).

On TPU the in-group data-parallel reduction is a compiled ``psum`` over the
ICI mesh (see torchft_tpu/parallel/); what needs fault tolerance is the
CROSS-replica-group average over DCN. ``DistributedDataParallel`` here takes
the grad pytree a jax step produced, packs leaves into fixed-layout buckets
(dtype-grouped, deterministic tree order — the bucket-rebuild-freeze parity,
ref ddp.py:55-61), reduces each bucket through the manager (error-latching),
and returns the averaged pytree. Healing replicas contribute zeros and
receive the average — which is exactly how they end a step bitwise-identical
to their donor.

Streamed step pipeline (default; ``streamed=False`` keeps the lock-step
shape as an A/B lever and bitwise oracle): the reduce path is a per-bucket
pipeline whose stages run concurrently instead of serializing on the
caller's thread —

    d2h   bucket k's device→host fetch + pack into its staging slice
          (caller thread; bucket k+1's D2H is already in flight)
    ef    error-feedback residual math for bucket k (bounded worker —
          OFF the submit path, so bucket k+1's pack/submit never stalls
          behind bucket k's quantizer)
    wire  the transport round trip (lanes; chunk-striped)
    h2d   unpack + the landing on each gradient leaf's own device(s),
          per bucket AS ITS WIRE FUTURE COMPLETES (continuation → one
          of the two workers of the PLACEMENT the bucket lands on), out
          of order — not after a global drain. Straight from the arena
          where the target cannot alias host memory, through a host copy
          where it can (utils/device.land_batch)

The step future resolves when the last bucket has landed AND every EF
task has finished, so ``.result()`` still means "arena quiescent,
residuals final" exactly as in the lock-step model. Per-stage wall times
land in the Manager's metrics (``ddp_d2h``/``ddp_ef``/``ddp_h2d`` spans,
``ddp_submit`` — the span around a bucket's ``allreduce_arrays``, whose
``op=`` the bucket's sub-ops on the lanes and its landing share — and
``ddp_land_queue``, one observation per bucket; counters
``ddp_land_borrowed_bytes`` / ``ddp_land_copied_bytes`` and gauge
``ddp_land_workers``) plus once-a-step timings:
the sums ``ddp_wire_total`` / ``ddp_d2h_total`` / ``ddp_h2d_total``, and
the step thread's own time, which ``ddp_step_pack`` (the submit loop, a
span) + ``ddp_wire_exposed`` (end of the loop → last wire completion) +
``ddp_step_land_tail`` (→ last landing) tile from entry to the step
future resolving (:meth:`DistributedDataParallel._observe_step`).

Buckets live in step-persistent staging ARENAS (one flat host array per
bucket per arena): D2H copies land into the arena, the transport reads
from it and reduces into it in place (the comm-layer donation contract),
and the result leaves are views of it until the H2D copy — no per-step
bucket-sized allocation, no transport-side payload copies
(docs/architecture.md, "Step pipeline"). The submit path is
DATA-PLANE AGNOSTIC: buckets go through ``manager.allreduce_arrays``
against whatever ``comm_backend`` the Manager was built with — "host"
(socket transport) or "xla" (on-device ``jax.lax`` collectives,
comm/xla_backend.py) — because both honor the same donation contract
(the reduced values are written back into the submitted staging arena
and the future resolves with those same arrays) and the same ``wire_*``
introspection the EF arena keys off, with bit-identical codecs
(tests/test_xla_backend.py pins full-step parity). There are ``staging_arenas``
(default 2) arena GENERATIONS: a second ``average_gradients_async`` may
pack into a fresh arena while the previous step's buckets are still on
the wire — cross-step comm/compute overlap — and the corruption guard
generalizes from "one outstanding" to a hard error only when every arena
is still in flight. A strictly sequential caller always reuses arena 0,
so extra generations cost nothing until overlap is actually used.

When the transport wire runs a lossy codec (bf16/int8), an ERROR-FEEDBACK
arena rides alongside each staging arena: per float bucket, the
quantization error of step t's transmitted contribution
(e_t = g'_t - C(g'_t), computed against the wire's own chunk grid via
``manager.wire_roundtrip``) persists in a host buffer and is added back
into the NEXT step that uses the same arena before encoding
(g' = g + e_prev). Every rank compensates its own contribution, so the
quantization error becomes a delayed correction instead of a bias — the
standard EF result that makes aggressive codecs (int8) converge like
full precision. With N arenas the compensation delay is N steps instead
of one — still unbiased (EF under pipelining), at 1/N the correction
rate. In streamed mode the quantizer runs on the bounded worker against
a snapshot of the transmitted bucket (the donated staging buffer is
reduced in place, so the contribution is unrecoverable after submit);
ordering is guaranteed by the step future: residuals are final before
it resolves, hence before the arena can be reacquired. Residuals are
RESET whenever ``manager.wire_generation`` changes (every quorum
membership change / transport reconfigure): a residual describes error
owed to a specific cohort, and replaying it into a new quorum would
inject stale gradient mass.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from contextlib import nullcontext
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.futures import FutureGroup, future_all, future_chain
from torchft_tpu.utils.device import land_batch, land_like, placement
from torchft_tpu.utils.profiling import span

__all__ = [
    "DistributedDataParallel",
    "PureDistributedDataParallel",
    "ShardedGradReducer",
    "shard_ranges",
]

_DEFAULT_BUCKET_BYTES = 32 * 1024 * 1024

# Shared bounded workers for the off-critical-path pipeline stages —
# process-wide pools rather than per-DDP threads so many wrapper
# instances (tests, multi-model apps) cannot accumulate idle threads.
# EF quantizer tasks and per-bucket landings (unpack+H2D) get SEPARATE
# pools: an EF roundtrip over a 32MB bucket is the heaviest task in the
# pipeline, and on a shared pool two back-to-back EF tasks would queue
# every completed bucket's landing behind them — re-serializing the
# pipeline exactly in the lossy-codec configuration it targets. Landings
# are further keyed by PLACEMENT — the devices of the leaves a bucket
# replaces — with two workers each, made on first use: a transfer to one
# chip never waits behind another chip's (several replica groups as
# threads of one process, a chip each, finish their buckets together
# because the ring is a barrier), while one group, or any number of
# wrappers on one device, hold exactly two. The pools live as long as
# the process; there are as many as distinct placements it has landed
# on. Tasks never block on other tasks (an EF task is pure compute, a
# landing waits only for its own transfers), so the bounded pools
# cannot deadlock.
_PIPELINE_LOCK = threading.Lock()
_PIPELINE_WORKERS = 2
_PIPELINE_EXECUTORS: (
    "Dict[Tuple[str, FrozenSet[Any]], ThreadPoolExecutor]"
) = {}


def _pipeline_executor(
    kind: str, placed: "FrozenSet[Any]" = frozenset()
) -> ThreadPoolExecutor:
    """The pool of stage ``kind`` for buckets that land on the devices
    ``placed`` (:func:`torchft_tpu.utils.device.placement`; none for the
    ``ef`` stage, which touches no device)."""
    with _PIPELINE_LOCK:
        ex = _PIPELINE_EXECUTORS.get((kind, placed))
        if ex is None:
            # the lowest device id and how many: a thread's line on a
            # trace names the chip it lands on
            tag = f"_d{min(d.id for d in placed)}x{len(placed)}" \
                if placed else ""
            ex = ThreadPoolExecutor(
                max_workers=_PIPELINE_WORKERS,
                thread_name_prefix=f"torchft_tpu_ddp_{kind}{tag}",
            )
            _PIPELINE_EXECUTORS[(kind, placed)] = ex
        return ex


def _land_workers() -> int:
    """Landing workers this process holds (gauge ``ddp_land_workers``)."""
    with _PIPELINE_LOCK:
        return _PIPELINE_WORKERS * sum(
            kind == "land" for kind, _placed in _PIPELINE_EXECUTORS
        )


def _ef_dtype(dt: np.dtype) -> bool:
    """Buckets the wire codecs actually compress (transport
    _is_compressible) — integer buckets pass through losslessly, so they
    carry no residual."""
    return dt in (np.float32, np.float64)


def _ef_gate(manager, error_feedback: "bool | str") -> bool:
    """THE error-feedback activation rule, shared by the bucketed DDP
    arena and the sharded reducer (one definition or the bitwise A/B
    between them could silently diverge): enabled AND this rank's
    contribution actually crosses the wire through a lossy codec
    (``wire_compensable`` — role-aware: a star root or ring member's
    contribution is never encoded, while on the quantized native psum
    path EVERY rank's contribution is phase-1 encoded, so every rank
    compensates) AND this replica contributes real gradients this step
    (healing/spare replicas ship zeros —
    compensating those would bank the whole gradient as 'error').
    ``error_feedback=True`` forces the arena on (documented force
    semantics); pre-striping managers fall back to codec lossiness."""
    if error_feedback is False:
        return False
    if error_feedback == "auto":
        compensable = getattr(manager, "wire_compensable", None)
        if callable(compensable):
            if not compensable():
                return False
        else:
            lossy = getattr(manager, "wire_is_lossy", None)
            if not callable(lossy) or not lossy():
                return False
    is_part = getattr(manager, "is_participating", None)
    return (not callable(is_part)) or bool(is_part())


def _land_leaf(view: np.ndarray, like: Any) -> Any:
    """The averaged gradient for leaf ``like``, as a device array with
    ``like``'s dtype and sharding (a numpy gradient's lands on the default
    device). Always a COPY of ``view`` made on the host first
    (:func:`land_like`): this is the leaf-by-leaf landing of
    :class:`PureDistributedDataParallel`, whose ``view`` is the
    transport's own result buffer and which owns no arena whose reuse it
    could hold back until a transfer has read it. The bucketed wrapper
    lands through :func:`land_batch` instead (:meth:`_land_bucket`)."""
    return land_like(view, like) if hasattr(like, "dtype") else view


def _step_arg(manager) -> Dict[str, int]:
    """``step=`` for a step's spans, so that one step's spans can be
    joined across its threads on a trace (duck-typed: a test double of
    the manager may not count steps)."""
    current_step = getattr(manager, "current_step", None)
    return {"step": int(current_step())} if callable(current_step) else {}


def _op_arg(op: Optional[int]) -> Dict[str, int]:
    """``op=`` for a bucket's spans: its number on the wire (``Work.op``),
    left out on a data plane that gives none."""
    return {} if op is None else {"op": op}


class _StepClock:
    """One classic step's clock reads. Per bucket (a slot each: landings
    and wire continuations run on other threads): when it was submitted,
    when its wire future resolved, the seconds of its ``ddp_d2h`` and
    ``ddp_h2d`` stages. Of the step's thread in the submit loop, summed
    over buckets along the loop's seams: seconds inside
    ``jax.device_get`` (the wait for the device to finish the gradients,
    plus the D2H DMA), inside ``pack_bucket_into`` (host memcpy into the
    staging arena) and inside ``manager.allreduce_arrays`` (enqueue);
    the thread's CPU time across the loop; when the loop ended.
    ``step`` is the spans' ``step=`` (:func:`_step_arg`); ``op`` a
    bucket's number on the wire (``Work.op``, None where the data plane
    gives none): the ``op=`` its ``ddp_submit`` and ``ddp_h2d`` spans
    share with its sub-ops' ``comm_wire_reduce`` on the lanes' lines."""

    __slots__ = ("step", "op", "submit_t", "wire_done_t", "d2h_t", "h2d_t",
                 "fetch", "copy", "submit", "cpu", "t_submitted", "_next_op")

    def __init__(self, manager, n_buckets: int) -> None:
        self.step = _step_arg(manager)
        self.op: List[Optional[int]] = [None] * n_buckets
        # duck-typed like ``step``: a test double numbers no ops
        self._next_op = getattr(manager, "next_wire_op", None)
        self.submit_t = [0.0] * n_buckets
        self.wire_done_t = [0.0] * n_buckets
        self.d2h_t = [0.0] * n_buckets
        self.h2d_t = [0.0] * n_buckets
        self.fetch = self.copy = self.submit = self.cpu = 0.0
        self.t_submitted = 0.0

    def submit_span(self, metrics, k: int, nbytes: int) -> span:
        """The ``ddp_submit`` span of bucket ``k``, opened around its
        ``allreduce_arrays``: the bucket's hand-over to the lanes, with
        the number the wire is about to give the op (a span's arguments
        are fixed when it opens, so it is asked for, not read back)."""
        op = self._next_op() if callable(self._next_op) else None
        return span(metrics, "ddp_submit", bucket=k, bytes=nbytes,
                    **_op_arg(op), **self.step)

    def submitted(self, k: int, work) -> None:
        """After the submit: the op's number as the wire gave it."""
        self.submit += time.perf_counter() - self.submit_t[k]
        self.op[k] = getattr(work, "op", None)

    def observe(self, metrics) -> None:
        """The once-a-step timings, observed by whichever thread resolves
        the step's future, right after the last landing."""
        t_landed = time.perf_counter()
        # Per-step sums over buckets: a step's wire cost split into
        # staging down / socket (submit → wire done, buckets in flight
        # together) / staging up. The benchmark reads them as
        # wire_d2h_ms / wire_socket_ms / wire_h2d_ms, and
        # scripts/fleet_top.py the total beside the exposed part.
        metrics.observe("ddp_wire_total", sum(
            done - sub for done, sub in zip(self.wire_done_t, self.submit_t)
        ))
        metrics.observe("ddp_d2h_total", sum(self.d2h_t))
        metrics.observe("ddp_h2d_total", sum(self.h2d_t))
        # The step thread's own time: the ``ddp_step_pack`` span, then
        # these two, tile entry → step future resolved. ``exposed`` is
        # the wire left after the submit loop ended (wire activity
        # during pack / EF / earlier landings is hidden by construction;
        # the benchmark's wire_exposed_ms), the tail is the last wire
        # completion → the last landing.
        wire_end = max(self.t_submitted, max(self.wire_done_t))
        metrics.observe("ddp_wire_exposed", wire_end - self.t_submitted)
        metrics.observe("ddp_step_land_tail", t_landed - wire_end)
        # ...and what the submit loop was made of (``ddp_step_pack``
        # minus ``ddp_step_cpu`` is time the step's thread was blocked
        # while packing: device, DMA, GIL)
        metrics.observe("ddp_step_fetch", self.fetch)
        metrics.observe("ddp_step_copy", self.copy)
        metrics.observe("ddp_step_submit", self.submit)
        metrics.observe("ddp_step_cpu", self.cpu)


class _BucketPlan:
    """Fixed mapping of flat leaf indices into dtype-homogeneous buckets.

    Built from leaf shapes/dtypes only (works on device arrays without
    fetching them) so bucket k's device→host copy and transport submit can
    happen before bucket k+1's gradients have even landed on host."""

    def __init__(self, leaves: Sequence[Any], bucket_bytes: int) -> None:
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [np.dtype(l.dtype) for l in leaves]
        self.sizes = [int(np.prod(s, dtype=np.int64)) for s in self.shapes]
        # Group leaf indices by dtype, then chunk by byte budget. Tree
        # order within a dtype is preserved — deterministic across replicas.
        by_dtype: Dict[str, List[int]] = {}
        for i, dt in enumerate(self.dtypes):
            by_dtype.setdefault(dt.str, []).append(i)
        self.buckets: List[List[int]] = []
        for dt_str, indices in sorted(by_dtype.items()):
            current: List[int] = []
            current_bytes = 0
            itemsize = np.dtype(dt_str).itemsize
            for i in indices:
                nbytes = self.sizes[i] * itemsize
                if current and current_bytes + nbytes > bucket_bytes:
                    self.buckets.append(current)
                    current = []
                    current_bytes = 0
                current.append(i)
                current_bytes += nbytes
            if current:
                self.buckets.append(current)

    def signature(self) -> Tuple:
        return tuple(zip(self.shapes, [d.str for d in self.dtypes]))

    def alloc_staging(self) -> List[np.ndarray]:
        """One flat host array per bucket — one step-persistent staging
        arena generation. Reused every step that acquires it: D2H copies
        land into it, the transport reads from it AND reduces into it in
        place (the comm donation contract), and the unpacked result
        leaves are views of it until the H2D copy. No per-step
        bucket-sized allocation survives."""
        return [
            np.empty(
                sum(self.sizes[i] for i in bucket),
                dtype=self.dtypes[bucket[0]],
            )
            for bucket in self.buckets
        ]

    def pack_bucket_into(
        self,
        bucket: Sequence[int],
        bucket_leaves: Sequence[np.ndarray],
        out: np.ndarray,
    ) -> np.ndarray:
        """Land one bucket's (already-host) leaves into its staging slice,
        in plan order — the reusable-arena replacement for the fresh
        np.concatenate pack_bucket did every step."""
        offset = 0
        for i, leaf in zip(bucket, bucket_leaves):
            n = self.sizes[i]
            np.copyto(
                out[offset: offset + n],
                np.asarray(leaf).reshape(-1),
                casting="no",
            )
            offset += n
        return out

    @staticmethod
    def pack_bucket(bucket_leaves: Sequence[np.ndarray]) -> np.ndarray:
        """Flatten one bucket's (already-host) leaves, in plan order
        (allocating variant, kept for callers without an arena)."""
        if len(bucket_leaves) == 1:
            return np.ascontiguousarray(bucket_leaves[0]).ravel()
        return np.concatenate([l.ravel() for l in bucket_leaves])

    def unpack_bucket(self, k: int, data: np.ndarray):
        """Yield ``(leaf_index, view)`` for bucket k's slices of ``data``
        — THE definition of the bucket byte layout's inverse, shared by
        the lock-step :meth:`unpack` and the streamed per-bucket landing
        so the two paths cannot drift."""
        offset = 0
        for i in self.buckets[k]:
            n = self.sizes[i]
            yield i, data[offset: offset + n].reshape(self.shapes[i])
            offset += n

    def unpack(self, flat_buckets: Sequence[np.ndarray]) -> List[np.ndarray]:
        leaves: List[np.ndarray] = [None] * len(self.shapes)  # type: ignore[list-item]
        for k, data in enumerate(flat_buckets):
            for i, view in self.unpack_bucket(k, data):
                leaves[i] = view
        return leaves


class _Arena:
    """One staging+residual generation: per-bucket staging buffers, the
    matching error-feedback residuals (+ the EF snapshot scratch the
    streamed quantizer reads), and the in-flight future of the last
    average that used this generation — the corruption guard."""

    __slots__ = ("staging", "residuals", "ef_scratch", "ef_generation",
                 "inflight")

    def __init__(self) -> None:
        self.staging: "Optional[List[np.ndarray]]" = None
        self.residuals: "Optional[List[Optional[np.ndarray]]]" = None
        self.ef_scratch: "Optional[List[Optional[np.ndarray]]]" = None
        self.ef_generation: "Optional[int]" = None
        self.inflight: "Optional[Future]" = None


class DistributedDataParallel:
    """Bucketed fault-tolerant gradient averager (ref ddp.py:32-71).

    ``error_feedback``: "auto" (default) enables the per-bucket residual
    compensation exactly when the manager's wire codec is lossy; True
    forces the arena on (still a no-op under an identity codec); False
    disables it (raw quantization — expect drift under int8).

    ``staging_arenas``: arena generations (default 2). A second
    ``average_gradients_async`` may start while the previous one is still
    on the wire as long as a free generation exists; all generations in
    flight is a hard error (the corruption guard). 1 restores the strict
    one-outstanding PR 2 semantics. Overlapping calls must come from ONE
    submitter thread, in the same program order on every rank — the
    transport pairs collectives across ranks by submission order, so
    racing submitters would mix steps cross-rank (see _acquire_arena).

    ``streamed``: True (default) runs the per-bucket streamed pipeline
    (see module docstring); False keeps the lock-step submit loop +
    global drain — the A/B lever and the bitwise oracle the streamed
    path is tested against."""

    def __init__(self, manager, bucket_bytes: int = _DEFAULT_BUCKET_BYTES,
                 error_feedback: "bool | str" = "auto",
                 staging_arenas: int = 2,
                 streamed: bool = True,
                 topology: "Optional[str]" = None) -> None:
        if error_feedback not in (True, False, "auto"):
            raise ValueError(
                f"error_feedback must be True/False/'auto', "
                f"got {error_feedback!r}"
            )
        if staging_arenas < 1:
            raise ValueError("staging_arenas must be >= 1")
        self._manager = manager
        self._bucket_bytes = bucket_bytes
        self._error_feedback = error_feedback
        self._streamed = bool(streamed)
        # Per-op data-path selector forwarded to every bucket's
        # allreduce ("flat"/"hier"; None = the comm context's own
        # default, and the kwarg is then not even passed — mock/legacy
        # managers without it keep working).
        self._topology = topology
        self._ar_kwargs = {} if topology is None else {
            "topology": topology
        }
        self._plan: "Optional[_BucketPlan]" = None
        self._arenas = [_Arena() for _ in range(int(staging_arenas))]
        self._plan_lock = threading.Lock()
        self._arena_lock = threading.Lock()

    # Introspection/test compat: the primary arena's EF state (a strictly
    # sequential caller only ever touches arena 0 — see _acquire_arena).

    @property
    def _residuals(self):
        return self._arenas[0].residuals

    @property
    def _ef_generation(self):
        return self._arenas[0].ef_generation

    def _metrics(self):
        return getattr(self._manager, "metrics", None)

    def _emit_abort(self, exc: BaseException) -> None:
        """Flight-recorder note that a step's submit loop died mid-flight
        (buckets already on the wire, arena sealed until they drain) —
        the rare failure whose postmortem otherwise requires correlating
        a caller traceback with lane-thread logs."""
        ev = getattr(self._manager, "events", None)
        if ev:
            ev.emit(
                "round_abort", source="ddp_submit", error=repr(exc)[:200]
            )

    def _wire_healthy(self) -> bool:
        """Gauge gate: the pipeline wire timers are only meaningful when
        ops actually ride the wire. After a latched transport error every
        allreduce resolves inline (CompletedWork fallback), and its ~0ms
        'wire' time would inflate the overlap gauge the bench grades —
        skip the observation instead (the step never commits anyway)."""
        errored = getattr(self._manager, "errored", None)
        return not callable(errored) or errored() is None

    def _ef_active(self) -> bool:
        """See :func:`_ef_gate` — the shared activation rule."""
        return _ef_gate(self._manager, self._error_feedback)

    def _get_plan(self, host_leaves: List[np.ndarray]) -> _BucketPlan:
        with self._plan_lock:
            if self._plan is None:
                # Built once, never rebuilt — bucket layout stays identical
                # across steps and across recovering replicas (parity with
                # the bucket-rebuild freeze, ref ddp.py:55-61).
                self._plan = _BucketPlan(host_leaves, self._bucket_bytes)
            else:
                fresh = tuple(
                    (tuple(l.shape), np.dtype(l.dtype).str)
                    for l in host_leaves
                )
                if fresh != self._plan.signature():
                    raise ValueError(
                        "gradient pytree shape/dtype changed between steps; "
                        "DDP bucket layout is frozen by design"
                    )
            return self._plan

    def _acquire_arena(self) -> "Tuple[_Arena, Future]":
        """First-free acquisition, arena 0 preferred: a strictly
        sequential caller always reuses generation 0 (later generations
        are never even allocated), while an overlapping caller spills to
        the next free one. The PR 2 one-outstanding corruption guard
        generalizes to N: packing into an arena whose previous step is
        still on the wire would reduce corrupted buffers WITHOUT any
        error — so the hard error now fires exactly when every
        generation is in flight.

        Check-and-claim is atomic: the arena is marked busy with an
        unresolved PLACEHOLDER future under a lock (the real step future
        does not exist until the submit loop finishes), so a misuse from
        two threads can never silently claim the same generation. NOTE
        the lock protects LOCAL buffers only — cross-step overlap must
        still be driven from ONE submitter thread (submit step t+1 after
        step t's average_gradients_async returns, before awaiting it):
        the transport matches collectives across ranks by per-lane
        submission ORDER, so two threads racing their submit loops would
        interleave differently on different ranks and reduce step t
        against step t+1 with no detectable frame mismatch (identical
        frozen bucket layouts). Single-submitter program order is what
        keeps the op sequence deterministic across ranks."""
        with self._arena_lock:
            for arena in self._arenas:
                f = arena.inflight
                if f is None or f.done():
                    placeholder: Future = Future()
                    placeholder.set_running_or_notify_cancel()
                    arena.inflight = placeholder
                    return arena, placeholder
            raise RuntimeError(
                f"average_gradients_async called with all "
                f"{len(self._arenas)} staging arena generations in "
                "flight; await a prior result first or raise "
                "staging_arenas"
            )

    def average_gradients(self, grads: Any) -> Any:
        """Average a grad pytree across replica groups. Blocking; returns a
        pytree of jax arrays with the input structure. On transport error
        the error is latched and the returned values are UNSPECIFIED (the
        staging buffers may be partially reduced — donation contract);
        that is safe because the commit gate (OptimizerWrapper.step)
        discards the step, but don't log/inspect grads after an error."""
        # the step's thread blocks here: the Manager accounts it as the
        # ``wire_wait`` span and recovery-episode phase (duck-typed: a
        # test double of the manager may not have it)
        blocked = getattr(self._manager, "blocked_on_wire", nullcontext)
        with blocked():
            return self.average_gradients_async(grads).result()

    def average_gradients_async(self, grads: Any):
        import jax

        from torchft_tpu.futures import completed_future

        # Solo-wire fast path: with no data-plane peer (observers don't
        # count — they neither contribute nor receive) the average is an
        # identity; skip the device→host fetch and the transport entirely
        # (see Manager.transport_world_size). The quorum still runs — it
        # is what detects rejoining peers.
        try:
            self._manager.wait_quorum()
        except Exception as e:  # noqa: BLE001
            # A failed quorum must latch so should_commit votes False —
            # falling through on stale quorum state would let the step
            # commit without any quorum at all.
            self._manager.report_error(e)
            return completed_future(grads)
        if self._manager.is_solo_wire():
            return completed_future(grads)

        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if not leaves:
            return completed_future(grads)
        # Kick off all device->host DMAs before blocking on any of them so
        # the transfers overlap (jax arrays expose async host copies).
        for l in leaves:
            if hasattr(l, "copy_to_host_async"):
                l.copy_to_host_async()
        # Plan from shapes/dtypes alone — no host fetch yet.
        plan = self._get_plan(leaves)

        arena, placeholder = self._acquire_arena()
        try:
            if arena.staging is None:
                arena.staging = plan.alloc_staging()
            ef = self._ef_active()
            if ef:
                # Residual arena lifecycle: (re)allocate zeroed on first
                # use and on every transport incarnation change —
                # membership changed, so the previous step's quantization
                # error no longer belongs to this cohort's stream
                # (docs/architecture.md, "Error feedback").
                gen = self._manager.wire_generation()
                if arena.residuals is None or gen != arena.ef_generation:
                    arena.residuals = [
                        np.zeros_like(s) if _ef_dtype(s.dtype) else None
                        for s in arena.staging
                    ]
                    arena.ef_generation = gen

            # Both paths replace the placeholder with the real inflight
            # future THEMSELVES, including on a mid-loop failure —
            # buckets already submitted keep reducing in place into this
            # arena, so the guard future must outlive them even when the
            # submit loop raises partway.
            if self._streamed:
                return self._average_streamed(
                    arena, plan, leaves, treedef, ef
                )
            return self._average_lockstep(arena, plan, leaves, treedef, ef)
        except BaseException:
            if arena.inflight is placeholder:
                # Failed before anything reached the wire (staging/
                # residual allocation, plan bug): release the claim —
                # nothing is touching the arena.
                arena.inflight = None
            raise

    # ------------------------------------------------------- pipeline stages

    def _pack_bucket(self, plan: _BucketPlan, k: int,
                     leaves: List[Any], staging: List[np.ndarray],
                     metrics, clock: _StepClock) -> np.ndarray:
        """Stage d2h: block only on bucket k's leaves and land them in
        bucket k's slice of the staging arena (the mid-backward comm-hook
        analog, ref ddp.py:49-71) — bucket k rides the wire while later
        host copies are still landing."""
        import jax

        bucket = plan.buckets[k]
        with span(metrics, "ddp_d2h", bucket=k, bytes=staging[k].nbytes,
                  **clock.step) as timed:
            t0 = time.perf_counter()
            host_b = [np.asarray(jax.device_get(leaves[i])) for i in bucket]
            t1 = time.perf_counter()
            packed = plan.pack_bucket_into(bucket, host_b, staging[k])
            clock.copy += time.perf_counter() - t1
            clock.fetch += t1 - t0
        clock.d2h_t[k] = timed.elapsed
        return packed

    def _ef_residual(self, transmitted: np.ndarray, res: np.ndarray,
                     metrics) -> None:
        """Stage ef (residual half): e_t = g' - C(g') where C is the
        wire's own per-chunk quantizer and ``transmitted`` is g' (or a
        snapshot of it — the donated staging buffer is reduced in place,
        so the contribution is unrecoverable after submit)."""
        with span(metrics, "ddp_ef"):
            self._manager.wire_roundtrip(transmitted, res)  # res = C(g')
            np.subtract(transmitted, res, out=res)
            if not np.all(np.isfinite(res)):
                # A non-finite gradient poisons its wire image (int8
                # NaN-scale poisoning, bf16 inf-inf) and the step is
                # discarded by the commit gate — but the residual
                # persists. Left NaN it would re-inject the spike into
                # EVERY later step's gradients until a membership change;
                # drop that error instead (one step of lost compensation).
                np.nan_to_num(res, copy=False,
                              nan=0.0, posinf=0.0, neginf=0.0)

    def _land_bucket(self, plan: _BucketPlan, k: int, reduced: np.ndarray,
                     in_leaves: List[Any], out_leaves: List[Any],
                     metrics, clock: _StepClock) -> None:
        """Stage h2d: unpack bucket k's reduced flat array into its
        leaves and land each on the device(s) of the gradient leaf it
        replaces (:func:`land_batch`). This runs on a pool thread, so
        the placement must come from the leaf, not from any thread-local
        default device of the caller. A result never aliases the arena:
        on a CPU device, or where a cast is needed, each view is copied
        on the host first; on an accelerator the views themselves are
        handed to the transfer, and this returns — the bucket counts as
        landed, and the step's future, the arena's ``inflight`` guard,
        can resolve — only once the transfers have read them."""
        with span(metrics, "ddp_h2d", bucket=k, **_op_arg(clock.op[k]),
                  **clock.step) as timed:
            indices, views = zip(*plan.unpack_bucket(k, reduced))
            landed, borrowed, copied = land_batch(
                views, [in_leaves[i] for i in indices]
            )
            for i, leaf in zip(indices, landed):
                out_leaves[i] = leaf
        clock.h2d_t[k] = timed.elapsed
        if metrics is not None:
            metrics.incr("ddp_land_borrowed_bytes", borrowed)
            metrics.incr("ddp_land_copied_bytes", copied)

    def _observe_step(self, metrics, clock: _StepClock) -> None:
        """:meth:`_StepClock.observe`, skipped where a bucket never rode
        the wire (see :meth:`_wire_healthy`)."""
        if metrics is not None and all(clock.wire_done_t) \
                and self._wire_healthy():
            clock.observe(metrics)

    # ----------------------------------------------------------- code paths

    def _average_streamed(self, arena: _Arena, plan: _BucketPlan,
                          leaves: List[Any], treedef, ef: bool) -> Future:
        """Streamed per-bucket pipeline (module docstring): EF off the
        submit thread, unpack/H2D per bucket as its wire future
        completes, step future resolves when the last bucket lands and
        the last EF task finishes."""
        import jax

        metrics = self._metrics()
        staging = arena.staging
        ef_pool = _pipeline_executor("ef")
        group = FutureGroup()
        n_buckets = len(plan.buckets)
        device_leaves: List[Any] = [None] * len(plan.shapes)
        clock = _StepClock(self._manager, n_buckets)

        try:
            # everything the step's thread does before it can only wait,
            # as ONE interval on its line of a trace; its CPU time across
            # the interval is taken here, on this thread
            cpu0 = time.thread_time()
            with span(metrics, "ddp_step_pack", **clock.step):
                for k in range(n_buckets):
                    packed = self._pack_bucket(
                        plan, k, leaves, staging, metrics, clock
                    )
                    if ef and arena.residuals[k] is not None:
                        res = arena.residuals[k]
                        # g' = g + e_prev stays inline (one vector add —
                        # cheap); the quantizer roundtrip moves to the
                        # worker, reading a SNAPSHOT of g' because the
                        # donated buffer below is reduced in place the
                        # moment the wire takes it.
                        np.add(packed, res, out=packed)
                        if arena.ef_scratch is None:
                            arena.ef_scratch = [None] * n_buckets
                        if arena.ef_scratch[k] is None:
                            arena.ef_scratch[k] = np.empty_like(packed)
                        scratch = arena.ef_scratch[k]
                        np.copyto(scratch, packed)
                        group.add(
                            ef_pool.submit(
                                self._ef_residual, scratch, res, metrics
                            )
                        )
                    clock.submit_t[k] = time.perf_counter()
                    with clock.submit_span(metrics, k, packed.nbytes):
                        work = self._manager.allreduce_arrays(
                            [packed], **self._ar_kwargs
                        )
                    clock.submitted(k, work)
                    landed: Future = Future()
                    landed.set_running_or_notify_cancel()
                    group.add(landed)
                    land_pool = _pipeline_executor(
                        "land", placement(leaves[i] for i in plan.buckets[k])
                    )

                    def _on_wire(wf: Future, k: int = k,
                                 landed: Future = landed,
                                 land_pool: ThreadPoolExecutor = land_pool,
                                 ) -> None:
                        # Lane-thread continuation: timestamp + enqueue
                        # only (the transport's O(enqueue) contract,
                        # _OpState docstring).
                        clock.wire_done_t[k] = time.perf_counter()

                        def _land() -> None:
                            # how long the finished bucket waited for one
                            # of its placement's two workers
                            if metrics is not None and self._wire_healthy():
                                metrics.observe(
                                    "ddp_land_queue",
                                    time.perf_counter()
                                    - clock.wire_done_t[k],
                                )
                            try:
                                reduced = wf.result()[0]
                                self._land_bucket(
                                    plan, k, reduced, leaves, device_leaves,
                                    metrics, clock,
                                )
                                landed.set_result(None)
                            except Exception as e:  # noqa: BLE001
                                landed.set_exception(e)

                        land_pool.submit(_land)

                    work.add_done_callback(_on_wire)
            clock.t_submitted = time.perf_counter()
            clock.cpu = time.thread_time() - cpu0
            if metrics is not None:
                metrics.gauge("ddp_land_workers", _land_workers())
        except BaseException as e:
            # Mid-loop failure with earlier buckets already ON THE WIRE
            # (reducing in place into this arena): seal the group over
            # the members added so far and store it as the arena's
            # inflight guard BEFORE re-raising, so a caller that catches
            # and retries cannot reacquire the arena while lane threads
            # are still writing into it. The guard fails with a wrapper
            # RuntimeError, never the original: a BaseException
            # (KeyboardInterrupt) would slip through the future
            # machinery's `except Exception` and leave the guard
            # unresolved forever — every later acquisition would then
            # see a permanently-in-flight arena.
            def _fail() -> None:
                raise RuntimeError(
                    "average_gradients submit loop failed mid-flight"
                ) from e

            arena.inflight = group.seal(_fail)
            self._emit_abort(e)
            raise

        def _assemble():
            self._observe_step(metrics, clock)
            return jax.tree_util.tree_unflatten(treedef, device_leaves)

        fut = group.seal(_assemble)
        arena.inflight = fut
        return fut

    def _average_lockstep(self, arena: _Arena, plan: _BucketPlan,
                          leaves: List[Any], treedef, ef: bool) -> Future:
        """PR 2 lock-step issue loop, kept as the streamed path's A/B
        lever and bitwise oracle: pack + inline EF + submit per bucket,
        then one global completion before any unpack begins. Same math,
        same buffers, same submission order as the streamed path — only
        the scheduling differs, which is what the identity tests pin."""
        import jax

        metrics = self._metrics()
        staging = arena.staging
        n_buckets = len(plan.buckets)
        works = []
        clock = _StepClock(self._manager, n_buckets)
        try:
            cpu0 = time.thread_time()
            with span(metrics, "ddp_step_pack", **clock.step):
                for k in range(n_buckets):
                    packed = self._pack_bucket(
                        plan, k, leaves, staging, metrics, clock
                    )
                    if ef and arena.residuals[k] is not None:
                        res = arena.residuals[k]
                        np.add(packed, res, out=packed)
                        self._ef_residual(packed, res, metrics)
                    clock.submit_t[k] = time.perf_counter()
                    with clock.submit_span(metrics, k, packed.nbytes):
                        work = self._manager.allreduce_arrays(
                            [packed], **self._ar_kwargs
                        )
                    clock.submitted(k, work)
                    works.append(work)

                    # Timestamp-only continuation (O(enqueue)), so an A/B
                    # run measures both arms' wire time rather than
                    # reporting the lock-step arm as null.
                    def _mark(wf: Future, k: int = k) -> None:
                        clock.wire_done_t[k] = time.perf_counter()

                    work.add_done_callback(_mark)
            clock.t_submitted = time.perf_counter()
            clock.cpu = time.thread_time() - cpu0
        except BaseException as e:
            # Same guard-integrity rule as the streamed path: buckets
            # already submitted keep reducing in place into this arena —
            # the inflight future must wait them out before the arena
            # can be reacquired, even though this call is failing. (The
            # RuntimeError wrap matters: future_chain's `except
            # Exception` would not transport a raw KeyboardInterrupt,
            # leaving the guard unresolved forever.)
            def _fail(_f) -> None:
                raise RuntimeError(
                    "average_gradients submit loop failed mid-flight"
                ) from e

            arena.inflight = future_chain(
                future_all([w.future() for w in works]), _fail
            )
            self._emit_abort(e)
            raise

        def _finish(_f) -> Any:
            # future_all already resolved every bucket future — collect
            # without blocking (the old submit-order .result() drain),
            # with per-bucket h2d spans instead of one global ddp_unpack.
            device_leaves: List[Any] = [None] * len(plan.shapes)
            for k, w in enumerate(works):
                reduced = w.future().result()[0]
                self._land_bucket(
                    plan, k, reduced, leaves, device_leaves, metrics,
                    clock,
                )
            self._observe_step(metrics, clock)
            return jax.tree_util.tree_unflatten(treedef, device_leaves)

        fut = future_chain(
            future_all([w.future() for w in works]), _finish
        )
        arena.inflight = fut
        return fut


def shard_ranges(sizes: Sequence[int], dtypes: Sequence[np.dtype],
                 world_size: int) -> "List[Tuple[int, int]]":
    """THE shard grid of the cross-replica sharded weight update:
    contiguous, byte-balanced leaf ranges over the flat leaf list, one
    per wire rank (``comm.wire.split_weighted`` — a pure function of
    shapes/dtypes, so every rank computes the identical grid). Fewer
    leaves than ranks leaves the tail ranks owning nothing."""
    nbytes = [
        int(sz) * np.dtype(dt).itemsize for sz, dt in zip(sizes, dtypes)
    ]
    from torchft_tpu.comm.wire import split_weighted

    return split_weighted(nbytes, max(1, int(world_size)))


class _ShardPlan(_BucketPlan):
    """Shard-aligned bucket plan: leaves split into ``world_size``
    byte-balanced contiguous ranges (:func:`shard_ranges`), each range's
    leaves packed into dtype-grouped flat buckets OWNED by that range's
    rank. Reuses _BucketPlan's staging/pack/unpack byte layout — only
    the bucket assignment differs, which is what lets the sharded and
    replicated arms submit byte-identical payloads over identical chunk
    grids (the bitwise-oracle precondition)."""

    def __init__(self, leaves: Sequence[Any], world_size: int) -> None:
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [np.dtype(l.dtype) for l in leaves]
        self.sizes = [int(np.prod(s, dtype=np.int64)) for s in self.shapes]
        self.world_size = int(world_size)
        self.ranges = shard_ranges(self.sizes, self.dtypes, world_size)
        self.buckets: List[List[int]] = []
        self.owners: List[int] = []
        for shard, (start, stop) in enumerate(self.ranges):
            by_dtype: Dict[str, List[int]] = {}
            for i in range(start, stop):
                by_dtype.setdefault(self.dtypes[i].str, []).append(i)
            for _, indices in sorted(by_dtype.items()):
                self.buckets.append(indices)
                self.owners.append(shard)

    def owner_of(self, leaf: int) -> int:
        for shard, (start, stop) in enumerate(self.ranges):
            if start <= leaf < stop:
                return shard
        raise IndexError(f"leaf {leaf} outside the shard grid")

    def shard_spec(self, model_shards: int = 1):
        """This grid as a redistribution destination spec — what the
        reshard exchange compiles (src holdings → this) transfer plans
        against (comm/redistribute.py). ``model_shards > 1`` prices the
        2-D (replica × model) layout: each leaf becomes ``model_shards``
        sub-units so a mesh-shape change is planned exactly."""
        from torchft_tpu.comm.redistribute import ShardSpec

        if model_shards > 1:
            return ShardSpec.from_ranges_2d(
                self.ranges, model_shards, len(self.sizes)
            )
        return ShardSpec.from_ranges(self.ranges, len(self.sizes))

    def owned_leaves(self, rank: int) -> "List[int]":
        if rank >= len(self.ranges):
            return []
        start, stop = self.ranges[rank]
        return list(range(start, stop))


class _ShardArena:
    """Per-world staging + EF-residual generation for the sharded
    reducer (one per seen wire world size, cached like the PR 6 mesh).
    Staging allocates LAZILY at first transport use: a solo wire (or a
    plan only ever consulted for its grid) must not pin a
    gradient-sized host arena."""

    __slots__ = ("plan", "staging", "residuals", "ef_generation")

    def __init__(self, plan: _ShardPlan) -> None:
        self.plan = plan
        self.staging: "Optional[List[np.ndarray]]" = None
        self.residuals: "Optional[List[Optional[np.ndarray]]]" = None
        self.ef_generation: "Optional[int]" = None


class ShardedGradReducer:
    """The gradient stage of the ZeRO-style sharded weight update.

    ``reduce(grads, sharded=True)`` packs the FULL grad pytree into
    shard-aligned buckets (every rank contributes everything — the
    upload side is identical to DDP's), reduce-scatters them so each
    rank RECEIVES only the 1/N byte-balanced leaf-shard its
    optimizer-state shard consumes, and returns host views of the
    received leaves. ``sharded=False`` allreduces the SAME buckets over
    the SAME chunk grid — the replicated A/B arm, whose values on any
    rank's shard are bitwise identical to the sharded arm's (transport
    reduce_scatter contract) — and returns every leaf.

    The DDP error-feedback arena rides the upload side unchanged (the
    full contribution crosses the wire in either mode, so the residual
    stays full-size; what the sharded mode divides by N is the
    optimizer state, update FLOPs, and heal bytes — not the EF arena).
    Residuals reset on every transport incarnation, as in DDP.

    The plan (and its staging arena) is cached PER WIRE WORLD SIZE and
    rebuilt at the quorum boundary when membership changes the world —
    the PR 6 mesh-cache pattern — emitting one ``shard_grid_rebuild``
    flight-recorder event per rebuild."""

    def __init__(self, manager,
                 error_feedback: "bool | str" = "auto") -> None:
        if error_feedback not in (True, False, "auto"):
            raise ValueError(
                f"error_feedback must be True/False/'auto', "
                f"got {error_feedback!r}"
            )
        self._manager = manager
        self._error_feedback = error_feedback
        self._arenas: Dict[int, _ShardArena] = {}
        self._signature: "Optional[Tuple]" = None
        self._last_world: "Optional[int]" = None
        self._lock = threading.Lock()

    def _metrics(self):
        return getattr(self._manager, "metrics", None)

    def _ef_active(self) -> bool:
        """See :func:`_ef_gate` — the shared activation rule."""
        return _ef_gate(self._manager, self._error_feedback)

    def plan_for(self, leaves: Sequence[Any], world: int) -> _ShardPlan:
        """The cached shard plan for ``world`` (building + arena
        allocation on first sight). Leaf layout is frozen like the DDP
        bucket plan — a changed pytree raises."""
        sig = tuple(
            (tuple(l.shape), np.dtype(l.dtype).str) for l in leaves
        )
        with self._lock:
            if self._signature is None:
                self._signature = sig
            elif sig != self._signature:
                raise ValueError(
                    "gradient pytree shape/dtype changed between steps; "
                    "the sharded-update leaf grid is frozen by design"
                )
            arena = self._arenas.get(world)
            if arena is None:
                arena = _ShardArena(_ShardPlan(leaves, world))
                self._arenas[world] = arena
                ev = getattr(self._manager, "events", None)
                if ev:
                    ev.emit(
                        "shard_grid_rebuild",
                        old_world=self._last_world, new_world=world,
                        shards=len(arena.plan.ranges),
                        buckets=len(arena.plan.buckets),
                    )
            self._last_world = world
            return arena.plan

    def _arena_for(self, world: int) -> _ShardArena:
        with self._lock:
            return self._arenas[world]

    def reduce(self, grads: Any,
               sharded: bool = True) -> "Tuple[_ShardPlan, int, Dict[int, np.ndarray]]":
        """Blocking reduce of a grad pytree. Returns ``(plan, my_rank,
        leaves)`` where ``leaves`` maps leaf index → a host view of its
        reduced, participant-scaled gradient — this rank's shard when
        ``sharded``, every leaf otherwise. Views alias the step-
        persistent staging arena: copy (``jnp.array``) before the next
        reduce. After a latched transport error the contents are
        unspecified — the step never commits, mirroring DDP."""
        import jax

        mgr = self._manager
        try:
            mgr.wait_quorum()
        except Exception as e:  # noqa: BLE001 — latch, never raise
            mgr.report_error(e)
            leaves = jax.tree_util.tree_flatten(grads)[0]
            # Throwaway plan for the discarded step: NOT cached (no
            # staging arena allocated, no shard_grid_rebuild event) — a
            # transient quorum failure must not pin a gradient-sized
            # world-1 arena nor pollute the reshard telemetry.
            return _ShardPlan(leaves, 1), 0, {}
        world = max(1, int(mgr.transport_world_size()))
        rank_fn = getattr(mgr, "transport_rank", None)
        my_rank = int(rank_fn()) if callable(rank_fn) else 0

        leaves = jax.tree_util.tree_flatten(grads)[0]
        plan = self.plan_for(leaves, world)
        if world == 1:
            # Solo wire: the average is an identity; hand back every
            # leaf without touching the transport (the DDP fast path).
            return plan, 0, {
                i: np.asarray(jax.device_get(l))
                for i, l in enumerate(leaves)
            }
        arena = self._arena_for(world)
        if arena.staging is None:
            arena.staging = arena.plan.alloc_staging()
        staging = arena.staging
        metrics = self._metrics()

        for l in leaves:
            if hasattr(l, "copy_to_host_async"):
                l.copy_to_host_async()
        ef = self._ef_active()
        if ef:
            gen_fn = getattr(mgr, "wire_generation", None)
            gen = int(gen_fn()) if callable(gen_fn) else 0
            if arena.residuals is None or gen != arena.ef_generation:
                arena.residuals = [
                    np.zeros_like(s) if _ef_dtype(s.dtype) else None
                    for s in staging
                ]
                arena.ef_generation = gen

        step = _step_arg(mgr)
        for k, bucket in enumerate(plan.buckets):
            with span(metrics, "ddp_d2h", bucket=k,
                      bytes=staging[k].nbytes, **step):
                host_b = [
                    np.asarray(jax.device_get(leaves[i])) for i in bucket
                ]
                packed = plan.pack_bucket_into(bucket, host_b, staging[k])
            if ef and arena.residuals[k] is not None:
                res = arena.residuals[k]
                np.add(packed, res, out=packed)
                with span(metrics, "ddp_ef"):
                    mgr.wire_roundtrip(packed, res)  # res = C(g')
                    np.subtract(packed, res, out=res)
                    if not np.all(np.isfinite(res)):
                        np.nan_to_num(res, copy=False,
                                      nan=0.0, posinf=0.0, neginf=0.0)

        if sharded:
            work = mgr.reduce_scatter_arrays(staging, owners=plan.owners)
        else:
            work = mgr.allreduce_arrays(staging)
        reduced = work.future().result()

        out: Dict[int, np.ndarray] = {}
        for k, bucket in enumerate(plan.buckets):
            if sharded and plan.owners[k] != my_rank:
                continue
            for i, view in plan.unpack_bucket(k, reduced[k]):
                out[i] = view
        return plan, my_rank, out


class PureDistributedDataParallel:
    """Per-leaf (unbucketed) variant — simpler, more round trips
    (ref ddp.py:75-97). Shares ``DistributedDataParallel``'s safety
    contract: the quorum gates the reduce (a failed quorum LATCHES so
    should_commit votes False — returning unreduced grads without the
    latch would let a quorumless step commit), and a solo wire skips the
    device→host fetch and the transport round trip entirely."""

    def __init__(self, manager) -> None:
        self._manager = manager

    def average_gradients(self, grads: Any) -> Any:
        import jax

        try:
            self._manager.wait_quorum()
        except Exception as e:  # noqa: BLE001 — parity with
            # DistributedDataParallel: latch, never raise mid-backward
            self._manager.report_error(e)
            return grads
        if self._manager.is_solo_wire():
            return grads

        leaves, treedef = jax.tree_util.tree_flatten(grads)
        host = [np.asarray(jax.device_get(l)) for l in leaves]
        works = [self._manager.allreduce_arrays([h]) for h in host]
        out = [
            _land_leaf(w.future().result()[0], l)
            for w, l in zip(works, leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)
