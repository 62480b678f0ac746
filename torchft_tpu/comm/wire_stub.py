"""Manager facade over a raw CommContext for single-process harnesses.

tests/test_localsgd_streaming.py and its sibling harnesses drive the
LocalSGD/DiLoCo round machinery over a real loopback transport without
a control plane. The wrapper probes the manager surface via ``getattr``
(``wire_compensable``, ``quorum_fence``, ``wire_nbytes``, ...), so a
drifted hand-rolled copy would silently exercise the getattr-fallback
path instead of the real one — one shared stub keeps every harness on
the same surface.

Semantics: quorum/fence/heal are no-ops, AVG scaling divides float
payloads by the wire world, and ``should_commit`` mirrors the real
manager's error-latch vote (a reported error aborts the round).
"""

from __future__ import annotations

from concurrent.futures import Future

import numpy as np

from torchft_tpu.comm.context import ReduceOp, Work
from torchft_tpu.futures import future_chain
from torchft_tpu.utils.events import EventRecorder
from torchft_tpu.utils.metrics import Metrics

__all__ = ["WireStubManager", "run_stub_ranks"]


def run_stub_ranks(store_addr: str, prefix: str, world: int, fn,
                   ctx_factory, timeout: float = 120.0):
    """Thread-per-rank loopback harness: one context per rank
    (``ctx_factory()``), configured against ``store_addr/prefix``,
    wrapped in a :class:`WireStubManager`, running ``fn(mgr, rank)``
    concurrently. Returns the per-rank results; any rank's exception
    aggregates into one RuntimeError; contexts always shut down.

    THE shared scaffold for every single-process sharded/outer-round
    harness (bench.py's sharded phase, tests/test_redistribute.py,
    tests/test_multijob.py) — the same drift argument as
    WireStubManager itself: hand-rolled copies of the
    configure/thread/join/shutdown dance would diverge silently."""
    import threading

    ctxs = [ctx_factory() for _ in range(world)]
    results = [None] * world
    errors: "list[str]" = []

    def _worker(rank: int) -> None:
        try:
            ctxs[rank].configure(f"{store_addr}/{prefix}", rank, world)
            results[rank] = fn(WireStubManager(ctxs[rank], world), rank)
        except Exception as e:  # noqa: BLE001 — aggregated below
            errors.append(f"rank {rank}: {e!r}")

    threads = [
        threading.Thread(target=_worker, args=(r,)) for r in range(world)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    for ctx in ctxs:
        ctx.shutdown()
    if errors or any(r is None for r in results):
        raise RuntimeError("; ".join(errors) or "a rank hung")
    return results


class WireStubManager:
    def __init__(self, ctx, world: int) -> None:
        self._ctx = ctx
        self._world = world
        self.metrics = Metrics()
        self.metrics.label(
            "comm_backend", str(getattr(ctx, "backend_name", "none"))
        )
        # Real-surface parity: the wrappers probe manager.events via
        # getattr and emit round_abort/... through it — the stub carries
        # a live recorder so harnesses exercise that path too.
        self.events = EventRecorder(replica_id="stub", rank=0)
        set_events = getattr(ctx, "set_events", None)
        if callable(set_events):
            set_events(self.events)
        self._use_async_quorum = True
        self._error = None
        self._stage_index = 0
        self._stage_count = 1

    def comm_backend(self) -> str:
        return str(getattr(self._ctx, "backend_name", "none"))

    def start_quorum(self, **kw) -> None:
        self._error = None

    def quorum_fence(self) -> None:
        pass

    def wait_quorum(self) -> None:
        pass

    def did_heal(self) -> bool:
        return False

    def errored(self):
        return self._error

    def report_error(self, e) -> None:
        if self._error is None:
            self._error = e

    def should_commit(self) -> bool:
        return self._error is None

    def is_participating(self) -> bool:
        return True

    def num_participants(self) -> int:
        return self._world

    def transport_world_size(self) -> int:
        return self._world

    def is_solo_wire(self) -> bool:
        return self._error is None and self._world == 1

    def wire_is_lossy(self) -> bool:
        return self._ctx.wire_is_lossy()

    def wire_compensable(self) -> bool:
        return self._ctx.wire_compensable()

    def wire_generation(self) -> int:
        return self._ctx.wire_generation()

    def wire_roundtrip(self, src, out) -> None:
        self._ctx.wire_roundtrip(src, out)

    def wire_nbytes(self, a) -> int:
        return self._ctx.wire_nbytes(a)

    def comm_unsupported_reason(self, algorithm, compression,
                                op=ReduceOp.SUM, topology="flat"):
        return self._ctx.unsupported_reason(
            algorithm, compression, op, topology
        )

    def comm_supports(self, algorithm, compression, op=ReduceOp.SUM,
                      topology="flat") -> bool:
        return self._ctx.supports(algorithm, compression, op, topology)

    def transport_rank(self) -> int:
        rank = getattr(self._ctx, "rank", None)
        return int(rank()) if callable(rank) else 0

    # -- pipeline-plane surface (mirrors Manager.bind_stage & co.) -----------

    def bind_stage(self, stage_index: int, stage_count: int) -> None:
        stage_index = int(stage_index)
        stage_count = int(stage_count)
        if not 0 <= stage_index < stage_count:
            raise ValueError(
                f"stage_index {stage_index} outside [0, {stage_count})"
            )
        self._stage_index = stage_index
        self._stage_count = stage_count
        self.metrics.gauge("pipe_stage_index", float(stage_index))
        self.metrics.gauge("pipe_stage_count", float(stage_count))

    def stage_index(self) -> int:
        return self._stage_index

    def stage_count(self) -> int:
        return self._stage_count

    def allreduce_arrays(self, arrays, op=ReduceOp.SUM,
                         topology=None) -> Work:
        # kwarg omitted when None, mirroring the real Manager — a
        # wrapped context predating the topology parameter keeps working
        if topology is None:
            work = self._ctx.allreduce(list(arrays), ReduceOp.SUM)
        else:
            work = self._ctx.allreduce(
                list(arrays), ReduceOp.SUM, topology=topology
            )
        scale = np.float32(1.0 / self._world)

        def _avg(f: Future):
            reduced = f.result()
            for a in reduced:
                if a.dtype in (np.float32, np.float64):
                    np.multiply(a, a.dtype.type(scale), out=a)
            return reduced

        return Work(future_chain(work.future(), _avg))

    def reduce_scatter_arrays(self, arrays, op=ReduceOp.SUM,
                              owners=None) -> Work:
        """Same participant scaling as allreduce_arrays, applied to this
        rank's OWNED arrays only (the rest are unspecified after a
        reduce_scatter — the real manager's rule)."""
        arrays = list(arrays)
        if owners is None:
            owners = [i % self._world for i in range(len(arrays))]
        owners = [int(o) for o in owners]
        work = self._ctx.reduce_scatter(arrays, ReduceOp.SUM, owners)
        my = self.transport_rank()
        scale = np.float32(1.0 / self._world)

        def _avg(f: Future):
            reduced = list(f.result())
            for i, a in enumerate(reduced):
                if owners[i] == my and a.dtype in (np.float32, np.float64):
                    np.multiply(a, a.dtype.type(scale), out=a)
            return reduced

        return Work(future_chain(work.future(), _avg))

    def allgather_arrays(self, arrays) -> Work:
        return self._ctx.allgather(list(arrays))
