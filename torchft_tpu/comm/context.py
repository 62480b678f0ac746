"""Reconfigurable cross-replica communication contexts.

TPU-native analog of the reference's reconfigurable ProcessGroups
(/root/reference/torchft/process_group.py:123-569). On TPU the two comm
planes split cleanly:

- **In-group (intra-slice)**: jax.lax collectives over the ICI mesh inside
  pjit/shard_map — compiled into the step function, never reconfigured
  (an ICI failure kills the whole slice; see torchft_tpu/parallel/).
- **Cross-replica (DCN)**: gradient averaging across replica groups, where
  membership changes per-step with the quorum. THAT plane is what a
  CommContext abstracts: host-side collectives over sockets that can be
  torn down and rebuilt at step boundaries (`configure`), with
  error-latching futures instead of job-killing exceptions.

Buffers are numpy arrays (host memory). The Manager moves jax arrays
device→host before reduction and host→device after; XLA's async dispatch
overlaps that with compute.
"""

from __future__ import annotations

import logging
import threading
from abc import ABC, abstractmethod
from concurrent.futures import Future
from datetime import timedelta
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.futures import completed_future, failed_future

logger = logging.getLogger(__name__)

__all__ = [
    "Work",
    "CompletedWork",
    "FailedWork",
    "CommContext",
    "DummyCommContext",
    "ErrorSwallowingCommContext",
    "ManagedCommContext",
    "ReduceOp",
]


class ReduceOp:
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"


class Work:
    """Handle for an in-flight collective (the c10d Work analog,
    ref process_group.py:150-187). ``future()`` resolves to the op's result
    (list of np.ndarray) or raises the transport error. ``op`` is the
    number the context gave a gradient op at submit, where it gives one
    (``TcpCommContext.next_grad_op``): what the op's spans on a trace
    carry as ``op=``; None otherwise."""

    def __init__(self, fut: "Future[List[np.ndarray]]",
                 op: Optional[int] = None) -> None:
        self._fut = fut
        self.op = op

    def wait(self, timeout: "float | timedelta | None" = None) -> bool:
        if isinstance(timeout, timedelta):
            timeout = timeout.total_seconds()
        self._fut.result(timeout=timeout)
        return True

    def future(self) -> "Future[List[np.ndarray]]":
        return self._fut

    def add_done_callback(self, fn) -> None:
        """Continuation hook: ``fn(future)`` runs when the op completes —
        streamed consumers (the DDP per-bucket pipeline) attach one per
        bucket so unpack/H2D can start the moment that bucket's wire
        round trip lands, out of order, instead of after a global drain.
        The callback runs on the completing thread (for TcpCommContext a
        transport lane): keep it O(enqueue) cheap — heavy per-bucket
        work belongs on a caller-owned worker (torchft_tpu/ddp.py)."""
        self._fut.add_done_callback(fn)


class CompletedWork(Work):
    """Immediately-successful work (the _DummyWork analog,
    ref process_group.py:339-351)."""

    def __init__(self, result: Optional[List[np.ndarray]] = None) -> None:
        super().__init__(completed_future(result if result is not None else []))


class FailedWork(Work):
    def __init__(self, exc: Exception) -> None:
        super().__init__(failed_future(exc))


class CommContext(ABC):
    """Abstract reconfigurable cross-replica collective context
    (ref process_group.py:123-247 `ProcessGroup`).

    ``configure(store_addr, rank, world_size)`` tears down any previous
    transport state and (re)builds for the new membership. The store address
    carries a per-quorum prefix (``host:port/torchft/{quorum_id}``) so
    stale rounds cannot cross-talk (ref manager.py:470-477).
    """

    # Which data plane this context's collectives ride: "host" (socket
    # transport — TcpCommContext and its subprocess proxy), "xla"
    # (on-device jax.lax collectives, comm/xla_backend.py), or "none"
    # (identity/test contexts that move no bytes). The Manager labels
    # its metrics sink with this so every comm_*/outer_* series in an
    # evidence JSON carries the backend that produced it.
    backend_name = "none"

    def __init__(self) -> None:
        self._rank = 0
        self._world_size = 1

    # ------------------------------------------------- capability query
    # ONE definition of which (algorithm, compression, op, topology)
    # combos each backend can run, shared by ctor validation,
    # Manager.comm_options and the backends' tests
    # (tests/test_quantized_psum.py) — so "can the psum path carry int8?"
    # or "does the host plane run the hierarchical tier?" has exactly
    # one answer everywhere instead of a hard ValueError here and a
    # drifted copy there.

    @classmethod
    def unsupported_reason(
        cls, algorithm: str, compression: str, op: str = ReduceOp.SUM,
        topology: str = "flat",
    ) -> Optional[str]:
        """``None`` when this backend can run ``algorithm`` with
        ``compression`` for reduce op ``op`` over ``topology`` ("flat" —
        one tier spanning the whole wire — or "hier" — the
        reduce-within → compress → exchange-across → broadcast-within
        domain hierarchy); otherwise a PRESCRIPTIVE error string (what
        to use instead). Real data planes override; identity/test
        contexts move no bytes, so every combo is a no-op they
        "support"."""
        return None

    @classmethod
    def supports(
        cls, algorithm: str, compression: str, op: str = ReduceOp.SUM,
        topology: str = "flat",
    ) -> bool:
        """Capability query: True when :meth:`unsupported_reason` is
        ``None`` for the combo."""
        return cls.unsupported_reason(
            algorithm, compression, op, topology
        ) is None

    @staticmethod
    def _prepare(a) -> np.ndarray:
        """Donation contract: ALLREDUCE reduces in place, so the submitted
        array must be contiguous and writable — anything else (e.g. the
        read-only views jax.device_get can return) is copied once here;
        caller-owned staging buffers pass through untouched and the future
        resolves to those same arrays, reduced. ONE definition shared by
        every data plane (host sockets and the xla backend) so donation
        semantics can never diverge across backends."""
        a = np.asarray(a)
        if not (a.flags["C_CONTIGUOUS"] and a.flags["WRITEABLE"]):
            a = np.array(a)
        return a

    @abstractmethod
    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        ...

    @abstractmethod
    def allreduce(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        topology: Optional[str] = None,
    ) -> Work:
        """Reduce arrays across ranks. The returned work's future resolves
        to the reduced arrays (same shapes/dtypes, index-aligned).

        ``topology`` selects the data path per op: ``"flat"`` (one tier
        spanning the whole wire), ``"hier"`` (reduce-within a domain at
        full precision → compress → exchange-across domains through the
        elected egress ranks → broadcast-within; requires a context
        configured for the hierarchical tier) or ``None`` (the
        context's own default — flat unless constructed otherwise).
        Identity/test contexts ignore it (every topology is a no-op on
        a wire that moves no bytes).

        Ownership: the caller donates ``arrays`` — implementations may
        reduce in place and resolve the future to the submitted arrays
        themselves (TcpCommContext does exactly that for contiguous,
        writable inputs). Don't read a donated array until the future
        resolves; on error its contents are unspecified."""

    def reduce_scatter(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        owners: "Optional[Sequence[int]]" = None,
    ) -> Work:
        """Reduce ``arrays`` across ranks, delivering each array's reduced
        values only to its owner rank (``owners[i]``, default
        ``i % world_size``). The future resolves to the donated array
        list with THIS rank's owned arrays reduced — bitwise identical to
        what :meth:`allreduce` would have produced there — and every
        other array's contents unspecified (donation contract). The
        collective under the sharded 1/N weight update. Default: not
        implemented (identity/legacy contexts); the real data planes
        (host sockets, xla) override."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement reduce_scatter; "
            "use the host (TcpCommContext) or xla (XlaCommContext) data "
            "plane for the sharded weight update"
        )

    @abstractmethod
    def allgather(self, arrays: Sequence[np.ndarray]) -> Work:
        """Future resolves to a list of per-rank lists of arrays."""

    @abstractmethod
    def broadcast(self, arrays: Sequence[np.ndarray], root: int = 0) -> Work:
        """Future resolves to root's arrays on every rank."""

    def size(self) -> int:
        return self._world_size

    def rank(self) -> int:
        return self._rank

    def shutdown(self) -> None:  # noqa: B027 — optional hook
        pass

    def errored(self) -> Optional[Exception]:
        """Latched transport error, if any (cleared by configure)."""
        return None

    # ------------------------------------------- data-plane commit votes
    # Backends that can fold a 1-byte health vote into their collectives
    # (host wire frames, xla psum) override these; the defaults describe
    # a backend with no vote channel, which the Manager's steady-state
    # fast path treats as ABSENT — it falls back to the full two-phase
    # should_commit barrier (never commits on weaker evidence).

    def set_vote_health(self, fn) -> None:  # noqa: B027 — optional hook
        """Install the local health provider for data-plane votes:
        ``fn() -> bool`` (True = healthy). Backends without a vote
        channel ignore it."""

    def take_commit_vote(self) -> "Optional[bool]":
        """Windowed aggregate of the health votes that rode this
        backend's collectives since the last call: True when at least
        one voted op completed and EVERY participant reported healthy,
        False when any participant dissented, None when no voted op
        completed (vote absent — the caller must use the full commit
        barrier). Default: votes are never present."""
        return None

    # ----------------------------------------------- wire introspection
    # Implementations with a real wire (TcpCommContext) override these;
    # the defaults describe an identity wire. Consumers: the DDP
    # error-feedback arena (torchft_tpu/ddp.py) keys its residual
    # lifecycle off codec lossiness and the generation counter.

    def wire_codec_name(self) -> str:
        """Name of the ALLREDUCE wire codec ("none" when the wire does
        not transform payloads)."""
        return "none"

    def wire_is_lossy(self) -> bool:
        """True when the allreduce wire codec loses precision (bf16/fp16/
        int8) — the condition under which error feedback pays."""
        return False

    def wire_compensable(self) -> bool:
        """True when THIS rank's allreduce contribution crosses the wire
        through the lossy codec (role-aware: star peers only) — the gate
        for running the error-feedback arena at all. Identity wire:
        never."""
        return False

    def wire_generation(self) -> int:
        """Monotonic transport incarnation (bumped by configure). Wire-
        derived step-persistent state — error-feedback residuals — must
        reset when this changes."""
        return 0

    def wire_roundtrip(self, src: np.ndarray, out: np.ndarray) -> None:
        """Write the wire's local image of ``src`` (decode(encode(src)),
        chunked exactly as an allreduce payload would be) into ``out``.
        Identity wire: a plain copy."""
        np.copyto(out, src)

    def wire_nbytes(self, a: np.ndarray) -> int:
        """Encoded payload size of ``a`` as ONE allreduce contribution
        (codec applied per grid chunk) — what one direction of the wire
        actually carries, for bandwidth/compression-ratio gauges.
        Identity wire: the raw byte count."""
        return int(np.asarray(a).nbytes)

    def mesh_shape(self) -> "Tuple[int, int]":
        """(replicas, model_shards) of the device layout behind this
        context. Host/wire contexts are 1-D by construction — one
        device per replica group — so the default reports the wire
        world with a degenerate model axis; the xla plane overrides
        with its 2-D mesh (comm/xla_backend.py)."""
        return (self.world_size(), 1)


class DummyCommContext(CommContext):
    """World-size-1 context that completes every op with its own inputs —
    used to soak bring-up collectives and as the cross-replica context when
    only one replica group participates (ref process_group.py:354-405)."""

    def __init__(self, rank: int = 0, world_size: int = 1) -> None:
        super().__init__()
        self._rank = rank
        self._world_size = world_size
        self.configure_count = 0

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self._rank = rank
        self._world_size = world_size
        self.configure_count += 1

    def allreduce(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        topology: Optional[str] = None,
    ) -> Work:
        return CompletedWork(list(arrays))

    def reduce_scatter(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        owners: "Optional[Sequence[int]]" = None,
    ) -> Work:
        return CompletedWork(list(arrays))

    def allgather(self, arrays: Sequence[np.ndarray]) -> Work:
        return CompletedWork([list(arrays)])

    def broadcast(self, arrays: Sequence[np.ndarray], root: int = 0) -> Work:
        return CompletedWork(list(arrays))


class ErrorSwallowingCommContext(CommContext):
    """Wrapper that latches the first transport error and turns subsequent
    ops into no-ops until the next configure — so one failed collective
    poisons the *step*, not the *process*
    (ref process_group.py:408-501 ErrorSwallowingProcessGroupWrapper)."""

    def __init__(self, inner: CommContext) -> None:
        super().__init__()
        self._inner = inner
        self._error: Optional[Exception] = None
        self._lock = threading.Lock()

    @property
    def backend_name(self) -> str:  # type: ignore[override]
        return self._inner.backend_name

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        with self._lock:
            self._error = None
        self._inner.configure(store_addr, rank, world_size)

    def errored(self) -> Optional[Exception]:
        with self._lock:
            return self._error

    def report_error(self, exc: Exception) -> None:
        with self._lock:
            if self._error is None:
                self._error = exc
                logger.warning("comm context error latched: %s", exc)

    def _wrap(self, work: Work, fallback: List[np.ndarray]) -> Work:
        out: "Future[List[np.ndarray]]" = Future()
        out.set_running_or_notify_cancel()

        def _done(f: Future) -> None:
            exc = f.exception()
            if exc is not None:
                self.report_error(exc)  # type: ignore[arg-type]
                out.set_result(fallback)  # swallowed: op becomes identity
            else:
                out.set_result(f.result())

        work.future().add_done_callback(_done)
        return Work(out)

    def allreduce(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        topology: Optional[str] = None,
    ) -> Work:
        if self.errored() is not None:
            return CompletedWork(list(arrays))
        return self._wrap(
            self._inner.allreduce(arrays, op, topology=topology),
            list(arrays),
        )

    def reduce_scatter(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        owners: "Optional[Sequence[int]]" = None,
    ) -> Work:
        if self.errored() is not None:
            return CompletedWork(list(arrays))
        return self._wrap(
            self._inner.reduce_scatter(arrays, op, owners), list(arrays)
        )

    def allgather(self, arrays: Sequence[np.ndarray]) -> Work:
        if self.errored() is not None:
            return CompletedWork([list(arrays)])
        return self._wrap(self._inner.allgather(arrays), [list(arrays)])

    def broadcast(self, arrays: Sequence[np.ndarray], root: int = 0) -> Work:
        if self.errored() is not None:
            return CompletedWork(list(arrays))
        return self._wrap(self._inner.broadcast(arrays, root), list(arrays))

    def size(self) -> int:
        return self._inner.size()

    def rank(self) -> int:
        return self._inner.rank()

    def shutdown(self) -> None:
        self._inner.shutdown()

    def wire_codec_name(self) -> str:
        return self._inner.wire_codec_name()

    def wire_is_lossy(self) -> bool:
        return self._inner.wire_is_lossy()

    def wire_compensable(self) -> bool:
        return self._inner.wire_compensable()

    def wire_generation(self) -> int:
        return self._inner.wire_generation()

    def wire_roundtrip(self, src: np.ndarray, out: np.ndarray) -> None:
        self._inner.wire_roundtrip(src, out)

    def wire_nbytes(self, a: np.ndarray) -> int:
        return self._inner.wire_nbytes(a)

    def set_vote_health(self, fn) -> None:
        self._inner.set_vote_health(fn)

    def take_commit_vote(self) -> "Optional[bool]":
        return self._inner.take_commit_vote()

    # instance-level shadow of the classmethod: capability follows the
    # wrapped backend, not this wrapper's (identity) default
    def unsupported_reason(  # type: ignore[override]
        self, algorithm: str, compression: str, op: str = ReduceOp.SUM,
        topology: str = "flat",
    ) -> Optional[str]:
        return self._inner.unsupported_reason(
            algorithm, compression, op, topology
        )

    def supports(  # type: ignore[override]
        self, algorithm: str, compression: str, op: str = ReduceOp.SUM,
        topology: str = "flat",
    ) -> bool:
        return self._inner.supports(algorithm, compression, op, topology)


class ManagedCommContext(CommContext):
    """Context that routes every collective through a Manager so errors and
    quorum state are handled centrally (ref process_group.py:504-569
    ManagedProcessGroup). size() reports the number of participating
    replicas in the current quorum."""

    def __init__(self, manager) -> None:  # torchft_tpu.manager.Manager
        super().__init__()
        self._manager = manager

    @property
    def backend_name(self) -> str:  # type: ignore[override]
        return self._manager.comm_backend()

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        raise RuntimeError(
            "ManagedCommContext is configured by its Manager, not directly"
        )

    def allreduce(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        topology: Optional[str] = None,
    ) -> Work:
        return self._manager.allreduce_arrays(
            arrays, op=op, topology=topology
        )

    def reduce_scatter(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        owners: "Optional[Sequence[int]]" = None,
    ) -> Work:
        return self._manager.reduce_scatter_arrays(
            arrays, op=op, owners=owners
        )

    def allgather(self, arrays: Sequence[np.ndarray]) -> Work:
        # Manager-mediated allgather with the same error-latch /
        # report_error semantics as allreduce — the sharded weight
        # update's param/opt-state exchange needs it (the old hard raise
        # predates any state-carrying collective on the step path).
        return self._manager.allgather_arrays(arrays)

    def broadcast(self, arrays: Sequence[np.ndarray], root: int = 0) -> Work:
        raise NotImplementedError(
            "managed broadcast is not part of the manager surface"
        )

    def size(self) -> int:
        return self._manager.num_participants()

    def rank(self) -> int:
        return self._manager.participating_rank() or 0

    def wire_codec_name(self) -> str:
        return self._manager.wire_codec_name()

    def wire_is_lossy(self) -> bool:
        return self._manager.wire_is_lossy()

    def wire_compensable(self) -> bool:
        return self._manager.wire_compensable()

    def wire_generation(self) -> int:
        return self._manager.wire_generation()

    def wire_roundtrip(self, src: np.ndarray, out: np.ndarray) -> None:
        self._manager.wire_roundtrip(src, out)

    def wire_nbytes(self, a: np.ndarray) -> int:
        return self._manager.wire_nbytes(a)

    def unsupported_reason(  # type: ignore[override]
        self, algorithm: str, compression: str, op: str = ReduceOp.SUM,
        topology: str = "flat",
    ) -> Optional[str]:
        return self._manager.comm_unsupported_reason(
            algorithm, compression, op, topology
        )

    def supports(  # type: ignore[override]
        self, algorithm: str, compression: str, op: str = ReduceOp.SUM,
        topology: str = "flat",
    ) -> bool:
        return self.unsupported_reason(
            algorithm, compression, op, topology
        ) is None
