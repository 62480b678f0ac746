"""Subprocess-isolated comm context (the "baby PG" analog).

The reference runs NCCL inside a spawned child process so a wedged or
crashed communicator can be killed and rebuilt without taking down the
trainer (ref /root/reference/torchft/process_group.py:572-1054,
ProcessGroupBabyGloo/BabyNCCL). The TPU rendering matters for the same
reason on the DCN plane: a peer that half-dies can wedge a socket in a
state close() doesn't always unstick promptly, and SIGKILLing a child is
the only abort that never blocks.

``SubprocessCommContext`` hosts a TcpCommContext in a spawn-context child;
``configure`` kills any previous child outright (the abort path) and
spawns a fresh one. Ops are shipped as numpy arrays over mp queues and
executed in issue order by the child's transport thread. A parent-side
pump thread matches results to futures, preserving the Work/Future API.

Concurrency design: every configure creates a fresh *epoch* — (child
process, tx/rx queues, calls queue, pump thread) — and the pump thread
closes over ITS epoch's objects, never reading them from self. A stale
pump stuck on a wedged child can therefore only drain its own dead
epoch's queue; it can never steal ops submitted after a reconfigure.
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import queue as queue_mod
import threading
from concurrent.futures import Future
from datetime import timedelta
from typing import Optional, Sequence

import numpy as np

from torchft_tpu.comm.context import CommContext, ReduceOp, Work

logger = logging.getLogger(__name__)

__all__ = ["SubprocessCommContext"]

_CMD_CONFIGURE = "configure"
_CMD_OP = "op"


def _child_main(tx: "mp.Queue", rx: "mp.Queue", timeout: float,
                transport_kwargs: Optional[dict] = None) -> None:
    """Child process: own a TcpCommContext, execute commands in order
    (the worker-loop role of ref process_group.py:727-834)."""
    from torchft_tpu.comm.transport import TcpCommContext

    ctx = TcpCommContext(timeout=timeout, **(transport_kwargs or {}))
    try:
        while True:
            cmd = tx.get()
            kind = cmd[0]
            if kind == _CMD_CONFIGURE:
                _, store_addr, rank, world_size, members = cmd
                try:
                    if members is not None:
                        ctx.set_wire_members(members)
                    ctx.configure(store_addr, rank, world_size)
                    rx.put(("ok", None))
                except Exception as e:  # noqa: BLE001
                    rx.put(("error", f"{type(e).__name__}: {e}"))
            elif kind == _CMD_OP:
                _, opcode, op, root, arrays = cmd
                try:
                    if opcode == "allreduce":
                        # ``root`` carries the per-op topology override
                        # for this opcode (None = the child context's
                        # ctor default) — same slot-reuse trick as
                        # reduce_scatter's owners below.
                        work = ctx.allreduce(arrays, op, topology=root)
                    elif opcode == "reduce_scatter":
                        # ``root`` carries the owners list for this
                        # opcode (unused otherwise) — keeps the command
                        # tuple layout stable across opcodes.
                        work = ctx.reduce_scatter(arrays, op, owners=root)
                    elif opcode == "allgather":
                        work = ctx.allgather(arrays)
                    elif opcode == "broadcast":
                        work = ctx.broadcast(arrays, root)
                    else:
                        raise ValueError(f"unknown op {opcode}")
                    rx.put(("ok", work.future().result()))
                except Exception as e:  # noqa: BLE001
                    rx.put(("error", f"{type(e).__name__}: {e}"))
            else:
                rx.put(("error", f"unknown command {kind}"))
    finally:
        ctx.shutdown()


class _PendingCall:
    def __init__(self, cmd, fut: Future) -> None:
        self.cmd = cmd
        self.fut = fut


class _Epoch:
    """One child-process generation and everything scoped to it."""

    def __init__(self, mp_ctx, timeout: float,
                 transport_kwargs: Optional[dict] = None) -> None:
        self.tx: "mp.Queue" = mp_ctx.Queue()
        self.rx: "mp.Queue" = mp_ctx.Queue()
        self.calls: "queue_mod.Queue[Optional[_PendingCall]]" = (
            queue_mod.Queue()
        )
        self.timeout = timeout
        self.proc: mp.Process = mp_ctx.Process(
            target=_child_main,
            args=(self.tx, self.rx, timeout, transport_kwargs),
            daemon=True,
            name="torchft_tpu_comm_child",
        )
        self.pump: Optional[threading.Thread] = None

    def start_pump(self, on_error) -> None:
        def _loop() -> None:
            while True:
                call = self.calls.get()
                if call is None:
                    return
                try:
                    if not self.proc.is_alive():
                        raise ConnectionError("comm child process is dead")
                    self.tx.put(call.cmd)
                    status, payload = self.rx.get(timeout=self.timeout + 10)
                    if status != "ok":
                        raise ConnectionError(payload)
                    call.fut.set_result(payload)
                except Exception as e:  # noqa: BLE001
                    on_error(e)
                    try:
                        call.fut.set_exception(e)
                    except Exception:
                        pass

        self.pump = threading.Thread(
            target=_loop, name="torchft_tpu_comm_pump", daemon=True
        )
        self.pump.start()

    def kill(self) -> None:
        """SIGKILL the child and fail stranded calls. A pump thread still
        blocked on the dead child's rx queue will fail its in-flight call
        when its timeout fires, then exit on the sentinel — it holds no
        references to any newer epoch."""
        self.calls.put(None)  # pump exit sentinel
        if self.proc.pid is not None:
            self.proc.kill()
            self.proc.join(timeout=5.0)
        while True:
            try:
                call = self.calls.get_nowait()
            except queue_mod.Empty:
                break
            if call is not None:
                call.fut.set_exception(
                    ConnectionError("comm child killed during reconfigure")
                )


class SubprocessCommContext(CommContext):
    """CommContext façade over a killable child process."""

    backend_name = "host"  # the child owns a TcpCommContext — same plane

    def __init__(self, timeout: "float | timedelta" = 60.0,
                 algorithm: str = "auto", channels: int = 4,
                 compression: str = "none",
                 chunk_bytes: Optional[int] = None,
                 topology: str = "flat") -> None:
        """``algorithm``/``channels``/``compression``/``chunk_bytes``/
        ``topology`` are forwarded to the child's TcpCommContext (see
        transport.py for their semantics, ``chunk_bytes=None`` included:
        the child then cuts ring ops as its own default does; the
        child resolves hier domains from its own TORCHFT_TPU_DOMAINS
        env or the wire members shipped with each configure)."""
        super().__init__()
        if isinstance(timeout, timedelta):
            timeout = timeout.total_seconds()
        self._timeout = float(timeout)
        self._wire_members = None
        self._transport_kwargs = {
            "algorithm": algorithm,
            "channels": channels,
            "compression": compression,
            "chunk_bytes": chunk_bytes,
            "topology": topology,
        }
        self._mp = mp.get_context("spawn")
        self._epoch: Optional[_Epoch] = None
        self._lock = threading.Lock()
        self._error: Optional[Exception] = None

    @classmethod
    def unsupported_reason(cls, algorithm: str, compression: str,
                           op: str = ReduceOp.SUM,
                           topology: str = "flat") -> "Optional[str]":
        # The child owns a TcpCommContext — capability IS the host
        # plane's (one shared definition, transport.py).
        from torchft_tpu.comm.transport import host_unsupported_reason

        return host_unsupported_reason(algorithm, compression, op,
                                       topology)

    def set_wire_members(self, members) -> None:
        """Cohort replica ids (transport rank order), shipped to the
        child with the next configure — the hier domain resolver's
        input (see TcpCommContext.set_wire_members)."""
        self._wire_members = [str(m) for m in members]

    # ------------------------------------------------------------ lifecycle

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        if self._epoch is not None:
            # SIGKILL, not graceful: this is the abort path for a WEDGED
            # transport (ref process_group.py:664-680 kills the prior baby
            # process on every configure).
            self._epoch.kill()
            self._epoch = None
        with self._lock:
            self._error = None
        self._rank = rank
        self._world_size = world_size

        epoch = _Epoch(self._mp, self._timeout,
                       self._transport_kwargs)
        epoch.proc.start()
        epoch.tx.put((
            _CMD_CONFIGURE, store_addr, rank, world_size,
            self._wire_members,
        ))
        try:
            status, payload = epoch.rx.get(timeout=self._timeout + 10)
        except queue_mod.Empty:
            epoch.kill()
            raise TimeoutError(
                f"comm child configure timed out after {self._timeout}s"
            ) from None
        if status != "ok":
            epoch.kill()
            raise RuntimeError(f"comm child configure failed: {payload}")

        epoch.start_pump(self._latch_error)
        self._epoch = epoch

    def _latch_error(self, e: Exception) -> None:
        with self._lock:
            if self._error is None:
                self._error = e

    def shutdown(self) -> None:
        if self._epoch is not None:
            self._epoch.kill()
            self._epoch = None

    def errored(self) -> Optional[Exception]:
        with self._lock:
            return self._error

    def child_pid(self) -> Optional[int]:
        return self._epoch.proc.pid if self._epoch is not None else None

    # ----------------------------------------------------------- collectives

    def _submit(self, opcode: str, arrays: Sequence[np.ndarray], op: str,
                root: int) -> Work:
        fut: Future = Future()
        fut.set_running_or_notify_cancel()
        err = self.errored()
        if err is not None:
            fut.set_exception(
                ConnectionError(f"comm context previously errored: {err}")
            )
            return Work(fut)
        epoch = self._epoch
        if epoch is None or epoch.pump is None:
            fut.set_exception(RuntimeError("comm context not configured"))
            return Work(fut)
        arrays = [np.asarray(a) for a in arrays]
        epoch.calls.put(
            _PendingCall((_CMD_OP, opcode, op, root, arrays), fut)
        )
        return Work(fut)

    def allreduce(self, arrays, op: str = ReduceOp.SUM,
                  topology=None) -> Work:
        return self._submit("allreduce", arrays, op, topology)

    def reduce_scatter(self, arrays, op: str = ReduceOp.SUM,
                       owners=None) -> Work:
        """Forwarded to the child's TcpCommContext. NOTE the donation
        contract weakens across the process boundary: results come back
        BY VALUE (fresh arrays), with this rank's owned entries reduced
        and the others unspecified."""
        if owners is not None:
            owners = [int(o) for o in owners]
        return self._submit("reduce_scatter", arrays, op, owners)

    def allgather(self, arrays) -> Work:
        return self._submit("allgather", arrays, ReduceOp.SUM, 0)

    def broadcast(self, arrays, root: int = 0) -> Work:
        return self._submit("broadcast", arrays, ReduceOp.SUM, root)
