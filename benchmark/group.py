"""One replica group's fault-tolerant training loop, as a user writes it
(``examples/train_ddp.py``), driven through public entry points only.

Copied from ``chip_smoke.ReplicaGroup`` / ``_CompileCounter`` (the
originals are listed in PERF.md for a later PR to retire) and changed in
what a benchmark needs: the loop runs free for a timed window instead of
in lock-step rounds, every step takes a new batch, the library runs at
its shipped defaults (no timeout is passed), and each call into a layer
sits in a ``bm.*`` ``TraceAnnotation`` so that the trace reduction can
say what the host was doing in a device gap.
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional


class CompileCounter:
    """Counts the programs jax hands to the backend compiler (one per
    jit-cache miss, whether the persistent cache then serves it or not)
    and the persistent cache's hits and misses. jax keeps listeners for
    the life of the process, so a process makes one."""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw: Any) -> None:
        if event == self._BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1

    def _on_event(self, event: str, **_kw: Any) -> None:
        with self._lock:
            if event == self._HIT:
                self.cache_hits += 1
            elif event == self._MISS:
                self.cache_misses += 1


def _span(name: str, chip: int) -> Any:
    import jax

    return jax.profiler.TraceAnnotation(name, chip=chip)


class ReplicaGroup:
    """StoreServer + Manager + CheckpointServer + DistributedDataParallel
    + OptimizerWrapper around one state on one device. ``step`` is one
    iteration of the user's loop; ``run`` repeats it until told to stop
    and keeps a record of every step."""

    def __init__(self, gid: int, incarnation: int, model: Any, family: Any,
                 device: Any, chip: int, lighthouse_addr: str, init_seed: int,
                 source: Any, train_step: Optional[Callable] = None) -> None:
        from torchft_tpu import (
            DistributedDataParallel,
            Manager,
            OptimizerWrapper,
            TcpCommContext,
        )
        from torchft_tpu.checkpointing import CheckpointServer
        from torchft_tpu.comm.store import StoreServer

        self.gid, self.incarnation = gid, incarnation
        self.device, self.chip = device, chip
        self.source = source
        self.records: List[Dict[str, Any]] = []
        self.error: Optional[BaseException] = None
        self.torn_down = False
        self.committed_once = False
        # Metrics snapshots at the window's start (empty for a group born
        # inside it) and at a teardown (its counters die with it)
        self.start_snapshot: Dict[str, Any] = {}
        self.end_snapshot: Optional[Dict[str, Any]] = None
        self.state: Dict[str, Any] = family.init_state(model, init_seed, device)

        def state_dict() -> Dict[str, Any]:
            return dict(self.state)

        def load_state_dict(sd: Dict[str, Any]) -> None:
            self.state.update(sd)

        self.store = StoreServer()
        self.manager = Manager(
            comm=TcpCommContext(),
            load_state_dict=load_state_dict,
            state_dict=state_dict,
            # template_fn: the heal lands each leaf on the device of the
            # healer's own leaf — the one placement a group that does not
            # live on the default device has to ask for
            checkpoint_transport=CheckpointServer(
                template_fn=lambda: {
                    "user": state_dict(),
                    "torchft": {"step": 0, "batches_committed": 0},
                },
            ),
            min_replica_size=1,
            rank=0, world_size=1,
            store_addr=self.store.addr,
            lighthouse_addr=lighthouse_addr,
            replica_id=f"bm_{gid}_{incarnation}_",
        )
        self.ddp = DistributedDataParallel(self.manager)
        self.opt = OptimizerWrapper(
            self.manager, model.tx,
            state_fn=lambda: (self.state["params"], self.state["opt"]),
        )
        self.grad_step = family.make_grad_step(model)
        self.train_step = train_step or family.make_train_step(model)

    # -- the loop ------------------------------------------------------------

    def step(self, tokens: Any, targets: Any) -> Dict[str, Any]:
        """Quorum, then the donated fused program on a solo wire or
        grad -> average -> gated update otherwise."""
        t0 = time.perf_counter()
        if self.incarnation > 0 and not self.committed_once:
            # a replacement that has not committed yet is rejoining: the
            # library heals it inside these calls
            with _span("bm.heal", self.chip):
                return self._step(t0, tokens, targets)
        return self._step(t0, tokens, targets)

    def _step(self, t0: float, tokens: Any, targets: Any) -> Dict[str, Any]:
        with _span("bm.quorum", self.chip):
            self.opt.begin_step()
            fuse = self.opt.can_fuse()
        params, opt_state = self.state["params"], self.state["opt"]
        if fuse:
            path = "fused"
            with _span("bm.fused", self.chip):
                params, opt_state, loss, committed = self.opt.fused_step(
                    self.train_step, params, opt_state, tokens, targets
                )
        else:
            path = "classic"
            with _span("bm.grad", self.chip):
                loss, grads = self.grad_step(params, tokens, targets)
            with _span("bm.average", self.chip):
                avg = self.ddp.average_gradients(grads)
            del grads
            with _span("bm.update", self.chip):
                params, opt_state, committed = self.opt.step(
                    params, opt_state, avg
                )
            del avg
        if committed:
            self.state["params"], self.state["opt"] = params, opt_state
            self.committed_once = True
        rec = {
            "gid": self.gid, "incarnation": self.incarnation,
            "t0": t0, "t1": time.perf_counter(),
            "committed": bool(committed),
            "step": self.manager.current_step(),
            "participants": self.manager.num_participants(),
            "path": path,
            "healed": bool(committed and self.manager.did_heal()),
            "loss": loss if committed else None,
        }
        self.records.append(rec)
        return rec

    def run(self, keep_going: Callable[["ReplicaGroup"], bool],
            first_batch: int = 0,
            on_step: Optional[Callable[[Dict[str, Any]], None]] = None) -> None:
        """The free-running loop: batch ``i`` is on the device before step
        ``i - 1`` is dispatched. Ends when ``keep_going`` says so, when
        the group has been torn down, or on the first exception out of a
        step, which is kept in ``error``. ``on_step`` is handed each
        step's record."""
        try:
            i = first_batch
            pending = self.source.device_batch(i, self.device)
            while not self.torn_down and keep_going(self):
                batch, i = pending, i + 1
                pending = self.source.device_batch(i, self.device)
                rec = self.step(*batch)
                if on_step is not None:
                    on_step(rec)
        except BaseException as e:  # noqa: BLE001 — kept and judged by the job
            self.error = e

    # -- at rest -------------------------------------------------------------

    def digest(self) -> str:
        """sha256 over the bytes of params and optimizer state: equal
        digests are bitwise-equal states."""
        import jax
        import numpy as np

        h = hashlib.sha256()
        for leaf in jax.device_get(jax.tree_util.tree_leaves(self.state)):
            a = np.ascontiguousarray(leaf)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(a.view(np.uint8).reshape(-1))
        return h.hexdigest()

    def snapshots(self) -> Dict[str, Dict[str, Any]]:
        return {
            "manager": self.manager.metrics.snapshot(),
            "optimizer": self.opt.metrics.snapshot(),
        }

    def reset_timings(self) -> None:
        self.manager.metrics.reset_timings()
        self.opt.metrics.reset_timings()

    def teardown(self) -> None:
        """Dead-host semantics, from outside, while the loop may be
        running: manager server, checkpoint server, transport sockets and
        store close together. The loop thread is left to find out."""
        self.torn_down = True
        self.manager.shutdown(wait=False)
        self.store.shutdown()

    def free(self) -> None:
        """Release the HBM at once (the loop thread has ended)."""
        from benchmark import harness

        harness.free(self.state)
        self.state = {}
