"""Process-level kill/heal integration: each replica group is a REAL OS
process, SIGKILL'd mid-step and relaunched.

The thread-based tests in test_integration.py model death as socket close
from within a shared process; the production event is a whole process dying
— manager server, store, checkpoint server, and transport sockets all going
down *together*, mid-collective, with no Python-level cleanup. The
reference proves composition with real process isolation
(/root/reference/torchft/fsdp_test.py:66-74 spawn workers,
process_group_test.py:461-466 ProcessPoolExecutor); this file is the
equivalent for the full FT loop.

Workers are numpy-only trainers (the toy W->target model from
test_integration.py), so the spawned processes never initialize a jax
backend: the kill/heal protocol under test does not depend on one, and a
numpy worker starts in a fraction of the time. The same scenario with a
real model, one process per chip, is stage ``procs`` of chip_smoke.py.
"""

import logging
import multiprocessing as mp
import queue as queue_mod
import time

import numpy as np

from torchft_tpu.control import Lighthouse

logger = logging.getLogger(__name__)

_TARGET = 10.0
_LR = 0.5


def _proc_replica_main(replica_id, incarnation, lighthouse_addr, stop_evt,
                       q) -> None:
    """One replica group as an OS process: own store, manager (with its
    native manager server + checkpoint server), own TCP transport."""
    import faulthandler
    import signal

    from torchft_tpu.comm.store import StoreServer
    from torchft_tpu.comm.transport import TcpCommContext
    from torchft_tpu.manager import Manager

    # SIGUSR1 dumps all thread stacks — the debugging handle for "replica
    # wedged after peer SIGKILL" investigations.
    faulthandler.register(signal.SIGUSR1, all_threads=True)

    target = np.full((2, 3), _TARGET, dtype=np.float32)
    # A relaunched incarnation starts from a poison value: only a real heal
    # (state fetched from the survivor) can make its trajectory match.
    w0 = 99.0 if incarnation > 0 else 0.0
    state = {"w": np.full((2, 3), w0, dtype=np.float32)}

    def load_state_dict(sd):
        state["w"] = np.array(sd["w"], dtype=np.float32)

    store = StoreServer()
    manager = Manager(
        comm=TcpCommContext(timeout=5.0),
        load_state_dict=load_state_dict,
        state_dict=lambda: {"w": state["w"]},
        min_replica_size=1,
        use_async_quorum=True,
        timeout=8.0,
        quorum_timeout=8.0,
        connect_timeout=8.0,
        rank=0,
        world_size=1,
        store_addr=store.addr,
        lighthouse_addr=lighthouse_addr,
        replica_id=f"proc_{replica_id}_i{incarnation}_",
        heartbeat_interval=0.05,
    )
    q.put(("started", replica_id, incarnation, manager.current_step()))
    try:
        while not stop_evt.is_set():
            try:
                manager.start_quorum()
                grad = state["w"] - target
                fut = manager.allreduce_arrays([grad]).future()
                avg = fut.result(timeout=20)[0]
                committed = manager.should_commit()
            except Exception as e:  # noqa: BLE001 — peer death mid-RPC;
                # retry like a real trainer
                logger.info("replica %s step retry: %s", replica_id, e)
                time.sleep(0.05)
                continue
            if committed:
                state["w"] = state["w"] - _LR * avg
                q.put((
                    "commit", replica_id, incarnation,
                    manager.current_step(), state["w"].tolist(),
                ))
                # Throttle: the toy step is all-RPC (no compute), so an
                # unthrottled solo survivor commits at ~2kHz — flooding the
                # mp queue and starving a small CI host until the parent
                # looks stalled. ~100 steps/sec is still far faster than
                # any real trainer.
                time.sleep(0.005)
            else:
                time.sleep(0.01)
    finally:
        manager.shutdown(wait=False)
        store.shutdown()


def test_process_replica_sigkill_relaunch_heal() -> None:
    """SIGKILL a whole replica-group process mid-collective; the survivor
    keeps committing; a fresh process relaunches, heals from the survivor's
    live checkpoint (fast-forwarding past the dead period), and the
    trajectories agree step-for-step."""
    ctx = mp.get_context("spawn")
    lighthouse = Lighthouse(
        min_replicas=1, join_timeout_ms=300, heartbeat_timeout_ms=1000
    )
    stop = ctx.Event()
    # ONE queue per replica: mp.Queue serializes writers through a shared
    # lock, so SIGKILLing a process mid-put leaves that lock held by a
    # corpse and wedges every other writer's feeder thread forever. With a
    # single writer per queue, a kill can only ever lose the victim's own
    # trailing messages.
    queues = {}
    procs = {}

    def launch(rid: int, incarnation: int) -> None:
        q = ctx.Queue()
        queues[(rid, incarnation)] = q
        p = ctx.Process(
            target=_proc_replica_main,
            args=(rid, incarnation, lighthouse.address(), stop, q),
            daemon=True,
        )
        p.start()
        procs[rid] = p

    # history[(rid, incarnation)] = {step: weights}
    history = {}

    def record(msg) -> None:
        if msg[0] == "commit":
            _, rid, inc, step, w = msg
            history.setdefault((rid, inc), {})[step] = np.array(
                w, dtype=np.float32
            )

    def max_step(rid, inc=None):
        steps = [
            s
            for (r, i), h in history.items()
            if r == rid and (inc is None or i == inc)
            for s in h
        ]
        return max(steps, default=0)

    def drain_once() -> bool:
        got = False
        for q in queues.values():
            try:
                while True:
                    record(q.get_nowait())
                    got = True
            except (queue_mod.Empty, OSError, EOFError):
                pass
        return got

    def drain_until(cond, timeout):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            if not drain_once():
                time.sleep(0.05)
        return cond()

    def overlap(key_a, key_b):
        return set(history.get(key_a, {})) & set(history.get(key_b, {}))

    try:
        launch(0, 0)
        launch(1, 0)
        # Phase 1: both replica processes training TOGETHER — require
        # overlapping committed steps, not just per-replica progress (one
        # replica can race ahead solo while the other is still joining).
        assert drain_until(
            lambda: len(overlap((0, 0), (1, 0))) >= 3, 90
        ), f"bring-up failed: {sorted(history)}"

        # Phase 2: SIGKILL replica 0 — its manager server, store,
        # checkpoint server and transport sockets die together, with the
        # step loop somewhere inside quorum/allreduce/commit.
        procs[0].kill()
        procs[0].join(timeout=10)
        kill_step = max_step(0, 0)

        # Phase 3: the survivor must keep committing well past the kill.
        assert drain_until(lambda: max_step(1, 0) >= kill_step + 3, 60), (
            f"survivor stalled after peer SIGKILL at step {kill_step}: "
            f"reached {max_step(1, 0)}"
        )

        # Phase 4: relaunch replica 0 as a fresh process; it must heal
        # from the survivor and rejoin the trajectory — again gated on
        # OVERLAPPING commits, the only evidence of joint training.
        launch(0, 1)
        assert drain_until(
            lambda: len(overlap((0, 1), (1, 0))) >= 3, 120
        ), (
            f"heal/rejoin failed: r0i1={sorted(history.get((0, 1), {}))} "
            f"r1 max={max_step(1, 0)}"
        )
    finally:
        stop.set()
        for p in procs.values():
            p.join(timeout=15)
            if p.is_alive():
                p.kill()
                p.join(timeout=5)
        lighthouse.shutdown()
        # drain any last messages so the oracle sees every commit
        drain_once()

    # Heal fast-forwards: the relaunched incarnation never re-commits the
    # early steps it missed while dead — its first commit is at/after the
    # survivor's frontier at relaunch time.
    inc1_steps = sorted(history[(0, 1)])
    assert inc1_steps, "relaunched replica never committed"
    assert min(inc1_steps) > kill_step, (
        f"relaunched replica replayed old steps: {inc1_steps[:5]}"
    )

    # Trajectory oracle: every step committed by multiple (replica,
    # incarnation) pairs has identical post-update weights — including
    # across the kill/heal boundary. The poison init (99.0) guarantees
    # this can only pass via a genuine state transfer.
    by_step = {}
    for key, h in history.items():
        for step, w in h.items():
            by_step.setdefault(step, []).append((key, w))
    overlapping = 0
    for step, entries in sorted(by_step.items()):
        if len(entries) > 1:
            overlapping += 1
            base_key, base = entries[0]
            for key, w in entries[1:]:
                np.testing.assert_allclose(
                    w, base, rtol=1e-6,
                    err_msg=f"divergence at step {step}: {key} vs {base_key}",
                )
    assert overlapping >= 3, f"too few overlapping steps: {overlapping}"
    # at least one overlapping step must be POST-heal
    post_heal = [
        s for s, entries in by_step.items()
        if len(entries) > 1 and s >= min(inc1_steps)
    ]
    assert post_heal, "no overlapping steps after the heal"
