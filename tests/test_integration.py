"""Multi-replica integration tests: real native lighthouse, real manager
servers, real TCP comm, real HTTP checkpoints — all on localhost threads.

Spec: the reference's manager_integ_test.py Runner pattern (:70-126), with
FailureInjector fault injection (:39-61) and a convergence oracle
(:376-429). This reproduces `test_ddp_recovery` — the single most
representative test of the whole framework (SURVEY.md §7) — without any
TPU or cluster.

Harness design note: replicas run until a shared stop event fires (set once
every replica has committed >= total_steps), because a replica that exits
early would strand a healing rejoiner below min_replicas. The oracle checks
*trajectory consistency*: for every step number committed by multiple
replicas, the post-update weights must match — the "zero loss-curve
divergence" invariant.
"""

import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np
import pytest

from torchft_tpu.comm.store import StoreServer
from torchft_tpu.comm.transport import TcpCommContext
from torchft_tpu.control import Lighthouse
from torchft_tpu.manager import Manager, WorldSizeMode

logger = logging.getLogger(__name__)


class InjectedFailure(Exception):
    pass


class FailureInjector:
    """Fault injection at (rank, step) (ref manager_integ_test.py:39-61):
    at the rank's first check AT OR PAST the step. A replica that starts
    behind on a loaded machine heals past a step and never stands on it
    (with ``min_replicas=1`` the other steps alone meanwhile), and a
    failure that waited for equality then never came."""

    def __init__(self) -> None:
        self._failures = set()
        self._lock = threading.Lock()
        self.count = 0

    def fail_at(self, rank: int, step: int) -> "FailureInjector":
        with self._lock:
            self._failures.add((rank, step))
        return self

    def check(self, rank: int, step: int) -> None:
        with self._lock:
            due = sorted(f for f in self._failures
                         if f[0] == rank and f[1] <= step)
            if due:
                self._failures.remove(due[0])
                self.count += 1
                logger.warning("injecting failure at %s step %s", rank, step)
                raise InjectedFailure(f"injected failure {rank=} {step=}")


class Harness:
    """Shared coordination: per-replica progress + collective stop."""

    def __init__(self, num_replicas: int, total_steps: int) -> None:
        self.num_replicas = num_replicas
        self.total_steps = total_steps
        self.stop = threading.Event()
        self.progress: Dict[int, int] = {}
        self._lock = threading.Lock()

    def report(self, replica_id: int, step: int) -> None:
        with self._lock:
            self.progress[replica_id] = max(
                self.progress.get(replica_id, 0), step
            )
            if len(self.progress) == self.num_replicas and all(
                s >= self.total_steps for s in self.progress.values()
            ):
                self.stop.set()


class Runner:
    """One replica group; restarts the whole replica on InjectedFailure
    (ref manager_integ_test.py:70-126)."""

    def __init__(
        self,
        replica_id: int,
        lighthouse_addr: str,
        failure_injector: FailureInjector,
        harness: Harness,
        target: Optional[np.ndarray] = None,
        lr: float = 0.5,
        comm_kwargs: Optional[dict] = None,
        replica_prefix: str = "replica",
    ) -> None:
        self.replica_id = replica_id
        self.lighthouse_addr = lighthouse_addr
        self.failure_injector = failure_injector
        self.harness = harness
        self.target = target if target is not None else np.full((2, 3), 10.0)
        self.lr = lr
        self.comm_kwargs = {"timeout": 5.0, **(comm_kwargs or {})}
        self.replica_prefix = replica_prefix
        # committed step -> post-update weights
        self.history: Dict[int, np.ndarray] = {}

    def run_replica(self) -> None:
        while not self.harness.stop.is_set():
            try:
                self._replica_main()
                return
            except InjectedFailure:
                logger.warning("replica %s restarting after injected failure",
                               self.replica_id)
                continue

    def _replica_main(self) -> None:
        store = StoreServer()
        # Toy model: W trained toward `target` with quadratic loss; healthy
        # replicas compute identical grads so synced replicas stay bitwise
        # identical step over step.
        state = {"w": np.zeros((2, 3), dtype=np.float32)}

        def load_state_dict(sd):
            state["w"] = np.array(sd["w"], dtype=np.float32)

        manager = Manager(
            comm=TcpCommContext(**self.comm_kwargs),
            load_state_dict=load_state_dict,
            state_dict=lambda: {"w": state["w"]},
            min_replica_size=1,
            use_async_quorum=True,
            timeout=5.0,
            quorum_timeout=5.0,
            connect_timeout=5.0,
            rank=0,
            world_size=1,
            store_addr=store.addr,
            lighthouse_addr=self.lighthouse_addr,
            replica_id=f"{self.replica_prefix}_{self.replica_id}_",
            heartbeat_interval=0.05,
        )
        try:
            while not self.harness.stop.is_set():
                self.failure_injector.check(0, manager.current_step())
                step_at_start = manager.current_step()
                try:
                    manager.start_quorum()
                except (TimeoutError, RuntimeError) as e:
                    # e.g. peers exited and min_replicas can't be met before
                    # the quorum deadline; retry until the stop event fires
                    logger.info("quorum attempt failed, retrying: %s", e)
                    continue
                grad = state["w"] - self.target  # dL/dW for 0.5||W-T||^2
                fut = manager.allreduce_arrays([grad]).future()
                avg_grad = fut.result(timeout=20)[0]
                if manager.should_commit():
                    # Every replica applies the allreduced average —
                    # including a replica that healed this step and
                    # contributed zeros. That is how a healed replica ends
                    # the step bitwise-identical to its donor (the DDP comm
                    # hook writes the result into every rank's grads,
                    # ref ddp.py:65-71 + manager.py:267-268).
                    state["w"] = state["w"] - self.lr * avg_grad
                    committed_step = manager.current_step()
                    self.history[committed_step] = np.array(state["w"])
                    self.harness.report(self.replica_id, committed_step)
                else:
                    # discarded step; tiny backoff to avoid hot-spinning on
                    # a quorum that cannot yet form
                    del step_at_start
                    time.sleep(0.01)
        finally:
            manager.shutdown(wait=False)
            store.shutdown()


def _run(num_replicas, total_steps, fail_at=(), min_replicas=1,
         heartbeat_timeout_ms=1000, timeout=90.0):
    lighthouse = Lighthouse(
        min_replicas=min_replicas,
        join_timeout_ms=200,
        heartbeat_timeout_ms=heartbeat_timeout_ms,
    )
    harness = Harness(num_replicas, total_steps)
    injectors = [FailureInjector() for _ in range(num_replicas)]
    for rid, step in fail_at:
        injectors[rid].fail_at(0, step)
    runners = [
        Runner(i, lighthouse.address(), injectors[i], harness)
        for i in range(num_replicas)
    ]
    try:
        with ThreadPoolExecutor(max_workers=num_replicas) as pool:
            futs = [pool.submit(r.run_replica) for r in runners]
            deadline = time.monotonic() + timeout
            for f in futs:
                f.result(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        harness.stop.set()
        lighthouse.shutdown()
    return runners, injectors


def _assert_trajectories_consistent(runners: List[Runner]) -> None:
    """For every step committed by >1 replica, post-update weights match."""
    all_steps = {}
    for r in runners:
        for step, w in r.history.items():
            all_steps.setdefault(step, []).append((r.replica_id, w))
    overlapping = 0
    for step, entries in sorted(all_steps.items()):
        if len(entries) > 1:
            overlapping += 1
            base_id, base = entries[0]
            for rid, w in entries[1:]:
                np.testing.assert_allclose(
                    w, base, rtol=1e-6,
                    err_msg=f"divergence at step {step}: replica {rid} vs "
                            f"{base_id}",
                )
    assert overlapping > 0, "no overlapping committed steps to compare"


def test_two_replicas_healthy_converge() -> None:
    # ref manager_integ_test.py:340-377 (ddp healthy path)
    runners, _ = _run(num_replicas=2, total_steps=5, min_replicas=2)
    _assert_trajectories_consistent(runners)
    final = runners[0].history[max(runners[0].history)]
    # loss actually decreased toward the target
    assert np.abs(final - 10.0).max() < 10.0
    assert max(runners[0].history) >= 5


def test_ddp_recovery_replica_killed_and_heals() -> None:
    # THE representative test (ref manager_integ_test.py:391-429): kill one
    # replica mid-run; survivor keeps committing; the dead replica restarts,
    # heals from the survivor's live checkpoint, and the trajectories agree.
    runners, injectors = _run(
        num_replicas=2, total_steps=8, fail_at=[(0, 2)], min_replicas=1,
    )
    assert injectors[0].count == 1
    _assert_trajectories_consistent(runners)
    # the killed replica healed and committed steps at/after the kill point
    assert max(runners[0].history) >= 8
    # survivor kept going
    assert max(runners[1].history) >= 8


def test_three_replicas_one_killed_others_continue() -> None:
    runners, injectors = _run(
        num_replicas=3, total_steps=7, fail_at=[(0, 3)], min_replicas=2,
    )
    assert injectors[0].count == 1
    _assert_trajectories_consistent(runners)
    for r in runners:
        assert max(r.history) >= 7


def test_recovery_with_sync_quorum() -> None:
    # sync-quorum variant of recovery (ref parameterization :379-390)
    lighthouse = Lighthouse(
        min_replicas=2, join_timeout_ms=200, heartbeat_timeout_ms=1000
    )
    harness = Harness(2, 6)
    injectors = [FailureInjector().fail_at(0, 2), FailureInjector()]

    class SyncRunner(Runner):
        def _replica_main(self) -> None:
            store = StoreServer()
            state = {"w": np.zeros((2, 3), dtype=np.float32)}

            def load_state_dict(sd):
                state["w"] = np.array(sd["w"], dtype=np.float32)

            manager = Manager(
                comm=TcpCommContext(timeout=5.0),
                load_state_dict=load_state_dict,
                state_dict=lambda: {"w": state["w"]},
                min_replica_size=1,
                use_async_quorum=False,
                timeout=5.0,
                quorum_timeout=5.0,
                connect_timeout=5.0,
                rank=0,
                world_size=1,
                store_addr=store.addr,
                lighthouse_addr=self.lighthouse_addr,
                replica_id=f"replica_{self.replica_id}_",
                heartbeat_interval=0.05,
            )
            try:
                while not self.harness.stop.is_set():
                    self.failure_injector.check(0, manager.current_step())
                    try:
                        manager.start_quorum()
                    except (TimeoutError, RuntimeError) as e:
                        logger.info("quorum attempt failed, retrying: %s", e)
                        continue
                    grad = state["w"] - self.target
                    avg = manager.allreduce_arrays([grad]).future().result(
                        timeout=20
                    )[0]
                    if manager.should_commit():
                        state["w"] = state["w"] - self.lr * avg
                        self.history[manager.current_step()] = np.array(
                            state["w"]
                        )
                        self.harness.report(
                            self.replica_id, manager.current_step()
                        )
                    else:
                        time.sleep(0.01)
            finally:
                manager.shutdown(wait=False)
                store.shutdown()

    runners = [
        SyncRunner(i, lighthouse.address(), injectors[i], harness)
        for i in range(2)
    ]
    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            futs = [pool.submit(r.run_replica) for r in runners]
            for f in futs:
                f.result(timeout=90)
    finally:
        harness.stop.set()
        lighthouse.shutdown()

    assert injectors[0].count == 1
    _assert_trajectories_consistent(runners)
    for r in runners:
        assert max(r.history) >= 6


def test_multi_rank_groups() -> None:
    # 2 replica groups x 2 local ranks: the manager server fans in both
    # local ranks before one lighthouse RPC; each local rank forms its own
    # cross-group comm under {store}/torchft/{qid}/{rank}
    # (ref manager_integ_test.py:431-470 multi-rank groups).
    lighthouse = Lighthouse(min_replicas=2, join_timeout_ms=300)
    num_groups, ranks_per_group = 2, 2
    results = {}
    errors = []

    def worker(group, rank, group_stores):
        try:
            store_addr = group_stores[group]
            state = {"w": np.zeros(4, dtype=np.float32)}
            manager = Manager(
                comm=TcpCommContext(timeout=10.0),
                load_state_dict=lambda sd: state.update(sd),
                state_dict=lambda: dict(state),
                min_replica_size=2,
                rank=rank,
                world_size=ranks_per_group,
                store_addr=store_addr,
                lighthouse_addr=lighthouse.address(),
                replica_id=f"mr_{group}_",
                timeout=10.0, quorum_timeout=15.0, connect_timeout=10.0,
                heartbeat_interval=0.05,
            )
            try:
                for _ in range(3):
                    manager.start_quorum()
                    # rank-dependent grads: counterpart ranks across groups
                    # average among themselves
                    grad = np.full(4, float(group * 10 + rank), np.float32)
                    avg = manager.allreduce_arrays([grad]).future().result(
                        timeout=30
                    )[0]
                    committed = manager.should_commit()
                    results[(group, rank, manager.current_step())] = (
                        avg.copy(), committed
                    )
            finally:
                manager.shutdown(wait=False)
        except Exception as e:  # noqa: BLE001
            errors.append((group, rank, e))

    stores = [StoreServer() for _ in range(num_groups)]
    group_stores = [s.addr for s in stores]
    try:
        with ThreadPoolExecutor(max_workers=4) as pool:
            futs = [
                pool.submit(worker, g, r, group_stores)
                for g in range(num_groups)
                for r in range(ranks_per_group)
            ]
            for f in futs:
                f.result(timeout=120)
    finally:
        lighthouse.shutdown()
        for s in stores:
            s.shutdown()

    assert not errors, errors
    for (group, rank, step), (avg, committed) in results.items():
        assert committed, (group, rank, step)
        if step >= 2:
            # post-bootstrap: rank r of group 0 averages with rank r of
            # group 1: avg = (0*10+r + 1*10+r)/2 = 5 + r. (Step 1 is the
            # step-0 bootstrap where the non-primary group heals and
            # contributes zeros — and the per-rank primary spread means
            # rank 0 and rank 1 heal OPPOSITE groups, by design:
            # ref manager.rs:397-399.)
            np.testing.assert_allclose(avg, np.full(4, 5.0 + rank))
    steps_seen = {s for (_, _, s) in results}
    assert {1, 2, 3} <= steps_seen


def test_chaos_churn_five_replicas() -> None:
    # The north-star scenario shape (BASELINE.md): repeated replica kills
    # while the job keeps committing, every rejoiner healing back in.
    runners, injectors = _run(
        num_replicas=5,
        total_steps=10,
        fail_at=[(1, 2), (3, 4), (1, 6)],  # replica 1 dies twice
        min_replicas=3,
        timeout=150.0,
    )
    assert injectors[1].count == 2
    assert injectors[3].count == 1
    _assert_trajectories_consistent(runners)
    for r in runners:
        assert max(r.history) >= 10
    # Never-killed replicas commit most steps; killed replicas legitimately
    # commit fewer — a heal FAST-FORWARDS past the steps missed while dead
    # (that is the point), so their history has gaps.
    killed = {1, 3}
    for r in runners:
        floor = 3 if r.replica_id in killed else 6
        assert len(r.history) >= floor, (
            f"replica {r.replica_id} committed only {len(r.history)} steps"
        )


def test_chaos_multi_rank_groups_kill_and_heal() -> None:
    # VERDICT item 6: chaos with ranks_per_group=2 — local fan-in through
    # the group's manager server, per-rank cross-group comm under
    # {store}/torchft/{qid}/{rank}, kill of a WHOLE 2-rank group, restart,
    # per-rank heal from the survivor group, trajectory oracle per rank.
    lighthouse = Lighthouse(
        min_replicas=1, join_timeout_ms=300, heartbeat_timeout_ms=800
    )
    num_groups, ranks_per_group, target_commits = 2, 2, 6
    stop = threading.Event()
    lock = threading.Lock()
    commits: Dict[tuple, int] = {}
    history: Dict[tuple, Dict[int, np.ndarray]] = {
        (g, r): {} for g in range(num_groups) for r in range(ranks_per_group)
    }
    kill_group, kill_at_step = 1, 3
    kill_count = [0]

    def rank_main(group, rank, store_addr, restarted, killed, errors):
        # per-rank target differs so a cross-rank comm mixup would show up
        target = np.full(4, 10.0 * (rank + 1), np.float32)
        w0 = 99.0 if restarted else 0.0
        state = {"w": np.full(4, w0, np.float32)}

        def load_state_dict(sd):
            state["w"] = np.array(sd["w"], dtype=np.float32)

        manager = Manager(
            comm=TcpCommContext(timeout=5.0),
            load_state_dict=load_state_dict,
            state_dict=lambda: {"w": state["w"]},
            min_replica_size=1,
            use_async_quorum=True,
            timeout=8.0, quorum_timeout=8.0, connect_timeout=8.0,
            rank=rank,
            world_size=ranks_per_group,
            store_addr=store_addr,
            lighthouse_addr=lighthouse.address(),
            replica_id=f"chaos_mr_{group}_",
            heartbeat_interval=0.05,
        )
        try:
            while not stop.is_set() and not killed.is_set():
                if (
                    group == kill_group
                    and not restarted
                    and manager.current_step() >= kill_at_step
                ):
                    killed.set()
                    kill_count[0] += 1
                    return
                try:
                    manager.start_quorum()
                    grad = state["w"] - target
                    fut = manager.allreduce_arrays([grad]).future()
                    avg = fut.result(timeout=20)[0]
                    committed = manager.should_commit()
                except (TimeoutError, RuntimeError) as e:
                    # quorum/commit RPCs race the peer group's kill-driven
                    # manager shutdown (503s); retry like a real trainer
                    logger.info("step retry g%d r%d: %s", group, rank, e)
                    continue
                if committed:
                    state["w"] = state["w"] - 0.2 * avg
                    step = manager.current_step()
                    history[(group, rank)][step] = np.array(state["w"])
                    with lock:
                        commits[(group, rank)] = (
                            commits.get((group, rank), 0) + 1
                        )
                        if all(
                            commits.get((g, r), 0) >= target_commits
                            for g in range(num_groups)
                            for r in range(ranks_per_group)
                        ):
                            stop.set()
                else:
                    time.sleep(0.01)
        except Exception as e:  # noqa: BLE001
            errors.append((group, rank, e))
        finally:
            manager.shutdown(wait=False)

    def group_main(group, errors):
        restarted = False
        while not stop.is_set():
            store = StoreServer()
            killed = threading.Event()
            rank_threads = [
                threading.Thread(
                    target=rank_main,
                    args=(group, r, store.addr, restarted, killed, errors),
                    daemon=True,
                )
                for r in range(ranks_per_group)
            ]
            for t in rank_threads:
                t.start()
            for t in rank_threads:
                t.join(timeout=120)
            store.shutdown()
            if killed.is_set() and not stop.is_set():
                logger.warning("group %d killed; restarting both ranks",
                               group)
                restarted = True
                continue
            return

    errors: list = []
    group_threads = [
        threading.Thread(target=group_main, args=(g, errors), daemon=True)
        for g in range(num_groups)
    ]
    try:
        for t in group_threads:
            t.start()
        deadline = time.monotonic() + 120
        for t in group_threads:
            t.join(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        stop.set()
        lighthouse.shutdown()

    assert not errors, errors
    assert kill_count[0] >= 1, "kill never fired"
    # every rank of every group reached the target, including the
    # twice-started group
    for g in range(num_groups):
        for r in range(ranks_per_group):
            assert commits.get((g, r), 0) >= target_commits, (
                g, r, commits
            )
    # per-rank trajectory oracle across groups; counterpart ranks share a
    # comm channel so their post-update weights must match step-for-step
    overlapping = 0
    for r in range(ranks_per_group):
        h0, h1 = history[(0, r)], history[(1, r)]
        common = sorted(set(h0) & set(h1))
        post_heal = [s for s in common if s > kill_at_step + 1]
        assert post_heal, f"rank {r}: no common steps after heal: {common}"
        for s in common:
            overlapping += 1
            np.testing.assert_allclose(
                h0[s], h1[s], rtol=1e-5,
                err_msg=f"rank {r} divergence at step {s}",
            )
    assert overlapping >= 4


def test_recovery_with_compressed_multilane_transport() -> None:
    # Compose the round-2 transport features with the FT loop: bf16 wire
    # compression + 4 lanes, kill a replica, heal, trajectory oracle.
    # Lossy compression must not break bitwise cross-replica consistency
    # (encoded bytes are fanned out verbatim) nor any heal path.
    lighthouse = Lighthouse(
        min_replicas=1, join_timeout_ms=200, heartbeat_timeout_ms=1000
    )
    harness = Harness(2, 6)
    injectors = [FailureInjector().fail_at(0, 2), FailureInjector()]

    runners = [
        Runner(
            i, lighthouse.address(), injectors[i], harness,
            comm_kwargs={
                "algorithm": "star", "channels": 4, "compression": "bf16",
            },
            replica_prefix="creplica",
        )
        for i in range(2)
    ]
    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            futs = [pool.submit(r.run_replica) for r in runners]
            for f in futs:
                f.result(timeout=90)
    finally:
        harness.stop.set()
        lighthouse.shutdown()

    assert injectors[0].count == 1
    # bitwise oracle: bf16-compressed averages must still be identical
    # across replicas (not merely close)
    all_steps = {}
    for r in runners:
        for step, w in r.history.items():
            all_steps.setdefault(step, []).append(w)
    overlapping = [ws for ws in all_steps.values() if len(ws) > 1]
    assert len(overlapping) >= 3
    for ws in overlapping:
        for w in ws[1:]:
            np.testing.assert_array_equal(w, ws[0])
    for r in runners:
        assert max(r.history) >= 6


def test_observer_replica_is_invisible_to_training() -> None:
    # An observer (Manager(data_plane=False)) joins the quorum alongside
    # two training replicas: the trainers' trajectory must be EXACTLY the
    # closed-form two-replica trajectory (if the observer were counted in
    # num_participants or the wire, the 1/N scaling would change and the
    # trajectory would diverge), while the observer itself sees the full
    # 3-member quorum, never participates, and never advances its step.
    lighthouse = Lighthouse(
        min_replicas=1, join_timeout_ms=200, heartbeat_timeout_ms=1000
    )
    harness = Harness(2, 6)
    injector = FailureInjector()
    target = np.full((2, 3), 10.0, dtype=np.float32)

    obs_view = {"world_max": 0, "participated": False, "steps": 0}

    def observer_main() -> None:
        store = StoreServer()
        manager = Manager(
            comm=TcpCommContext(timeout=5.0),
            load_state_dict=lambda sd: None,
            state_dict=lambda: {},
            min_replica_size=1,
            timeout=5.0,
            quorum_timeout=5.0,
            connect_timeout=5.0,
            rank=0,
            world_size=1,
            store_addr=store.addr,
            lighthouse_addr=lighthouse.address(),
            replica_id="observer_0_",
            heartbeat_interval=0.05,
            data_plane=False,
        )
        try:
            while not harness.stop.is_set():
                try:
                    manager.start_quorum(allow_heal=False)
                    manager.wait_quorum()
                except (TimeoutError, RuntimeError):
                    continue
                obs_view["world_max"] = max(
                    obs_view["world_max"], manager.replica_world_size()
                )
                obs_view["participated"] |= manager.is_participating()
                obs_view["steps"] = manager.current_step()
                time.sleep(0.02)
        finally:
            manager.shutdown(wait=False)
            store.shutdown()

    runners = [
        Runner(i, lighthouse.address(), injector, harness, target=target,
               replica_prefix="obstrain")
        for i in range(2)
    ]
    obs_thread = threading.Thread(target=observer_main, daemon=True)
    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            futs = [pool.submit(r.run_replica) for r in runners]
            obs_thread.start()
            for f in futs:
                f.result(timeout=90)
    finally:
        harness.stop.set()
        obs_thread.join(timeout=10)
        lighthouse.shutdown()

    # Trajectory oracle 1: replicas that committed the same step agree.
    for step in runners[0].history:
        if step in runners[1].history:
            np.testing.assert_allclose(
                runners[0].history[step], runners[1].history[step],
                rtol=1e-6, atol=1e-6,
            )
    # Trajectory oracle 2: every update's implied contribution ratio must
    # be a 2-participant scale — 1.0 (both trainers contributed) or 0.5
    # (one bootstrap-healer contributed zeros). A 3-participant scale
    # (2/3 or 1/3) would mean the observer was counted in the average.
    checked = 0
    for r in runners:
        steps = sorted(r.history)
        for a, b in zip(steps, steps[1:]):
            if b != a + 1:
                continue
            w_a, w_b = r.history[a], r.history[b]
            denom = 0.5 * (w_a - target)
            ratio = float(np.mean((w_a - w_b) / denom))
            assert min(abs(ratio - 1.0), abs(ratio - 0.5)) < 1e-4, (
                f"step {b}: implied contribution ratio {ratio} is not a "
                "2-participant scale — observer contaminated the average?"
            )
            checked += 1
    assert checked >= 4  # the oracle actually ran over real transitions
    assert obs_view["world_max"] == 3, obs_view  # saw the full quorum
    assert not obs_view["participated"]
    assert obs_view["steps"] == 0  # never committed


def test_observer_heal_and_spares_together() -> None:
    # VERDICT r3 weak #6: the three membership filters — observer
    # (data_plane=False), healing (is_participating=False during heal),
    # and FIXED_WITH_SPARES clamping — are individually tested but
    # interact in exactly the places quorum bugs live. One scenario with
    # all three: 3 trainers under FIXED_WITH_SPARES(min=2) + 1 observer;
    # a participant is killed mid-run, restarts, and heals. Asserts at
    # every step: participant counts clamped to 2, gradient scale is a
    # 2-participant scale, the observer never participates, and the
    # killed replica's heal actually happened.
    lighthouse = Lighthouse(
        min_replicas=2, join_timeout_ms=200, heartbeat_timeout_ms=1000
    )
    harness = Harness(3, 7)
    injectors = [FailureInjector() for _ in range(3)]
    injectors[0].fail_at(0, 3)  # kill a PARTICIPANT (spare is rank 2)
    target = np.full((2, 3), 10.0, dtype=np.float32)
    records = {"spare_seen": False, "heals": 0, "participants": set()}
    rec_lock = threading.Lock()

    class SpareRunner(Runner):
        def _replica_main(self) -> None:
            store = StoreServer()
            state = {"w": np.zeros((2, 3), dtype=np.float32)}

            def load_state_dict(sd):
                state["w"] = np.array(sd["w"], dtype=np.float32)

            manager = Manager(
                comm=TcpCommContext(**self.comm_kwargs),
                load_state_dict=load_state_dict,
                state_dict=lambda: {"w": state["w"]},
                min_replica_size=2,
                world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
                use_async_quorum=True,
                timeout=5.0,
                quorum_timeout=5.0,
                connect_timeout=5.0,
                rank=0,
                world_size=1,
                store_addr=store.addr,
                lighthouse_addr=self.lighthouse_addr,
                replica_id=f"{self.replica_prefix}_{self.replica_id}_",
                heartbeat_interval=0.05,
            )
            try:
                while not self.harness.stop.is_set():
                    self.failure_injector.check(0, manager.current_step())
                    try:
                        manager.start_quorum()
                    except (TimeoutError, RuntimeError):
                        continue
                    grad = state["w"] - self.target
                    fut = manager.allreduce_arrays([grad]).future()
                    avg_grad = fut.result(timeout=20)[0]
                    if manager.should_commit():
                        with rec_lock:
                            # spares-mode invariant: the divisor is CLAMPED
                            records["participants"].add(
                                manager.num_participants()
                            )
                            if (
                                not manager.is_participating()
                                and not manager.did_heal()
                                and manager.replica_world_size() >= 3
                            ):
                                records["spare_seen"] = True
                            if manager.did_heal():
                                records["heals"] += 1
                        state["w"] = state["w"] - self.lr * avg_grad
                        step = manager.current_step()
                        self.history[step] = np.array(state["w"])
                        self.harness.report(self.replica_id, step)
                    else:
                        time.sleep(0.01)
            finally:
                manager.shutdown(wait=False)
                store.shutdown()

    obs_view = {"participated": False, "world_max": 0}

    def observer_main() -> None:
        store = StoreServer()
        manager = Manager(
            comm=TcpCommContext(timeout=5.0),
            load_state_dict=lambda sd: None,
            state_dict=lambda: {},
            min_replica_size=2,
            world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
            timeout=5.0,
            quorum_timeout=5.0,
            connect_timeout=5.0,
            rank=0,
            world_size=1,
            store_addr=store.addr,
            lighthouse_addr=lighthouse.address(),
            replica_id="swh_zobs_",  # sorts AFTER trainers
            heartbeat_interval=0.05,
            data_plane=False,
        )
        try:
            while not harness.stop.is_set():
                try:
                    manager.start_quorum()  # allow_heal forced off
                    manager.wait_quorum()
                except (TimeoutError, RuntimeError):
                    continue
                obs_view["world_max"] = max(
                    obs_view["world_max"], manager.replica_world_size()
                )
                obs_view["participated"] |= manager.is_participating()
                time.sleep(0.02)
        finally:
            manager.shutdown(wait=False)
            store.shutdown()

    runners = [
        SpareRunner(i, lighthouse.address(), injectors[i], harness,
                    target=target, replica_prefix="swh")
        for i in range(3)
    ]
    obs_thread = threading.Thread(target=observer_main, daemon=True)
    try:
        with ThreadPoolExecutor(max_workers=3) as pool:
            futs = [pool.submit(r.run_replica) for r in runners]
            obs_thread.start()
            for f in futs:
                f.result(timeout=120)
    finally:
        harness.stop.set()
        obs_thread.join(timeout=10)
        lighthouse.shutdown()

    _assert_trajectories_consistent(runners)
    # participant divisor was ALWAYS the clamped spares count, never 3
    # (unclamped cohort) and never 4 (observer leak)
    assert records["participants"] <= {1, 2}, records
    assert 2 in records["participants"], records
    # the gradient scale at every committed transition is a 2-participant
    # scale: 1.0 (two full contributors) or 0.5 (one zero contributor —
    # spare or healer); 2/3, 1/3, or 1/4 would mean a membership filter
    # leaked into the average
    checked = 0
    for r in runners:
        steps = sorted(r.history)
        for a, b in zip(steps, steps[1:]):
            if b != a + 1:
                continue
            w_a, w_b = r.history[a], r.history[b]
            denom = 0.5 * (w_a - target)
            with np.errstate(divide="ignore", invalid="ignore"):
                ratio = float(np.mean((w_a - w_b) / denom))
            assert min(abs(ratio - 1.0), abs(ratio - 0.5)) < 1e-4, (
                f"step {b}: ratio {ratio} is not a 2-participant scale"
            )
            checked += 1
    assert checked >= 4
    assert records["spare_seen"], "no replica ever observed spare status"
    assert records["heals"] >= 1, "the killed replica never healed"
    assert not obs_view["participated"]
    assert obs_view["world_max"] == 4  # trainers + observer all seen


def test_latched_transport_recovers_via_comm_epoch() -> None:
    """A transient transport fault under STABLE membership (no kill, no
    join, no leave) must not poison the wire. A latched TcpCommContext
    fails every op until configure(), and configure historically ran only
    on a transport-key change — so a timed-out collective with an
    unchanged quorum latched the peers forever. The fix: the latched
    member bumps its comm_epoch in the next quorum request; the
    lighthouse treats any epoch change as a membership change
    (native/quorum.cc quorum_changed) and issues a fresh quorum_id, so
    EVERY wire member reconfigures onto a fresh rendezvous prefix
    together. This is BASELINE config 3's "injected allreduce fault"
    shape (ref manager_integ_test.py:39-61 InjectedFailure, which the
    reference only recovers via process restart)."""
    lighthouse = Lighthouse(
        min_replicas=2, join_timeout_ms=200, heartbeat_timeout_ms=2000
    )
    stop = threading.Event()
    histories: Dict[int, Dict[int, np.ndarray]] = {0: {}, 1: {}}
    post_latch_commits = {0: 0, 1: 0}
    latch_fired = threading.Event()
    epochs_seen = {0: 0, 1: 0}
    errors: List[str] = []
    target_post = 3

    def replica(rid: int) -> None:
        store = StoreServer()
        state = {"w": np.zeros(3, dtype=np.float32)}
        comm = TcpCommContext(timeout=3.0)
        manager = Manager(
            comm=comm,
            load_state_dict=lambda sd: state.update(
                w=np.array(sd["w"], dtype=np.float32)
            ),
            state_dict=lambda: {"w": state["w"]},
            min_replica_size=2,
            use_async_quorum=True,
            timeout=5.0,
            quorum_timeout=10.0,
            connect_timeout=5.0,
            rank=0,
            world_size=1,
            store_addr=store.addr,
            lighthouse_addr=lighthouse.address(),
            replica_id=f"epoch_{rid}_",
            heartbeat_interval=0.05,
        )
        try:
            while not stop.is_set():
                try:
                    manager.start_quorum()
                except (TimeoutError, RuntimeError):
                    continue
                if (
                    rid == 0
                    and len(histories[0]) >= 2
                    and not latch_fired.is_set()
                ):
                    # Inject the fault: latch the transport directly (the
                    # same state a timed-out/failed collective leaves via
                    # _Lane._run_loop -> _latch_error). Membership does
                    # NOT change.
                    latch_fired.set()
                    comm._latch_error(
                        RuntimeError("injected transport fault")
                    )
                grad = state["w"] - np.full(3, 10.0, np.float32)
                fut = manager.allreduce_arrays([grad]).future()
                avg = fut.result(timeout=20)[0]
                if manager.should_commit():
                    state["w"] = state["w"] - 0.5 * avg
                    step = manager.current_step()
                    histories[rid][step] = np.array(state["w"])
                    if latch_fired.is_set():
                        post_latch_commits[rid] += 1
                    epochs_seen[rid] = manager._comm_epoch
                    if all(
                        v >= target_post for v in post_latch_commits.values()
                    ):
                        stop.set()
                else:
                    time.sleep(0.01)
        except Exception:  # noqa: BLE001
            import traceback

            errors.append(f"replica {rid}:\n{traceback.format_exc()}")
            stop.set()
        finally:
            manager.shutdown(wait=False)
            store.shutdown()

    threads = [
        threading.Thread(target=replica, args=(r,), daemon=True)
        for r in (0, 1)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 90.0
    for t in threads:
        t.join(max(1.0, deadline - time.monotonic()))
    stop.set()
    for t in threads:
        t.join(10.0)
    lighthouse.shutdown()

    assert not errors, "\n".join(errors)
    assert latch_fired.is_set()
    assert all(v >= target_post for v in post_latch_commits.values()), (
        f"wire never recovered from the latched transport: "
        f"{post_latch_commits}"
    )
    # the latched member requested (at least) one coordinated reconfigure
    assert epochs_seen[0] >= 1, epochs_seen
    # trajectories stayed consistent across the fault + recovery
    common = sorted(set(histories[0]) & set(histories[1]))
    assert common, "no overlapping committed steps"
    for s in common:
        np.testing.assert_allclose(
            histories[0][s], histories[1][s], rtol=1e-6,
            err_msg=f"divergence at step {s}",
        )


def test_classic_ft_step_overhead_small_on_solo_cpu() -> None:
    """End-to-end FT tax of the OVERLAPPED classic path (VERDICT r4 #2
    done-criterion), measured by THE SAME harness the graded artifact
    uses (bench._classic_overhead_phase — one harness, so a fence or
    methodology fix there is automatically what this regression checks):
    real lighthouse + manager + commit barrier, classic
    `OptimizerWrapper.step()` against the bare jitted grad+update loop.
    The residue is a fixed per-step cost (sub-ms on loopback); bounds are
    generous because CI shares one contended core — the bench artifact's
    `projected_ratio` carries the headline number."""
    import sys

    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    from bench import _classic_overhead_phase

    out = _classic_overhead_phase(t0_step_ms=80.0)  # ~125m on-chip step
    assert out["bare_s"] > 0 and out["ft_s"] > 0
    for phase in ("prologue", "dispatch", "barrier", "fence"):
        assert phase in out["phase_ms"], out
    assert out["phase_ms"]["barrier"] > 0
    if not out["inverted_measurement"]:
        # the fixed residue must be small in absolute terms: ms-scale
        # (loopback RPC + bookkeeping), nowhere near a step time
        assert out["overhead_ms_per_step"] < 10.0, out
        assert out["projected_ratio"] < 1.15, out
def test_donated_step_loop_with_real_manager() -> None:
    """donate_update=True against the real control plane: committing
    steps consume (params, opt_state) into ONE donated program each; a
    latched-error discard dispatches nothing and returns the caller's
    live references; the trajectory matches the overlapped default path
    step for step."""
    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu.ddp import DistributedDataParallel
    from torchft_tpu.optim import OptimizerWrapper

    lighthouse = Lighthouse(
        min_replicas=1, join_timeout_ms=100, heartbeat_timeout_ms=2000
    )
    trajectories = {}
    try:
        for mode, donate in (("overlapped", False), ("donated", True)):
            store = StoreServer()
            holder = {}
            manager = Manager(
                comm=TcpCommContext(timeout=5.0),
                load_state_dict=lambda sd: holder.update(sd),
                state_dict=lambda: dict(holder),
                min_replica_size=1,
                rank=0, world_size=1,
                store_addr=store.addr,
                lighthouse_addr=lighthouse.address(),
                replica_id=f"donate_{mode}_",
                timeout=5.0, quorum_timeout=5.0, connect_timeout=5.0,
                heartbeat_interval=0.05,
            )
            try:
                params = {"w": jnp.ones(32)}
                tx = optax.adam(0.1)
                opt = OptimizerWrapper(manager, tx, donate_update=donate)
                ddp = DistributedDataParallel(manager)
                state = opt.init(params)

                @jax.jit
                def grad_fn(p):
                    return jax.grad(
                        lambda p: jnp.mean((p["w"] - 5.0) ** 2)
                    )(p)

                traj = []
                committed_steps = 0
                injected = False
                while committed_steps < 4:
                    opt.begin_step()
                    g = ddp.average_gradients(grad_fn(params))
                    if (committed_steps == 2 and mode == "donated"
                            and not injected):
                        injected = True
                        # inject a discard mid-loop (once): the donated
                        # path must not have consumed any caller buffer
                        # on a non-commit
                        manager.report_error(RuntimeError("injected"))
                        p2, s2, ok = opt.step(params, state, g)
                        assert not ok
                        assert p2 is params and s2 is state
                        # liveness probe: reading a donated/deleted
                        # buffer would raise here
                        assert np.isfinite(float(jnp.sum(params["w"])))
                        continue
                    params, state, ok = opt.step(params, state, g)
                    assert ok
                    committed_steps += 1
                    traj.append(np.asarray(jax.device_get(params["w"])))
                trajectories[mode] = traj
            finally:
                manager.shutdown(wait=False)
                store.shutdown()
    finally:
        lighthouse.shutdown()
    for a, b in zip(trajectories["overlapped"], trajectories["donated"]):
        np.testing.assert_allclose(a, b, rtol=1e-6)
