"""Zero-RPC steady-state fast path (ISSUE 18).

Epoch-leased quorum + data-plane commit votes: while a lease is live the
manager steps without ANY control RPC — start_quorum is a local check
and should_commit consumes the 1-byte health vote that rode the step's
collective. Every invalidation edge (epoch bump, latch, lease expiry,
dissenting/absent vote) must fall back to the full Quorum + two-phase
barrier path, never commit on weaker evidence, and never hang.

All scenarios run over the REAL native lighthouse + HTTP control plane
and real TCP loopback wires — no mocked clients — because the thing
under test is precisely which RPCs do (not) happen.
"""

import json
import os
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu.comm.store import StoreClient, StoreServer
from torchft_tpu.comm.transport import TcpCommContext
from torchft_tpu.control import Lighthouse, LighthouseClient
from torchft_tpu.manager import Manager

_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)


@pytest.fixture(autouse=True)
def _fastpath_env(monkeypatch):
    monkeypatch.setenv("TORCHFT_TPU_FASTPATH", "1")


@pytest.fixture()
def lease_lighthouse():
    lh = Lighthouse(
        min_replicas=1, join_timeout_ms=100, quorum_tick_ms=10,
        lease_ms=2000,
    )
    yield lh
    lh.shutdown()


@pytest.fixture()
def store():
    server = StoreServer()
    yield server
    server.shutdown()


def _make_solo(store, lighthouse, replica_id="fp_rep_", **kwargs):
    defaults = dict(
        min_replica_size=1,
        rank=0, world_size=1,
        store_addr=store.addr,
        lighthouse_addr=lighthouse.address(),
        replica_id=replica_id,
        timeout=20.0, quorum_timeout=20.0, connect_timeout=20.0,
        heartbeat_interval=0.05,
        use_async_quorum=False,
    )
    defaults.update(kwargs)
    return Manager(**defaults)


def _step(manager):
    manager.start_quorum(allow_heal=False)
    manager.allreduce_arrays(
        [np.ones(8, np.float32)]
    ).future().result(timeout=20)
    return manager.should_commit()


def _break_reasons(manager):
    events = manager.events.since(0)[0]
    return [e.get("reason") for e in events if e["kind"] == "lease_break"]


def _wait_lease_broken(manager, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not manager._lease_valid():
            return True
        time.sleep(0.02)
    return False


def _stranger_heartbeat(lighthouse, rid="stranger"):
    """Heartbeat from an unrelated replica id: the membership set grows,
    so the lighthouse bumps membership_epoch and every parked EpochWatch
    fires."""
    LighthouseClient(lighthouse.address()).heartbeat(rid)


# ---------------------------------------------------------------- steady state


def test_steady_state_steps_are_zero_rpc(store, lease_lighthouse) -> None:
    manager = _make_solo(store, lease_lighthouse)
    try:
        # step 0 pays the full path (quorum RPC + commit barrier) and
        # arms the lease; every later step must be EXACTLY zero-RPC
        assert _step(manager)
        assert manager._control_rpcs >= 2
        for i in range(1, 5):
            assert _step(manager), f"step {i} did not commit"
            assert manager._control_rpcs == 0, (
                f"steady-state step {i} issued {manager._control_rpcs} "
                "control RPCs"
            )
        snap = manager.metrics.snapshot()
        assert snap["fastpath_steps"] == 4.0
        assert snap["fallback_steps"] == 1.0
        assert snap["lease_grants"] >= 1.0
        assert snap["control_rpcs_per_step"] == 0.0
        assert manager.current_step() == 5
        info = manager._telemetry_info()
        assert info["lease_live"] is True
        assert isinstance(info["lease_epoch"], int)
        assert info["control_rpcs_per_step"] == 0
    finally:
        manager.shutdown(wait=False)


def test_fastpath_disabled_by_env(store, lease_lighthouse, monkeypatch) -> None:
    # TORCHFT_TPU_FASTPATH=0 is the live A/B lever:
    # same lighthouse, same lease grants upstream, but the manager pays
    # the full path every step.
    monkeypatch.setenv("TORCHFT_TPU_FASTPATH", "0")
    manager = _make_solo(store, lease_lighthouse, replica_id="fp_off_")
    try:
        for _ in range(3):
            assert _step(manager)
            assert manager._control_rpcs >= 2
        snap = manager.metrics.snapshot()
        assert snap.get("fastpath_steps") is None
        assert snap.get("lease_grants") is None
    finally:
        manager.shutdown(wait=False)


# ---------------------------------------------------- lease invalidation races


def test_epoch_bump_mid_vote_falls_back(store, lease_lighthouse) -> None:
    # The vote is already recorded on the wire when the membership epoch
    # advances: should_commit must NOT consume it — the lease watcher
    # breaks the lease and the step re-runs the full barrier.
    manager = _make_solo(store, lease_lighthouse, replica_id="fp_bump_")
    try:
        assert _step(manager)
        assert _step(manager) and manager._control_rpcs == 0
        step_before = manager.current_step()

        manager.start_quorum(allow_heal=False)
        assert manager._fastpath_active
        manager.allreduce_arrays(
            [np.ones(8, np.float32)]
        ).future().result(timeout=20)  # vote now in flight
        _stranger_heartbeat(lease_lighthouse)
        assert _wait_lease_broken(manager), "epoch bump did not break lease"

        assert manager.should_commit()  # healthy step still commits...
        assert manager._control_rpcs >= 1  # ...but via the full barrier
        assert manager.current_step() == step_before + 1  # never twice
        assert "epoch_advanced" in _break_reasons(manager)
    finally:
        manager.shutdown(wait=False)


def test_latch_edge_during_local_start_quorum(store, lease_lighthouse) -> None:
    # An error latched BETWEEN steps must force start_quorum off the
    # local fast check and back onto the full quorum RPC.
    manager = _make_solo(store, lease_lighthouse, replica_id="fp_latch_")
    try:
        assert _step(manager)
        assert _step(manager) and manager._control_rpcs == 0

        manager.report_error(RuntimeError("latched between steps"))
        manager.start_quorum(allow_heal=False)
        assert not manager._fastpath_active
        assert manager._control_rpcs >= 1  # the quorum RPC ran
        assert "latch_edge" in _break_reasons(manager)
        # (the sync full quorum may already have RE-granted a fresh
        # lease by the time start_quorum returns — that is fine; what
        # matters is that THIS step never armed the fast path)
        # the step itself proceeds through the full path (the error
        # latches for the step it occurred in, which already discarded)
        manager.allreduce_arrays(
            [np.ones(8, np.float32)]
        ).future().result(timeout=20)
        assert manager.should_commit()
        assert manager._control_rpcs >= 2
    finally:
        manager.shutdown(wait=False)


def test_injected_error_mid_lease_never_fast_commits(
    store, lease_lighthouse
) -> None:
    manager = _make_solo(store, lease_lighthouse, replica_id="fp_err_")
    try:
        assert _step(manager)
        assert _step(manager) and manager._control_rpcs == 0

        manager.start_quorum(allow_heal=False)
        assert manager._fastpath_active
        manager.allreduce_arrays(
            [np.ones(8, np.float32)]
        ).future().result(timeout=20)
        manager.report_error(RuntimeError("fault after the collective"))
        assert manager.should_commit() is False  # full barrier discards
        assert not manager._lease_valid()
        snap = manager.metrics.snapshot()
        assert snap["steps_discarded"] >= 1.0
        assert snap["lease_breaks"] >= 1.0
        # recovery: the next healthy step re-arms through the full path
        assert _step(manager)
        assert _step(manager) and manager._control_rpcs == 0
    finally:
        manager.shutdown(wait=False)


def test_lease_expiry_racing_should_commit(store, lease_lighthouse) -> None:
    # Lease dies between the collective and the commit decision: the
    # vote is stale evidence and must be discarded in favour of the full
    # barrier — which still commits (nothing is actually wrong), just
    # not for free.
    manager = _make_solo(store, lease_lighthouse, replica_id="fp_exp_")
    try:
        assert _step(manager)
        assert _step(manager) and manager._control_rpcs == 0

        manager.start_quorum(allow_heal=False)
        assert manager._fastpath_active
        manager.allreduce_arrays(
            [np.ones(8, np.float32)]
        ).future().result(timeout=20)
        with manager._lease_lock:
            manager._lease_deadline = 0.0
        assert manager.should_commit()
        assert manager._control_rpcs >= 1
        assert "lease_expired" in _break_reasons(manager)
    finally:
        manager.shutdown(wait=False)


def test_kill_mid_lease_before_vote_lands(lease_lighthouse) -> None:
    # Two replicas under one lease-granting lighthouse; the second dies
    # abruptly MID-STEP (after the lease check, before its vote reaches
    # the wire). The survivor must discard exactly that in-flight step —
    # an absent vote is never evidence of health — and then resume
    # committing solo once the dead peer ages out of the quorum.
    stores = [StoreServer(), StoreServer()]
    managers = [None, None]
    barrier = threading.Barrier(2, timeout=60.0)
    kill_at, post_kill = 3, 6
    results = [None, None]

    def _replica(idx: int) -> None:
        mgr = Manager(
            min_replica_size=1, rank=0, world_size=1,
            store_addr=stores[idx].addr,
            lighthouse_addr=lease_lighthouse.address(),
            replica_id=f"fp_kill{idx}_",
            timeout=5.0, quorum_timeout=5.0, connect_timeout=5.0,
            heartbeat_interval=0.05,
            use_async_quorum=False,
        )
        managers[idx] = mgr
        commits = discards = post_kill_commits = 0
        for step in range(kill_at + post_kill):
            if step <= kill_at:
                barrier.wait()
            if idx == 1 and step == kill_at:
                mgr.start_quorum(allow_heal=False)
                mgr.shutdown(wait=False)
                break
            mgr.start_quorum(allow_heal=False)
            mgr.allreduce_arrays(
                [np.ones(8, np.float32)]
            ).future().result(timeout=30)
            if mgr.should_commit():
                commits += 1
                if step > kill_at:
                    post_kill_commits += 1
            else:
                discards += 1
                time.sleep(0.5)  # let the dead peer age out
        results[idx] = {
            "commits": commits,
            "discards": discards,
            "post_kill_commits": post_kill_commits,
        }

    threads = [
        threading.Thread(target=_replica, args=(i,)) for i in range(2)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
            assert not t.is_alive(), "replica hung after mid-lease kill"
    finally:
        for mgr in managers:
            if mgr is not None:
                try:
                    mgr.shutdown(wait=False)
                except Exception:  # noqa: BLE001
                    pass
        for s in stores:
            s.shutdown()

    survivor = results[0]
    assert survivor is not None
    assert survivor["discards"] == 1  # exactly the in-flight step
    assert survivor["post_kill_commits"] >= 2  # converged solo


# -------------------------------------------------------------- epoch watch


def test_epoch_watch_renews_and_reports_change(
    store, lease_lighthouse
) -> None:
    manager = _make_solo(store, lease_lighthouse, replica_id="fp_watch_")
    try:
        assert _step(manager)
        epoch = manager._lease_epoch
        assert epoch is not None
        # unchanged epoch: the watch parks for ~timeout then renews
        t0 = time.monotonic()
        new_epoch, changed = manager._client.epoch_watch(epoch, timeout=0.3)
        assert not changed
        assert new_epoch == epoch
        assert time.monotonic() - t0 >= 0.1  # it parked, not spun
        # membership change: the parked watch fires promptly
        waker = threading.Timer(
            0.2, _stranger_heartbeat, (lease_lighthouse, "watch_stranger")
        )
        waker.start()
        try:
            new_epoch, changed = manager._client.epoch_watch(
                epoch, timeout=10.0
            )
        finally:
            waker.join()
        assert changed
        assert new_epoch > epoch
    finally:
        manager.shutdown(wait=False)


# ------------------------------------------------------- vote wire semantics


def _run_ranks(store, world_size, fn, prefix="vote"):
    ctxs = [TcpCommContext(timeout=10.0) for _ in range(world_size)]
    results = [None] * world_size

    def _worker(rank):
        ctx = ctxs[rank]
        ctx.configure(f"{store.addr}/{prefix}", rank, world_size)
        results[rank] = fn(ctx, rank)

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        futs = [pool.submit(_worker, r) for r in range(world_size)]
        for f in futs:
            f.result(timeout=30)
    for ctx in ctxs:
        ctx.shutdown()
    return results


def test_take_commit_vote_semantics(store) -> None:
    # absent (no ops since configure) -> None; all healthy -> True on
    # every rank; one dissenter -> False on EVERY rank (the vote rides
    # the collective, so the OR reaches everyone); consumed once.
    def _fn(ctx, rank):
        out = {"initial": ctx.take_commit_vote()}
        ctx.allreduce([np.ones(4, np.float32)]).future().result(timeout=10)
        out["healthy"] = ctx.take_commit_vote()
        out["consumed"] = ctx.take_commit_vote()
        if rank == 1:
            ctx.set_vote_health(lambda: False)
        ctx.allreduce([np.ones(4, np.float32)]).future().result(timeout=10)
        out["dissent"] = ctx.take_commit_vote()
        return out

    results = _run_ranks(store, 2, _fn)
    for r in results:
        assert r["initial"] is None
        assert r["healthy"] is True
        assert r["consumed"] is None
        assert r["dissent"] is False


def test_vote_window_resets_on_configure(store) -> None:
    def _fn(ctx, rank):
        ctx.allreduce([np.ones(4, np.float32)]).future().result(timeout=10)
        ctx.configure(f"{store.addr}/vote2", rank, 2)
        return ctx.take_commit_vote()

    results = _run_ranks(store, 2, _fn)
    assert results == [None, None]


# --------------------------------------------------------------- observability


def test_telemetry_metrics_serve_fastpath_counters(
    store, lease_lighthouse
) -> None:
    # The exact discovery + fetch path fleet_top uses: the group store
    # advertises the checkpoint/telemetry server, /telemetry/metrics
    # carries the lease fields and the new counters.
    manager = _make_solo(store, lease_lighthouse, replica_id="fp_tel_")
    try:
        for _ in range(3):
            assert _step(manager)
        url = (
            StoreClient(store.addr, connect_timeout=5.0)
            .get("checkpoint_addr_0").decode()
        )
        with urllib.request.urlopen(
            url + "/telemetry/metrics", timeout=10
        ) as resp:
            tel = json.load(resp)
        assert tel["lease_live"] is True
        assert isinstance(tel["lease_epoch"], int)
        assert tel["control_rpcs_per_step"] == 0
        m = tel["metrics"]
        assert m["fastpath_steps"] == 2.0
        assert m["fallback_steps"] == 1.0
        assert m["lease_grants"] >= 1.0
        assert m["control_rpcs_per_step"] == 0.0
    finally:
        manager.shutdown(wait=False)


def test_fleet_top_build_row_lease_columns() -> None:
    import fleet_top

    ep = {"replica_id": "row_rep", "rank": 0, "step": 7}
    polled = {
        "metrics": {
            "step": 7,
            "epoch": 3,
            "lease_live": True,
            "lease_epoch": 5,
            "control_rpcs_per_step": 0,
            "metrics": {"steps_committed": 7.0},
        },
        "events": {"events": []},
    }
    row = fleet_top.build_row(ep, polled)
    assert row["lease"] == "e5"
    assert row["rpc_step"] == 0

    polled["metrics"]["lease_live"] = False
    polled["metrics"]["control_rpcs_per_step"] = 2
    row = fleet_top.build_row(ep, polled)
    assert row["lease"] == "-"
    assert row["rpc_step"] == 2

    # pre-ISSUE-18 payloads (no lease fields) keep the columns empty
    del polled["metrics"]["lease_live"]
    del polled["metrics"]["control_rpcs_per_step"]
    row = fleet_top.build_row(ep, polled)
    assert row["lease"] is None
    assert row["rpc_step"] is None
