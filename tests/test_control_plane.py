"""End-to-end tests for the native lighthouse + manager servers.

Mirrors the reference's server-level tests (lighthouse.rs:912-954 e2e quorum,
manager.rs:504-718 should_commit voting / quorum / checkpoint metadata,
lighthouse_test.py timing bound) over real HTTP on localhost.
"""

import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

from torchft_tpu.control import (
    Lighthouse,
    ManagerClient,
    ManagerServer,
    lighthouse_heartbeat,
    lighthouse_quorum,
)


@pytest.fixture()
def lighthouse():
    lh = Lighthouse(min_replicas=1, join_timeout_ms=100)
    yield lh
    lh.shutdown()


def _make_manager(lighthouse, replica_id="rep_0", world_size=1, **kwargs):
    return ManagerServer(
        replica_id=replica_id,
        lighthouse_addr=lighthouse.address(),
        store_addr=f"store:{replica_id}",
        world_size=world_size,
        exit_on_kill=False,
        **kwargs,
    )


def test_lighthouse_address(lighthouse) -> None:
    addr = lighthouse.address()
    assert addr.startswith("http://")


def test_lighthouse_quorum_join_timing() -> None:
    # A single replica's quorum does not wait for the join window (parity
    # with ref lighthouse_test.py:44-47): every heartbeating replica has
    # joined, so nobody is waited for. The window here is a minute and
    # the RPC's deadline 5 s: a lighthouse that held the request for the
    # window would fail the call, on any machine at any load.
    lighthouse = Lighthouse(min_replicas=1, join_timeout_ms=60_000)
    try:
        result = lighthouse_quorum(
            lighthouse.address(),
            {
                "replica_id": "timing",
                "address": "addr",
                "store_address": "store",
                "step": 0,
                "world_size": 1,
                "shrink_only": False,
            },
            timeout=5.0,
        )
    finally:
        lighthouse.shutdown()
    ids = [p["replica_id"] for p in result["quorum"]["participants"]]
    assert ids == ["timing"]


def test_lighthouse_heartbeat(lighthouse) -> None:
    lighthouse_heartbeat(lighthouse.address(), "hb_rep")


def test_lighthouse_status_json(lighthouse) -> None:
    # Machine-readable fleet status — the discovery root for
    # scripts/fleet_top.py: quorum members carry manager AND store
    # addresses, heartbeats carry ages + a dead flag.
    import json

    addr = lighthouse.address()
    # before any quorum: reason present, no quorum key
    empty = json.load(
        urllib.request.urlopen(addr + "/status.json", timeout=5)
    )
    assert "reason" in empty and "quorum" not in empty
    lighthouse_quorum(
        addr,
        {
            "replica_id": "statusj",
            "address": "http://mgr:1",
            "store_address": "store:2",
            "step": 4,
            "world_size": 2,
            "shrink_only": False,
        },
        timeout=5.0,
    )
    lighthouse_heartbeat(addr, "statusj")
    status = json.load(
        urllib.request.urlopen(addr + "/status.json", timeout=5)
    )
    members = status["quorum"]["participants"]
    assert [m["replica_id"] for m in members] == ["statusj"]
    assert members[0]["address"] == "http://mgr:1"
    assert members[0]["store_address"] == "store:2"
    assert members[0]["world_size"] == 2
    assert status["max_step"] == 4
    assert status["quorum_age_ms"] >= 0
    hb = status["heartbeats"]["statusj"]
    assert hb["age_ms"] >= 0 and hb["dead"] is False


def test_lighthouse_dashboard(lighthouse) -> None:
    addr = lighthouse.address()
    html = urllib.request.urlopen(addr + "/", timeout=5).read().decode()
    assert "lighthouse" in html
    status = urllib.request.urlopen(addr + "/status", timeout=5).read().decode()
    assert "quorum" in status


def test_manager_single_replica_quorum(lighthouse) -> None:
    mgr = _make_manager(lighthouse, "rep_0")
    try:
        client = ManagerClient(mgr.address())
        result = client.quorum(
            rank=0, step=0, checkpoint_metadata="ckpt0", shrink_only=False,
            timeout=10.0,
        )
        assert result.quorum_id >= 1
        assert result.replica_rank == 0
        assert result.replica_world_size == 1
        assert result.max_step == 0
        assert not result.heal  # sole replica is the primary at step 0
        assert result.store_address == "store:rep_0"
    finally:
        mgr.shutdown()


def test_manager_two_replica_quorum_and_heal_assignment() -> None:
    # Two replica groups at different steps: behind group must heal from the
    # up-to-date one (ref manager.rs:551-671 semantics).
    lh = Lighthouse(min_replicas=2, join_timeout_ms=200)
    mgr_a = None
    mgr_b = None
    try:
        mgr_a = _make_manager(lh, "rep_a")
        mgr_b = _make_manager(lh, "rep_b")
        client_a = ManagerClient(mgr_a.address())
        client_b = ManagerClient(mgr_b.address())

        with ThreadPoolExecutor(max_workers=2) as pool:
            fut_a = pool.submit(
                client_a.quorum, 0, 10, "ckpt_a", False, 10.0
            )
            fut_b = pool.submit(
                client_b.quorum, 0, 4, "ckpt_b", False, 10.0
            )
            res_a = fut_a.result(timeout=15)
            res_b = fut_b.result(timeout=15)

        assert res_a.quorum_id == res_b.quorum_id
        assert res_a.replica_world_size == 2
        assert res_a.max_step == 10
        assert not res_a.heal
        assert res_a.recover_dst_ranks == [1]  # rep_b sorts after rep_a
        assert res_b.heal
        assert res_b.recover_src_rank == 0
        assert res_b.recover_src_manager_address == mgr_a.address()
        assert res_b.max_rank is None
        assert res_b.replica_rank == 1
    finally:
        if mgr_a:
            mgr_a.shutdown()
        if mgr_b:
            mgr_b.shutdown()
        lh.shutdown()


def test_manager_local_fanin_two_ranks(lighthouse) -> None:
    # world_size=2: the manager waits for BOTH local ranks before issuing
    # one lighthouse request on behalf of the group.
    mgr = _make_manager(lighthouse, "rep_0", world_size=2)
    try:
        client0 = ManagerClient(mgr.address())
        client1 = ManagerClient(mgr.address())

        results = {}

        def _quorum(rank, client):
            results[rank] = client.quorum(rank, 7, f"meta{rank}", False, 10.0)

        t0 = threading.Thread(target=_quorum, args=(0, client0))
        t0.start()
        time.sleep(0.2)
        assert not results, "rank 0 must block until rank 1 joins"
        t1 = threading.Thread(target=_quorum, args=(1, client1))
        t1.start()
        t0.join(timeout=10)
        t1.join(timeout=10)
        assert results[0].quorum_id == results[1].quorum_id
        assert results[0].replica_world_size == 1  # one replica group
    finally:
        mgr.shutdown()


def test_manager_fanin_takes_max_comm_epoch(lighthouse) -> None:
    """Any local rank's latched transport must force the group-wide
    coordinated reconfigure: the group's lighthouse Member carries the
    MAX comm_epoch across ranks (native/manager.cc fan-in), and a later
    quorum with the bumped epoch mints a fresh quorum_id even though
    membership did not change (native/quorum.cc quorum_changed)."""
    mgr = _make_manager(lighthouse, "rep_0", world_size=2)
    try:
        c0 = ManagerClient(mgr.address())
        c1 = ManagerClient(mgr.address())

        def q(client, rank, step, epoch):
            return client.quorum(
                rank, step, f"meta{rank}", False, 10.0, comm_epoch=epoch
            )

        with ThreadPoolExecutor(max_workers=2) as pool:
            f0 = pool.submit(q, c0, 0, 1, 0)
            f1 = pool.submit(q, c1, 1, 1, 0)
            base = f0.result(timeout=15).quorum_id
            assert f1.result(timeout=15).quorum_id == base

            # only rank 1's transport latched -> its epoch bump must
            # still bump the quorum id for the whole group
            f0 = pool.submit(q, c0, 0, 2, 0)
            f1 = pool.submit(q, c1, 1, 2, 1)
            r0, r1 = f0.result(timeout=15), f1.result(timeout=15)
            assert r0.quorum_id == r1.quorum_id == base + 1

            # stable epochs again -> no further bump
            f0 = pool.submit(q, c0, 0, 3, 0)
            f1 = pool.submit(q, c1, 1, 3, 1)
            assert f0.result(timeout=15).quorum_id == base + 1
            assert f1.result(timeout=15).quorum_id == base + 1
    finally:
        mgr.shutdown()


def test_should_commit_unanimous_and_veto(lighthouse) -> None:
    # Two-phase commit barrier over 2 local ranks (ref manager.rs:504-549).
    mgr = _make_manager(lighthouse, "rep_0", world_size=2)
    try:
        c0 = ManagerClient(mgr.address())
        c1 = ManagerClient(mgr.address())

        with ThreadPoolExecutor(max_workers=2) as pool:
            f0 = pool.submit(c0.should_commit, 0, 1, True, 10.0)
            f1 = pool.submit(c1.should_commit, 1, 1, True, 10.0)
            assert f0.result(timeout=15) is True
            assert f1.result(timeout=15) is True

            # Round 2: one rank votes False -> everyone aborts.
            f0 = pool.submit(c0.should_commit, 0, 2, True, 10.0)
            f1 = pool.submit(c1.should_commit, 1, 2, False, 10.0)
            assert f0.result(timeout=15) is False
            assert f1.result(timeout=15) is False

            # Round 3: state reset -> True again.
            f0 = pool.submit(c0.should_commit, 0, 3, True, 10.0)
            f1 = pool.submit(c1.should_commit, 1, 3, True, 10.0)
            assert f0.result(timeout=15) is True
            assert f1.result(timeout=15) is True
    finally:
        mgr.shutdown()


def _raw_vote(addr, rank, step, ok, attempt, timeout=10.0):
    """Drive the ShouldCommit wire protocol directly, with an explicit
    attempt id — the only way to simulate a transport-level RESEND (the
    real client mints a fresh id per logical call)."""
    import json as _json
    import urllib.request

    req = urllib.request.Request(
        addr + "/torchft.ManagerService/ShouldCommit",
        data=_json.dumps({
            "rank": rank, "step": step, "should_commit": ok,
            "attempt": attempt,
        }).encode(),
        headers={
            "x-timeout-ms": str(int(timeout * 1000)),
            "Content-Type": "application/json",
        },
    )
    with urllib.request.urlopen(req, timeout=timeout + 5) as r:
        return _json.loads(r.read())["should_commit"]


def test_should_commit_replay_and_stale_votes(lighthouse) -> None:
    # A vote resent after a lost reply (pooled-connection retry) carries
    # the SAME attempt id and must get its own round's cached decision —
    # for TRUE and FALSE rounds alike — never be counted into a later
    # round's barrier. Fresh votes for already-committed steps are stale;
    # a half-round abandoned by a timeout is drained by newer-step votes.
    import urllib.error

    mgr = _make_manager(lighthouse, "rep_0", world_size=2)
    try:
        addr = mgr.address()
        # 4 workers: the stranded step-3 vote must not starve the step-5
        # pair out of the pool
        with ThreadPoolExecutor(max_workers=4) as pool:
            f0 = pool.submit(_raw_vote, addr, 0, 1, True, 100)
            f1 = pool.submit(_raw_vote, addr, 1, 1, True, 101)
            assert f0.result(timeout=15) is True
            assert f1.result(timeout=15) is True

            # transport resend (same attempt id): cached decision, no wait
            assert _raw_vote(addr, 0, 1, True, 100, timeout=2.0) is True

            # a FRESH vote for the committed step is a protocol violation
            with pytest.raises(urllib.error.HTTPError) as ei:
                _raw_vote(addr, 0, 1, True, 102, timeout=2.0)
            assert ei.value.code == 409
            # an older vote likewise
            with pytest.raises(urllib.error.HTTPError) as ei:
                _raw_vote(addr, 0, 0, True, 103, timeout=2.0)
            assert ei.value.code == 409

            # FALSE round: the resend must replay FALSE, and the same
            # step must still be re-votable as a fresh barrier
            f0 = pool.submit(_raw_vote, addr, 0, 2, True, 110)
            f1 = pool.submit(_raw_vote, addr, 1, 2, False, 111)
            assert f0.result(timeout=15) is False
            assert f1.result(timeout=15) is False
            assert _raw_vote(addr, 1, 2, False, 111, timeout=2.0) is False
            f0 = pool.submit(_raw_vote, addr, 0, 2, True, 112)
            f1 = pool.submit(_raw_vote, addr, 1, 2, True, 113)
            assert f0.result(timeout=15) is True
            assert f1.result(timeout=15) is True

            # abandoned half-round: rank 0 opens step 3 and blocks; the
            # group moves on to step 5 (heal semantics). The new round
            # must complete — not 409 forever — and the stranded step-3
            # voter must be told its round was abandoned.
            f_stranded = pool.submit(
                _raw_vote, addr, 0, 3, True, 120, 8.0
            )
            time.sleep(0.3)  # let the step-3 vote open its round
            f0 = pool.submit(_raw_vote, addr, 0, 5, True, 121)
            f1 = pool.submit(_raw_vote, addr, 1, 5, True, 122)
            assert f0.result(timeout=15) is True
            assert f1.result(timeout=15) is True
            with pytest.raises(urllib.error.HTTPError) as ei:
                f_stranded.result(timeout=15)
            assert ei.value.code == 409
    finally:
        mgr.shutdown()


def test_checkpoint_metadata_roundtrip(lighthouse) -> None:
    mgr = _make_manager(lighthouse, "rep_0")
    try:
        client = ManagerClient(mgr.address())
        with pytest.raises(RuntimeError, match="rank not found"):
            client.checkpoint_metadata(0, timeout=5.0)
        client.quorum(0, 0, "the-metadata", False, 10.0)
        assert client.checkpoint_metadata(0, timeout=5.0) == "the-metadata"
    finally:
        mgr.shutdown()


def test_quorum_timeout_is_bounded(lighthouse) -> None:
    # A quorum that cannot complete (world_size=2, only one rank calls) must
    # raise TimeoutError within ~the requested timeout, not hang
    # (ref manager_integ_test.py:653-665 bound <1.0s).
    mgr = _make_manager(lighthouse, "rep_0", world_size=2)
    try:
        client = ManagerClient(mgr.address())
        start = time.monotonic()
        with pytest.raises(TimeoutError):
            client.quorum(0, 0, "", False, timeout=0.3)
        assert time.monotonic() - start < 1.0
    finally:
        mgr.shutdown()


def test_should_commit_timeout_is_bounded(lighthouse) -> None:
    mgr = _make_manager(lighthouse, "rep_0", world_size=2)
    try:
        client = ManagerClient(mgr.address())
        start = time.monotonic()
        with pytest.raises(TimeoutError):
            client.should_commit(0, 0, True, timeout=0.3)
        assert time.monotonic() - start < 1.0
    finally:
        mgr.shutdown()


def test_kill_rpc_sets_flag(lighthouse) -> None:
    mgr = _make_manager(lighthouse, "rep_0")
    try:
        client = ManagerClient(mgr.address())
        assert not mgr.kill_requested()
        client.kill("test kill")
        deadline = time.monotonic() + 5
        while not mgr.kill_requested() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert mgr.kill_requested()
    finally:
        mgr.shutdown()


def test_dashboard_kill_button_path(lighthouse) -> None:
    # POST /replica/{id}/kill proxies to that replica's manager Kill RPC
    # (ref lighthouse.rs:414-439).
    mgr = _make_manager(lighthouse, "rep_k")
    try:
        client = ManagerClient(mgr.address())
        client.quorum(0, 0, "", False, 10.0)  # register in a quorum
        req = urllib.request.Request(
            lighthouse.address() + "/replica/rep_k/kill", method="POST"
        )
        urllib.request.urlopen(req, timeout=10)
        deadline = time.monotonic() + 5
        while not mgr.kill_requested() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert mgr.kill_requested()
    finally:
        mgr.shutdown()


def test_manager_unreachable_lighthouse_fails_fast() -> None:
    start = time.monotonic()
    with pytest.raises((RuntimeError, TimeoutError)):
        ManagerServer(
            replica_id="r",
            lighthouse_addr="http://127.0.0.1:1",  # nothing listening
            world_size=1,
            connect_timeout=0.3,
        )
    assert time.monotonic() - start < 3.0


def test_repeated_quorums_stable_id(lighthouse) -> None:
    # Same membership across rounds -> quorum_id stays put; the id only
    # bumps on membership change (ref lighthouse.rs:272-283).
    mgr = _make_manager(lighthouse, "rep_0")
    try:
        client = ManagerClient(mgr.address())
        first = client.quorum(0, 1, "", False, 10.0)
        second = client.quorum(0, 2, "", False, 10.0)
        third = client.quorum(0, 3, "", False, 10.0)
        assert first.quorum_id == second.quorum_id == third.quorum_id
    finally:
        mgr.shutdown()


def _status_json(addr):
    import json

    return json.load(
        urllib.request.urlopen(addr + "/status.json", timeout=5)
    )


def test_batched_heartbeat_and_counters(lighthouse) -> None:
    # One RPC carrying a whole domain's replica_ids (the tier-1
    # aggregator wire form) registers every id, and the control counters
    # pin the RPC-vs-ids accounting the fleet bench reads.
    from torchft_tpu.control import LighthouseClient

    addr = lighthouse.address()
    client = LighthouseClient(addr)
    client.heartbeat(["batch_a", "batch_b", "batch_c"])
    client.heartbeat("single")
    status = _status_json(addr)
    for rid in ("batch_a", "batch_b", "batch_c", "single"):
        assert status["heartbeats"][rid]["dead"] is False
    ctl = status["control"]
    assert ctl["heartbeat_rpcs"] == 2
    assert ctl["heartbeat_ids"] == 4
    assert ctl["cache_enabled"] is True
    assert ctl["tier"] == 0 and ctl["upstream"] == ""
    for key in ("quorum_compute_count", "quorum_cache_hits",
                "membership_epoch", "quorum_rpcs", "heartbeats_pruned",
                "participants_pruned", "healthy_replicas"):
        assert isinstance(ctl[key], int), key


def test_status_polls_hit_decision_cache(lighthouse) -> None:
    # Membership-stable status polls must be served from the epoch cache
    # (recompute count is O(membership changes), not O(RPCs)); with
    # cache_quorum=False the same polls recompute every time.
    addr = lighthouse.address()
    lighthouse_heartbeat(addr, "pollster")
    base = _status_json(addr)["control"]
    for _ in range(20):
        _status_json(addr)
    ctl = _status_json(addr)["control"]
    assert ctl["quorum_compute_count"] == base["quorum_compute_count"]
    assert ctl["quorum_cache_hits"] >= base["quorum_cache_hits"] + 20

    lh2 = Lighthouse(min_replicas=1, join_timeout_ms=100,
                     cache_quorum=False)
    try:
        addr2 = lh2.address()
        lighthouse_heartbeat(addr2, "pollster")
        base2 = _status_json(addr2)["control"]
        assert base2["cache_enabled"] is False
        for _ in range(20):
            _status_json(addr2)
        ctl2 = _status_json(addr2)["control"]
        assert ctl2["quorum_compute_count"] >= (
            base2["quorum_compute_count"] + 20
        )
        assert ctl2["quorum_cache_hits"] == 0
    finally:
        lh2.shutdown()


def test_lighthouse_prunes_departed_heartbeats() -> None:
    # Nothing used to erase state_.heartbeats; now long-dead entries are
    # pruned at sweep boundaries with a counter (never silently).
    import time as _time

    lh = Lighthouse(min_replicas=1, join_timeout_ms=50,
                    quorum_tick_ms=25, heartbeat_timeout_ms=100,
                    prune_after_ms=300)
    try:
        addr = lh.address()
        lighthouse_heartbeat(addr, "ephemeral")
        assert "ephemeral" in _status_json(addr)["heartbeats"]
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            status = _status_json(addr)
            if "ephemeral" not in status["heartbeats"]:
                break
            _time.sleep(0.05)
        assert "ephemeral" not in status["heartbeats"], status["heartbeats"]
        assert status["control"]["heartbeats_pruned"] >= 1
    finally:
        lh.shutdown()


def test_quorum_longpoll_piggybacks_heartbeats() -> None:
    # A manager with a lighthouse quorum RPC in flight must (a) SKIP its
    # separate heartbeat RPCs (the piggyback path) and (b) stay healthy
    # the whole time via the server-side waiter re-stamp — with a
    # heartbeat timeout far shorter than the park duration, liveness can
    # only come from the re-stamp. Then a second replica joins and the
    # parked quorum completes.
    lh = Lighthouse(min_replicas=2, join_timeout_ms=60000,
                    quorum_tick_ms=50, heartbeat_timeout_ms=600)
    mgr_a = mgr_b = None
    try:
        mgr_a = _make_manager(lh, "park_a", heartbeat_interval=0.05)
        client_a = ManagerClient(mgr_a.address())
        with ThreadPoolExecutor(max_workers=2) as pool:
            fut_a = pool.submit(
                client_a.quorum, 0, 1, "meta", False, 30.0
            )
            time.sleep(0.3)  # the quorum RPC is now parked lighthouse-side
            c0 = _status_json(lh.address())["control"]
            park_window = 1.5  # >> heartbeat_timeout of 0.6s
            time.sleep(park_window)
            status = _status_json(lh.address())
            c1 = status["control"]
            # (a) piggyback: at 50ms intervals the old path would post
            # ~30 heartbeats over the window; the in-flight quorum
            # suppresses (nearly) all of them
            assert c1["heartbeat_rpcs"] - c0["heartbeat_rpcs"] <= 3, (
                c0, c1,
            )
            # (b) waiter re-stamp: parked for 2.5x the heartbeat timeout
            # yet still alive
            assert status["heartbeats"]["park_a"]["dead"] is False
            # release: second replica joins -> quorum forms for both
            mgr_b = _make_manager(lh, "park_b", heartbeat_interval=0.05)
            client_b = ManagerClient(mgr_b.address())
            fut_b = pool.submit(
                client_b.quorum, 0, 1, "meta", False, 30.0
            )
            res_a = fut_a.result(timeout=30)
            res_b = fut_b.result(timeout=30)
            assert res_a.quorum_id == res_b.quorum_id
            assert res_a.replica_world_size == 2
    finally:
        if mgr_a:
            mgr_a.shutdown()
        if mgr_b:
            mgr_b.shutdown()
        lh.shutdown()


def test_dead_longpoll_waiter_is_not_kept_alive() -> None:
    # The waiter re-stamp must not outlive its client: a requester whose
    # process dies mid-long-poll (socket closed, no response read) has to
    # expire after heartbeat_timeout like any dead replica — NOT stay
    # "healthy" until the RPC deadline because the parked handler keeps
    # stamping it. The handler peeks the serving socket before each
    # re-stamp (native/lighthouse.cc handle_quorum).
    import json as _json
    import socket

    lh = Lighthouse(min_replicas=2, join_timeout_ms=60000,
                    quorum_tick_ms=50, heartbeat_timeout_ms=400)
    try:
        addr = lh.address()
        host, port = addr[len("http://"):].rsplit(":", 1)
        body = _json.dumps({"requester": {
            "replica_id": "ghost", "address": "a", "store_address": "s",
            "step": 0, "world_size": 1, "shrink_only": False,
        }}).encode()
        sock = socket.create_connection((host, int(port)), timeout=5)
        sock.sendall(
            b"POST /torchft.LighthouseService/Quorum HTTP/1.1\r\n"
            b"Host: x\r\nContent-Type: application/json\r\n"
            b"x-timeout-ms: 30000\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
            + body
        )
        time.sleep(0.3)  # the waiter is parked (min_replicas=2)
        status = _status_json(addr)
        assert status["heartbeats"]["ghost"]["dead"] is False
        sock.close()  # the "process" dies without ever reading a reply
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            status = _status_json(addr)
            if status["heartbeats"].get("ghost", {}).get("dead"):
                break
            time.sleep(0.05)
        assert status["heartbeats"]["ghost"]["dead"] is True, status
    finally:
        lh.shutdown()


def test_control_plane_connection_reuse() -> None:
    # Keep-alive parity with ref src/net.rs: a manager heartbeating every
    # 50ms for ~1.5s (~30 RPCs) must NOT open a socket per request — the
    # lighthouse-side accepted-connection count stays near one per client.
    import json
    import time
    import urllib.request

    lh = Lighthouse(min_replicas=1, join_timeout_ms=100)
    mgr = ManagerServer(
        "reuse_0",
        lh.address(),
        store_addr="s:1",
        world_size=1,
        heartbeat_interval=0.05,
        exit_on_kill=False,
    )
    try:
        time.sleep(1.5)
        with urllib.request.urlopen(
            f"{lh.address()}/statsz", timeout=5
        ) as resp:
            stats = json.load(resp)
        # one pooled conn for heartbeats (+1 slack for races/pool misses);
        # the /statsz fetch below this count was not made yet when read
        assert stats["http_conns_accepted"] <= 3, stats
    finally:
        mgr.shutdown()
        lh.shutdown()


# ------------------------------------------------------------ the door-knock
#
# A member of the last quorum that holds a new one up and whose manager
# address REFUSES a connection is expired at once; every other outcome is
# left to the heartbeat timeout. The timeout here is one no test waits
# for, so "formed" always means "formed while that heartbeat was fresh".

_KNOCK_HB_TIMEOUT_MS = 30000
_KNOCK_SOON_S = _KNOCK_HB_TIMEOUT_MS / 1000 / 3


def _knock_lighthouse(**kwargs):
    return Lighthouse(min_replicas=1, join_timeout_ms=60000,
                      heartbeat_timeout_ms=_KNOCK_HB_TIMEOUT_MS, **kwargs)


def _hand_member(replica_id, address):
    return {"replica_id": replica_id, "address": address,
            "store_address": f"store:{replica_id}", "step": 1,
            "world_size": 1, "shrink_only": False}


def _first_quorum(pool, lh, n):
    """n real ManagerServers, all in the lighthouse's last quorum."""
    mgrs = [_make_manager(lh, f"r{i}") for i in range(n)]
    clients = [ManagerClient(m.address()) for m in mgrs]
    first = list(pool.map(
        lambda c: c.quorum(0, 1, "", False, 20.0), clients))
    assert [r.replica_world_size for r in first] == [n] * n
    return mgrs, clients


def _ask(pool, clients, step=2):
    return [pool.submit(c.quorum, 0, step, "", False, 20.0) for c in clients]


def _wait_status(addr, pred, timeout=_KNOCK_SOON_S):
    deadline = time.monotonic() + timeout
    while True:
        status = _status_json(addr)
        if pred(status) or time.monotonic() > deadline:
            return status
        time.sleep(0.02)


class _Listener:
    """A manager address that is no manager: ``accepts`` takes every
    connection and never answers; otherwise its backlog is full, so a
    connect gets no answer at all (what a silent host looks like)."""

    def __init__(self, accepts: bool) -> None:
        import socket

        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16 if accepts else 0)
        self.address = f"http://127.0.0.1:{self.sock.getsockname()[1]}"
        self.held = []
        if accepts:
            self.sock.settimeout(0.05)
            self.stop = threading.Event()
            self.thread = threading.Thread(target=self._accept, daemon=True)
            self.thread.start()
        else:
            for _ in range(6):  # more than a backlog of 0 ever admits
                c = socket.socket()
                c.setblocking(False)
                c.connect_ex(self.sock.getsockname())
                self.held.append(c)

    def _accept(self) -> None:
        while not self.stop.is_set():
            try:
                self.held.append(self.sock.accept()[0])
            except OSError:
                pass

    def close(self) -> None:
        if hasattr(self, "stop"):
            self.stop.set()
            self.thread.join()
        for c in self.held + [self.sock]:
            c.close()


def _knock_refused(pool, lh):
    # a previous member whose server was shut is expired, and the rest
    # get their quorum
    mgrs, clients = _first_quorum(pool, lh, 4)
    try:
        mgrs[3].shutdown()
        second = [f.result(timeout=20) for f in _ask(pool, clients[:3])]
        assert [r.replica_world_size for r in second] == [3] * 3
        status = _status_json(lh.address())
        # dead by the refusal (counted below), not by the heartbeat timeout
        assert status["heartbeats"]["r3"]["dead"] is True
        assert status["heartbeats"]["r0"]["dead"] is False
        job = status["jobs"]["default"]
        assert job["refused_expiries"] == 1 and job["door_knocks"] >= 1
        assert status["control"]["refused_expiries"] == 1
        assert status["control"]["door_knocks"] == job["door_knocks"]
        assert "[3 heartbeating]" in job["reason"]
        # an expired id that heartbeats again is healthy again
        lighthouse_heartbeat(lh.address(), "r3")
        status = _status_json(lh.address())
        assert status["heartbeats"]["r3"]["dead"] is False
        assert status["control"]["healthy_replicas"] == 4
    finally:
        for m in mgrs:
            m.shutdown()


def _knock_listening(pool, lh):
    # a previous member that listens but does not ask is waited for
    mgrs, clients = _first_quorum(pool, lh, 4)
    try:
        futs = _ask(pool, clients[:3])
        status = _wait_status(
            lh.address(), lambda s: s["control"]["door_knocks"] >= 3)
        assert status["control"]["door_knocks"] >= 3
        assert status["control"]["refused_expiries"] == 0
        assert status["heartbeats"]["r3"]["dead"] is False
        assert "stragglers" in status["reason"]
        assert not any(f.done() for f in futs)
        futs += _ask(pool, clients[3:])
        assert [f.result(timeout=20).replica_world_size for f in futs] == [4] * 4
    finally:
        for m in mgrs:
            m.shutdown()


def _knock_waits_for_a_lull(pool, lh):
    # every member that asks starts the two ticks again: four that ask
    # 0.3 s apart hold the quorum up for 0.9 s, more than two ticks of
    # 0.4 s, and no live manager gets a connection for it
    mgrs, clients = _first_quorum(pool, lh, 4)
    try:
        futs = []
        for client in clients:
            futs += _ask(pool, [client])
            time.sleep(0.3)
        assert [f.result(timeout=20).replica_world_size for f in futs] == [4] * 4
        assert _status_json(lh.address())["control"]["door_knocks"] == 0
    finally:
        for m in mgrs:
            m.shutdown()


def _knock_two_of_four(pool, lh):
    # two of four refused leaves two: the split-brain guard, which holds
    # two askers against four heartbeats, decides on the state after the
    # expiries as it would after the timeout
    mgrs, clients = _first_quorum(pool, lh, 4)
    try:
        mgrs[2].shutdown()
        mgrs[3].shutdown()
        second = [f.result(timeout=20) for f in _ask(pool, clients[:2])]
        assert [r.replica_world_size for r in second] == [2] * 2
        # by the refusals, not by the heartbeat timeout
        assert _status_json(lh.address())["control"]["refused_expiries"] == 2
    finally:
        for m in mgrs:
            m.shutdown()


def _knock_heartbeat_only(pool, lh):
    # a replica known by heartbeat only is never knocked: once the shut
    # member is expired, one asker is still held by one heartbeat and
    # nobody's door is left to knock on
    mgrs, clients = _first_quorum(pool, lh, 2)
    try:
        lighthouse_heartbeat(lh.address(), "beats_only")
        mgrs[1].shutdown()
        (fut,) = _ask(pool, clients[:1])
        status = _wait_status(
            lh.address(), lambda s: s["control"]["refused_expiries"] == 1)
        assert status["control"]["refused_expiries"] == 1
        knocks = status["control"]["door_knocks"]
        time.sleep(0.6)  # six ticks of the same hold
        status = _status_json(lh.address())
        assert status["control"]["door_knocks"] == knocks
        assert status["heartbeats"]["beats_only"]["dead"] is False
        assert "need at least half of 2" in status["reason"]
        assert not fut.done()
        # the heartbeat turns out to be a replica: it asks, both get one
        late = pool.submit(lighthouse_quorum, lh.address(),
                           _hand_member("beats_only", "addr"), 20.0)
        assert fut.result(timeout=20).replica_world_size == 2
        late.result(timeout=20)
    finally:
        for m in mgrs:
            m.shutdown()


def _knock_leaves_alone(address, knocked):
    def scenario(pool, lh):
        # a hand-made member with this address is in the last quorum and
        # then does not ask: nothing expires
        addr = lh.address()
        mgr = _make_manager(lh, "r0")
        client = ManagerClient(mgr.address())
        listener = address if isinstance(address, str) else address()
        try:
            absent = _hand_member(
                "absent", getattr(listener, "address", listener))
            lighthouse_heartbeat(addr, "absent")
            first = [pool.submit(client.quorum, 0, 1, "", False, 20.0),
                     pool.submit(lighthouse_quorum, addr, absent, 20.0)]
            assert first[0].result(timeout=20).replica_world_size == 2
            first[1].result(timeout=20)
            (fut,) = _ask(pool, [client])
            time.sleep(0.8)  # the hold is two ticks old after 0.2 s
            status = _status_json(addr)
            assert (status["control"]["door_knocks"] > 0) is knocked
            assert status["control"]["refused_expiries"] == 0
            assert status["heartbeats"]["absent"]["dead"] is False
            assert not fut.done()
            late = pool.submit(lighthouse_quorum, addr, absent, 20.0)
            assert fut.result(timeout=20).replica_world_size == 2
            late.result(timeout=20)
        finally:
            mgr.shutdown()
            if not isinstance(listener, str):
                listener.close()
    return scenario


def _knock_holds_no_lock(pool, lh):
    # the tick thread holds no lock while it connects: one absentee
    # accepts and never answers, the other answers nothing at all, so
    # every tick's knock lasts the whole tick, and no heartbeat waits
    addr = lh.address()
    mgr = _make_manager(lh, "r0")
    client = ManagerClient(mgr.address())
    listeners = [_Listener(accepts=True), _Listener(accepts=False)]
    try:
        absent = [_hand_member(f"absent{i}", l.address)
                  for i, l in enumerate(listeners)]
        for m in absent:
            lighthouse_heartbeat(addr, m["replica_id"])
        first = [pool.submit(client.quorum, 0, 1, "", False, 20.0)] + [
            pool.submit(lighthouse_quorum, addr, m, 20.0) for m in absent]
        assert first[0].result(timeout=20).replica_world_size == 3
        # known before the hold: a first sighting would start its count anew
        lighthouse_heartbeat(addr, "bystander")
        (fut,) = _ask(pool, [client])
        # a heartbeat is ANSWERED WHILE THE TICK THREAD KNOCKS, three
        # times: the accepting listener sees a round of knocks begin (its
        # connection comes), the bystander's heartbeat goes out and comes
        # back, and the lighthouse has still not counted that round (it
        # counts both absentees when the round ends, a whole tick later,
        # under the lock). Under a lock held through the round the
        # heartbeat would come back after the count. The deadline is for
        # that, not a bound on a heartbeat's time.
        rounds, answered_inside = listeners[0].held, 0
        deadline = time.monotonic() + 20.0
        while answered_inside < 3:
            before = len(rounds)
            while len(rounds) == before:
                assert time.monotonic() < deadline, answered_inside
                time.sleep(0.005)
            lighthouse_heartbeat(addr, "bystander")
            counted = _status_json(addr)["control"]["door_knocks"]
            answered_inside += counted < 2 * len(rounds)
        status = _wait_status(
            addr, lambda s: s["control"]["door_knocks"] >= 6)
        assert status["control"]["door_knocks"] >= 6, status["control"]
        assert status["control"]["refused_expiries"] == 0
        assert not fut.done()
        late = [pool.submit(lighthouse_quorum, addr, m, 20.0) for m in absent]
        assert fut.result(timeout=20).replica_world_size == 3
        for f in late:
            f.result(timeout=20)
    finally:
        mgr.shutdown()
        for l in listeners:
            l.close()


def _knock_kernels_agree(pool, lh):
    # quorum_compute and the incremental evaluator give byte-equal
    # decisions before and after an early expiry, and the guards decide
    # on the state after it
    import json

    from torchft_tpu.control import IncrementalQuorum, quorum_compute_raw

    opts = {"min_replicas": 1, "join_timeout_ms": 60000,
            "heartbeat_timeout_ms": _KNOCK_HB_TIMEOUT_MS}
    for incremental in (True, False):
        iq = IncrementalQuorum(opts, incremental=incremental)
        now = 100_000
        ids = [f"r{i}" for i in range(4)]
        for rid in ids:
            iq.heartbeat(rid, now)
            iq.join(now, _hand_member(rid, f"addr_{rid}"))
        assert iq.install(now)["installed"]
        reasons = []

        def agree():
            decision = iq.decision(now)
            assert decision == quorum_compute_raw(now, iq.state(), opts)
            reasons.append(json.loads(decision))

        for rid in ids[:2]:
            iq.heartbeat(rid, now)
            iq.join(now, _hand_member(rid, f"addr_{rid}"))
        agree()
        assert "need at least half of 4" in reasons[-1]["reason"]
        epoch = iq.counters()["epoch"]
        assert iq.expire("r3", now + 10) is True
        assert iq.expire("r3", now + 10) is False  # it is dead already
        assert iq.expire("never_seen", now + 10) is False
        assert iq.counters()["epoch"] == epoch + 1
        now += 10
        agree()
        assert "waiting for 1 healthy" in reasons[-1]["reason"]
        assert iq.expire("r2", now)
        agree()
        assert [m["replica_id"] for m in reasons[-1]["quorum"]] == ids[:2]
        assert iq.counters()["healthy"] == 2
        # the heartbeat entry is aged to the timeout, no further
        assert json.loads(iq.state())["heartbeats"]["r3"] == (
            now - _KNOCK_HB_TIMEOUT_MS)
        iq.heartbeat("r3", now + 1)  # dead -> alive, as after any expiry
        now += 1
        agree()
        assert iq.counters()["healthy"] == 3
        assert reasons[-1]["quorum"] is None


_DOOR_KNOCK_CASES = {
    "refused_is_expired_and_a_heartbeat_revives": _knock_refused,
    "listening_and_silent_is_waited_for": _knock_listening,
    "two_of_four_refused_leaves_two": _knock_two_of_four,
    "members_asking_in_turn_restart_the_two_ticks": _knock_waits_for_a_lull,
    "known_by_heartbeat_only_is_never_knocked": _knock_heartbeat_only,
    "address_that_does_not_parse": _knock_leaves_alone("addr_0", False),
    "address_that_does_not_resolve": _knock_leaves_alone(
        "http://no-such-manager.invalid:29500", False),
    "address_that_times_out": _knock_leaves_alone(
        lambda: _Listener(accepts=False), True),
    "tick_thread_connects_off_the_lock": _knock_holds_no_lock,
    "both_kernels_agree_around_an_early_expiry": _knock_kernels_agree,
}


@pytest.mark.parametrize("case", sorted(_DOOR_KNOCK_CASES))
def test_door_knock(case) -> None:
    slow = "off_the_lock" in case or "in_turn" in case
    tick = {"quorum_tick_ms": 400} if slow else {}
    lh = _knock_lighthouse(**tick)
    try:
        with ThreadPoolExecutor(max_workers=8) as pool:
            _DOOR_KNOCK_CASES[case](pool, lh)
    finally:
        lh.shutdown()
