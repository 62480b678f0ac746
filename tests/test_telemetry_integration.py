"""Flight-recorder integration: the full kill→heal lifecycle must be
reconstructible from the /telemetry/events HTTP endpoints ALONE.

Two replica groups run over a live native lighthouse with real TCP comm
and real HTTP checkpoints; replica 0 is killed mid-run and restarts. The
assertion reads ONLY the per-manager telemetry endpoints (discovered the
way scripts/fleet_top.py discovers them — via the group store's
checkpoint_addr_{rank} key) and reconstructs, in order:

    quorum epoch N (both on the wire) → member_dead → quorum epoch > N
    → heal_start/heal_done on the rejoiner → step_commit resumes

No log scraping, no reaching into Manager internals for event data.
"""

import json
import logging
import time
import urllib.request
from typing import List, Optional

import numpy as np
# the harness that ends a run on the restart's first full-width commit,
# and the pool that runs the replicas to it behind a deadline
from test_sharded_e2e import _Harness, _run_to_the_end

from torchft_tpu.comm.store import StoreClient, StoreServer
from torchft_tpu.comm.transport import TcpCommContext
from torchft_tpu.control import Lighthouse
from torchft_tpu.manager import Manager
from torchft_tpu.utils.events import to_chrome_trace, validate_chrome_trace

logger = logging.getLogger(__name__)


class InjectedFailure(Exception):
    pass


def _fetch(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.load(resp)


class _Replica:
    """One replica group; restarts after the injected kill. Each
    incarnation's telemetry (events + metrics) is captured OVER HTTP in
    the finally block, before the manager dies with the incarnation."""

    def __init__(self, replica_id: int, lighthouse_addr: str,
                 harness: _Harness,
                 fail_at_step: Optional[int] = None) -> None:
        self.replica_id = replica_id
        self.lighthouse_addr = lighthouse_addr
        self.harness = harness
        self.fail_at_step = fail_at_step
        self.failures = 0
        # one entry per incarnation: {"events": ..., "metrics": ...}
        self.telemetry: List[dict] = []

    def run(self) -> None:
        while not self.harness.stop.is_set():
            try:
                self._main()
                return
            except InjectedFailure:
                logger.warning("replica %s restarting after injected kill",
                               self.replica_id)
                continue

    def _main(self) -> None:
        store = StoreServer()
        state = {"w": np.zeros((2, 3), dtype=np.float32)}

        def load_state_dict(sd):
            state["w"] = np.array(sd["w"], dtype=np.float32)

        manager = Manager(
            comm=TcpCommContext(timeout=5.0),
            load_state_dict=load_state_dict,
            state_dict=lambda: {"w": state["w"]},
            min_replica_size=1,
            use_async_quorum=True,
            timeout=5.0, quorum_timeout=5.0, connect_timeout=5.0,
            rank=0, world_size=1,
            store_addr=store.addr,
            lighthouse_addr=self.lighthouse_addr,
            replica_id=f"telemetry_rep_{self.replica_id}_",
            heartbeat_interval=0.05,
        )
        # Endpoint discovery exactly as fleet_top does it: the group
        # store advertises each rank's checkpoint/telemetry server.
        telemetry_url = (
            StoreClient(store.addr, connect_timeout=5.0)
            .get("checkpoint_addr_0").decode()
        )
        # the flight recording, read the way a poller reads it: by the
        # cursor, every 256 commits (a step leaves fewer than 8 events
        # and the ring holds 4096), so that however many steps a
        # survivor takes alone before the restart is back, the kill the
        # assertions read has not been overwritten
        recording: List[dict] = []
        cursor = polled_at = 0

        def poll() -> dict:
            nonlocal cursor, polled_at
            polled_at = manager.current_step()
            page = _fetch(telemetry_url + f"/telemetry/events?since={cursor}")
            assert page["dropped"] == 0, page["dropped"]
            recording.extend(page["events"])
            cursor = page["next"]
            return page

        try:
            while not self.harness.stop.is_set():
                if manager.current_step() >= polled_at + 256:
                    poll()
                if (
                    self.fail_at_step is not None
                    and self.failures == 0
                    and manager.current_step() >= self.fail_at_step
                    # ...and has itself committed a step with the
                    # survivor: a victim that starts first on a loaded
                    # machine reaches the step alone, and the survivor
                    # never had it on its wire
                    and self.harness.at_full_width(self.replica_id)
                ):
                    self.failures += 1
                    raise InjectedFailure(
                        f"injected kill of replica {self.replica_id}"
                    )
                try:
                    manager.start_quorum()
                except (TimeoutError, RuntimeError) as e:
                    logger.info("quorum retry: %s", e)
                    continue
                grad = state["w"] - 10.0
                fut = manager.allreduce_arrays([grad]).future()
                avg_grad = fut.result(timeout=20)[0]
                if manager.should_commit():
                    state["w"] = state["w"] - 0.5 * avg_grad
                    self.harness.report(
                        self.replica_id, manager.current_step(),
                        restarted=self.failures > 0,
                        participants=manager.num_participants(),
                    )
                else:
                    time.sleep(0.01)
        finally:
            # Capture this incarnation's flight recording over HTTP
            # while the server is still up — the endpoints are the only
            # data source the assertions use.
            try:
                events = dict(poll(), events=recording)
                metrics = _fetch(telemetry_url + "/telemetry/metrics")
                # incremental-cursor contract on a live manager
                assert poll()["events"] == [], "cursor returned stale events"
                self.telemetry.append(
                    {"events": events, "metrics": metrics}
                )
            except Exception as e:  # noqa: BLE001 — a capture failure
                # must surface as a test failure, not a hang
                self.telemetry.append({"capture_error": repr(e)})
            manager.shutdown(wait=False)
            store.shutdown()


def _events_of(dump: dict) -> List[dict]:
    assert "capture_error" not in dump, dump
    return sorted(dump["events"]["events"], key=lambda e: e["seq"])


def test_kill_heal_lifecycle_reconstructed_from_endpoints() -> None:
    lighthouse = Lighthouse(
        min_replicas=1, join_timeout_ms=200, heartbeat_timeout_ms=1000
    )
    harness = _Harness(num_replicas=2, total_steps=8)
    replicas = [
        _Replica(0, lighthouse.address(), harness, fail_at_step=2),
        _Replica(1, lighthouse.address(), harness),
    ]
    _run_to_the_end(replicas, harness, lighthouse)

    assert replicas[0].failures == 1
    # survivor: one incarnation; killed replica: two
    assert len(replicas[1].telemetry) == 1
    assert len(replicas[0].telemetry) == 2

    surv = _events_of(replicas[1].telemetry[0])
    dead_id = json.loads(json.dumps(
        replicas[0].telemetry[0]
    ))["events"].get("replica_id", "")
    assert dead_id.startswith("telemetry_rep_0_")

    # --- survivor's ring: epoch N with both on the wire, then
    # member_dead for the killed replica, then a LATER epoch ---------------
    two_wire = [e for e in surv
                if e["kind"] == "quorum_complete" and e["wire_world"] == 2]
    assert two_wire, "survivor never saw a 2-member wire"
    # found BY MEMBER: where the lighthouse's knock expires the restarted
    # incarnation first (it refuses connections while it binds), the
    # survivor's first member_dead names that one
    md = [e for e in surv
          if e["kind"] == "member_dead" and e["member"] == dead_id]
    assert md, (
        "no member_dead for the killed incarnation on the survivor",
        dead_id,
        [{k: e.get(k) for k in ("kind", "seq", "epoch", "step", "member",
                                "wire_world", "participants", "heal")}
         for e in surv if e["kind"] in ("quorum_complete", "member_dead")],
    )
    death = md[0]
    epoch_n = [e for e in two_wire if e["seq"] < death["seq"]]
    assert epoch_n, "member_dead not preceded by a 2-member quorum"
    assert death["epoch"] > epoch_n[-1]["epoch"]
    shrunk = [
        e for e in surv
        if e["kind"] == "quorum_complete" and e["seq"] > death["seq"]
    ]
    assert shrunk and shrunk[0]["epoch"] >= death["epoch"]
    # the survivor kept committing after the death
    assert any(
        e["kind"] == "step_commit" and e["seq"] > death["seq"]
        for e in surv
    )
    # ...and eventually saw the rejoiner back on a 2-member wire
    assert any(e["wire_world"] == 2 for e in shrunk), (
        "rejoined replica never re-entered the survivor's wire"
    )

    # --- rejoiner's ring: heal_start → heal_done → commits resume ---------
    healer = _events_of(replicas[0].telemetry[1])
    hs = [e for e in healer if e["kind"] == "heal_start"]
    hd = [e for e in healer if e["kind"] == "heal_done"]
    assert hs and hd, "rejoiner recorded no heal lifecycle"
    assert hs[0]["seq"] < hd[0]["seq"]
    assert hs[0]["epoch"] >= death["epoch"]
    resumed = [e for e in healer
               if e["kind"] == "step_commit" and e["seq"] > hd[0]["seq"]]
    assert resumed, "no step_commit after heal_done on the rejoiner"
    # the heal fast-forwarded the rejoiner past its kill point
    assert max(e["step"] for e in resumed) > 2
    # events carry the identity stamps a merger needs
    for e in healer:
        assert e["replica_id"].startswith("telemetry_rep_0_")
        assert e["rank"] == 0

    # --- allreduce p50 is served and sane with the recorder enabled ------
    m = replicas[1].telemetry[0]["metrics"]["metrics"]
    # every commit the recording holds is counted (the survivor's step
    # is past 8, the harness's stop, but a survivor that started second
    # healed to its first steps and did not commit them)
    commits = [e for e in surv if e["kind"] == "step_commit"]
    assert m.get("steps_committed", 0) >= len(commits) > 0
    p50 = m.get("allreduce_p50_ms")
    assert p50 is not None and p50 >= 0

    # --- the merged dumps convert to one valid Chrome trace ---------------
    dumps = [replicas[1].telemetry[0]["events"],
             replicas[0].telemetry[0]["events"],
             replicas[0].telemetry[1]["events"]]
    trace = json.loads(json.dumps(to_chrome_trace(dumps)))
    assert validate_chrome_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"quorum", "heal", "step_commit", "member_dead"} <= names
    # distinct tracks for the two replicas (the restarted incarnation
    # keeps its replica_id prefix but gets a fresh uuid → its own track)
    pids = {e["pid"] for e in trace["traceEvents"] if e["ph"] != "M"}
    assert len(pids) == 3
