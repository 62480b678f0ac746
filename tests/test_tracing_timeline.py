"""The program's own timeline (ISSUE 23): one span primitive that feeds
the Metrics sink AND the profiler's host plane, recovery episodes split
into phases by the Manager, and stable step/kernel names on the device
timeline."""

import dataclasses
import glob
import os
import threading
import time
from typing import Any, Dict, List

import numpy as np
import pytest

from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.comm.store import StoreServer
from torchft_tpu.comm.transport import TcpCommContext
from torchft_tpu.control import Lighthouse
from torchft_tpu.manager import Manager
from torchft_tpu.utils.metrics import Metrics
from torchft_tpu.utils.profiling import (
    SPAN_PREFIX,
    StepProgram,
    scope_tables,
    span,
    throughput_span,
)

# ------------------------------------------------------------------ spans


def test_span_observes_into_the_sink() -> None:
    m = Metrics()
    with span(m, "quorum_wait", step=3) as timed:
        time.sleep(0.01)
    snap = m.snapshot()
    assert snap["quorum_wait_max_ms"] >= 10.0
    assert timed.elapsed * 1e3 == pytest.approx(snap["quorum_wait_max_ms"])
    with pytest.raises(ValueError):
        with span(m, "quorum_wait"):
            raise ValueError("observed all the same")
    assert len(m._timings["quorum_wait"]) == 2
    with span(None, "no_sink"):  # annotation only
        pass


def test_span_leaves_a_tft_event_with_replica_and_step(tmp_path) -> None:
    import jax
    from jax.profiler import ProfileData

    m = Metrics()
    m.label("replica_id", "bm_2_0_abc")
    with span(m, "outside"):  # no trace: a no-op annotation, still timed
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span(m, "configure", step=41, bucket=7):
            time.sleep(0.002)
        with throughput_span(m, "heal_wire", 1000, step=41):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    found: Dict[str, Dict[str, Any]] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    found[e.name] = dict(e.stats)
                    assert e.duration_ns >= 2e6
    assert set(found) == {"tft.configure", "tft.heal_wire"}
    assert found["tft.configure"] == {
        "replica": "bm_2_0_abc", "step": 41, "bucket": 7,
    }
    assert found["tft.heal_wire"]["replica"] == "bm_2_0_abc"
    assert m.snapshot()["heal_wire_bytes"] == 1000
    assert {"outside_max_ms", "configure_max_ms"} <= set(m.snapshot())


# ------------------------------------------------------ recovery episodes

_HEARTBEAT_TIMEOUT_MS = 1000
# How a group dies, and the heartbeat timeout its test runs under.
# ``killed``: its process is gone and its host lives, so its manager
# address refuses a connection and the lighthouse's door-knock expires it
# at once; the timeout is one no test waits for. ``hung``: it stops
# stepping and beating while its manager address stays open, so the
# knock is answered and only the heartbeat timeout tells.
_DEATHS = {"killed": 30000, "hung": _HEARTBEAT_TIMEOUT_MS}


class _Replica:
    """One replica group's training loop on a thread: quorum, averaged
    "gradient", commit."""

    def __init__(self, name: str, value: float, lighthouse_addr: str,
                 will_hang: bool = False, leaves: int = 0) -> None:
        self.store = StoreServer()
        self.state = {"w": np.full((4,), value, np.float32)}
        transport = None
        if leaves:
            # a many-leaf device state beside the weights, healed over
            # the sharding-aware plane as the kill cell's is
            import jax.numpy as jnp

            self.state["p"] = [jnp.full((64, 32), value + i, jnp.float32)
                               for i in range(leaves)]
            transport = CheckpointServer(timeout=5.0, template_fn=lambda: {
                "user": dict(self.state),
                "torchft": {"step": 0, "batches_committed": 0},
            })
        # weights as committed, by the step they made: two free-running
        # loops are compared at a step both have, never mid-commit
        self.committed: Dict[int, np.ndarray] = {}
        self.stop = threading.Event()
        self.manager = Manager(
            comm=TcpCommContext(timeout=5.0),
            load_state_dict=lambda sd: self.state.update(
                sd, w=np.array(sd["w"], np.float32)),
            state_dict=lambda: dict(self.state),
            checkpoint_transport=transport,
            min_replica_size=1,
            timeout=5.0, quorum_timeout=20.0, connect_timeout=10.0,
            rank=0, world_size=1, store_addr=self.store.addr,
            lighthouse_addr=lighthouse_addr, replica_id=f"tl_{name}_",
            # one that will hang lives by its quorum requests alone, so
            # that its last step is its last sign of life
            heartbeat_interval=3600.0 if will_hang else 0.05,
        )
        self.held_open: Any = None
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self) -> None:
        m = self.manager
        while not self.stop.is_set():
            try:
                m.start_quorum()
                with m.blocked_on_wire():
                    g = m.allreduce_arrays(
                        [self.state["w"] * 0.1]
                    ).future().result(timeout=30)[0]
                time.sleep(0.01)  # the step's compute: lands in ``other``
                if m.should_commit():
                    self.state["w"] = self.state["w"] - g
                    self.committed[m.current_step()] = self.state["w"]
            except Exception:  # noqa: BLE001 — torn down under the loop
                if self.stop.is_set():
                    return
                time.sleep(0.05)

    def kill(self, hang: bool = False) -> None:
        self.stop.set()
        if hang:
            self.held_open, self.manager._manager = self.manager._manager, None
        elif self.held_open is not None:
            self.held_open.shutdown()
            self.held_open = None
        self.manager.shutdown(wait=False)
        self.store.shutdown()

    def episodes(self) -> List[Dict[str, Any]]:
        return [e for e in self.manager.events.since(0)[0]
                if e["kind"] == "recovery_episode"]

    def run_to(self, step: int, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        while self.manager.current_step() < step:
            assert time.monotonic() < deadline, "the loop stopped committing"
            time.sleep(0.01)


def _equal_at_last_common_step(a: _Replica, b: _Replica) -> bool:
    step = max(set(a.committed) & set(b.committed))
    return np.array_equal(a.committed[step], b.committed[step])


@pytest.fixture(scope="module", params=sorted(_DEATHS))
def kill_and_rejoin(request):
    """Two groups; one is torn down, the survivor runs on alone, a
    replacement with other weights joins, then 20 steady steps."""
    death = request.param
    lh = Lighthouse(min_replicas=1, join_timeout_ms=200,
                    heartbeat_timeout_ms=_DEATHS[death])
    live: List[_Replica] = []
    try:
        survivor = _Replica("a", 1.0, lh.address())
        victim = _Replica("b", 1.0, lh.address(), will_hang=death == "hung")
        live += [survivor, victim]
        survivor.run_to(8)
        victim.run_to(8)
        n_before_kill = len(survivor.episodes())
        victim.kill(hang=death == "hung")
        survivor.run_to(survivor.manager.current_step() + 8)
        n_alone = len(survivor.episodes())
        replacement = _Replica("c", 99.0, lh.address())
        live.append(replacement)
        replacement.run_to(survivor.manager.current_step() + 5)
        survivor.run_to(replacement.manager.current_step())
        n_rejoined = len(survivor.episodes())
        snapshot = survivor.manager.metrics.snapshot()
        survivor.run_to(survivor.manager.current_step() + 20)
        yield {
            "death": death,
            "after_kill": survivor.episodes()[n_before_kill:n_alone],
            "after_rejoin": survivor.episodes()[n_alone:n_rejoined],
            "steady": survivor.episodes()[n_rejoined:],
            "replacement": replacement.episodes(),
            "everything": survivor.episodes() + replacement.episodes(),
            "snapshot": snapshot,
            "equal": _equal_at_last_common_step(survivor, replacement),
        }
    finally:
        for r in live:
            r.kill()
        lh.shutdown()


_PARTITION = ("quorum_wait_ms", "wire_wait_ms", "heal_ms", "barrier_ms",
              "init_ms", "first_step_ms", "other_ms")


def test_survivor_emits_exactly_one_shrink_episode(kill_and_rejoin) -> None:
    (episode,) = kill_and_rejoin["after_kill"]
    assert episode["episode"] == "shrink"
    assert (episode["members_before"], episode["members_after"]) == (2, 1)
    assert (episode["left"], episode["joined"]) == (1, 0)
    if kill_and_rejoin["death"] == "hung":
        # the survivor could not form a quorum before the dead group's
        # heartbeat had expired (its last beat is at most one interval old)
        assert episode["quorum_wait_ms"] >= _HEARTBEAT_TIMEOUT_MS - 100
        assert episode["quorum_wait_ms"] > 0.8 * episode["gap_ms"]
    else:
        # the lighthouse knocked once the survivor had waited two ticks,
        # was refused, and dropped the dead group while its heartbeat
        # was fresh
        assert 190 <= episode["quorum_wait_ms"] < _DEATHS["killed"] / 3
    assert episode["configure_ms"] <= episode["quorum_wait_ms"]


def test_every_episodes_phases_partition_its_gap(kill_and_rejoin) -> None:
    episodes = kill_and_rejoin["everything"]
    assert len(episodes) >= 4
    for e in episodes:
        parts = [e[k] for k in _PARTITION if k in e]
        assert sum(parts) == pytest.approx(e["gap_ms"], rel=0.02)
        # nothing is counted twice: the remainder is never negative
        assert all(p >= -0.5 for p in parts), e
        assert e["other_ms"] >= 5.0  # the 10 ms of "compute" each step


def test_replacement_emits_a_rejoin_episode_with_a_heal(
        kill_and_rejoin) -> None:
    (episode,) = kill_and_rejoin["replacement"]
    assert episode["episode"] == "rejoin"
    assert episode["heal_ms"] > 0 and episode["init_ms"] > 0
    assert episode["first_step_ms"] >= 0
    assert kill_and_rejoin["equal"]  # 99.0 became the survivor's weights


def test_survivor_sees_the_rejoin_as_a_grow_episode(kill_and_rejoin) -> None:
    (episode,) = kill_and_rejoin["after_rejoin"]
    assert episode["episode"] == "grow"
    assert (episode["left"], episode["joined"]) == (0, 1)
    # it waited on the wire while the joiner healed
    assert episode["wire_wait_ms"] > 0


def test_steady_steps_emit_no_episode(kill_and_rejoin) -> None:
    assert kill_and_rejoin["steady"] == []


def test_episode_phases_are_timings_of_the_managers_sink(
        kill_and_rejoin) -> None:
    snap = kill_and_rejoin["snapshot"]
    (shrink,) = kill_and_rejoin["after_kill"]
    for phase in ("gap", "quorum_wait", "wire_wait", "heal", "barrier",
                  "other", "configure"):
        assert snap[f"episode_shrink_{phase}_max_ms"] == pytest.approx(
            shrink[f"{phase}_ms"], abs=1e-3)
    assert "episode_grow_gap_max_ms" in snap
    assert "episode_rejoin_init_max_ms" in snap      # its own start
    if kill_and_rejoin["death"] == "hung":
        assert snap["quorum_wait_max_ms"] >= _HEARTBEAT_TIMEOUT_MS - 100
    else:
        assert snap["quorum_wait_max_ms"] < _DEATHS["killed"] / 3
    assert snap["replica_id"].startswith("tl_a_")


def test_failed_wire_is_inside_wire_wait_in_every_episode(
        kill_and_rejoin) -> None:
    """As ``configure`` is inside ``quorum_wait``: no phase of the gap's
    partition, never more than ``wire_wait``, nothing where no step was
    discarded, and a timing of the sink under the episode's kind."""
    for e in kill_and_rejoin["everything"]:
        assert 0.0 <= e["failed_wire_ms"] <= e["wire_wait_ms"] + 1e-3, e
        assert (e["failed_wire_ms"] > 0.0) <= (e["discards"] > 0), e
    (shrink,) = kill_and_rejoin["after_kill"]
    snap = kill_and_rejoin["snapshot"]
    assert snap["episode_shrink_failed_wire_max_ms"] == pytest.approx(
        shrink["failed_wire_ms"], abs=1e-3)
    assert "failed_wire_ms" not in _PARTITION  # the gap's tiling is as it was


def test_failed_wire_is_the_discarded_steps_wire_apart_from_the_next_steps(
) -> None:
    """A step waits on the wire and is discarded (a peer died under it);
    the next one, narrower, waits again and commits. The shrink episode's
    ``wire_wait`` holds both waits, its ``failed_wire`` the first alone."""
    lh = Lighthouse(min_replicas=1, join_timeout_ms=200)
    store = StoreServer()
    try:
        manager = Manager(
            comm=TcpCommContext(timeout=5.0),
            load_state_dict=lambda sd: None, state_dict=dict,
            min_replica_size=1, rank=0, world_size=1,
            store_addr=store.addr, lighthouse_addr=lh.address(),
            replica_id="tl_failed_wire_",
        )
        try:
            for _ in range(2):      # past its first commit: no rejoin
                manager.start_quorum()
                manager.wait_quorum()
                assert manager.should_commit()
            manager.metrics.reset_timings()
            manager._interval.members += ("gone",)  # it will have left
            manager.start_quorum()
            with manager.blocked_on_wire():
                time.sleep(0.03)    # on the dead peer's sockets
            manager.report_error(ConnectionError("peer died"))
            assert not manager.should_commit()
            manager.start_quorum()
            with manager.blocked_on_wire():
                time.sleep(0.01)    # the first narrow step's own wire
            assert manager.should_commit()
            (episode,) = [e for e in manager.events.since(0)[0]
                          if e["kind"] == "recovery_episode"
                          and e["episode"] == "shrink"]
            snap = manager.metrics.snapshot()
        finally:
            manager.shutdown(wait=False)
    finally:
        store.shutdown()
        lh.shutdown()
    assert episode["discards"] == 1
    assert 30.0 <= episode["failed_wire_ms"] <= episode["wire_wait_ms"] - 10.0
    assert snap["episode_shrink_failed_wire_max_ms"] == pytest.approx(
        episode["failed_wire_ms"], abs=1e-3)
    assert snap["episode_shrink_failed_wire_max_ms"] <= (
        snap["episode_shrink_wire_wait_max_ms"])
    parts = [episode[k] for k in _PARTITION if k in episode]
    assert sum(parts) == pytest.approx(episode["gap_ms"], rel=0.02)


def test_three_survivors_commit_on_while_a_killed_groups_heartbeat_is_fresh(
) -> None:
    """Four groups under the kill cell's lighthouse settings but for a
    heartbeat timeout no test waits for; one is torn down as
    ``benchmark.group.ReplicaGroup.teardown`` does it. The three others
    ask, are held as three of four heartbeats, the lighthouse knocks on
    the fourth's manager address and is refused, and they step on."""
    import json
    import urllib.request

    lh = Lighthouse(min_replicas=1, join_timeout_ms=60000,
                    heartbeat_timeout_ms=_DEATHS["killed"])
    groups: List[_Replica] = []
    try:
        for name in "abcd":
            groups.append(_Replica(name, 1.0, lh.address()))
        for g in groups:
            g.run_to(8)
        survivors, victim = groups[:3], groups[3]
        seen = [len(g.episodes()) for g in survivors]
        t_kill = time.monotonic()
        victim.kill()
        for g in survivors:
            g.run_to(g.manager.current_step() + 3)
        assert time.monotonic() - t_kill < _DEATHS["killed"] / 1e3 / 3
        for g, n in zip(survivors, seen):
            (episode,) = g.episodes()[n:]
            assert episode["episode"] == "shrink"
            assert (episode["members_before"],
                    episode["members_after"]) == (4, 3)
            assert episode["quorum_wait_ms"] < _DEATHS["killed"] / 3
        with urllib.request.urlopen(lh.address() + "/status.json") as resp:
            control = json.load(resp)["control"]
        assert control["refused_expiries"] == 1
        assert control["door_knocks"] >= 1
    finally:
        for g in groups:
            g.kill()
        lh.shutdown()


@pytest.fixture(scope="module")
def merged_quorum():
    """Two groups; one hangs and its replacement is started at
    once, so it asks for a quorum while the dead group's heartbeat still
    counts: ONE quorum drops ``b`` and admits ``c``. (A group that is
    killed on a live host is dropped within two ticks of the survivor's
    asking, before any replacement can have started.)"""
    # the dead group's heartbeat outlives the replacement's start-up on
    # any machine; nothing below waits for it to expire
    lh = Lighthouse(min_replicas=1, join_timeout_ms=200,
                    heartbeat_timeout_ms=3 * _HEARTBEAT_TIMEOUT_MS)
    live: List[_Replica] = []
    try:
        survivor = _Replica("a", 1.0, lh.address())
        victim = _Replica("b", 1.0, lh.address(), will_hang=True)
        live += [survivor, victim]
        survivor.run_to(8)
        victim.run_to(8)
        n_before_kill = len(survivor.episodes())
        # the sink then holds this recovery alone, as a window's does
        survivor.manager.metrics.reset_timings()
        victim.kill(hang=True)
        replacement = _Replica("c", 99.0, lh.address())
        live.append(replacement)
        replacement.run_to(survivor.manager.current_step() + 5)
        survivor.run_to(replacement.manager.current_step())
        after_kill = survivor.episodes()[n_before_kill:]
        if not after_kill or after_kill[0]["joined"] == 0:
            pytest.fail(f"the quorum did not merge: {after_kill}")
        yield {
            "after_kill": after_kill,
            "replacement": replacement.episodes(),
            "snapshot": survivor.manager.metrics.snapshot(),
            "equal": _equal_at_last_common_step(survivor, replacement),
        }
    finally:
        for r in live:
            r.kill()
        lh.shutdown()


_TIMED = ("gap", "quorum_wait", "wire_wait", "heal", "barrier", "other",
          "configure", "failed_wire")
_INSIDE_ANOTHER = ("configure", "failed_wire")


def test_one_quorum_that_drops_and_admits_is_one_episode_of_both_kinds(
        merged_quorum) -> None:
    (episode,) = merged_quorum["after_kill"]
    assert episode["episode"] == "shrink+grow"
    assert (episode["members_before"], episode["members_after"]) == (2, 2)
    assert (episode["left"], episode["joined"]) == (1, 1)
    # it waited on the wire while the joiner healed
    assert episode["wire_wait_ms"] > 0


def test_merged_episode_is_observed_under_shrink_and_under_grow(
        merged_quorum) -> None:
    snap = merged_quorum["snapshot"]
    (episode,) = merged_quorum["after_kill"]
    assert snap["episode_shrink_gap_max_ms"] == snap["episode_grow_gap_max_ms"]
    assert snap["episode_grow_gap_max_ms"] == pytest.approx(
        episode["gap_ms"], abs=1e-3)
    assert not any(k.startswith("episode_error_") for k in snap)


@pytest.mark.parametrize("phase", _TIMED)
def test_merged_episodes_grow_phase_equals_its_shrink_phase(
        merged_quorum, phase) -> None:
    snap = merged_quorum["snapshot"]
    (episode,) = merged_quorum["after_kill"]
    assert snap[f"episode_grow_{phase}_max_ms"] == (
        snap[f"episode_shrink_{phase}_max_ms"])
    assert snap[f"episode_grow_{phase}_max_ms"] == pytest.approx(
        episode[f"{phase}_ms"], abs=1e-3)


def test_merged_episodes_phases_partition_its_gap(merged_quorum) -> None:
    (episode,) = merged_quorum["after_kill"]
    parts = [episode[k] for k in _PARTITION if k in episode]
    assert sum(parts) == pytest.approx(episode["gap_ms"], rel=0.02)
    assert all(p >= -0.5 for p in parts), episode
    snap = merged_quorum["snapshot"]
    for kind in ("shrink", "grow"):   # and so do each kind's timings
        timed = [snap[f"episode_{kind}_{phase}_max_ms"] for phase in _TIMED
                 if phase != "gap" and phase not in _INSIDE_ANOTHER]
        assert sum(timed) == pytest.approx(
            snap[f"episode_{kind}_gap_max_ms"], rel=0.02)


def test_merged_quorums_replacement_emits_a_rejoin_episode_with_a_heal(
        merged_quorum) -> None:
    (episode,) = merged_quorum["replacement"]
    assert episode["episode"] == "rejoin"
    assert episode["heal_ms"] > 0 and episode["init_ms"] > 0
    assert merged_quorum["equal"]  # 99.0 became the survivor's weights


@pytest.mark.parametrize("left,joined,kinds", [
    (1, 0, {"shrink"}),
    (0, 1, {"grow"}),
    (1, 1, {"shrink", "grow"}),
    (0, 0, {"error"}),
])
def test_an_episode_is_named_by_its_membership_edges(
        left, joined, kinds) -> None:
    lh = Lighthouse(min_replicas=1, join_timeout_ms=200)
    store = StoreServer()
    try:
        manager = Manager(
            comm=TcpCommContext(timeout=5.0),
            load_state_dict=lambda sd: None, state_dict=dict,
            min_replica_size=1, rank=0, world_size=1,
            store_addr=store.addr, lighthouse_addr=lh.address(),
            replica_id="tl_edges_",
        )
        try:
            iv = manager._interval
            iv.first, iv.dirty = False, True   # past its first commit
            iv.members = ("self",) + ("gone",) * left
            manager._wire_members = ("self",) + ("new",) * joined
            manager._close_interval()
            snap = manager.metrics.snapshot()
            (episode,) = [e for e in manager.events.since(0)[0]
                          if e["kind"] == "recovery_episode"]
        finally:
            manager.shutdown(wait=False)
    finally:
        store.shutdown()
        lh.shutdown()
    assert {k.split("_")[1] for k in snap
            if k.startswith("episode_") and k.endswith("_gap_max_ms")
            } == kinds
    assert set(episode["episode"].split("+")) == kinds
    assert (episode["left"], episode["joined"]) == (left, joined)
    assert not manager._interval.dirty    # and the next interval is open


# ------------------------------------------------- the heal on one clock

_JOINER_SPANS = {"heal_meta", "heal_fetch", "heal_wire", "heal_wire_wait",
                 "heal_wire_read", "heal_wire_crc", "heal_h2d", "heal_apply"}
_DONOR_SPANS = {"heal_stage", "heal_gate", "heal_serve"}
_SHUTDOWN_PARTS = ("shutdown_checkpoint", "shutdown_server",
                   "shutdown_executor", "shutdown_comm")
_LEAVES = 12


@pytest.fixture(scope="module")
def traced_heal(tmp_path_factory):
    """A group steps alone; under ``jax.profiler`` a second one with
    other weights joins, heals a many-leaf state from it and is then torn
    down. Every ``tft.*`` event of the trace, and both sinks."""
    import jax
    from jax.profiler import ProfileData

    lh = Lighthouse(min_replicas=1, join_timeout_ms=200)
    live: List[_Replica] = []
    trace_dir = str(tmp_path_factory.mktemp("heal_trace"))
    try:
        donor = _Replica("a", 1.0, lh.address(), leaves=_LEAVES)
        live.append(donor)
        donor.run_to(5)
        jax.profiler.start_trace(trace_dir)
        try:
            joiner = _Replica("c", 99.0, lh.address(), leaves=_LEAVES)
            live.append(joiner)
            joiner.run_to(donor.manager.current_step() + 3)
            sinks = {"joiner": joiner.manager.metrics.snapshot(),
                     "donor": donor.manager.metrics.snapshot()}
            joiner.kill()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        events = []
        planes = ProfileData.from_file(path).planes
        for i, plane in enumerate(planes):
            for j, line in enumerate(plane.lines):  # a line a thread
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        events.append(dict(
                            e.stats, name=e.name[len(SPAN_PREFIX):],
                            t0=e.start_ns, t1=e.start_ns + e.duration_ns,
                            line=(i, j)))
        yield {
            "events": events, **sinks,
            "ids": {"joiner": joiner.manager.replica_id(),
                    "donor": donor.manager.replica_id()},
            "equal": all(np.array_equal(a, b) for a, b in zip(
                joiner.state["p"], donor.state["p"])),
        }
    finally:
        for r in live:
            r.kill()
        lh.shutdown()


def _named(traced, *names):
    return [e for e in traced["events"] if e["name"] in names]


def test_every_heal_span_is_on_the_trace_under_its_sides_replica(
        traced_heal) -> None:
    assert traced_heal["equal"]
    heal = [e for e in traced_heal["events"] if e["name"].startswith("heal_")]
    assert {e["name"] for e in heal} == _JOINER_SPANS | _DONOR_SPANS
    for e in heal:
        side = "joiner" if e["name"] in _JOINER_SPANS else "donor"
        assert e.get("replica") == traced_heal["ids"][side], e
    # one heal: one address, one fetch, one apply, the state's step
    (meta,), (fetch,), (apply_,) = (
        _named(traced_heal, n) for n in ("heal_meta", "heal_fetch",
                                         "heal_apply"))
    assert meta["step"] == fetch["step"] == apply_["step"] >= 5
    assert fetch["workers"] == 2 and "src" in meta
    assert meta["t1"] <= fetch["t0"] <= fetch["t1"] <= apply_["t0"]


def test_a_heals_leaf_spans_carry_leaf_and_bytes_that_sum_to_the_heal(
        traced_heal) -> None:
    joiner = traced_heal["joiner"]
    wires = _named(traced_heal, "heal_wire")
    for e in _named(traced_heal, "heal_wire", "heal_h2d", "heal_stage",
                    "heal_serve"):
        assert "leaf" in e and e["bytes"] >= 0, e
    assert all("host" in e for e in wires)
    assert sum(e["bytes"] for e in wires) == joiner["heal_bytes"] == (
        _LEAVES * 64 * 32 * 4 + 4 * 4)
    # a fetch a leaf (w, the p leaves, torchft's two counters); the device
    # leaves go up a leaf at a time, and the donor staged and served them
    assert joiner["heal_leaves"] == len(wires) == _LEAVES + 3
    assert len(_named(traced_heal, "heal_h2d")) == _LEAVES
    for name in ("heal_stage", "heal_serve"):
        assert sum(e["bytes"] for e in _named(traced_heal, name)) == (
            joiner["heal_bytes"])
    # inside a worker's heal_wire: the wait for the response, the body,
    # the checksum, in that order and nowhere else
    fetch = _named(traced_heal, "heal_fetch")[0]
    for wire in wires:
        inside = sorted(
            (e for e in _named(traced_heal, "heal_wire_wait",
                               "heal_wire_read", "heal_wire_crc")
             if e["line"] == wire["line"]
             and wire["t0"] <= e["t0"] and e["t1"] <= wire["t1"]),
            key=lambda e: e["t0"])
        assert [e["name"] for e in inside] in (
            ["heal_wire_wait"],  # an object: pickled, no tensor body
            ["heal_wire_wait", "heal_wire_read", "heal_wire_crc"]), wire
        assert fetch["t0"] <= wire["t0"] and wire["t1"] <= fetch["t1"]


def test_the_heals_phases_tile_heal_wall_ms(traced_heal) -> None:
    """``heal_wall_ms`` (assignment → applied, the Manager's alone) is
    the address, the fetch, the wait for the step thread and the apply,
    to within 1 % or 20 ms."""
    s = traced_heal["joiner"]
    tiles = (s["heal_meta_max_ms"] + s["heal_fetch_ms"]
             + s["heal_apply_wait_ms"] + s["heal_apply_max_ms"])
    assert s["heal_wall_ms"] >= tiles - 1e-6
    assert s["heal_wall_ms"] - tiles <= max(0.01 * s["heal_wall_ms"], 20.0)
    assert s["heal_fetch_ms"] <= s["heal_fetch_max_ms"]  # inside its span
    assert 0.0 <= s["heal_donor_wait_share"] <= 1.0
    assert not any(k.startswith("heal_wall") for k in traced_heal["donor"])
    assert traced_heal["donor"]["heal_serve_crc_s"] > 0.0


def test_a_shutdown_holds_its_four_parts_in_order(traced_heal) -> None:
    mine = [e for e in traced_heal["events"]
            if e.get("replica") == traced_heal["ids"]["joiner"]]
    (whole,) = [e for e in mine if e["name"] == "shutdown"]
    parts = sorted((e for e in mine if e["name"] in _SHUTDOWN_PARTS),
                   key=lambda e: e["t0"])
    assert tuple(e["name"] for e in parts) == _SHUTDOWN_PARTS
    assert all(e["step"] == whole["step"] for e in parts)
    edges = [whole["t0"]] + [t for e in parts for t in (e["t0"], e["t1"])] + [
        whole["t1"]]
    assert edges == sorted(edges)


# ------------------------------------------- names on the device timeline


def _tiny():
    import jax
    import optax

    from torchft_tpu.models import CONFIGS, init_params

    cfg = dataclasses.replace(CONFIGS["tiny"], xent_chunks=2)
    tx = optax.adamw(1e-3)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.numpy.zeros((2, cfg.max_seq_len), jax.numpy.int32)
    return cfg, tx, params, tokens


@pytest.mark.parametrize("which,scopes", [
    ("train", {"embed", "attn", "mlp", "lm_head_xent", "opt_update"}),
    ("grad", {"embed", "attn", "mlp", "lm_head_xent"}),
])
def test_lowered_step_carries_scopes_and_module_name(which, scopes) -> None:
    from torchft_tpu.models import loss_fn, make_grad_step, make_train_step

    cfg, tx, params, tokens = _tiny()
    if which == "train":
        step = make_train_step(cfg, tx, donate=False)
        args = (params, tx.init(params), tokens, tokens)
    else:
        # equal arguments return the process's one program, which another
        # test may have run: a loss of its own makes this one new
        step = make_grad_step(
            cfg, loss=lambda c, p, x, y, a=None: loss_fn(c, p, x, y, a))
        args = (params, tokens, tokens)
    assert isinstance(step, StepProgram)
    lowered = step.lower(*args)  # a jitted function's own attribute
    text = lowered.as_text(debug_info=True)
    assert f"jit_tft_{which}_step" in text
    for scope in scopes:
        assert f"({scope})/" in text or f"/{scope}/" in text, scope
    assert step.scope_table() == {}      # never called: nothing to say
    step(*args)
    table = step.scope_table()
    assert table and scope_tables()[f"jit_tft_{which}_step"] == table
    paths = set(table.values())
    for scope in scopes - {"opt_update"}:
        assert any(f"/jvp({scope})/" in p for p in paths), scope
        assert any(f"/transpose(jvp({scope}))/" in p for p in paths), scope
    if which == "train":
        assert any(p.startswith("jit(tft_train_step)/opt_update/")
                   for p in paths)


def test_optimizer_update_program_is_named() -> None:
    import optax

    from torchft_tpu.optim import OptimizerWrapper

    class _Manager:
        def replica_id(self) -> str:
            return "tl_opt_"

    opt = OptimizerWrapper(_Manager(), optax.sgd(0.1))
    assert opt._update.name == "tft_opt_update"
    assert opt._update_donated.name == "tft_opt_update_donated"
    assert opt.metrics.label_value("replica_id") == "tl_opt_"
    text = opt._update.lower(
        {"w": np.ones(3, np.float32)}, opt.init({"w": np.ones(3, np.float32)}),
        {"w": np.ones(3, np.float32)},
    ).as_text(debug_info=True)
    assert "jit_tft_opt_update" in text and "opt_update/" in text


def test_flash_kernels_carry_their_names() -> None:
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.flash import flash_attention

    q = jnp.ones((1, 256, 2, 64), jnp.bfloat16)

    def loss(q, k, v, **kw):
        return flash_attention(q, k, v, interpret=True, **kw).sum()

    for kw in ({}, {"_resident_kv_bytes": 0}):   # resident, streamed
        jaxpr = str(jax.make_jaxpr(
            jax.grad(lambda q, k, v: loss(q, k, v, **kw), argnums=(0, 1, 2))
        )(q, q, q))
        # one backward kernel for dq, dk and dv in either regime (PR 74)
        for name in ("flash_fwd", "flash_bwd"):
            assert f"name={name}" in jaxpr, (kw, name)
