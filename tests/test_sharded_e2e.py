"""Sharded weight update e2e (ISSUE 9 acceptance): kill→shrink→rejoin
over a live lighthouse WITH the sharded path enabled.

Three replica groups train through ``ShardedOptimizerWrapper`` (real
Managers, real TCP comm, real HTTP checkpoints). Replica 0 is killed
mid-run and restarts. Required lifecycle, reconstructed from the
``/telemetry/events`` endpoints alone (the fleet_top discovery path):

    quorum at wire_world 3 → member_dead → reshard onto the shrunken
    grid (new_world 2) → step_commit resuming at wire_world 2 →
    heal_start/heal_done on the rejoiner → reshard back to new_world 3
    → step_commit past the kill point

plus ``shard_grid_rebuild`` events marking the plan-cache misses.
"""

import json
import logging
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np

from torchft_tpu.comm.store import StoreClient, StoreServer
from torchft_tpu.comm.transport import TcpCommContext
from torchft_tpu.control import Lighthouse
from torchft_tpu.manager import Manager

logger = logging.getLogger(__name__)


class InjectedFailure(Exception):
    pass


def _fetch(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.load(resp)


class _Harness:
    """Moves the run along on the EVENTS the tests are about, not on
    counts of steps or on times a loaded machine has to beat. The kill
    comes once the victim has itself committed at full width
    (:meth:`at_full_width`); the restart comes once a survivor has
    committed a step one narrower AFTER the kill (``shrunk``: the shrink
    has happened, whatever the lighthouse's timeouts are); the run ends
    when the next incarnation has committed a step in which all
    ``num_replicas`` took part (and everyone is past ``total_steps``).
    The survivors keep stepping until then; the only clock is
    :func:`_run_to_the_end`'s deadline for a hung run."""

    def __init__(self, num_replicas: int, total_steps: int) -> None:
        self.num_replicas = num_replicas
        self.total_steps = total_steps
        self.stop = threading.Event()
        self.shrunk = threading.Event()
        self.progress: Dict[int, int] = {}
        self.rejoined_at_full_width = False
        self._full_width: set = set()
        self._killed = False
        self._lock = threading.Lock()

    def at_full_width(self, replica_id: int) -> bool:
        """Has this replica committed a step all ``num_replicas`` took
        part in?"""
        with self._lock:
            return replica_id in self._full_width

    def killed(self) -> None:
        with self._lock:
            self._killed = True

    def end(self) -> None:
        """Releases every loop and every waiter."""
        self.stop.set()
        self.shrunk.set()

    def report(self, replica_id: int, step: int, restarted: bool,
               participants: int) -> None:
        with self._lock:
            self.progress[replica_id] = max(
                self.progress.get(replica_id, 0), step
            )
            if participants == self.num_replicas:
                self._full_width.add(replica_id)
            if (self._killed and not restarted
                    and participants == self.num_replicas - 1):
                self.shrunk.set()
            if restarted and participants == self.num_replicas:
                self.rejoined_at_full_width = True
            if (
                self.rejoined_at_full_width
                and len(self.progress) == self.num_replicas
                and all(s >= self.total_steps
                        for s in self.progress.values())
            ):
                self.end()


def _lighthouse() -> Lighthouse:
    """The lighthouse of both tests. ``join_timeout_ms`` is how long a
    quorum waits for a healthy member that has not asked yet. Three
    groups whose members ask further apart than that rotate through
    two-wide quorums for ever (``native/quorum.cc``: the wait is counted
    from the LONGEST-waiting member, so the one left out last time forms
    a quorum with the first other that asks, and the third heals into
    the next: docs/operations.md, "join_timeout_ms"); a healer on a CPU
    under six test workers asks hundreds of milliseconds after its
    donors. 4 s is above any heal seen here and below the managers' 5 s
    quorum time-out. It no longer decides whether the shrink happens:
    the dead incarnation leaves by its heartbeat (1 s) or the
    lighthouse's knock, and the restart waits for the shrink."""
    return Lighthouse(
        min_replicas=1, join_timeout_ms=4000, heartbeat_timeout_ms=1000
    )


def _run_to_the_end(replicas: List["_Replica"], harness: _Harness,
                    lighthouse: Lighthouse) -> None:
    """Every replica's loop to the harness's stop, or 180 s: a run still
    going then is hung, and is stopped INSIDE the pool (whose exit waits
    for loops that only ``harness.stop`` ends) so that it fails here and
    does not hang there."""
    try:
        with ThreadPoolExecutor(max_workers=len(replicas)) as pool:
            futs = [pool.submit(r.run) for r in replicas]
            deadline = time.monotonic() + 180.0
            try:
                for f in futs:
                    f.result(timeout=max(1.0, deadline - time.monotonic()))
            finally:
                harness.end()
    finally:
        lighthouse.shutdown()


class _Replica:
    """One replica group training through the sharded wrapper; restarts
    after the injected kill (and after the documented
    allgather-after-commit failure window, whose recovery IS restart +
    heal)."""

    def __init__(self, replica_id: int, lighthouse_addr: str,
                 harness: _Harness,
                 fail_at_step: Optional[int] = None,
                 model_shards: int = 1) -> None:
        self.replica_id = replica_id
        self.lighthouse_addr = lighthouse_addr
        self.harness = harness
        self.fail_at_step = fail_at_step
        self.model_shards = model_shards
        self.failures = 0
        self.telemetry: List[dict] = []

    def run(self) -> None:
        while not self.harness.stop.is_set():
            try:
                self._main()
                return
            except InjectedFailure:
                logger.warning("replica %s restarting after injected kill",
                               self.replica_id)
                # A killed host is not back before the survivors have
                # gone on without it: the next incarnation starts when
                # one of them has committed a step two wide. Started
                # earlier it can land in the SAME quorum as the
                # survivors' next step, and the lifecycle under test
                # (shrink, then rejoin) never occurs.
                self.harness.shrunk.wait()
                continue
            except RuntimeError as e:
                # the failure-after-vote window: restart + heal
                logger.warning("replica %s restarting after %s",
                               self.replica_id, e)
                continue

    def _main(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from torchft_tpu.optim import ShardedOptimizerWrapper

        store = StoreServer()
        rng = np.random.default_rng(5)
        holder = {
            "params": {
                f"w{i}": jnp.asarray(
                    rng.standard_normal(8 + i).astype(np.float32)
                )
                for i in range(6)
            },
            "opt": None,
        }
        opt_box = {"opt": None}  # wrapper bound after the manager exists

        def state_dict():
            return {
                "params": {
                    k: np.asarray(v)
                    for k, v in holder["params"].items()
                },
                "opt": opt_box["opt"].opt_state_dict(holder["opt"]),
            }

        def load_state_dict(sd):
            holder["params"] = {
                k: jnp.asarray(np.asarray(v))
                for k, v in sd["params"].items()
            }
            holder["opt"] = opt_box["opt"].load_opt_state_dict(sd["opt"])

        manager = Manager(
            comm=TcpCommContext(timeout=5.0),
            load_state_dict=load_state_dict,
            state_dict=state_dict,
            min_replica_size=1,
            use_async_quorum=True,
            timeout=5.0, quorum_timeout=5.0, connect_timeout=5.0,
            rank=0, world_size=1,
            store_addr=store.addr,
            lighthouse_addr=self.lighthouse_addr,
            replica_id=f"sharded_rep_{self.replica_id}_",
            heartbeat_interval=0.05,
            model_shards=self.model_shards,
        )
        opt = ShardedOptimizerWrapper(
            manager, optax.adam(1e-2),
            state_fn=lambda: (holder["params"], holder["opt"]),
            sharded=True,
        )
        opt_box["opt"] = opt
        holder["opt"] = opt.init(holder["params"])
        telemetry_url = (
            StoreClient(store.addr, connect_timeout=5.0)
            .get("checkpoint_addr_0").decode()
        )
        try:
            while not self.harness.stop.is_set():
                if (
                    self.fail_at_step is not None
                    and self.failures == 0
                    and manager.current_step() >= self.fail_at_step
                    and self.harness.at_full_width(self.replica_id)
                ):
                    self.failures += 1
                    self.harness.killed()
                    raise InjectedFailure(
                        f"injected kill of replica {self.replica_id}"
                    )
                try:
                    manager.start_quorum()
                except (TimeoutError, RuntimeError) as e:
                    logger.info("quorum retry: %s", e)
                    continue
                grads = jax.tree_util.tree_map(
                    lambda x: x - 10.0, holder["params"]
                )
                params, opt_state, committed = opt.step(
                    holder["params"], holder["opt"], grads
                )
                holder["params"], holder["opt"] = params, opt_state
                if committed:
                    self.harness.report(
                        self.replica_id, manager.current_step(),
                        restarted=self.failures > 0,
                        participants=manager.num_participants(),
                    )
                else:
                    time.sleep(0.01)
        finally:
            try:
                events = _fetch(telemetry_url + "/telemetry/events?since=0")
                self.telemetry.append({"events": events})
            except Exception as e:  # noqa: BLE001
                self.telemetry.append({"capture_error": repr(e)})
            manager.shutdown(wait=False)
            store.shutdown()


def _events_of(dump: dict) -> List[dict]:
    assert "capture_error" not in dump, dump
    return sorted(dump["events"]["events"], key=lambda e: e["seq"])


def test_sharded_kill_shrink_rejoin_lifecycle() -> None:
    lighthouse = _lighthouse()
    harness = _Harness(num_replicas=3, total_steps=8)
    replicas = [
        _Replica(0, lighthouse.address(), harness, fail_at_step=3),
        _Replica(1, lighthouse.address(), harness),
        _Replica(2, lighthouse.address(), harness),
    ]
    _run_to_the_end(replicas, harness, lighthouse)

    assert replicas[0].failures == 1
    # the killed replica restarted at least once; every replica finished
    assert all(
        harness.progress.get(r.replica_id, 0) >= harness.total_steps
        for r in replicas
    ), harness.progress

    # -- reconstruct the lifecycle from a SURVIVOR's endpoint dump ------
    surv = _events_of(replicas[1].telemetry[-1])
    kinds = [e["kind"] for e in surv]
    assert "shard_grid_rebuild" in kinds
    # full-wire quorum seen
    full_q = [
        e for e in surv
        if e["kind"] == "quorum_complete" and e.get("wire_world") == 3
    ]
    assert full_q, "never saw a 3-wire quorum"
    dead = [e for e in surv if e["kind"] == "member_dead"]
    assert dead, "the kill left no member_dead event"
    death_seq = dead[0]["seq"]
    # reshard onto the shrunken grid AFTER the death...
    shrink_resh = [
        e for e in surv
        if e["kind"] == "reshard" and e.get("new_world") == 2
        and e["seq"] > death_seq
    ]
    assert shrink_resh, "no reshard onto the 2-wire grid after the kill"
    # ...with commits resuming at wire_world 2
    w2_commits = [
        e for e in surv
        if e["kind"] == "step_commit" and e["seq"] > shrink_resh[0]["seq"]
    ]
    assert w2_commits, "no commits after the shrink reshard"
    # the rejoin reshards back to 3 and commits keep flowing past it
    grow_resh = [
        e for e in surv
        if e["kind"] == "reshard" and e.get("new_world") == 3
        and e["seq"] > death_seq
    ]
    assert grow_resh, "no reshard back onto the 3-wire grid"
    post_grow_commits = [
        e for e in surv
        if e["kind"] == "step_commit" and e["seq"] > grow_resh[0]["seq"]
    ]
    assert post_grow_commits, "no commits after the rejoin reshard"

    # -- the rejoiner healed (its second incarnation's recording) -------
    rejoin = _events_of(replicas[0].telemetry[-1])
    heal_done = [e for e in rejoin if e["kind"] == "heal_done"]
    assert heal_done, "the rejoiner never recorded heal_done"
    heal_starts = [e for e in rejoin if e["kind"] == "heal_start"]
    assert heal_starts and heal_starts[0]["seq"] < heal_done[0]["seq"]
    # and resharded onto the live grid after the heal
    rj_resh = [e for e in rejoin if e["kind"] == "reshard"]
    assert rj_resh, "the rejoiner never resharded"
    # commits resumed past the kill point on the rejoiner too
    rj_commits = [
        e for e in rejoin
        if e["kind"] == "step_commit"
        and e["seq"] > heal_done[0]["seq"]
    ]
    assert rj_commits, "the rejoiner never committed after healing"


def _sub_unit_bytes(model_shards: int) -> List[int]:
    """Per-sub-unit byte sizes of the harness's adam states: leaf i has
    an (8+i,) param, its state splits into model_shards contiguous
    payloads exactly as optim.py ships them."""
    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu.checkpointing import split_leaf_payload

    tx = optax.adam(1e-2)
    out: List[int] = []
    for i in range(6):
        arrays = [
            np.asarray(a) for a in jax.tree_util.tree_leaves(
                tx.init(jnp.zeros((8 + i,), jnp.float32))
            )
        ]
        for shard in split_leaf_payload(arrays, model_shards):
            out.append(sum(int(a.nbytes) for a in shard))
    return out


def test_sharded_2d_kill_shrink_rejoin_lower_bound() -> None:
    """ISSUE 16 satellite: kill → shrink on the REPLICA axis at a fixed
    model axis (model_shards=2) → rejoin. The shrink reshard must move
    exactly the PR 14 set-theoretic lower bound for the 2-D spec
    transition — reconstructed from the ``/telemetry/events`` endpoints
    ALONE: each survivor's old/new ranks come from its own reshard
    events, the 2-D specs from the deterministic shard grid, and the
    event's wire/lower-bound byte counts must equal the independently
    computed ``TransferPlan`` bound."""
    from torchft_tpu.comm.redistribute import ShardSpec, TransferPlan
    from torchft_tpu.ddp import shard_ranges

    M = 2
    lighthouse = _lighthouse()
    harness = _Harness(num_replicas=3, total_steps=8)
    replicas = [
        _Replica(0, lighthouse.address(), harness, fail_at_step=3,
                 model_shards=M),
        _Replica(1, lighthouse.address(), harness, model_shards=M),
        _Replica(2, lighthouse.address(), harness, model_shards=M),
    ]
    _run_to_the_end(replicas, harness, lighthouse)

    assert replicas[0].failures == 1

    # -- per-survivor rank history, from events alone -------------------
    def _reshards(events: List[dict], world: int) -> List[dict]:
        """Reshards onto a ``world``-wire grid: the last one before the
        kill for the full grid (replicas that start 200 ms apart grow
        2 -> 3 first), the ones after it for the shrunken grid."""
        death = [e["seq"] for e in events if e["kind"] == "member_dead"]
        assert death, "the kill left no member_dead event"
        resh = [
            e for e in events
            if e["kind"] == "reshard" and e.get("new_world") == world
            and (e["seq"] > death[0]) == (world == 2)
        ]
        assert resh, f"no reshard onto the {world}-wire grid"
        return resh

    def _rank_at(events: List[dict], world: int) -> int:
        resh = _reshards(events, world)
        return int(resh[0 if world == 2 else -1]["rank"])

    surv_events = {
        rid: _events_of(replicas[rid].telemetry[-1]) for rid in (1, 2)
    }
    old_rank = {rid: _rank_at(ev, 3) for rid, ev in surv_events.items()}
    new_rank = {rid: _rank_at(ev, 2) for rid, ev in surv_events.items()}
    assert sorted(new_rank.values()) == [0, 1]

    # -- the independent 2-D pricing ------------------------------------
    sizes = [8 + i for i in range(6)]
    dtypes = [np.dtype(np.float32)] * 6
    spec3 = ShardSpec.from_ranges_2d(
        shard_ranges(sizes, dtypes, 3), M, 6
    )
    spec2 = ShardSpec.from_ranges_2d(
        shard_ranges(sizes, dtypes, 2), M, 6
    )
    src = ShardSpec(6 * M, {
        new_rank[rid]: spec3.units_of(old_rank[rid]) for rid in (1, 2)
    })
    plan = TransferPlan(src, spec2, _sub_unit_bytes(M))
    assert plan.lower_bound_bytes == plan.moved_bytes

    for rid in (1, 2):
        shrink = _reshards(surv_events[rid], 2)[0]
        expected = plan.lower_bound_bytes.get(new_rank[rid], 0)
        assert shrink["mesh_shape"] == f"2x{M}"
        assert shrink["lower_bound_bytes"] == expected, (
            f"survivor {rid}: event bound {shrink['lower_bound_bytes']} "
            f"!= independently priced 2-D bound {expected}"
        )
        # the planned arm RECEIVES exactly the bound, never more
        assert shrink["wire_bytes"] == expected
        # dead-owner sub-units reinit whole leaves (M sub-units each)
        unsourced = plan.receiver_unsourced(new_rank[rid])
        assert shrink["reinit_leaves"] == len(unsourced) // M
        # every executed transfer plan was minimal, per its own event
        for e in surv_events[rid]:
            if e["kind"] == "redist_plan":
                assert e["moved_bytes"] == e["lower_bound_bytes"]

    # the transition must genuinely exercise the 2-D pricing: someone
    # fetched sub-units, and someone reinitialized a dead slice
    assert any(
        plan.moved_bytes.get(new_rank[rid], 0) > 0 for rid in (1, 2)
    )
    assert any(
        plan.receiver_unsourced(new_rank[rid]) for rid in (1, 2)
    )
