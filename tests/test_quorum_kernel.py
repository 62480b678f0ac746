"""Pure quorum-kernel tests against the native C++ library.

Coverage mirrors the reference's in-file Rust test matrices
(lighthouse.rs:584-1037 quorum_compute scenarios; manager.rs:720-850
compute_quorum_results matrices), driven from Python through the C API.
"""

import ctypes
import json

import pytest

from torchft_tpu.control._native import check_error, get_lib, take_string


def member(replica_id, step=0, world_size=1, shrink_only=False):
    return {
        "replica_id": replica_id,
        "address": f"addr_{replica_id}",
        "store_address": f"store_addr_{replica_id}",
        "step": step,
        "world_size": world_size,
        "shrink_only": shrink_only,
    }


def quorum_compute(now_ms, participants, heartbeats, prev_quorum, opts):
    """participants: list of (joined_ms, member); heartbeats: {id: ms}."""
    lib = get_lib()
    state = {
        "participants": [
            {"joined_ms": j, "member": m} for j, m in participants
        ],
        "heartbeats": heartbeats,
        "prev_quorum": prev_quorum,
    }
    err = ctypes.c_char_p()
    ptr = lib.ft_quorum_compute(
        now_ms,
        json.dumps(state).encode(),
        json.dumps(opts).encode(),
        ctypes.byref(err),
    )
    check_error(err)
    out = json.loads(take_string(ptr))
    return out["quorum"], out["reason"]


def compute_quorum_results(replica_id, rank, participants, quorum_id=1):
    lib = get_lib()
    q = {"quorum_id": quorum_id, "participants": participants, "created_ms": 0}
    err = ctypes.c_char_p()
    ptr = lib.ft_compute_quorum_results(
        replica_id.encode(), rank, json.dumps(q).encode(), ctypes.byref(err)
    )
    check_error(err)
    return json.loads(take_string(ptr))


OPTS = {"min_replicas": 1, "join_timeout_ms": 60000, "heartbeat_timeout_ms": 5000}


def test_json_roundtrip() -> None:
    lib = get_lib()
    cases = [
        '{"a":1,"b":[true,false,null],"c":"x\\ny","d":-3.5}',
        '{"nested":{"deep":{"n":9223372036854775807}}}',
        '{"uni":"\\u00e9\\u4e2d"}',
        "[]",
    ]
    for c in cases:
        err = ctypes.c_char_p()
        out = take_string(lib.ft_json_roundtrip(c.encode(), ctypes.byref(err)))
        check_error(err)
        assert json.loads(out) == json.loads(c)


def test_empty_state_no_quorum() -> None:
    q, reason = quorum_compute(1000, [], {}, None, OPTS)
    assert q is None
    assert "min_replicas" in reason


def test_basic_quorum_all_joined() -> None:
    # Both heartbeating replicas joined -> quorum without join timeout wait.
    participants = [(100, member("a")), (100, member("b"))]
    heartbeats = {"a": 900, "b": 900}
    q, reason = quorum_compute(1000, participants, heartbeats, None, OPTS)
    assert q is not None
    assert [m["replica_id"] for m in q] == ["a", "b"]
    assert "Valid quorum" in reason


def test_join_timeout_holds_for_stragglers() -> None:
    # "c" heartbeats but hasn't joined; quorum waits out join_timeout_ms
    # (ref lighthouse.rs:584-657).
    participants = [(100, member("a")), (100, member("b"))]
    heartbeats = {"a": 900, "b": 900, "c": 900}
    q, reason = quorum_compute(1000, participants, heartbeats, None, OPTS)
    assert q is None
    assert "stragglers" in reason

    # After join timeout expires (first_joined=100, now > 100+60000): proceed
    # without the straggler (2 of 3 also satisfies the split-brain guard).
    heartbeats = {"a": 69900, "b": 69900, "c": 69900}
    q, reason = quorum_compute(70000, participants, heartbeats, None, OPTS)
    assert q is not None
    assert [m["replica_id"] for m in q] == ["a", "b"]


def test_heartbeat_expiry_excludes_replica() -> None:
    # "b" joined but its heartbeat is stale (ref lighthouse.rs:659-739).
    participants = [(100, member("a")), (100, member("b"))]
    heartbeats = {"a": 99000, "b": 1000}
    q, _ = quorum_compute(100000, participants, heartbeats, None, OPTS)
    assert q is not None
    assert [m["replica_id"] for m in q] == ["a"]


def test_min_replicas_floor() -> None:
    opts = dict(OPTS, min_replicas=2)
    participants = [(100, member("a"))]
    q, reason = quorum_compute(1000, participants, {"a": 900}, None, opts)
    assert q is None
    assert "min_replicas" in reason


def test_fast_quorum_skips_join_timeout() -> None:
    # All prev-quorum members healthy + joined => no join-timeout wait even
    # though a new healthy replica hasn't joined (ref lighthouse.rs:741-823).
    prev = {
        "quorum_id": 1,
        "participants": [member("a"), member("b")],
        "created_ms": 0,
    }
    participants = [(100, member("a")), (100, member("b"))]
    heartbeats = {"a": 900, "b": 900, "c": 900}  # "c" healthy, not joined
    q, reason = quorum_compute(1000, participants, heartbeats, prev, OPTS)
    assert q is not None
    assert "Fast quorum" in reason
    assert [m["replica_id"] for m in q] == ["a", "b"]


def test_fast_quorum_includes_new_joiner() -> None:
    # Fast quorum returns ALL healthy participants, including new joiners.
    prev = {
        "quorum_id": 1,
        "participants": [member("a")],
        "created_ms": 0,
    }
    participants = [(100, member("a")), (100, member("c"))]
    heartbeats = {"a": 900, "c": 900}
    q, reason = quorum_compute(1000, participants, heartbeats, prev, OPTS)
    assert q is not None
    assert "Fast quorum" in reason
    assert [m["replica_id"] for m in q] == ["a", "c"]


@pytest.mark.parametrize(
    "asked, expect, why",
    [
        # (joined_ms of a, b, c; None = heartbeats and has not asked)
        pytest.param((9990, None, 5000), ["a", "c"], "Valid quorum",
                     id="left_out_member_and_first_asker_two_wide_at_once"),
        pytest.param((9990, 9980, 5000), ["a", "b", "c"], "Fast quorum",
                     id="both_previous_members_asked_fast_three_wide"),
        pytest.param((9990, None, 9950), None, "stragglers",
                     id="left_out_member_waited_less_than_the_timeout"),
        pytest.param((9950, 5000, 9990), ["a", "b", "c"], "Fast quorum",
                     id="all_three_asked_in_another_order_three_wide"),
    ],
)
def test_the_wait_for_a_straggler_counts_from_the_longest_waiting_member(
    asked, expect, why
) -> None:
    """The rule a kill-and-heal test ran into (tests/test_sharded_e2e.py,
    PR 61): the previous quorum is {a, b}, and ``c`` was left out of it.
    ``first_joined`` is the minimum over ALL healthy participants
    (``native/quorum.cc``), so once ``c`` has waited ``join_timeout_ms``
    it forms a quorum with the FIRST previous member that asks, with no
    grace for the other; three groups whose members ask further apart
    than the timeout therefore rotate through two-wide quorums
    (docs/operations.md, "join_timeout_ms"; ROADMAP.md S5a). The other
    three cases fence that one."""
    opts = dict(OPTS, join_timeout_ms=200)
    prev = {
        "quorum_id": 1,
        "participants": [member("a"), member("b")],
        "created_ms": 0,
    }
    participants = [
        (joined, member(r)) for r, joined in zip("abc", asked)
        if joined is not None
    ]
    heartbeats = {"a": 9990, "b": 9990, "c": 9990}
    q, reason = quorum_compute(10000, participants, heartbeats, prev, opts)
    assert why in reason, reason
    assert (q and sorted(m["replica_id"] for m in q)) == expect


def test_shrink_only_restricts_to_prev_members() -> None:
    # shrink_only drops non-prev-members from candidates
    # (ref lighthouse.rs:825-910).
    prev = {
        "quorum_id": 1,
        "participants": [member("a"), member("b")],
        "created_ms": 0,
    }
    participants = [
        (100, member("a", shrink_only=True)),
        (100, member("b")),
        (100, member("c")),  # new joiner, must be excluded
    ]
    heartbeats = {"a": 900, "b": 900, "c": 900}
    q, _ = quorum_compute(1000, participants, heartbeats, prev, OPTS)
    assert q is not None
    assert [m["replica_id"] for m in q] == ["a", "b"]


def test_split_brain_guard() -> None:
    # 1 participant of 3 healthy heartbeaters: 1 <= 3/2 -> blocked
    # (ref lighthouse.rs:956-1003). Join timeout already expired.
    participants = [(100, member("a"))]
    heartbeats = {"a": 99000, "b": 99000, "c": 99000}
    q, reason = quorum_compute(100000, participants, heartbeats, None, OPTS)
    assert q is None
    assert "half" in reason

    # 2 of 3: 2 > 3/2=1 -> allowed once join timeout passes.
    participants = [(100, member("a")), (100, member("b"))]
    q, _ = quorum_compute(100000, participants, heartbeats, None, OPTS)
    assert q is not None


def test_compute_results_first_step() -> None:
    # Port of manager.rs:720-768: at step 0 everyone but the primary heals.
    parts = [member("replica_0", step=0), member("replica_1", step=0)]

    r = compute_quorum_results("replica_0", 0, parts)
    assert not r["heal"]
    assert r["replica_rank"] == 0
    assert r["recover_src_rank"] is None
    assert r["recover_dst_ranks"] == [1]

    r = compute_quorum_results("replica_1", 0, parts)
    assert r["heal"]
    assert r["replica_rank"] == 1
    assert r["recover_src_rank"] == 0
    assert r["recover_dst_ranks"] == []

    # local rank 1 assignments are offset from rank 0's.
    r = compute_quorum_results("replica_1", 1, parts)
    assert not r["heal"]
    assert r["replica_rank"] == 1
    assert r["recover_src_rank"] is None
    assert r["recover_dst_ranks"] == [0]


def test_compute_results_mixed_step_recovery() -> None:
    # Port of manager.rs:770-850: replicas 1,3 at step 1; 0,2,4 behind.
    parts = [
        member("replica_0", step=0),
        member("replica_1", step=1),
        member("replica_2", step=0),
        member("replica_3", step=1),
        member("replica_4", step=0),
    ]

    r = compute_quorum_results("replica_0", 0, parts)
    assert r["heal"]
    assert r["recover_src_manager_address"] == "addr_replica_1"
    assert r["replica_rank"] == 0
    assert r["recover_src_rank"] == 1
    assert r["recover_dst_ranks"] == []

    r = compute_quorum_results("replica_1", 0, parts)
    assert not r["heal"]
    assert r["recover_src_manager_address"] == ""
    assert r["replica_rank"] == 1
    assert r["recover_src_rank"] is None
    assert r["recover_dst_ranks"] == [0, 4]

    r = compute_quorum_results("replica_3", 0, parts)
    assert not r["heal"]
    assert r["replica_rank"] == 3
    assert r["recover_src_rank"] is None
    assert r["recover_dst_ranks"] == [2]

    # local rank 1: assignments rotate by one donor.
    r = compute_quorum_results("replica_1", 1, parts)
    assert not r["heal"]
    assert r["replica_rank"] == 1
    assert r["recover_src_rank"] is None
    assert r["recover_dst_ranks"] == [2]


def test_compute_results_max_cohort_fields() -> None:
    parts = [
        member("replica_0", step=5),
        member("replica_1", step=3),
        member("replica_2", step=5),
    ]
    r = compute_quorum_results("replica_2", 0, parts)
    assert r["max_step"] == 5
    assert r["max_world_size"] == 2
    assert r["max_rank"] == 1  # index within the max-step cohort
    assert r["replica_world_size"] == 3

    r = compute_quorum_results("replica_1", 0, parts)
    assert r["max_rank"] is None
    assert r["heal"]


def test_compute_results_missing_replica_raises() -> None:
    parts = [member("replica_0", step=0)]
    with pytest.raises(RuntimeError, match="not participating"):
        compute_quorum_results("ghost", 0, parts)


def test_transport_membership_excludes_observers() -> None:
    # Member.data_plane=false (observer) replicas join the quorum but are
    # excluded from the data-plane transport fields; data-plane members
    # get contiguous transport ranks in sorted-replica order.
    parts = [
        member("a", step=5),
        {**member("b", step=0), "data_plane": False},  # observer, behind
        member("c", step=5),
    ]
    res_a = compute_quorum_results("a", 0, parts)
    assert res_a["transport_replica_ids"] == ["a", "c"]
    assert res_a["transport_rank"] == 0
    assert res_a["transport_world_size"] == 2
    # cohort (step-based) info is independent of data-plane membership
    assert res_a["max_replica_ids"] == ["a", "c"]

    res_c = compute_quorum_results("c", 0, parts)
    assert res_c["transport_rank"] == 1

    # the observer itself: in the quorum, off the wire
    res_b = compute_quorum_results("b", 0, parts)
    assert res_b["transport_rank"] is None
    assert res_b["transport_world_size"] == 2
    assert res_b["replica_world_size"] == 3


def test_transport_membership_includes_healing_members() -> None:
    # A behind (healing) data-plane replica stays on the wire: it must
    # receive the cohort average in its heal step.
    parts = [member("a", step=9), member("b", step=2)]
    res_b = compute_quorum_results("b", 0, parts)
    assert res_b["heal"] is True
    assert res_b["transport_replica_ids"] == ["a", "b"]
    assert res_b["transport_rank"] == 1
    assert res_b["max_replica_ids"] == ["a"]


def test_observers_invisible_to_step_and_recovery_logic() -> None:
    # Observers must not: define max_step, be elected bootstrap primary /
    # donor, appear in recover_dst, or count in the participating cohort.
    # Bootstrap (everyone at step 0, observer sorts first):
    parts0 = [
        {**member("_obs", step=0), "data_plane": False},
        member("a", step=0),
        member("b", step=0),
    ]
    res_a = compute_quorum_results("a", 0, parts0)
    # primary is a data-plane member ("a", first dp in sorted order), so
    # recover_dst is the OTHER dp member only — never the observer
    assert res_a["recover_dst_ranks"] == [2]  # "b"'s replica_rank
    assert res_a["max_world_size"] == 2
    assert res_a["store_address"] == "store_addr_a"

    # An observer with a bogus ahead step must not drag max_step up:
    parts_ahead = [
        {**member("obs", step=99), "data_plane": False},
        member("a", step=5),
        member("b", step=5),
    ]
    res = compute_quorum_results("a", 0, parts_ahead)
    assert res["max_step"] == 5
    assert res["max_replica_ids"] == ["a", "b"]
    assert res["heal"] is False

    # The observer's own view: never healing, never participating.
    res_obs = compute_quorum_results("obs", 0, parts_ahead)
    assert res_obs["heal"] is False
    assert res_obs["max_rank"] is None
    assert res_obs["transport_rank"] is None


# --------------------------------------------------------------- fleet scale
# ISSUE 10: scale/property coverage for the decision kernel and the
# incremental cached plane the lighthouse serves at O(100-1000) groups.


def quorum_compute_raw_state(now_ms, participants, heartbeats, prev_quorum,
                             opts):
    """Like quorum_compute but returns the RAW decision JSON string (the
    byte-identity currency)."""
    from torchft_tpu.control import quorum_compute_raw

    state = {
        "participants": [
            {"joined_ms": j, "member": m} for j, m in participants
        ],
        "heartbeats": heartbeats,
        "prev_quorum": prev_quorum,
    }
    return quorum_compute_raw(now_ms, json.dumps(state), opts)


def test_scale_decision_arrival_order_independent() -> None:
    # n>=100 groups: the decision must be deterministic and independent
    # of the order participants appear in the request state — the wire
    # arrival order at a real lighthouse is racy by nature.
    import random

    n = 120
    members = [
        member(f"grp_{i:04d}", step=i % 3, world_size=1 + i % 2)
        for i in range(n)
    ]
    participants = [(100 + i, m) for i, m in enumerate(members)]
    heartbeats = {m["replica_id"]: 900 for m in members}
    baseline = quorum_compute_raw_state(
        1000, participants, heartbeats, None, OPTS
    )
    q, reason = quorum_compute(1000, participants, heartbeats, None, OPTS)
    assert q is not None and len(q) == n
    assert [m["replica_id"] for m in q] == sorted(
        m["replica_id"] for m in members
    )
    for seed in range(3):
        shuffled = list(participants)
        random.Random(seed).shuffle(shuffled)
        hb_items = list(heartbeats.items())
        random.Random(seed + 99).shuffle(hb_items)
        assert quorum_compute_raw_state(
            1000, shuffled, dict(hb_items), None, OPTS
        ) == baseline


def test_scale_prev_quorum_tie_break_stable_under_churn() -> None:
    # With a prev quorum installed, repeated evaluations under churn
    # (members dying/rejoining in different arrival orders) must keep the
    # candidate ordering and fast/slow classification stable.
    import random

    n = 100
    members = [member(f"grp_{i:04d}") for i in range(n)]
    prev = {
        "quorum_id": 7,
        "participants": members,
        "created_ms": 0,
    }
    # all prev members back -> fast quorum, sorted ids, any arrival order
    participants = [(500, m) for m in members]
    heartbeats = {m["replica_id"]: 900 for m in members}
    ref = quorum_compute_raw_state(1000, participants, heartbeats, prev, OPTS)
    assert "Fast quorum" in json.loads(ref)["reason"]
    for seed in range(3):
        shuffled = list(participants)
        random.Random(seed).shuffle(shuffled)
        assert quorum_compute_raw_state(
            1000, shuffled, heartbeats, prev, OPTS
        ) == ref
    # kill one member: no longer fast; the survivor candidate list stays
    # the sorted survivor set regardless of arrival order
    dead = members[37]["replica_id"]
    alive = [(500, m) for m in members if m["replica_id"] != dead]
    hb_alive = {k: v for k, v in heartbeats.items() if k != dead}
    q, reason = quorum_compute(1000, alive, hb_alive, prev, OPTS)
    assert "Fast quorum" not in reason
    assert q is not None
    assert [m["replica_id"] for m in q] == sorted(hb_alive)
    for seed in range(3):
        shuffled = list(alive)
        random.Random(seed).shuffle(shuffled)
        q2, _ = quorum_compute(1000, shuffled, hb_alive, prev, OPTS)
        assert q2 == q


def _iq_random_sequence(seed: int, n_replicas: int, ops: int,
                        incremental: bool = True):
    """Drive the native IncrementalQuorum through a random monotonic
    heartbeat/join/expiry/early-expiry/install sequence, checking at every step that
    its decision JSON is byte-identical to a from-scratch kernel
    recompute over the dumped state. Returns (iq, mismatches, checks)."""
    import random

    from torchft_tpu.control import IncrementalQuorum, quorum_compute_raw

    rng = random.Random(seed)
    opts = {
        "min_replicas": rng.choice([1, 2, n_replicas // 2]),
        "join_timeout_ms": rng.choice([50, 60000]),
        "heartbeat_timeout_ms": 5000,
    }
    iq = IncrementalQuorum(opts, incremental=incremental)
    now = 1_000_000
    checks = mismatches = 0
    ids = [f"r_{i:03d}" for i in range(n_replicas)]
    for _ in range(ops):
        now += rng.choice([0, 1, 7, 100])
        op = rng.random()
        rid = rng.choice(ids)
        if op < 0.35:
            iq.heartbeat(rid, now)
        elif op < 0.72:
            iq.heartbeat(rid, now)
            iq.join(now, member(rid, step=rng.randrange(3),
                                shrink_only=rng.random() < 0.05))
        elif op < 0.77:
            # the door-knock's early expiry, of a live id or a dead one
            iq.expire(rid, now)
        elif op < 0.85:
            # time jump: some heartbeats expire (and may be pruned)
            now += rng.choice([5001, 10000, 70000])
        else:
            iq.install(now, wall_ms=now)
        checks += 1
        if iq.decision(now) != quorum_compute_raw(now, iq.state(), opts):
            mismatches += 1
    return iq, mismatches, checks


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_incremental_decision_byte_identical_to_kernel(seed) -> None:
    # The core PR-10 oracle: after ARBITRARY heartbeat/join/expiry/install
    # sequences, the incremental cached plane's decision JSON is
    # byte-identical to a from-scratch recompute — including the reason
    # strings and candidate ordering.
    _, mismatches, checks = _iq_random_sequence(
        seed, n_replicas=30, ops=300
    )
    assert checks == 300
    assert mismatches == 0


def test_incremental_decision_byte_identical_at_scale() -> None:
    # Same property at n>=100 with a join-heavy mix (the formation-storm
    # shape a real lighthouse sees).
    _, mismatches, checks = _iq_random_sequence(
        17, n_replicas=120, ops=400
    )
    assert checks == 400
    assert mismatches == 0


def test_incremental_cache_serves_stable_state() -> None:
    # Counter contract: with no membership change, repeated decisions are
    # cache hits — recompute count is O(membership changes), not O(calls).
    from torchft_tpu.control import IncrementalQuorum

    opts = {"min_replicas": 2, "join_timeout_ms": 60000,
            "heartbeat_timeout_ms": 5000}
    iq = IncrementalQuorum(opts)
    now = 1000
    for i in range(8):
        iq.heartbeat(f"r{i}", now)
        iq.join(now, member(f"r{i}"))
    before = iq.counters()
    for k in range(100):
        iq.decision(now + k)  # within the heartbeat window
    after = iq.counters()
    assert after["epoch"] == before["epoch"]
    # at most one recompute to fill the cache; the other 99+ are hits
    assert after["compute_count"] - before["compute_count"] <= 1
    assert after["cache_hits"] - before["cache_hits"] >= 99
    # a membership edge invalidates exactly once
    iq.heartbeat("r_new", now + 100)
    iq.decision(now + 100)
    iq.decision(now + 100)
    end = iq.counters()
    assert end["compute_count"] - after["compute_count"] == 1


def test_incremental_prunes_departed_replicas() -> None:
    # Satellite: heartbeats/participants of long-dead replicas are erased
    # at sweep time with counters — the state no longer grows
    # monotonically across churn.
    from torchft_tpu.control import IncrementalQuorum

    opts = {"min_replicas": 1, "join_timeout_ms": 50,
            "heartbeat_timeout_ms": 100}
    iq = IncrementalQuorum(opts, prune_after_ms=300)
    now = 1000
    for i in range(5):
        iq.heartbeat(f"dead{i}", now)
        iq.join(now, member(f"dead{i}"))
    iq.heartbeat("alive", now)
    iq.join(now, member("alive"))
    # advance past prune_after for the dead cohort, keeping one alive
    for t in range(now + 80, now + 500, 80):
        iq.heartbeat("alive", t)
        iq.decision(t)
    iq.decision(now + 600)
    state = json.loads(iq.state())
    assert set(state["heartbeats"]) == {"alive"}
    assert [p["member"]["replica_id"] for p in state["participants"]] == [
        "alive"
    ]
    counters = iq.counters()
    assert counters["pruned_heartbeats"] == 5
    assert counters["pruned_participants"] == 5
    # the survivor still forms a quorum after the prune (fresh stamp:
    # the final wait above aged its last heartbeat past the timeout)
    iq.heartbeat("alive", now + 600)
    decision = json.loads(iq.decision(now + 600))
    assert decision["quorum"] is not None
    assert [m["replica_id"] for m in decision["quorum"]] == ["alive"]


def test_all_observer_fallback_emits_coherent_transport() -> None:
    # Degenerate quorum where EVERY member is an observer: the kernel
    # falls back to treating the full membership as data-plane so it
    # stays total — and the transport fields must describe that same
    # fallback membership, not stay empty (which would push Python onto
    # the legacy full-membership branch while the kernel had elected
    # observer primaries/donors; ADVICE r3 #1).
    parts = [
        {**member("a", step=3), "data_plane": False},
        {**member("b", step=3), "data_plane": False},
    ]
    res_a = compute_quorum_results("a", 0, parts)
    assert res_a["transport_replica_ids"] == ["a", "b"]
    assert res_a["transport_rank"] == 0
    assert res_a["transport_world_size"] == 2
    res_b = compute_quorum_results("b", 0, parts)
    assert res_b["transport_rank"] == 1
    # and the fallback election itself still holds
    assert res_b["max_replica_ids"] == ["a", "b"]
