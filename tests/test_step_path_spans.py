"""The step under a wire on one clock (ISSUE 35): the step thread's pack /
wire tail / landing tail, the landing pool's queue and a lane's exchange /
reduce / CPU, as timings of the manager's sink and ``tft.*`` spans with
``replica`` and ``step``. Counts and structure only: no time, rate or
share is asserted as a value (ROADMAP D10), bar the tiling, which is held
against the test's own clock with room a loaded core cannot use up."""

import glob
import os
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np
import pytest

from torchft_tpu.comm import ReduceOp, StoreServer, TcpCommContext
from torchft_tpu.comm.context import Work
from torchft_tpu.ddp import DistributedDataParallel
from torchft_tpu.futures import future_chain
from torchft_tpu.utils.metrics import Metrics
from torchft_tpu.utils.profiling import SPAN_PREFIX

WORLD = 2
STEPS = 3
# once a classic step, by whichever thread resolves the step's future
STEP_TIMINGS = ("ddp_step_pack", "ddp_step_fetch", "ddp_step_copy",
                "ddp_step_submit", "ddp_step_cpu", "ddp_step_land_tail",
                "ddp_wire_exposed", "ddp_wire_total", "ddp_d2h_total",
                "ddp_h2d_total")
# once a bucket (a bucket is one op on the wire: its hand-over to the
# lanes, and the last lane resolving its future)
BUCKET_TIMINGS = ("ddp_land_queue", "ddp_d2h", "ddp_h2d", "ddp_submit",
                  "comm_op_resolve")
# once a lane's share of a bucket (a ring sub-op)
SUBOP_TIMINGS = ("comm_wire_reduce", "comm_submit_wire",
                 "comm_subop_exchange", "comm_subop_reduce",
                 "comm_subop_cpu", "comm_subop_first_hop")
GONE = ("ddp_wire", "comm_reduce_future")


class _StubManager:
    """Manager facade over a raw TcpCommContext, with what the spans ask
    of a Manager: a sink labelled with the replica's id, shared with the
    transport, and a step count."""

    def __init__(self, ctx: TcpCommContext, world: int, replica: str) -> None:
        self._ctx, self._world = ctx, world
        self.metrics = Metrics()
        self.metrics.label("replica_id", replica)
        ctx.set_metrics(self.metrics)  # before configure: lanes bind it
        self.step = 0

    def current_step(self) -> int:
        return self.step

    def wait_quorum(self) -> None:
        pass

    def is_solo_wire(self) -> bool:
        return self._world == 1

    def is_participating(self) -> bool:
        return True

    def errored(self):
        return None

    def report_error(self, e) -> None:
        raise e

    def wire_compensable(self) -> bool:
        return False

    def next_wire_op(self) -> int:
        return self._ctx.next_grad_op()

    def allreduce_arrays(self, arrays, op=ReduceOp.SUM) -> Work:
        work = self._ctx.allreduce(list(arrays), ReduceOp.SUM)
        scale = 1.0 / self._world

        def _avg(f: Future):
            reduced = f.result()
            for a in reduced:
                np.multiply(a, a.dtype.type(scale), out=a)
            return reduced

        return Work(future_chain(work.future(), _avg), op=work.op)


def _grads(rank: int) -> Dict[str, np.ndarray]:
    """Five f32 leaves of 4 KiB: five buckets at ``bucket_bytes`` 4096."""
    rng = np.random.default_rng(7 + rank)
    return {f"w{i}": rng.standard_normal(1024).astype(np.float32)
            for i in range(5)}


N_BUCKETS = 5


def _run(world: int, prefix: str, trace_dir: "str | None" = None,
         streamed: bool = True, chunk_bytes: "int | None" = None,
         ) -> List[Dict[str, Any]]:
    """``STEPS`` classic steps of ``world`` groups as threads of this
    process over a ring of real sockets. Returns, a rank: the sink, and a
    pair a step of the test's own clock around ``average_gradients`` and
    the tiling the sink observed for it. ``chunk_bytes`` deals a bucket's
    chunks over both lanes: two sub-ops an op."""
    import jax

    store = StoreServer()
    ctxs = [TcpCommContext(timeout=15.0, algorithm="ring", channels=2,
                           chunk_bytes=chunk_bytes)
            for _ in range(world)]
    out: List[Dict[str, Any]] = [{} for _ in range(world)]

    def worker(rank: int) -> None:
        mgr = _StubManager(ctxs[rank], world, f"bm_{rank}_0_test")
        ctxs[rank].configure(f"{store.addr}/{prefix}", rank, world)
        ddp = DistributedDataParallel(mgr, bucket_bytes=4096,
                                      streamed=streamed)
        grads = _grads(rank)
        pairs = []
        for step in range(STEPS):
            mgr.step = 40 + step
            t0 = time.perf_counter()
            ddp.average_gradients(grads)
            outside = time.perf_counter() - t0
            timings = mgr.metrics._timings
            pairs.append((outside, sum(
                timings.get(n)[-1] for n in
                ("ddp_step_pack", "ddp_wire_exposed", "ddp_step_land_tail")
                if timings.get(n)
            )))
        out[rank] = {"metrics": mgr.metrics, "pairs": pairs}

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        with ThreadPoolExecutor(max_workers=world) as pool:
            for f in [pool.submit(worker, r) for r in range(world)]:
                f.result(timeout=120)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
        for ctx in ctxs:
            ctx.shutdown()
        store.shutdown()
    return out


@pytest.fixture(scope="module")
def classic():
    return _run(WORLD, "step_path")


@pytest.mark.parametrize("name", STEP_TIMINGS)
def test_step_timing_is_observed_once_a_classic_step(classic, name) -> None:
    for rank in classic:
        assert len(rank["metrics"]._timings[name]) == STEPS, name


@pytest.mark.parametrize("name", BUCKET_TIMINGS)
def test_bucket_timing_is_observed_once_a_bucket(classic, name) -> None:
    for rank in classic:
        assert len(rank["metrics"]._timings[name]) == STEPS * N_BUCKETS


@pytest.mark.parametrize("name", SUBOP_TIMINGS)
def test_lane_timing_is_observed_once_a_sub_op(classic, name) -> None:
    # every sub-op observes each of the five once: the same count, which
    # is at least one a bucket (a bucket this small rides one lane)
    for rank in classic:
        timings = rank["metrics"]._timings
        assert len(timings[name]) == len(timings["comm_wire_reduce"])
        assert len(timings[name]) >= STEPS * N_BUCKETS


@pytest.mark.parametrize("name", GONE)
def test_removed_timing_is_gone(classic, name) -> None:
    for rank in classic:
        assert name not in rank["metrics"]._timings


def test_a_sub_ops_seams_lie_inside_its_wall(classic) -> None:
    # structure, not speed: exchange and reduce are disjoint stretches of
    # the lane thread inside the span (summed: two lanes observe in any
    # order, so the windows do not pair up entry by entry)
    for rank in classic:
        t = rank["metrics"]._timings
        assert min(t["comm_subop_exchange"]) >= 0.0
        assert min(t["comm_subop_reduce"]) >= 0.0
        assert sum(t["comm_subop_exchange"]) + sum(t["comm_subop_reduce"]) \
            <= sum(t["comm_wire_reduce"]) + 1e-4


def test_the_first_hop_lies_inside_the_exchange(classic) -> None:
    # sub-op start -> the end of its first _ring_sendrecv: the stretch
    # before that hop is Python between the seams, so, summed, the first
    # hops fit in what the walls leave once the reductions are taken out
    for rank in classic:
        t = rank["metrics"]._timings
        assert min(t["comm_subop_first_hop"]) >= 0.0
        assert sum(t["comm_subop_first_hop"]) <= (
            sum(t["comm_wire_reduce"]) - sum(t["comm_subop_reduce"]) + 1e-4)


def test_pack_wire_tail_and_landing_tail_tile_the_call(classic) -> None:
    """``ddp_step_pack + ddp_wire_exposed + ddp_step_land_tail`` is the
    step thread's time inside ``average_gradients``, measured here from
    outside: equal within 20 % or 20 ms, whichever is larger (what is
    outside the tiling is the plan, the arena and waking the caller)."""
    for rank in classic:
        for outside, tiled in rank["pairs"]:
            assert tiled <= outside + 1e-4
            assert outside - tiled <= max(0.2 * outside, 0.020), (
                outside, tiled)


def _traced(trace_dir: str, prefix: str, **how: Any
            ) -> Dict[str, List[Any]]:
    """The ``tft.*`` spans of a traced ``_run``: {span name: [(line,
    stats)]}; a thread's line is told by its place in the plane (the
    profiler names every Python thread's line alike)."""
    from jax.profiler import ProfileData

    _run(WORLD, prefix, trace_dir=trace_dir, **how)
    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    found: Dict[str, List[Any]] = {}
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    found.setdefault(e.name[len(SPAN_PREFIX):], []).append(
                        ((p, i), dict(e.stats))
                    )
    return found


def test_spans_carry_replica_and_step_on_their_threads_lines(tmp_path) -> None:
    found = _traced(str(tmp_path), "step_path_traced")
    replicas = {f"bm_{r}_0_test" for r in range(WORLD)}
    for name, events in found.items():
        assert {s.get("replica") for _l, s in events} <= replicas, name
    # the step's marker: one a classic step a replica, on the step's thread
    packs = found["ddp_step_pack"]
    assert len(packs) == WORLD * STEPS
    assert sorted(s["step"] for _l, s in packs) == sorted(
        list(range(40, 40 + STEPS)) * WORLD)
    pack_lines = {(s["replica"], line) for line, s in packs}
    assert len(pack_lines) == WORLD  # each replica's on ONE line
    for name in ("ddp_d2h", "ddp_h2d"):
        assert len(found[name]) == WORLD * STEPS * N_BUCKETS
        for _line, stats in found[name]:
            assert 40 <= stats["step"] < 40 + STEPS and "bucket" in stats
    # the fetch + pack runs on the step's thread, a landing never does
    assert {(s["replica"], line) for line, s in found["ddp_d2h"]} \
        == pack_lines
    assert not {line for line, _s in found["ddp_h2d"]} \
        & {line for _r, line in pack_lines}
    # the lanes: on lines of their own, a line a lane of a replica
    lanes = found["comm_wire_reduce"]
    assert len(lanes) >= WORLD * STEPS * N_BUCKETS
    by_line: Dict[Any, set] = {}
    for line, stats in lanes:
        assert stats["lane"] in (0, 1)
        by_line.setdefault(line, set()).add((stats["replica"], stats["lane"]))
    assert all(len(owners) == 1 for owners in by_line.values())
    assert not set(by_line) & {line for _r, line in pack_lines}
    assert "ddp_wire" not in found and "comm_reduce_future" not in found


@pytest.fixture(scope="module", params=["streamed", "lockstep"])
def timeline(request, tmp_path_factory):
    """A traced run a code path, every bucket dealt over both lanes."""
    return _traced(
        str(tmp_path_factory.mktemp(request.param)),
        "step_path_" + request.param,
        streamed=request.param == "streamed", chunk_bytes=2048,
    )


def _by_replica(events: List[Any]) -> Dict[str, List[Dict[str, Any]]]:
    out: Dict[str, List[Dict[str, Any]]] = {}
    for _line, stats in events:
        out.setdefault(stats["replica"], []).append(stats)
    return out


def test_every_sub_op_says_whose_it_is(timeline) -> None:
    lanes = timeline["comm_wire_reduce"]
    # two sub-ops an op: a 4 KiB bucket is two chunks of 2 KiB
    assert len(lanes) == WORLD * STEPS * N_BUCKETS * 2
    for _line, stats in lanes:
        assert {"op", "bytes", "queue_us", "lane", "replica"} <= set(stats)
        assert stats["lane"] in (0, 1) and stats["bytes"] == 2048
        assert stats["queue_us"] >= 0 and "step" not in stats


def test_a_steps_spans_join_by_op_to_its_buckets(timeline) -> None:
    submits = _by_replica(timeline["ddp_submit"])
    assert len(submits) == WORLD
    for replica, mine in submits.items():
        # an op number is one bucket of one step, and a step's buckets
        # are 0 ... n-1 in the order their ops were numbered
        assert len({s["op"] for s in mine}) == len(mine) == STEPS * N_BUCKETS
        for step in range(40, 40 + STEPS):
            ops = sorted(s["op"] for s in mine if s["step"] == step)
            assert [s["bucket"] for s in sorted(
                (s for s in mine if s["step"] == step),
                key=lambda s: s["op"])] == list(range(N_BUCKETS))
            assert ops == list(range(ops[0], ops[0] + N_BUCKETS))
        bucket_of = {s["op"]: (s["step"], s["bucket"]) for s in mine}
        # the landing carries the same three
        lands = _by_replica(timeline["ddp_h2d"])[replica]
        assert sorted((s["op"], (s["step"], s["bucket"])) for s in lands) \
            == sorted(bucket_of.items())
        # the last lane resolves an op once, and every sub-op is an op's
        resolved = _by_replica(timeline["comm_op_resolve"])[replica]
        assert sorted(s["op"] for s in resolved) == sorted(bucket_of)
        subops = _by_replica(timeline["comm_wire_reduce"])[replica]
        assert {s["op"] for s in subops} == set(bucket_of)


def test_an_ops_sub_ops_carry_the_ops_bytes_between_them(timeline) -> None:
    for replica, mine in _by_replica(timeline["ddp_submit"]).items():
        subops = _by_replica(timeline["comm_wire_reduce"])[replica]
        for s in mine:
            assert s["bytes"] == 4096 == sum(
                x["bytes"] for x in subops if x["op"] == s["op"])
            assert {x["lane"] for x in subops if x["op"] == s["op"]} \
                == {0, 1}
    for _line, stats in timeline["ddp_d2h"]:
        assert stats["bytes"] == 4096


def test_ranks_that_configured_together_number_their_ops_alike(
        timeline) -> None:
    per_rank = [sorted((s["op"], s["step"], s["bucket"]) for s in mine)
                for mine in _by_replica(timeline["ddp_submit"]).values()]
    assert per_rank[0] == per_rank[1]


@pytest.fixture(scope="module")
def solo():
    (rank,) = _run(1, "step_path_solo")
    return rank


@pytest.mark.parametrize(
    "name", STEP_TIMINGS + BUCKET_TIMINGS + SUBOP_TIMINGS)
def test_a_solo_wire_step_emits_none_of_it(solo, name) -> None:
    assert name not in solo["metrics"]._timings
    assert all(tiled == 0 for _outside, tiled in solo["pairs"])
