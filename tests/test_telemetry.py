"""Flight recorder + telemetry plane unit tests (ISSUE 7).

Covers: the event ring's bound/ordering/cursor semantics (including
under writer concurrency), the disabled-path no-op and the emit-cost
envelope behind the "does not move allreduce p50" claim, the Chrome
trace converter, the /telemetry HTTP routes on the checkpoint server,
fleet_top's row building, and the satellites (Metrics concurrency,
throughput_span byte counters, StepProfiler as a context manager).
"""

import importlib.util
import json
import os
import sys
import threading
import time
import tracemalloc
import urllib.request

import numpy as np
import pytest

from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.comm.store import StoreServer
from torchft_tpu.comm.transport import TcpCommContext
from torchft_tpu.utils import events as events_py
from torchft_tpu.utils.events import (
    EventRecorder,
    to_chrome_trace,
    validate_chrome_trace,
)
from torchft_tpu.utils.metrics import Metrics
from torchft_tpu.utils.profiling import StepProfiler, throughput_span

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_fleet_top():
    spec = importlib.util.spec_from_file_location(
        "fleet_top", os.path.join(_REPO, "scripts", "fleet_top.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ event recorder


def test_recorder_stamps_and_cursor() -> None:
    rec = EventRecorder(capacity=64, enabled=True,
                        replica_id="rep_a", rank=3)
    s0 = rec.emit("quorum_start", step=5, epoch=2)
    s1 = rec.emit("quorum_complete", step=5, epoch=2, wire_world=2)
    assert (s0, s1) == (0, 1)
    events, nxt, dropped = rec.since(0)
    assert nxt == 2 and dropped == 0
    assert [e["seq"] for e in events] == [0, 1]
    e = events[1]
    assert e["kind"] == "quorum_complete"
    assert e["replica_id"] == "rep_a" and e["rank"] == 3
    assert e["step"] == 5 and e["epoch"] == 2 and e["wire_world"] == 2
    assert e["t_wall"] > 0 and e["t_mono"] > 0
    # incremental poll: the cursor picks up exactly the new tail
    rec.emit("step_commit", step=5, epoch=2)
    tail, nxt2, dropped = rec.since(nxt)
    assert [e["kind"] for e in tail] == ["step_commit"]
    assert nxt2 == 3 and dropped == 0
    assert rec.since(nxt2)[0] == []


def test_recorder_ring_bound_and_drop_accounting() -> None:
    rec = EventRecorder(capacity=8, enabled=True)
    for i in range(20):
        rec.emit("step_commit", step=i)
    events, nxt, dropped = rec.since(0)
    assert nxt == 20
    assert len(events) == 8  # never exceeds the bound
    assert dropped == 12  # overwrites are reported, never silent
    seqs = [e["seq"] for e in events]
    assert seqs == list(range(12, 20))  # contiguous, oldest first
    # a cursor inside the live window drops nothing
    events, _, dropped = rec.since(15)
    assert dropped == 0 and [e["seq"] for e in events] == [15, 16, 17, 18, 19]


def test_recorder_disabled_is_noop() -> None:
    rec = EventRecorder(capacity=16, enabled=False)
    assert not rec  # the hot-path guard
    assert rec.emit("step_commit", step=1) == -1
    assert rec.next_seq == 0
    assert rec.since(0) == ([], 0, 0)
    assert rec.dump()["events"] == []
    # env-var contract
    os.environ["TORCHFT_TPU_EVENTS"] = "0"
    try:
        assert not EventRecorder().enabled
    finally:
        del os.environ["TORCHFT_TPU_EVENTS"]
    assert EventRecorder().enabled


def test_recorder_concurrent_writers_ordered_and_bounded() -> None:
    """Satellite: N writers racing readers — seq numbers stay unique and
    ordered, the ring never exceeds its bound, reads never raise."""
    rec = EventRecorder(capacity=128, enabled=True)
    writers, per = 8, 500
    errors = []
    stop = threading.Event()

    def _write(w: int) -> None:
        try:
            for i in range(per):
                rec.emit("step_commit", step=i, writer=w)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def _read() -> None:
        try:
            while not stop.is_set():
                events, nxt, _ = rec.since(max(0, nxt0[0] - 50))
                seqs = [e["seq"] for e in events]
                assert seqs == sorted(seqs)
                assert len(seqs) == len(set(seqs))
                assert len(events) <= 128
                nxt0[0] = nxt
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    nxt0 = [0]
    threads = [threading.Thread(target=_write, args=(w,))
               for w in range(writers)]
    reader = threading.Thread(target=_read)
    reader.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    stop.set()
    reader.join(timeout=30)
    assert not errors
    assert rec.next_seq == writers * per  # no emit lost or duplicated
    events, nxt, dropped = rec.since(0)
    assert nxt == writers * per
    assert len(events) == 128 and dropped == writers * per - 128
    seqs = [e["seq"] for e in events]
    assert seqs == list(range(nxt - 128, nxt))


def _calls_made(fn) -> int:
    """How many functions, Python's and C's, ``fn()`` enters on this
    thread (``sys.setprofile`` is per thread: nobody else's work counts)."""
    def _count(run) -> int:
        n = 0

        def _on(frame, event, arg):
            nonlocal n
            n += event in ("call", "c_call")

        sys.setprofile(_on)
        try:
            run()
        finally:
            sys.setprofile(None)
        return n

    return _count(fn) - _count(lambda: None)


def _bytes_held_by_events_py() -> int:
    return sum(
        st.size for st in tracemalloc.take_snapshot().filter_traces(
            [tracemalloc.Filter(True, events_py.__file__)]
        ).statistics("filename"))


def test_emit_overhead_envelope() -> None:
    """The overhead pin behind the acceptance criterion: the manager
    emits a handful of events per step, and what one emit costs is
    COUNTED, not timed (a CPU's microseconds say nothing): an emit is one
    append, enters under ten functions however full the ring and however
    long the run (``emit``, two clocks, the lock's two ends), and once
    the ring has wrapped the recorder holds no more memory than it held;
    a disabled recorder behind the hot paths' guard enters one function
    (``__bool__``), appends nothing and allocates nothing."""
    n = 20000
    tracemalloc.start()
    try:
        off = EventRecorder(capacity=256, enabled=False)

        def _guarded() -> None:
            if off:  # the allocation-free guard hot paths use
                off.emit("step_commit", step=0)

        assert _calls_made(_guarded) == 1
        idle = _bytes_held_by_events_py()
        for _ in range(n):
            _guarded()
        assert _bytes_held_by_events_py() == idle
        assert off.next_seq == 0

        rec = EventRecorder(capacity=256, enabled=True)
        first = _calls_made(lambda: rec.emit("step_commit", step=0, epoch=7))
        assert 0 < first < 10, first
        # (steps and seqs past CPython's shared small ints on both sides)
        for i in range(n, n + 1024):
            rec.emit("step_commit", step=i, epoch=7)
        full = _bytes_held_by_events_py()
        for i in range(n):
            rec.emit("step_commit", step=n + i, epoch=7)
        assert _bytes_held_by_events_py() - full <= full // 256  # a record
        assert rec.next_seq == 1 + 1024 + n
        assert _calls_made(
            lambda: rec.emit("step_commit", step=n, epoch=7)) == first
    finally:
        tracemalloc.stop()


def test_allreduce_unmoved_by_enabled_recorder() -> None:
    """End-to-end pin: per-step emits (the manager's real event load)
    around a live 2-rank loopback allreduce change nothing the op gives
    back, and every emit is appended — none lost, none waiting on the
    wire. Arms are rep-interleaved on the SAME configured transport. What
    an emit costs is counted above; a CPU p50 against a CPU p50 said
    nothing about either (ROADMAP: a CPU run never yields a time)."""
    store = StoreServer()
    world = 2
    ctxs = [TcpCommContext(timeout=20.0, algorithm="star", channels=2)
            for _ in range(world)]
    rec = EventRecorder(capacity=4096, enabled=True)
    rng = np.random.default_rng(0)
    payload = [rng.standard_normal(1 << 15).astype(np.float32)
               for _ in range(world)]  # 128KB
    reps_per_arm, arms = 10, 2  # interleaved: off, on, off, on
    sums: "dict[bool, list]" = {False: [], True: []}
    try:
        def _configure(rank):
            ctxs[rank].configure(f"{store.addr}/events_ab", rank, world)

        tcfg = [threading.Thread(target=_configure, args=(r,))
                for r in range(world)]
        for t in tcfg:
            t.start()
        for t in tcfg:
            t.join(timeout=30)

        def _rank_loop(rank, emit):
            for i in range(reps_per_arm):
                w = ctxs[rank].allreduce([payload[rank].copy()])
                if emit and rank == 0:
                    # the manager's realistic per-step event load
                    for _ in range(4):
                        rec.emit("step_commit", step=i, epoch=1)
                out = w.future().result(timeout=30)
                if rank == 0:
                    sums[emit].append(np.array(out[0], copy=True))

        for arm in range(arms):
            for emit in (False, True):
                ts = [threading.Thread(target=_rank_loop, args=(r, emit))
                      for r in range(world)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=60)
                assert rec.next_seq == 4 * reps_per_arm * (arm + emit)
    finally:
        for c in ctxs:
            c.shutdown()
        store.shutdown()
    assert len(sums[False]) == len(sums[True]) == reps_per_arm * arms
    for got in sums[False] + sums[True]:
        np.testing.assert_array_equal(got, sums[False][0])


# ------------------------------------------------------------- chrome export


def _mk_dump(rid, rank, events):
    rec = EventRecorder(capacity=256, enabled=True,
                        replica_id=rid, rank=rank)
    for kind, kw in events:
        rec.emit(kind, **kw)
    return rec.dump()


def test_to_chrome_trace_pairs_and_tracks() -> None:
    d0 = _mk_dump("rep_a", 0, [
        ("quorum_start", dict(step=1, epoch=1)),
        ("quorum_complete", dict(step=1, epoch=1, wire_world=2)),
        ("step_commit", dict(step=1, epoch=1)),
        ("member_dead", dict(step=2, epoch=2, member="rep_b")),
    ])
    d1 = _mk_dump("rep_b", 0, [
        ("heal_start", dict(step=0, epoch=2)),
        ("heal_done", dict(step=3, epoch=2, wall_ms=12.5)),
        ("step_commit", dict(step=3, epoch=2)),
    ])
    trace = json.loads(json.dumps(to_chrome_trace([d0, d1])))
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    # one process track per replica
    procs = {e["args"]["name"] for e in evs if e["name"] == "process_name"}
    assert procs == {"replica rep_a", "replica rep_b"}
    pids = {e["pid"] for e in evs if e["ph"] != "M"}
    assert len(pids) == 2
    # paired kinds became duration slices with the merged args
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(spans) == {"quorum", "heal"}
    assert spans["quorum"]["dur"] >= 0
    assert spans["heal"]["args"]["wall_ms"] == 12.5
    # unpaired lifecycle events are instants carrying their fields
    instants = {e["name"] for e in evs if e["ph"] == "i"}
    assert {"step_commit", "member_dead"} <= instants
    md = [e for e in evs if e["name"] == "member_dead"][0]
    assert md["args"]["member"] == "rep_b"


def test_to_chrome_trace_unclosed_span_degrades_to_instant() -> None:
    d = _mk_dump("rep_c", 1, [
        ("quorum_start", dict(step=9, epoch=4)),  # crash before complete
    ])
    trace = to_chrome_trace([d])
    assert validate_chrome_trace(trace) == []
    names = [(e["name"], e["ph"]) for e in trace["traceEvents"]
             if e["ph"] != "M"]
    assert ("quorum_start", "i") in names
    assert not any(ph == "X" for _, ph in names)


def test_validate_chrome_trace_catches_garbage() -> None:
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "pid": 1}]}
    ) != []


# -------------------------------------------------------- telemetry endpoints


def test_telemetry_endpoints_serve_without_checkpoint_gate() -> None:
    """/telemetry must answer while the checkpoint gate is CLOSED (no
    staged checkpoint at all) — a fleet poller hits mid-step managers."""
    server = CheckpointServer(timeout=5.0)
    metrics = Metrics()
    rec = EventRecorder(capacity=64, enabled=True,
                        replica_id="rep_t", rank=0)
    state = {"step": 7}
    server.set_metrics(metrics)
    server.set_events(rec)
    server.set_telemetry(lambda: {
        "replica_id": "rep_t", "rank": 0, "step": state["step"],
        "epoch": 3, "comm_backend": "host",
    })
    try:
        metrics.incr("steps_committed", 5)
        metrics.gauge("heal_wall_ms", 17.0)
        metrics.observe("allreduce", 0.002)
        metrics.label("comm_backend", "host")
        rec.emit("quorum_start", step=7, epoch=3)
        rec.emit("quorum_complete", step=7, epoch=3, wire_world=2)

        base = server.metadata()
        with urllib.request.urlopen(
            base + "/telemetry/metrics", timeout=5
        ) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            m = json.load(resp)
        assert m["replica_id"] == "rep_t" and m["step"] == 7
        assert m["epoch"] == 3
        assert m["metrics"]["steps_committed"] == 5.0
        assert m["metrics"]["heal_wall_ms"] == 17.0
        assert m["metrics"]["comm_backend"] == "host"
        assert m["metrics"]["allreduce_p50_ms"] > 0

        with urllib.request.urlopen(
            base + "/telemetry/events?since=0", timeout=5
        ) as resp:
            ev = json.load(resp)
        assert ev["replica_id"] == "rep_t" and ev["enabled"] is True
        assert [e["kind"] for e in ev["events"]] == [
            "quorum_start", "quorum_complete",
        ]
        assert ev["next"] == 2 and ev["dropped"] == 0
        # seq-cursored incremental poll
        rec.emit("step_commit", step=7, epoch=3)
        with urllib.request.urlopen(
            base + f"/telemetry/events?since={ev['next']}", timeout=5
        ) as resp:
            tail = json.load(resp)
        assert [e["kind"] for e in tail["events"]] == ["step_commit"]
        # bad cursor is a 400, not a traceback
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                base + "/telemetry/events?since=abc", timeout=5
            )
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/telemetry/nope", timeout=5)
        assert ei.value.code == 404
    finally:
        server.shutdown()


def test_telemetry_endpoints_unwired_server_still_answers() -> None:
    server = CheckpointServer(timeout=5.0)
    try:
        base = server.metadata()
        with urllib.request.urlopen(
            base + "/telemetry/events", timeout=5
        ) as resp:
            ev = json.load(resp)
        assert ev["events"] == [] and ev["enabled"] is False
        with urllib.request.urlopen(
            base + "/telemetry/metrics", timeout=5
        ) as resp:
            assert json.load(resp)["metrics"] == {}
    finally:
        server.shutdown()


# ------------------------------------------------------------------ fleet_top


def test_fleet_top_rows_from_live_endpoint() -> None:
    ft = _load_fleet_top()
    server = CheckpointServer(timeout=5.0)
    metrics = Metrics()
    rec = EventRecorder(capacity=64, enabled=True,
                        replica_id="rep_f", rank=0)
    server.set_metrics(metrics)
    server.set_events(rec)
    server.set_telemetry(lambda: {
        "replica_id": "rep_f", "rank": 0, "step": 11, "epoch": 4,
        "healing": False,
    })
    try:
        metrics.incr("steps_committed", 9)
        metrics.incr("steps_discarded", 1)
        metrics.observe("allreduce", 0.004)
        metrics.gauge("outer_overlap", 0.5)
        rec.emit("step_commit", step=11, epoch=4)
        polled = ft.poll_manager(server.metadata(), 0, timeout=5.0)
        ep = {"replica_id": "rep_f", "rank": 0, "url": server.metadata()}
        row = ft.build_row(ep, polled)
        assert row["step"] == 11 and row["epoch"] == 4
        assert row["committed"] == 9.0 and row["discarded"] == 1.0
        assert row["allreduce_p50_ms"] > 0
        assert row["outer_overlap"] == 0.5
        assert row["last_event"].startswith("step_commit")
        text = ft.render({"quorum": {"participants": [{}]}}, [row])
        assert "rep_f" in text and "step_commit" in text
        # unreachable rows render without raising
        bad = ft.build_row(ep, None, error="ConnectionRefusedError")
        assert "UNREACHABLE" in ft.render({}, [bad])
        # a snapshot taken BETWEEN the overlap pair's two observations
        # (wire_total present, wire_exposed not yet) must not crash
        torn = ft.build_row(ep, {
            "metrics": {"metrics": {"ddp_wire_total_avg_ms": 5.0}},
            "events": {"events": []},
        })
        assert torn["ddp_overlap"] is None
        # an empty incremental poll keeps the cached last event (with a
        # growing age) instead of blanking the column
        cached = {"kind": "step_commit", "t_wall": time.time() - 3.0}
        quiet = ft.build_row(
            ep, {"metrics": {"metrics": {}}, "events": {"events": []}},
            last_event=cached,
        )
        assert quiet["last_event"].startswith("step_commit")
        trace = ft.gather_trace([ep], timeout=5.0)
        assert validate_chrome_trace(trace) == []
        assert any(
            e["name"] == "step_commit" for e in trace["traceEvents"]
        )
    finally:
        server.shutdown()


def test_fleet_top_mesh_and_mode_columns_live() -> None:
    # ISSUE 16: the mesh column is the manager's "{replicas}x{model}"
    # label; mode derives from the fused plane's step_executable_count
    # gauge (1 = fused single-executable arm, >=2 = staged A/B arm).
    ft = _load_fleet_top()
    server = CheckpointServer(timeout=5.0)
    metrics = Metrics()
    server.set_metrics(metrics)
    server.set_telemetry(lambda: {
        "replica_id": "rep_m", "rank": 0, "step": 3, "healing": False,
    })
    try:
        metrics.label("mesh_shape", "2x2")
        metrics.gauge("step_executable_count", 1.0)
        ep = {"replica_id": "rep_m", "rank": 0, "url": server.metadata()}
        row = ft.build_row(
            ep, ft.poll_manager(server.metadata(), 0, timeout=5.0)
        )
        assert row["mesh"] == "2×2"
        assert row["mode"] == "fused"
        text = ft.render({"quorum": {"participants": [{}]}}, [row])
        assert "2×2" in text and "fused" in text
        # staged arm: four executables dispatched per step
        metrics.gauge("step_executable_count", 4.0)
        row2 = ft.build_row(
            ep, ft.poll_manager(server.metadata(), 0, timeout=5.0)
        )
        assert row2["mode"] == "staged"
        # a replica that never ran the fused plane renders "-", no crash
        bare = ft.build_row(
            ep, {"metrics": {"metrics": {}}, "events": {"events": []}}
        )
        assert bare["mesh"] is None and bare["mode"] is None
        assert "rep_m" in ft.render({}, [bare])
    finally:
        server.shutdown()


# ------------------------------------------------------------------ satellites


def test_metrics_concurrent_writers_exact_counters() -> None:
    """Satellite: N writer threads racing snapshot/reset_timings —
    snapshot never raises and counters land exactly."""
    m = Metrics(window=64)
    writers, per = 8, 400
    errors = []
    stop = threading.Event()

    def _write(w):
        try:
            for i in range(per):
                m.incr("c")
                m.incr("bytes", 3.0)
                m.observe(f"t{w % 2}", 0.001)
                m.gauge("g", float(i))
                m.label("backend", "host")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def _read():
        try:
            while not stop.is_set():
                snap = m.snapshot()
                assert snap.get("c", 0) <= writers * per
                m.reset_timings()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=_write, args=(w,))
               for w in range(writers)]
    reader = threading.Thread(target=_read)
    reader.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    stop.set()
    reader.join(timeout=30)
    assert not errors
    snap = m.snapshot()
    assert snap["c"] == writers * per
    assert snap["bytes"] == writers * per * 3.0
    assert snap["backend"] == "host"


def test_throughput_span_cumulative_byte_counter() -> None:
    """Satellite: throughput_span now also incrs a {name}_bytes counter
    so bandwidth is integrable across a run (the rate gauge alone is
    last-write-wins)."""
    m = Metrics()
    with throughput_span(m, "heal_wire", 1000):
        time.sleep(0.001)
    with throughput_span(m, "heal_wire", 500):
        time.sleep(0.001)
    late = [0]
    with throughput_span(m, "heal_wire", late):
        late[0] = 250  # byte count only known at exit
    snap = m.snapshot()
    assert snap["heal_wire_bytes"] == 1750.0  # cumulative
    assert snap["heal_wire_bytes_per_s"] > 0  # last-write-wins rate
    assert snap["heal_wire_avg_ms"] > 0
    # zero-byte spans record time but no byte keys
    m2 = Metrics()
    with throughput_span(m2, "x", 0):
        pass
    assert "x_bytes" not in m2.snapshot()


def test_step_profiler_context_manager_closes_trace() -> None:
    """Satellite: StepProfiler is a context manager whose __exit__ calls
    close() — no reliance on __del__ to stop an open trace."""
    with StepProfiler(log_dir=None) as prof:  # disabled: pure no-op
        assert not prof.enabled
        prof.step()
    assert prof._done

    class _FakeProfiler:
        def __init__(self):
            self.started = []
            self.stopped = 0

        def start_trace(self, d):
            self.started.append(d)

        def stop_trace(self):
            self.stopped += 1

    import jax

    fake = _FakeProfiler()
    real = jax.profiler
    jax.profiler = fake
    try:
        with StepProfiler(log_dir="/tmp/x", start=0, num_steps=100) as prof:
            prof.step()  # opens the trace at step 0
            assert fake.started == ["/tmp/x"]
        # the block ended inside the window: __exit__ must stop the trace
        assert fake.stopped == 1
        assert prof._done and not prof._active
        prof.close()  # idempotent
        assert fake.stopped == 1
    finally:
        jax.profiler = real
