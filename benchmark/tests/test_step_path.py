"""``readers/step_path.py`` on numbers worked out by hand, and the metric
files of PR 35 against the manifest."""

import json
import os

import pytest

from benchmark.readers import step_path
from benchmark.tests import rehearse

MS = 1e-3

NEW = ("step_pack_ms", "step_d2h_fetch_ms", "step_d2h_copy_ms",
       "step_land_tail_ms", "land_queue_max_ms", "step_thread_cpu_ms",
       "lane_subop_ms", "lane_exchange_ms", "lane_reduce_ms",
       "step_period_ms", "step_uncovered_ms", "wire_busy_ms",
       "land_pool_full_share", "step_device_busy_ms",
       # PR 37: the four timings the reader's note carried until then
       "step_submit_ms", "land_queue_ms", "lane_cpu_ms", "lane_queue_ms")


def _spans(scale=MS):
    """Two survivors on two chips, a victim and its replacement. Replica
    A (group 0, chip 0) steps on line 1, its lanes are lines 2 and 3;
    replica B (group 1, chip 1) steps on line 4, its lane is line 5; the
    pool's two landing threads are lines 6 and 7. Times in ms."""
    a, b = "bm_0_0_aa", "bm_1_0_bb"
    rows = [
        # -- A: packs at 0, 100, 250, 400: three periods of 100, 150, 150
        ("tft.wire_wait", a, 1, 0, 60),
        ("tft.ddp_step_pack", a, 1, 0, 30),
        ("tft.ddp_d2h", a, 1, 5, 25),
        ("tft.barrier", a, 1, 70, 80),
        ("tft.wire_wait", a, 1, 100, 200),
        ("tft.ddp_step_pack", a, 1, 100, 140),
        ("tft.quorum_wait", a, 1, 210, 240),
        ("tft.wire_wait", a, 1, 250, 330),
        ("tft.ddp_step_pack", a, 1, 250, 280),
        ("tft.ddp_step_pack", a, 1, 400, 410),
        # A's lanes, partly overlapping: 10-40 u 30-55 = 45; 110-150 and
        # 160-190 = 70; one that starts in period 3 and ends past it
        ("tft.comm_wire_reduce", a, 2, 10, 40),
        ("tft.comm_wire_reduce", a, 3, 30, 55),
        ("tft.comm_wire_reduce", a, 2, 110, 150),
        ("tft.comm_wire_reduce", a, 3, 160, 190),
        ("tft.comm_wire_reduce", a, 2, 390, 420),
        # -- B: packs at 20, 220, 420: two periods of 200
        ("tft.ddp_step_pack", b, 4, 20, 50),
        ("tft.wire_wait", b, 4, 20, 120),
        ("tft.ddp_step_pack", b, 4, 220, 260),
        ("tft.ddp_step_pack", b, 4, 420, 430),
        ("tft.comm_wire_reduce", b, 5, 40, 100),
        ("tft.comm_wire_reduce", b, 5, 230, 330),
        # -- the pool: both threads inside a landing over 45-50, 185-195
        # and 300-310
        ("tft.ddp_h2d", a, 6, 40, 50),
        ("tft.ddp_h2d", b, 7, 45, 58),
        ("tft.ddp_h2d", a, 6, 180, 195),
        ("tft.ddp_h2d", b, 7, 185, 200),
        ("tft.ddp_h2d", b, 6, 300, 320),
        ("tft.ddp_h2d", a, 7, 290, 310),
        # -- the victim and its replacement: no period is theirs
        ("tft.ddp_step_pack", "bm_2_0_cc", 8, 0, 10),
        ("tft.ddp_step_pack", "bm_2_0_cc", 8, 50, 60),
        ("tft.ddp_step_pack", "bm_2_1_dd", 9, 300, 310),
        ("tft.ddp_step_pack", "bm_2_1_dd", 9, 350, 360),
        # someone else's replica id: not placed
        ("tft.ddp_step_pack", "tl_a_ee", 10, 0, 10),
        ("tft.ddp_step_pack", "tl_a_ee", 10, 90, 100),
    ]
    return [(n, r, line, x * scale, y * scale) for n, r, line, x, y in rows]


OPS = {
    # chip 0: 14 ms in A's first period, 20 in its second (one op runs
    # across the boundary at 250: 5 of its 10 count), 5 + 8 in its third
    0: [("f", 2 * MS, 16 * MS), ("f", 120 * MS, 135 * MS),
        ("f", 245 * MS, 255 * MS), ("f", 300 * MS, 308 * MS)],
    # chip 1: 30 ms in each of B's periods
    1: [("f", 60 * MS, 90 * MS), ("f", 240 * MS, 270 * MS)],
}

BM = [
    # around A's calls: 0-62 holds the pack at 0; 98-205 the pack at 100
    ("bm.average", 0, 0.0, 62 * MS), ("bm.average", 0, 98 * MS, 205 * MS),
    ("bm.update", 0, 62 * MS, 85 * MS), ("bm.quorum", 0, 85 * MS, 92 * MS),
    ("bm.grad", 0, 92 * MS, 98 * MS),
    # B's chip has none
]


def test_periods_of_two_survivors_on_two_chips() -> None:
    got = step_path.reduce(OPS, _spans(), BM)
    assert got["periods"] == 5 and got["replicas"] == 2
    # periods 100, 150, 150 (A) and 200, 200 (B)
    assert got["period_ms"] == pytest.approx(150.0)
    # A: line 1 is covered 0-60 and 70-80 of 0-100 (30 uncovered), 100-200
    # and 210-240 of 100-250 (20), 250-330 of 250-400 (70); B: line 4 is
    # covered 20-120 of 20-220 (100) and 220-260 of 220-420 (160)
    assert got["uncovered_ms"] == pytest.approx(70.0)
    # A: 45, 70, 30 (the span that starts at 390 counts whole); B: 60, 100
    assert got["wire_busy_ms"] == pytest.approx(60.0)
    # both landing threads busy: 45-50 (A1: 5/100; B1: 5/200), 185-195
    # (A2: 10/150; B1: 10/200), 300-310 (A3: 10/150; B2: 10/200)
    # -> A 0.05, 0.0667, 0.0667; B 0.075, 0.05
    assert got["land_pool_full_share"] == pytest.approx(10 / 150)
    # A: 14, 20, 13; B: 30, 30
    assert got["device_busy_ms"] == pytest.approx(20.0)
    # the tiling off the spans' ends, A's first period: pack 30, the last
    # lane span that started in it ends at 55 (25), its last landing that
    # started in it ends at 50: before the wire's end, 0. B's first: its
    # landing 185-200 ends 100 after its last lane span
    assert got["pack_ms"] == pytest.approx(30.0)       # 30 40 30 | 30 40
    assert got["wire_tail_ms"] == pytest.approx(50.0)  # 25 50 140 | 50 70
    assert got["land_tail_ms"] == pytest.approx(0.0)   # 0 5 0 | 100 0
    # bm.average holds A's first two packs: 62 and 107 ms, the second
    # with the 30 ms of quorum_wait... which starts at 210: outside it
    assert got["bm_average_ms"] == pytest.approx((62 + 107) / 2)
    assert got["quorum_wait_ms"] == pytest.approx(0.0)
    assert got["tiled_over_bm_average"] == pytest.approx(
        (55 / 62 + 95 / 107) / 2)
    # what no tft.* span covers, each piece under the benchmark's span
    # over most of it. A's first period: 60-70 is bm.update's (8 of 10),
    # 80-100 bm.quorum's (7, against 5, 6 and 2); its second: 200-210 has
    # only bm.average on it, 240-250 nothing; its third and B's chip have
    # no bm.* span at all. Per period, over the five
    under = dict(got["uncovered_under"])
    assert under == pytest.approx({
        "bm.update": 10 / 5, "bm.quorum": 20 / 5, "bm.average": 10 / 5,
        "(no span)": (10 + 70 + 100 + 160) / 5,
    })


def test_median_periods_tiling_by_innermost_span() -> None:
    got = step_path.reduce(OPS, _spans(), BM)
    mid = got["median_period"]
    # the third of five by length (a stable sort: 100, 150, 150, 200, 200)
    # is A's period from 250
    assert (mid["replica"], mid["a"], mid["period_ms"]) == (
        "bm_0_0_aa", pytest.approx(250 * MS), pytest.approx(150.0))
    assert {n: s / MS for n, s in mid["tiling"].items()} == pytest.approx({
        "tft.ddp_step_pack": 30, "tft.wire_wait": 50, "(no tft span)": 70,
    })
    notes = step_path._notes(got, {"sinks": []})
    assert len(notes) == 2 and "tft.wire_wait" in notes[0]
    assert "bm.average" in notes[1]


def test_a_trace_without_two_packs_of_a_survivor_reads_nothing() -> None:
    spans = [s for s in _spans() if s[0] != "tft.ddp_step_pack"]
    assert step_path.reduce(OPS, spans, BM) is None     # the parent
    assert step_path.reduce(OPS, [], BM) is None        # a solo wire
    one = [s for s in _spans()
           if s[0] != "tft.ddp_step_pack" or s[3] < 50 * MS]
    assert step_path.reduce(OPS, one, BM) is None       # one pack each
    # nothing to read is nothing reported, not an error
    record = {"_step_path": None}
    for what in ("period_ms", "uncovered_ms", "wire_busy_ms",
                 "land_pool_full_share", "device_busy_ms"):
        assert step_path.read(record, {"what": what}) is None


def test_one_landing_thread_is_never_a_full_pool() -> None:
    spans = [s for s in _spans() if not (s[0] == "tft.ddp_h2d" and s[2] == 7)]
    got = step_path.reduce(OPS, spans, BM)
    assert got["land_pool_full_share"] == 0.0
    assert step_path.all_inside([[(0.0, 2.0)], [(1.0, 3.0)],
                                 [(1.5, 1.8), (1.9, 5.0)]]) == [
        (1.5, 1.8), (1.9, 2.0)]


def test_survivors_are_first_incarnations_with_no_successor() -> None:
    assert step_path.survivors(
        ["bm_0_0_a", "bm_0_0_a", "bm_1_0_b", "bm_1_1_c", "bm_2_1_d",
         "tl_x_y", "bm_3_0_"]) == ["bm_0_0_a", "bm_3_0_"]


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_agrees_with_the_manifest(name) -> None:
    with open(os.path.join(rehearse._REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    with open(os.path.join(rehearse._BENCH, "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["workloads"] == ["c111m-x4-kill"]
    assert entry["moves"] == "goodput_tokens_per_s"
    assert entry["layer"] == (
        "device" if name == "step_device_busy_ms"
        else "cross-replica collective")
    assert ("reader" in spec) != ("key" in spec)
    assert entry["source"] == (
        "device_trace" if "reader" in spec else "program_span")
    if "reader" in spec:
        assert spec["reader"] == "step_path"
    else:
        assert spec["sink"] == "manager"
