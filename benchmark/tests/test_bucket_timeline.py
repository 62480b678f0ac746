"""``readers/bucket_timeline.py`` on periods built by hand, and the metric
files of PR 53 against the manifest. Plain data: no chip, no jax."""

import json
import os

import pytest

from benchmark.readers import bucket_timeline
from benchmark.tests import rehearse

MS = 1e-3
MB = 1_000_000

NEW = bucket_timeline.METRICS + ("lane_first_hop_ms",)


def _period(replica, line, t0, step, first_op, pack_ms, buckets):
    """The spans of one classic step that starts at ``t0`` ms: the pack,
    and per bucket ``(bytes, submit, [(lane, dequeue, end)], landing
    start)``, times in ms from ``t0``. A sub-op carries its share of the
    bytes and the wait since the submit; the last lane resolves the op for
    1 ms and the landing takes 10."""
    def at(name, ln, a, b, **stats):
        return (name, replica, ln, (t0 + a) * MS, (t0 + b) * MS, stats)

    out = [at("tft.ddp_step_pack", line, 0, pack_ms, step=step),
           at("tft.wire_wait", line, 0, pack_ms + 1, step=step)]
    for k, (nbytes, submit, subops, land) in enumerate(buckets):
        op = first_op + k
        out.append(at("tft.ddp_d2h", line, submit - 5, submit - 1,
                      bucket=k, step=step, bytes=nbytes))
        out.append(at("tft.ddp_submit", line, submit, submit + 0.5,
                      bucket=k, step=step, op=op, bytes=nbytes))
        for lane, deq, end in subops:
            out.append(at("tft.comm_wire_reduce", f"{line}.l{lane}", deq, end,
                          lane=lane, op=op, bytes=nbytes // len(subops),
                          queue_us=int((deq - submit) * 1000)))
        done = max(end for _l, _d, end in subops)
        last = next(lane for lane, _d, end in subops if end == done)
        out.append(at("tft.comm_op_resolve", f"{line}.l{last}", done,
                      done + 1, op=op))
        out.append(at("tft.ddp_h2d", f"{line}.land", land, land + 10,
                      bucket=k, step=step, op=op))
    return out


# Reading A: the lanes idle through the pack, the large bucket goes last.
# Three 10 MB buckets on a lane each, 40 ms a sub-op, 5 ms after their
# submits at 50 / 100 / 150; the 100 MB bucket is submitted at 480 and
# rides four lanes 485 - 900. Busy in the pack: 3 x 40 + 15.
A_BUCKETS = [
    (10 * MB, 50, [(0, 55, 95)], 97),
    (10 * MB, 100, [(1, 105, 145)], 147),
    (10 * MB, 150, [(2, 155, 195)], 197),
    (100 * MB, 480, [(0, 485, 900), (1, 485, 890), (2, 485, 880),
                     (3, 485, 870)], 902),
]
# Reading B: two lanes, backed up from the first submit. Six 10 MB
# buckets submitted every 20 ms from 20 on, 150 ms a sub-op, alternate
# lanes: the third pair waits 225 ms. The 100 MB bucket is submitted at
# 490 and finds lane 0 free (1 ms), lane 1 at 495.
B_BUCKETS = [
    (10 * MB, 20, [(0, 25, 175)], 177),
    (10 * MB, 40, [(1, 45, 195)], 197),
    (10 * MB, 60, [(0, 175, 325)], 327),
    (10 * MB, 80, [(1, 195, 345)], 347),
    (10 * MB, 100, [(0, 325, 475)], 477),
    (10 * MB, 120, [(1, 345, 495)], 497),
    (100 * MB, 490, [(0, 491, 700), (1, 495, 705)], 707),
]


def _trace(replica, line, buckets, periods=2, step0=7, same_step=False):
    """``periods`` periods of 1 000 ms and the pack that closes the last."""
    out, op = [], 100
    for p in range(periods + 1):
        step = step0 if same_step and p < 2 else step0 + p
        out += _period(replica, line, 1000 * p, step, op, 500, buckets)
        op += len(buckets)
    return out


OPS = {0: [("f", 10 * MS, 150 * MS), ("f", 1010 * MS, 1150 * MS)],
       1: [("f", 20 * MS, 160 * MS)]}


def test_reading_a_the_lanes_idle_through_the_pack() -> None:
    got = bucket_timeline.reduce(OPS, _trace("bm_0_0_aa", 1, A_BUCKETS))
    assert got["periods"] == 2 and got["replicas"] == 1
    assert got["left_out"] == 0 and got["unjoined"] == 0
    assert got["wire_first_busy_ms"] == pytest.approx(55.0)
    assert got["wire_idle_in_pack_ms"] == pytest.approx(500 - 135)
    assert got["wire_idle_after_pack_ms"] == pytest.approx(0.0)
    assert got["wire_last_busy_ms"] == pytest.approx(900.0)
    # 3 x 40 alone, then 415 + 405 + 395 + 385 over the 415 of the union
    assert got["lanes_busy_mean"] == pytest.approx((120 + 1600) / (120 + 415))
    assert got["big_bucket_submit_ms"] == pytest.approx(480.0)
    assert got["big_bucket_wire_ms"] == pytest.approx(420.0)
    assert got["big_bucket_queue_ms"] == pytest.approx(5.0)
    assert got["small_bucket_queue_ms"] == pytest.approx(5.0)
    # what the note checks: last - idle in - idle after = the union
    assert got["busy_ms"] == pytest.approx(900 - 365 - 0)
    # a step that one replica alone packed is a ring one wide
    assert [(w, n) for w, n, *_ in got["by_width"]] == [(1, 2)]


def test_reading_b_the_lanes_are_backed_up_from_the_first_submit() -> None:
    got = bucket_timeline.reduce(OPS, _trace("bm_1_0_bb", 4, B_BUCKETS))
    assert got["periods"] == 2
    assert got["wire_first_busy_ms"] == pytest.approx(25.0)
    assert got["wire_idle_in_pack_ms"] == pytest.approx(25.0)
    assert got["wire_idle_after_pack_ms"] == pytest.approx(0.0)
    assert got["wire_last_busy_ms"] == pytest.approx(705.0)
    # lane 0: 25 - 475 and 491 - 700, lane 1: 45 - 495 and 495 - 705,
    # over the union 25 - 705
    assert got["lanes_busy_mean"] == pytest.approx(
        (450 + 209 + 450 + 210) / 680)
    assert got["big_bucket_submit_ms"] == pytest.approx(490.0)
    assert got["big_bucket_wire_ms"] == pytest.approx(215.0)
    assert got["big_bucket_queue_ms"] == pytest.approx(3.0)   # 1 and 5
    assert got["small_bucket_queue_ms"] == pytest.approx(115.0)
    assert got["queue_ms"] == pytest.approx(60.0)  # 1 5 5 5 115 115 225 225


def test_the_ten_metrics_tell_the_two_readings_apart() -> None:
    """ISSUE 53's rule: idle in the pack >= 300 with the large bucket
    submitted after 400 is reading A; idle <= 100 with the small buckets
    queueing is reading B."""
    from benchmark import run

    a = bucket_timeline.reduce(OPS, _trace("bm_0_0_aa", 1, A_BUCKETS))
    b = bucket_timeline.reduce(OPS, _trace("bm_1_0_bb", 4, B_BUCKETS))
    assert a["wire_idle_in_pack_ms"] >= 300 <= 400 <= a["big_bucket_submit_ms"]
    assert b["wire_idle_in_pack_ms"] <= 100 <= b["small_bucket_queue_ms"]
    assert a["small_bucket_queue_ms"] < 10 and a["lanes_busy_mean"] > 3
    for name in bucket_timeline.METRICS:
        assert a[name] is not None and b[name] is not None
    assert sum(a[n] != pytest.approx(b[n])
               for n in bucket_timeline.METRICS) >= 8
    # the tenth is a key of the managers' sinks, the median over groups
    record = {"sinks": [
        {"manager": {"comm_subop_first_hop_p50_ms": v}, "replacement": False}
        for v in (2.0, 41.0, 40.0)]}
    assert run.layer_metric_value("lane_first_hop_ms", record) == 40.0
    assert run.layer_metric_value("lane_first_hop_ms", {"sinks": [
        {"manager": {}, "replacement": False}]}) is None    # the parent


def test_both_replicas_in_one_trace_and_the_note() -> None:
    spans = _trace("bm_0_0_aa", 1, A_BUCKETS) \
        + _trace("bm_1_0_bb", 4, B_BUCKETS)
    got = bucket_timeline.reduce(OPS, spans)
    assert got["periods"] == 4 and got["replicas"] == 2
    # medians over two periods of each reading
    assert got["wire_idle_in_pack_ms"] == pytest.approx((365 + 25) / 2)
    assert got["wire_last_busy_ms"] == pytest.approx((900 + 705) / 2)
    note = bucket_timeline._note(got, {"_step_path": {"wire_busy_ms": 1.5}})
    assert "(step_path's wire_busy_ms 1.5)" in note
    assert "step_path" not in bucket_timeline._note(got, {})
    mid = got["median_period"]
    rows = note.split("\n")[1:]
    assert len(rows) == len(mid["buckets"])
    assert rows[0].split()[0] == "k0" and "10.0MB" in rows[0]
    assert "joined by op, 0 spans unjoined" in note
    # the chip's busy stretches of that period, from its start: A's second
    # period on chip 0 (10 - 150 of it), B's first on chip 1 (20 - 160)
    assert ("chip 0 busy 10.0 - 150.0;" in note
            or "chip 1 busy 20.0 - 160.0;" in note)
    # both replicas packed steps 7, 8 and 9: a ring two wide
    assert got["by_width"] == [(2, 4, pytest.approx(1000.0),
                                pytest.approx(40.0), pytest.approx(802.5))]
    assert "2 wide: 4 periods of 1000.0, first busy 40.0" in note


def test_a_discarded_steps_repeated_step_makes_two_periods() -> None:
    """The step that did not commit runs again under the same ``step``:
    a bucket is its submit inside ITS pack span and joins by ``op``, so
    neither period borrows the other's spans."""
    got = bucket_timeline.reduce(
        OPS, _trace("bm_0_0_aa", 1, A_BUCKETS, same_step=True))
    assert got["periods"] == 2 and got["unjoined"] == 0
    assert got["wire_last_busy_ms"] == pytest.approx(900.0)
    assert got["lanes_busy_mean"] == pytest.approx((120 + 1600) / 535)


def test_what_does_not_join_is_counted_and_its_period_left_out() -> None:
    spans = _trace("bm_0_0_aa", 1, A_BUCKETS, periods=3)
    # the first period loses a landing; in the second a step that errored
    # before the wire left its number to the next submit (two submits, one
    # op) and a sub-op arrives whose op no submit names
    lost = next(i for i, s in enumerate(spans)
                if s[0] == "tft.ddp_h2d" and s[5]["op"] == 102)
    del spans[lost]
    twice = next(s for s in spans
                 if s[0] == "tft.ddp_submit" and s[5]["op"] == 105)
    spans.append(twice[:3] + (twice[3] - 2 * MS, twice[3] - 1 * MS, twice[5]))
    spans.append(("tft.comm_wire_reduce", "bm_0_0_aa", "1.l3", 1300 * MS,
                  1310 * MS, {"lane": 3, "op": 9, "bytes": 1, "queue_us": 0}))
    got = bucket_timeline.reduce(OPS, spans)
    assert got["periods"] == 1 and got["left_out"] == 2
    # one landing; the op claimed twice, on both submits; one stray sub-op
    assert got["unjoined"] == 1 + 2 + 1
    assert got["median_period"]["a"] == pytest.approx(2000 * MS)


def test_a_trace_with_no_survivor_or_no_submit_reads_nothing() -> None:
    # a victim and its replacement only
    spans = _trace("bm_2_0_cc", 1, A_BUCKETS) \
        + _trace("bm_2_1_dd", 4, B_BUCKETS)
    assert bucket_timeline.reduce(OPS, spans) is None
    assert bucket_timeline.reduce(OPS, []) is None          # a solo wire
    # the parent of PR 53: packs, lanes and landings, no submit and no op
    parent = [s[:5] + ({k: v for k, v in s[5].items()
                        if k in ("lane", "bucket", "step")},)
              for s in _trace("bm_0_0_aa", 1, A_BUCKETS)
              if s[0] not in ("tft.ddp_submit", "tft.comm_op_resolve")]
    assert bucket_timeline.reduce(OPS, parent) is None
    # nothing to read is nothing reported, not an error
    record = {"_bucket_timeline": None}
    for what in bucket_timeline.METRICS:
        assert bucket_timeline.read(record, {"what": what}) is None


def test_a_step_of_equal_buckets_has_no_small_ones() -> None:
    equal = [(10 * MB, 50, [(0, 55, 95)], 97), (10 * MB, 100, [(1, 105, 145)],
                                                147)]
    got = bucket_timeline.reduce(OPS, _trace("bm_0_0_aa", 1, equal))
    assert got["big_bucket_submit_ms"] == pytest.approx(50.0)  # the first
    assert got["small_bucket_queue_ms"] is None
    record = {"_bucket_timeline": got}
    assert bucket_timeline.read(
        record, {"what": "small_bucket_queue_ms"}) is None
    assert bucket_timeline.read(
        record, {"what": "big_bucket_queue_ms"}) == pytest.approx(5.0)


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
    assert entry["layer"] == "cross-replica collective"
    if name == "lane_first_hop_ms":
        assert (spec["sink"], spec["key"], entry["source"]) == (
            "manager", "comm_subop_first_hop_p50_ms", "program_span")
    else:
        assert (spec["reader"], spec["what"], entry["source"]) == (
            "bucket_timeline", name, "device_trace")
    # appended: the accepted entries stand before them, in their order
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(name) >= len(names) - len(NEW)
