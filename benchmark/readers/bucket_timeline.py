"""A gradient bucket's life on the trace's clock: submit, lane dequeue,
wire done, the lane's continuation, the landing — every bucket of a
survivor's classic step, joined by the number its op has on the wire.

Since PR 53 ``torchft_tpu`` numbers every gradient op at submit
(``TcpCommContext.next_grad_op``) and the spans of one bucket carry that
number as ``op``: ``tft.ddp_submit`` (the step's thread, inside the
step's ``tft.ddp_step_pack``, with ``bucket``, ``step`` and ``bytes``),
one ``tft.comm_wire_reduce`` a lane the op rides (``lane``, ``bytes`` of
that sub-op, ``queue_us`` from submit to dequeue; the span starts at the
dequeue), ``tft.comm_op_resolve`` (the last lane running the op's
continuations) and ``tft.ddp_h2d`` (the landing). A *period* is
``readers/step_path.py``'s: between two consecutive ``tft.ddp_step_pack``
spans of a survivor. Its buckets are the ``tft.ddp_submit`` spans inside
its pack span (a discarded step repeats its ``step``, so the step number
will not do); everything else of a bucket is found by ``op`` alone, never
by order or by time: this replaces ``step_path``'s rule of taking the
lane spans that START inside the period. A period is *whole* when every
bucket has its submit's ``op``, sub-ops whose ``bytes`` sum to the
bucket's, one resolve and one landing, and no op number is claimed by two
submits (a step that errored before the wire leaves its number to the
next); only whole periods are read, and what did not join is counted.

Medians over the whole periods of every survivor, times in ms from the
period's pack start:

``wire_first_busy_ms``      the first sub-op's dequeue.
``wire_idle_in_pack_ms``    of the pack span, the time in which no lane
                            is inside a sub-op of this step.
``wire_idle_after_pack_ms`` the same between pack end and the step's last
                            sub-op end.
``wire_last_busy_ms``       the step's last sub-op end.
``lanes_busy_mean``         sub-op seconds over the seconds at least one
                            lane is inside one: 1.0 is a wire one lane
                            wide.
``big_bucket_submit_ms``    submit of the largest bucket by ``bytes`` (the
                            first of equals).
``big_bucket_wire_ms``      that bucket: submit to its last sub-op's end.
``big_bucket_queue_ms``     median ``queue_us`` of the sub-ops of the
                            buckets within 1 % of the largest.
``small_bucket_queue_ms``   ... and of all other buckets.

One note a run: the whole period of median length as a table, a row a
bucket, and the sums the metrics above are checked against. A trace
without such a period (a solo wire, any parent of PR 53) leaves the nine
metrics out. The reader opens the run's newest ``.xplane.pb`` itself.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

from benchmark import trace_reduce
from benchmark.readers import program_spans
from benchmark.readers.device_scopes import newest_trace
from benchmark.readers.step_path import LAND, LANE, PACK, overlap, survivors

SUBMIT = "tft.ddp_submit"
RESOLVE = "tft.comm_op_resolve"

# name, replica, the thread's line, start s, end s, the span's other stats
StatSpan = Tuple[str, str, Hashable, float, float, Dict[str, Any]]

METRICS = (
    "wire_first_busy_ms", "wire_idle_in_pack_ms", "wire_idle_after_pack_ms",
    "wire_last_busy_ms", "lanes_busy_mean", "big_bucket_submit_ms",
    "big_bucket_wire_ms", "big_bucket_queue_ms", "small_bucket_queue_ms",
)


def stat_spans(profile: Any) -> List[StatSpan]:
    """``step_path.line_spans`` with each span's other stats beside it
    (that file is the accepted benchmark's and is not edited)."""
    out = []
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(program_spans.SPAN_PREFIX):
                    stats = dict(e.stats)
                    replica = stats.pop("replica", None)
                    if replica is not None:
                        out.append((
                            e.name, str(replica), i, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, stats,
                        ))
    return out


def _bucket(submit: StatSpan, by_op: Dict[str, Dict[Any, List[StatSpan]]],
            claimed: Dict[Any, int]) -> Dict[str, Any]:
    """One bucket from its submit span and whatever joins it by ``op``;
    ``missing`` names what a whole bucket would have and this one lacks."""
    _n, _r, _line, a, b, stats = submit
    op = stats.get("op")
    subops = sorted(by_op[LANE].get(op, []), key=lambda s: s[3])
    resolves = by_op[RESOLVE].get(op, [])
    lands = by_op[LAND].get(op, [])
    missing = []
    if op is None or claimed.get(op, 0) != 1:
        missing.append("op")
    if not subops or sum(s[5].get("bytes", 0) for s in subops) \
            != stats.get("bytes"):
        missing.append("subops")
    if len(resolves) != 1:
        missing.append("resolve")
    if len(lands) != 1:
        missing.append("landing")
    return {
        "k": stats.get("bucket"), "bytes": stats.get("bytes", 0), "op": op,
        "submit": a, "submitted": b, "missing": missing,
        "subops": [(s[5].get("lane"), s[3], s[4], s[5].get("queue_us", 0))
                   for s in subops],
        "resolve": (resolves[0][3], resolves[0][4]) if resolves else None,
        "land": (lands[0][3], lands[0][4]) if lands else None,
    }


def _measure(period: Dict[str, Any]) -> None:
    """The nine numbers of a whole period, into it."""
    a, pack_end, buckets = period["a"], period["pack_end"], period["buckets"]
    spans = [(x, y) for bk in buckets for _l, x, y, _q in bk["subops"]]
    busy = trace_reduce.union(spans)
    first, last = busy[0][0], max(y for _x, y in busy)
    in_pack = (pack_end - a) - overlap(busy, a, pack_end)
    after = max(0.0, last - pack_end) - overlap(busy, pack_end, last)
    largest = max(bk["bytes"] for bk in buckets)
    big = next(bk for bk in buckets if bk["bytes"] == largest)
    queues: Dict[bool, List[float]] = {True: [], False: []}
    for bk in buckets:
        queues[bk["bytes"] >= 0.99 * largest].extend(
            q * 1e-3 for _l, _x, _y, q in bk["subops"])
    period.update({
        "wire_first_busy_ms": (first - a) * 1e3,
        "wire_idle_in_pack_ms": in_pack * 1e3,
        "wire_idle_after_pack_ms": after * 1e3,
        "wire_last_busy_ms": (last - a) * 1e3,
        "lanes_busy_mean": sum(y - x for x, y in spans)
        / sum(y - x for x, y in busy),
        "big_bucket_submit_ms": (big["submit"] - a) * 1e3,
        "big_bucket_wire_ms": (max(y for _l, _x, y, _q in big["subops"])
                               - big["submit"]) * 1e3,
        "big_bucket_queue_ms": statistics.median(queues[True]),
        "small_bucket_queue_ms":
            statistics.median(queues[False]) if queues[False] else None,
        # for the note's checks against step_path's and the sinks' numbers
        "busy_ms": 1e3 * sum(y - x for x, y in busy),
        "queue_ms": statistics.median(queues[True] + queues[False]),
        "lanes": len({lane for bk in buckets
                      for lane, _x, _y, _q in bk["subops"]}),
    })


def reduce(ops: Dict[int, List[Tuple[str, float, float]]],
           spans: Sequence[StatSpan]) -> Optional[Dict[str, Any]]:
    """The reduction on plain data; ``None`` where no survivor has a
    whole period."""
    chips = len([c for c, evs in ops.items() if evs]) or 1
    busy = {chip: trace_reduce.union((a, b) for _n, a, b in evs)
            for chip, evs in ops.items()}
    # a step's ring is as wide as the replicas that packed it, victims,
    # replacements and healing joiners among them
    packed: Dict[Any, set] = {}
    for s in spans:
        if s[0] == PACK:
            packed.setdefault(s[5].get("step"), set()).add(s[1])
    periods: List[Dict[str, Any]] = []
    left_out = unjoined = 0
    for replica in survivors([s[1] for s in spans]):
        mine = [s for s in spans if s[1] == replica]
        packs = sorted((s for s in mine if s[0] == PACK), key=lambda s: s[3])
        submits = sorted((s for s in mine if s[0] == SUBMIT),
                         key=lambda s: s[3])
        if len(packs) < 2 or not submits:
            continue
        by_op: Dict[str, Dict[Any, List[StatSpan]]] = {
            LANE: {}, RESOLVE: {}, LAND: {}}
        for s in mine:
            if s[0] in by_op:
                by_op[s[0]].setdefault(s[5].get("op"), []).append(s)
        claimed: Dict[Any, int] = {}
        for s in submits:
            claimed[s[5].get("op")] = claimed.get(s[5].get("op"), 0) + 1
        # a sub-op whose op no submit of the trace names (its step began
        # before the trace did) belongs to no bucket
        stray = [s for op, found in by_op[LANE].items()
                 if op not in claimed for s in found]
        chip = program_spans.chip_of(replica, chips)
        for pack, following in zip(packs, packs[1:]):
            a, b = pack[3], following[3]
            buckets = [_bucket(s, by_op, claimed) for s in submits
                       if pack[3] <= s[3] and s[4] <= pack[4]]
            period = {
                "replica": replica, "chip": chip, "a": a, "b": b,
                "pack_end": pack[4], "step": pack[5].get("step"),
                "width": len(packed[pack[5].get("step")]),
                "period_ms": (b - a) * 1e3, "buckets": buckets,
                "unjoined": sum(len(bk["missing"]) for bk in buckets)
                + sum(1 for s in stray if a <= s[3] < b),
                "device": [(max(a, x), min(b, y))
                           for x, y in busy.get(chip, []) if y > a and x < b],
            }
            if buckets and not period["unjoined"]:
                _measure(period)
                periods.append(period)
            else:
                left_out += 1
                unjoined += period["unjoined"]
    if not periods:
        return None
    out: Dict[str, Any] = {}
    for key in METRICS + ("period_ms", "busy_ms", "queue_ms"):
        values = [p[key] for p in periods if p[key] is not None]
        out[key] = statistics.median(values) if values else None
    out["periods"] = len(periods)
    out["replicas"] = len({p["replica"] for p in periods})
    out["left_out"], out["unjoined"] = left_out, unjoined
    out["median_period"] = sorted(
        periods, key=lambda p: p["period_ms"])[len(periods) // 2]
    out["by_width"] = [
        (width, len(found)) + tuple(
            statistics.median(p[key] for p in found) for key in (
                "period_ms", "wire_first_busy_ms", "wire_last_busy_ms"))
        for width in sorted({p["width"] for p in periods})
        for found in [[p for p in periods if p["width"] == width]]]
    return out


def _ms(t: float, a: float) -> str:
    return f"{(t - a) * 1e3:.1f}"


def _note(result: Dict[str, Any], record: Dict[str, Any]) -> str:
    mid = result["median_period"]
    a = mid["a"]
    rows = []
    for bk in mid["buckets"]:
        subops = " ".join(
            f"(l{lane} {_ms(x, a)} {_ms(y, a)})"
            for lane, x, y, _q in bk["subops"])
        rows.append(
            f"k{bk['k']} {bk['bytes'] / 1e6:.1f}MB submit {_ms(bk['submit'], a)}"
            f" sub-ops (lane dequeue end) {subops} wire done "
            f"{_ms(max(y for _l, _x, y, _q in bk['subops']), a)} resolved "
            f"{_ms(bk['resolve'][1], a)} landing {_ms(bk['land'][0], a)} "
            f"{_ms(bk['land'][1], a)}")
    # the chip's busy stretches of that period, pauses under 1 ms closed
    device: List[List[float]] = []
    for x, y in mid["device"]:
        if device and x - device[-1][1] < 1e-3:
            device[-1][1] = y
        else:
            device.append([x, y])
    chip = f"chip {mid['chip']} busy " + (
        ", ".join(f"{_ms(x, a)} - {_ms(y, a)}" for x, y in device[:6])
        if device else "never")
    widths = "; ".join(
        f"{width} wide: {n} periods of {period:.1f}, first busy {first:.1f}, "
        f"last busy {last:.1f}"
        for width, n, period, first, last in result["by_width"])
    # period by period the union is last busy - idle in pack - idle after
    # pack; step_path takes the lane spans that START in the period
    theirs = (record.get("_step_path") or {}).get("wire_busy_ms")
    return (
        f"a bucket's life ({result['periods']} whole periods of "
        f"{result['replicas']} replicas joined by op, 0 spans unjoined in "
        f"them; {result['left_out']} periods left out with "
        f"{result['unjoined']} unjoined): medians - the sub-ops' union "
        f"{result['busy_ms']:.1f} ms a period"
        + ("" if theirs is None else
           f" (step_path's wire_busy_ms {theirs:.1f})")
        + f", every sub-op's queue {result['queue_ms']:.1f}; by the ring's "
        f"width (replicas that packed the step): {widths}; the period of "
        f"median length, {mid['period_ms']:.1f} ms of {mid['replica']} step "
        f"{mid['step']}, {mid['width']} wide, pack ends "
        f"{_ms(mid['pack_end'], a)}, {mid['lanes']} lanes, {chip}; ms from "
        "pack start:\n  " + "\n  ".join(rows)
    )


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_bucket_timeline" not in record:
        record["_bucket_timeline"] = None
        path = newest_trace()
        if path is not None:
            from jax.profiler import ProfileData

            profile = ProfileData.from_file(path)
            result = reduce(trace_reduce.device_lines(profile),
                            stat_spans(profile))
            if result is not None:
                record.setdefault("notes", []).append(_note(result, record))
            record["_bucket_timeline"] = result
    return record["_bucket_timeline"]


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    result = _reduction(record)
    if result is None or result[spec["what"]] is None:
        return None
    return float(result[spec["what"]])
