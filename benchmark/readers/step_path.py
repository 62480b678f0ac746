"""A survivor's classic step on one clock: the step thread's line, the
lanes, the landing pool and the chip, all from the one trace.

``DistributedDataParallel`` opens exactly one ``tft.ddp_step_pack`` span
a classic step, on the step's thread, with ``replica`` and ``step``
(``torchft_tpu/ddp.py``). Two consecutive ones of a replica bound a
*period*: one whole turn of its loop (pack, wait on the wire, landings,
commit, update, next quorum, next gradient dispatch). ``tft.wire_wait``
will not do as the marker: a step opens up to three, and a discarded
step repeats its ``step``. Periods are taken from every replica that is
no victim's and no replacement's (``bm_<gid>_0_*`` whose ``gid`` shows no
later incarnation in the trace); the metrics are medians over all of
them:

``period_ms``            the period.
``uncovered_ms``         of it, on the step thread's own line (the one
                         that holds the ``ddp_step_pack`` spans), what no
                         ``tft.*`` span covers: what the library does not
                         explain of a survivor's step.
``wire_busy_ms``         union of the replica's ``tft.comm_wire_reduce``
                         lane spans that start inside the period: the
                         time at least one of its lanes was executing a
                         gradient sub-op.
``land_pool_full_share`` of the period, the share in which every thread
                         that lands buckets (the lines that hold
                         ``tft.ddp_h2d`` spans: the pool is process-wide)
                         is inside one, of any replica.
``device_busy_ms``       busy union of the replica's chip inside the
                         period.

Two notes a run. The first prints the period of median length tiled by
the innermost ``tft.*`` span of the step's thread, and what is uncovered
by the ``bm.*`` span over it. The second is the check that the spans are
where the time is: pack + wire tail + landing tail read from the spans
(pack start -> pack end -> the last lane span's end -> the last landing's
end) beside the benchmark's own ``bm.average`` span around the same call,
and the sums of the library's timings of the same three from the sinks.
A trace with no such period (a solo wire, the parent of PR 35) leaves the
five metrics out. The reader opens the run's newest ``.xplane.pb`` itself.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

from benchmark import trace_reduce
from benchmark.readers import program_spans
from benchmark.readers.device_scopes import newest_trace

PACK = "tft.ddp_step_pack"
LANE = "tft.comm_wire_reduce"
LAND = "tft.ddp_h2d"
QUORUM_WAIT = "tft.quorum_wait"
AVERAGE = "bm.average"

# name, replica, the thread's line, start s, end s
LineSpan = Tuple[str, str, Hashable, float, float]
Interval = Tuple[float, float]


def line_spans(profile: Any) -> List[LineSpan]:
    """The ``tft.*`` spans that say whose they are, each with the line of
    the host plane it sits on. A thread's line is told by its place in the
    plane: the profiler names every Python thread's line alike."""
    out = []
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(program_spans.SPAN_PREFIX):
                    replica = dict(e.stats).get("replica")
                    if replica is not None:
                        out.append((
                            e.name, str(replica), i, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                        ))
    return out


def survivors(replicas: Sequence[str]) -> List[str]:
    """``bm_<gid>_0_*`` of every ``gid`` with no later incarnation."""
    by_gid: Dict[str, List[Tuple[str, str]]] = {}
    for replica in set(replicas):
        if replica.startswith(program_spans.REPLICA_PREFIX):
            parts = replica[len(program_spans.REPLICA_PREFIX):].split("_", 2)
            if len(parts) >= 2:
                by_gid.setdefault(parts[0], []).append((parts[1], replica))
    return sorted(found[0][1] for found in by_gid.values()
                  if len(found) == 1 and found[0][0] == "0")


def overlap(intervals: Sequence[Interval], a: float, b: float) -> float:
    """Seconds of ``[a, b]`` under the disjoint ``intervals``."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in intervals)


def all_inside(per_line: Sequence[Sequence[Interval]]) -> List[Interval]:
    """Where every one of two or more lines is inside one of its
    (disjoint, sorted) intervals; nothing for fewer than two lines."""
    if len(per_line) < 2:
        return []
    edges = sorted([(a, 1) for line in per_line for a, _b in line]
                   + [(b, -1) for line in per_line for _a, b in line])
    out, depth, at = [], 0, 0.0
    for t, d in edges:
        if depth == len(per_line) and t > at:
            out.append((at, t))
        depth, at = depth + d, t
    return out


def reduce(ops: Dict[int, List[Tuple[str, float, float]]],
           spans: Sequence[LineSpan],
           bm_spans: Sequence[Tuple[str, Optional[int], float, float]] = (),
           ) -> Optional[Dict[str, Any]]:
    """The reduction on plain data; ``None`` where no survivor has two
    ``ddp_step_pack`` spans."""
    chips = len([c for c, evs in ops.items() if evs]) or 1
    busy = {chip: trace_reduce.union((a, b) for _n, a, b in evs)
            for chip, evs in ops.items()}
    by_line: Dict[Hashable, List[Interval]] = {}
    for name, _r, line, a, b in spans:
        if name == LAND:
            by_line.setdefault(line, []).append((a, b))
    pool_full = all_inside([trace_reduce.union(v) for v in by_line.values()])

    periods: List[Dict[str, Any]] = []
    for replica in survivors([s[1] for s in spans]):
        mine = [s for s in spans if s[1] == replica]
        packs = sorted((s for s in mine if s[0] == PACK), key=lambda s: s[3])
        if len(packs) < 2:
            continue
        chip = program_spans.chip_of(replica, chips)
        step_line = statistics.mode(s[2] for s in packs)
        # the step thread's own line, whoever's span it is
        on_line = [(n, a, b) for n, _r, line, a, b in spans
                   if line == step_line]
        segments = program_spans.innermost(on_line)
        covered = trace_reduce.union((a, b) for _n, a, b in on_line)
        lanes = sorted((a, b) for n, _r, _l, a, b in mine if n == LANE)
        lands = sorted((a, b) for n, _r, _l, a, b in mine if n == LAND)
        waits = [(a, b) for n, _r, _l, a, b in mine if n == QUORUM_WAIT]
        calls = [(a, b) for n, c, a, b in bm_spans
                 if n == AVERAGE and c == chip]
        for pack, following in zip(packs, packs[1:]):
            a, b = pack[3], following[3]
            in_period = [(x, y) for x, y in lanes if a <= x < b]
            landed = [y for x, y in lands if a <= x < b]
            wire_end = max([pack[4]] + [y for _x, y in in_period])
            period = {
                "replica": replica, "chip": chip, "a": a, "b": b,
                "period_ms": (b - a) * 1e3,
                "tiling": program_spans.attribute([(a, b)], segments),
                "gaps": trace_reduce.gaps(covered, a, b),
                "wire_busy_ms": 1e3 * sum(
                    y - x for x, y in trace_reduce.union(in_period)),
                "land_pool_full_share": overlap(pool_full, a, b) / (b - a),
                "device_busy_ms": 1e3 * overlap(busy.get(chip, []), a, b),
                # the library's tiling of the call, off the spans' ends
                "pack_ms": (pack[4] - pack[3]) * 1e3,
                "wire_tail_ms": (wire_end - pack[4]) * 1e3,
                "land_tail_ms": (max(landed + [wire_end]) - wire_end) * 1e3,
            }
            period["uncovered_ms"] = 1e3 * sum(
                y - x for x, y in period["gaps"])
            # the benchmark's own span around the same call, and the
            # wait for the quorum inside it, which is not the wire's
            call = next(((x, y) for x, y in calls if x <= a < y), None)
            if call is not None:
                period["bm_average_ms"] = 1e3 * (call[1] - call[0])
                period["quorum_wait_ms"] = 1e3 * sum(
                    y - x for x, y in waits if call[0] <= x < call[1])
            periods.append(period)
    if not periods:
        return None

    out: Dict[str, Any] = {
        key: statistics.median(p[key] for p in periods) for key in (
            "period_ms", "uncovered_ms", "wire_busy_ms",
            "land_pool_full_share", "device_busy_ms", "pack_ms",
            "wire_tail_ms", "land_tail_ms",
        )}
    out["periods"] = len(periods)
    out["replicas"] = len({p["replica"] for p in periods})
    out["median_period"] = sorted(
        periods, key=lambda p: p["period_ms"])[len(periods) // 2]
    checked = [p for p in periods if "bm_average_ms" in p]
    if checked:
        out["bm_average_ms"] = statistics.median(
            p["bm_average_ms"] for p in checked)
        out["quorum_wait_ms"] = statistics.median(
            p["quorum_wait_ms"] for p in checked)
        ratios = [
            (p["pack_ms"] + p["wire_tail_ms"] + p["land_tail_ms"])
            / (p["bm_average_ms"] - p["quorum_wait_ms"])
            for p in checked if p["bm_average_ms"] > p["quorum_wait_ms"]
        ]
        out["tiled_over_bm_average"] = \
            statistics.median(ratios) if ratios else None
    # the benchmark's span over each piece no tft.* span covers
    under: Dict[str, float] = {}
    for p in periods:
        here = [(n, x, y) for n, c, x, y in bm_spans
                if (c is None or c == p["chip"]) and y > p["a"]
                and x < p["b"]]
        for gap in p["gaps"]:
            name = trace_reduce.attribute(gap, here)
            under[name] = under.get(name, 0.0) + (gap[1] - gap[0]) * 1e3
    out["uncovered_under"] = sorted(
        ((n, ms / len(periods)) for n, ms in under.items()),
        key=lambda kv: -kv[1])
    return out


def _sink_ms(record: Dict[str, Any], key: str) -> Optional[float]:
    """Median over the groups of one key of the managers' snapshots."""
    values = sorted(s["manager"][key] for s in record.get("sinks", [])
                    if key in s.get("manager", {}))
    return values[len(values) // 2] if values else None


def _notes(result: Dict[str, Any], record: Dict[str, Any]) -> List[str]:
    mid = result["median_period"]
    tiling = sorted(mid["tiling"].items(), key=lambda kv: -kv[1])
    notes = [
        f"a survivor's step ({result['periods']} periods of "
        f"{result['replicas']} replicas between tft.ddp_step_pack spans): "
        f"the period of median length, {mid['period_ms']:.1f} ms of "
        f"{mid['replica']}, by innermost tft.* span of the step's thread: "
        + ", ".join(f"{n} {s * 1e3:.1f}" for n, s in tiling[:8])
        + "; uncovered, ms a period by the bm.* span over it: "
        + ", ".join(f"{n} {ms:.1f}" for n, ms in result["uncovered_under"][:4])
    ]
    tiled = result["pack_ms"] + result["wire_tail_ms"] + result["land_tail_ms"]
    check = (
        f"tiling of average_gradients off the spans' ends, medians: pack "
        f"{result['pack_ms']:.1f} + wire tail {result['wire_tail_ms']:.1f} + "
        f"landing tail {result['land_tail_ms']:.1f} = {tiled:.1f} ms"
    )
    if "bm_average_ms" in result:
        ratio = result["tiled_over_bm_average"]
        check += (
            f"; bm.average around the same calls {result['bm_average_ms']:.1f}"
            f" ms with tft.quorum_wait {result['quorum_wait_ms']:.1f} inside;"
            " tiled / (bm.average - quorum_wait), median over periods: "
            + ("none" if ratio is None else f"{ratio:.4f}")
        )
    sinks = {k: _sink_ms(record, k + "_p50_ms") for k in (
        "ddp_step_pack", "ddp_wire_exposed", "ddp_step_land_tail",
    )}
    if all(v is not None for v in sinks.values()):
        three = (sinks["ddp_step_pack"] + sinks["ddp_wire_exposed"]
                 + sinks["ddp_step_land_tail"])
        check += (
            "; the sinks' p50 over the run's last steps: ddp_step_pack "
            f"{sinks['ddp_step_pack']:.1f} + ddp_wire_exposed "
            f"{sinks['ddp_wire_exposed']:.1f} + ddp_step_land_tail "
            f"{sinks['ddp_step_land_tail']:.1f} = {three:.1f} ms"
        )
    return notes + [check]


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_step_path" not in record:
        record["_step_path"] = None
        path = newest_trace()
        if path is not None:
            from jax.profiler import ProfileData

            profile = ProfileData.from_file(path)
            result = reduce(trace_reduce.device_lines(profile),
                            line_spans(profile),
                            trace_reduce.host_spans(profile))
            if result is not None:
                record.setdefault("notes", []).extend(_notes(result, record))
            record["_step_path"] = result
    return record["_step_path"]


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    result = _reduction(record)
    return None if result is None else float(result[spec["what"]])
