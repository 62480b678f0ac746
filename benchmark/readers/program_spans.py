"""Idle device time put down to what the LIBRARY was doing: the
program's own ``tft.*`` spans (``torchft_tpu.utils.profiling.span``) sit
on the host plane of the same trace, on the same clock as the device
lines, each with the ``replica`` it belongs to. The benchmark's ``bm.*``
spans say which call into the library a gap fell in; these say what the
library did inside the call.

``idle_unexplained_share``: of the idle time of the idlest chip between
its first and its last operation, the share that no ``tft.*`` span of a
replica living on that chip covers. A replica's chip is its group's:
``group.py`` names replicas ``bm_<gid>_<incarnation>_<uuid>`` and
``kill_cadence.py`` puts group ``gid`` on chip ``gid`` modulo the chips.
A program that writes no such span (the parent of PR 23) leaves the
metric out. The reader opens the run's newest ``.xplane.pb`` itself.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import trace_reduce
from benchmark.readers.device_scopes import newest_trace

SPAN_PREFIX = "tft."
REPLICA_PREFIX = "bm_"
NO_SPAN = "(no tft span)"

Span = Tuple[str, float, float]  # name, start s, end s


def program_spans(profile: Any) -> List[Tuple[str, str, float, float]]:
    """``[(name, replica id, start s, end s), ...]`` of the tft.* spans
    that say whose they are."""
    out = []
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    replica = dict(e.stats).get("replica")
                    if replica is not None:
                        out.append((
                            e.name, str(replica), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                        ))
    return out


def chip_of(replica: str, chips: int) -> Optional[int]:
    """``bm_<gid>_<incarnation>_<uuid>`` lives on chip ``gid % chips``."""
    if not replica.startswith(REPLICA_PREFIX):
        return None
    gid = replica[len(REPLICA_PREFIX):].split("_", 1)[0]
    return int(gid) % chips if gid.isdigit() else None


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Disjoint, sorted segments covering what ``spans`` cover, each
    named after the shortest span over it — the innermost, where spans
    nest; spans of different threads may also overlap in part."""
    edges = sorted(
        [(a, 1, i) for i, (_n, a, _b) in enumerate(spans)]
        + [(b, 0, i) for i, (_n, _a, b) in enumerate(spans)]
    )
    out: List[Span] = []
    active: set = set()
    at = None
    for t, opens, i in edges:
        if active and t > at:
            name = min(active, key=lambda j: spans[j][2] - spans[j][1])
            if out and out[-1][0] == spans[name][0] and out[-1][2] == at:
                out[-1] = (out[-1][0], out[-1][1], t)
            else:
                out.append((spans[name][0], at, t))
        at = t
        if opens:
            active.add(i)
        else:
            active.discard(i)
    return out


def attribute(idle: Sequence[Tuple[float, float]],
              segments: Sequence[Span]) -> Dict[str, float]:
    """Seconds of the disjoint sorted ``idle`` intervals under each
    segment's name, and under ``NO_SPAN`` what no segment covers."""
    totals: Dict[str, float] = {}
    k = 0
    for a, b in idle:
        covered = 0.0
        while k < len(segments) and segments[k][2] <= a:
            k += 1
        j = k
        while j < len(segments) and segments[j][1] < b:
            name, sa, sb = segments[j]
            overlap = min(b, sb) - max(a, sa)
            if overlap > 0:
                totals[name] = totals.get(name, 0.0) + overlap
                covered += overlap
            j += 1
        totals[NO_SPAN] = totals.get(NO_SPAN, 0.0) + (b - a) - covered
    return totals


def reduce(ops: Dict[int, List[Tuple[str, float, float]]],
           spans: Sequence[Tuple[str, str, float, float]]
           ) -> Optional[Dict[str, Any]]:
    """The reduction on plain data; ``None`` without spans or device
    operations."""
    ops = {chip: evs for chip, evs in ops.items() if evs}
    if not ops or not spans:
        return None
    busy = {chip: trace_reduce.union((a, b) for _n, a, b in evs)
            for chip, evs in ops.items()}
    chip = min(busy, key=lambda c: sum(b - a for a, b in busy[c]))
    idle = trace_reduce.gaps(busy[chip], busy[chip][0][0], busy[chip][-1][1])
    mine = [(n, a, b) for n, replica, a, b in spans
            if chip_of(replica, len(ops)) == chip]
    totals = attribute(idle, innermost(mine))
    idle_s = sum(b - a for a, b in idle)
    if idle_s <= 0:
        return None
    return {"chip": chip, "idle_s": idle_s, "totals": totals,
            "unexplained_share": totals.get(NO_SPAN, 0.0) / idle_s}


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_program_spans" not in record:
        record["_program_spans"] = None
        path = newest_trace()
        if path is not None:
            from jax.profiler import ProfileData

            profile = ProfileData.from_file(path)
            result = reduce(trace_reduce.device_lines(profile),
                            program_spans(profile))
            if result is not None:
                top = sorted(result["totals"].items(),
                             key=lambda kv: -kv[1])[:5]
                record.setdefault("notes", []).append(
                    f"idle of chip {result['chip']} (the idlest) between "
                    f"its first and last operation: {result['idle_s']:.3f}s"
                    "; by innermost tft.* span of its replicas: "
                    + ", ".join(f"{n} {s:.3f}" for n, s in top)
                )
            record["_program_spans"] = result
    return record["_program_spans"]


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    result = _reduction(record)
    return None if result is None else float(result[spec["what"]])
