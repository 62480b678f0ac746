"""From a profiler trace (``.xplane.pb``) to numbers: device busy time
as the union of the intervals in which an operation ran, the idle share,
the operations that took most time, and the longest idle gaps, each
attributed to the ``bm.*`` host span that covers most of it.

Part of the yardstick: every PR reduces its trace with this code. Device
operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane (the ``XLA Modules`` and ``Steps`` lines span
whole programs, idle time inside them included, and are not read). Their
names are the trace's own — fusion and custom-call names today; the
program gives its kernels and steps no stable name yet. Host spans are
the ``TraceAnnotation`` events named ``bm.*`` on the host plane's thread
lines; a ``chip`` argument on a span ties it to one device.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bm."

Interval = Tuple[float, float]  # seconds


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering exactly what ``intervals``
    cover (touching intervals merge)."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``[lo, hi]`` has outside the disjoint sorted ``busy``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def attribute(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The span covering most of ``gap``; of equals the shortest, which is
    the innermost. ``(no span)`` where none touches it."""
    best, best_key = "(no span)", (0.0, 0.0)
    for name, a, b in spans:
        overlap = min(b, gap[1]) - max(a, gap[0])
        if overlap > 0 and (overlap, -(b - a)) > best_key:
            best, best_key = name, (overlap, -(b - a))
    return best


def op_name(event_name: str) -> str:
    """The trace prints an operation as its whole HLO instruction,
    ``%fusion.12 = bf16[...] fusion(...)``: keep the instruction's own
    name, ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def device_lines(profile: Any) -> Dict[int, List[Tuple[str, float, float]]]:
    """``{chip: [(op name, start s, end s), ...]}`` from the trace."""
    out: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        chip = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0])
        for line in plane.lines:
            if line.name == OP_LINE:
                out.setdefault(chip, []).extend(
                    (op_name(e.name), e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                )
    return out


def host_spans(profile: Any) -> List[Tuple[str, Optional[int], float, float]]:
    """``[(name, chip or None, start s, end s), ...]`` of the bm.* spans."""
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    chip = dict(e.stats).get("chip")
                    out.append((
                        e.name, None if chip is None else int(chip),
                        e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9,
                    ))
    return out


def reduce(ops: Dict[int, List[Tuple[str, float, float]]],
           spans: Sequence[Tuple[str, Optional[int], float, float]],
           window_s: float, top: int = 10, longest: int = 5) -> Dict[str, Any]:
    """The reduction proper, on plain data (so a test can feed it numbers
    worked out by hand). ``window_s`` is the length of the traced window
    on the host's clock; gaps are looked for between the first and the
    last device operation of any chip."""
    if not ops or not any(ops.values()):
        raise ValueError("no device operation in the trace")
    lo = min(a for evs in ops.values() for _n, a, _b in evs)
    hi = max(b for evs in ops.values() for _n, _a, b in evs)
    busy_by_chip: Dict[int, float] = {}
    by_op: Dict[str, float] = {}
    found: List[Tuple[float, str, int]] = []
    for chip, evs in sorted(ops.items()):
        busy = union((a, b) for _n, a, b in evs)
        busy_by_chip[chip] = sum(b - a for a, b in busy)
        for name, a, b in evs:
            by_op[name] = by_op.get(name, 0.0) + (b - a)
        mine = [(n, a, b) for n, c, a, b in spans if c is None or c == chip]
        for gap in gaps(busy, lo, hi):
            found.append((gap[1] - gap[0], attribute(gap, mine), chip))
    by_span: Dict[str, float] = {}
    for seconds, name, _chip in found:
        by_span[name] = by_span.get(name, 0.0) + seconds
    found.sort(reverse=True)
    idle_gaps = [[f"{name} chip{chip}", s] for s, name, chip in found[:longest]]
    idle_gaps += [
        [f"total {name}", s] for name, s in
        sorted(by_span.items(), key=lambda kv: -kv[1])[:top - len(idle_gaps)]
    ]
    n = len(busy_by_chip)
    return {
        "window_s": window_s,
        "busy_s": sum(busy_by_chip.values()) / n,
        "busy_by_chip": busy_by_chip,
        # the chip that sat idle longest
        "idle_share": 1.0 - min(busy_by_chip.values()) / window_s,
        "device_ops": [[name, s] for name, s in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle_gaps,
    }


def reduce_file(path: str, window_s: float) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    return reduce(device_lines(profile), host_spans(profile), window_s)
