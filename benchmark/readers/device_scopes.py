"""Device time by the program's own scopes: which share of a chip's busy
time ran under ``attn``, ``mlp``, ``lm_head_xent``, ``embed``,
``opt_update`` (the ``jax.named_scope``s of ``models/transformer.py`` and
the step programs), and which under none of them. The metric's file
names the share: ``{"reader": "device_scopes", "what": "xent"}``.

Where the scope path comes from (looked at by hand on the v5e, PR 23):
under the harness's profiler options (``enable_hlo_proto=False``) an
``XLA Ops`` event is named by its whole HLO instruction and carries three
stats (``device_offset_ps``, ``device_duration_ps``, ``Time Scale
Multiplier``) — no ``metadata={op_name=...}``, no ``tf_op``. So the
program hands the table over: ``torchft_tpu.utils.profiling.scope_tables``
gives, per step program it ran (``jit_tft_train_step``...), instruction
name -> ``op_name`` from the compiled text. An event is matched through
the ``XLA Modules`` event that contains it. A program without that
function (the parent of PR 23) yields no table, and every share is left
out.

Self time: the ``XLA Ops`` line nests — a ``while`` event spans its
body's events, a ``checkpoint`` call its callee's — so an event counts
for its duration less that of the events directly inside it, and the
self times of a chip add up to its busy time.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import harness, trace_reduce

MODULE_LINE = "XLA Modules"

# scope as it stands in an op_name path -> the share's name
SCOPES = {
    "lm_head_xent": "xent", "attn": "attn", "mlp": "mlp",
    "opt_update": "opt", "embed": "embed",
}
UNNAMED = "unnamed"

Op = Tuple[str, float, float]  # name, start s, end s


def newest_trace() -> Optional[str]:
    """The run's trace: the newest ``.xplane.pb`` under the harness's
    trace directory (the record holds only the reduction)."""
    found = glob.glob(os.path.join(
        harness.TRACE_DIR, "*", "plugins", "profile", "*", "*.xplane.pb"
    ))
    return max(found, key=os.path.getmtime) if found else None


def self_times(ops: Sequence[Op]) -> List[Tuple[str, float, float]]:
    """``[(name, start, self seconds), ...]`` of one chip's line: each
    event's duration less the durations of the events directly nested in
    it. An event is nested in the nearest earlier event that spans it
    whole; one that only overlaps an earlier event (two threads on one
    line, as in the CPU rehearsal) is nobody's child."""
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []      # (end, index into out)
    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] < b:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= b - a
        out.append([name, a, b - a])
        stack.append((b, len(out) - 1))
    return [(n, a, s) for n, a, s in out]


def classify(path: Optional[str]) -> Tuple[str, str]:
    """``(share, direction)`` of one ``op_name`` path, e.g.
    ``jit(tft_train_step)/transpose(jvp(attn))/dot_general`` ->
    ``("attn", "backward")``. The outermost scope of the list decides;
    ``rematted_computation`` in the path marks the forward pass run
    again under ``jax.checkpoint``, ``transpose`` the backward pass."""
    if not path:
        return UNNAMED, "forward"
    tokens = path.replace("(", "/").replace(")", "/").split("/")
    share = next((SCOPES[t] for t in tokens if t in SCOPES), UNNAMED)
    if "rematted_computation" in tokens:
        return share, "recomputed"
    return share, "backward" if "transpose" in tokens else "forward"


def module_lines(profile: Any) -> Dict[int, List[Op]]:
    """``{chip: [(program name, start s, end s), ...]}``: the trace
    prints a program as ``jit_tft_train_step(<fingerprint>)``."""
    out: Dict[int, List[Op]] = {}
    prefix = trace_reduce.DEVICE_PLANE_PREFIX
    for plane in profile.planes:
        if not plane.name.startswith(prefix):
            continue
        chip = int(plane.name[len(prefix):].split()[0])
        for line in plane.lines:
            if line.name == MODULE_LINE:
                out.setdefault(chip, []).extend(
                    (e.name.split("(", 1)[0], e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                )
    return out


def reduce(ops: Dict[int, List[Op]], modules: Dict[int, List[Op]],
           tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """The reduction on plain data. ``None`` where no event finds its
    scope path (no table: nothing to say, as against "all unnamed")."""
    seconds: Dict[Tuple[str, str], float] = {}
    by_program: Dict[str, float] = {}
    matched, no_path_s = 0, 0.0
    for chip, events in ops.items():
        programs = sorted(modules.get(chip, []), key=lambda m: m[1])
        at = 0
        for name, start, self_s in self_times(events):
            while at < len(programs) and programs[at][2] <= start:
                at += 1
            program = programs[at][0] if (
                at < len(programs) and programs[at][1] <= start
            ) else ""
            by_program[program] = by_program.get(program, 0.0) + self_s
            path = tables.get(program, {}).get(name)
            if path is None and not program:
                # a trace without a programs line (the CPU rehearsal)
                path = next((t[name] for t in tables.values() if name in t),
                            None)
            matched += path is not None
            if path is None:
                no_path_s += self_s
            key = classify(path)
            seconds[key] = seconds.get(key, 0.0) + self_s
    total = sum(seconds.values())
    if not matched or total <= 0:
        return None
    shares = {share: 0.0 for share in list(SCOPES.values()) + [UNNAMED]}
    for (share, _direction), s in seconds.items():
        shares[share] += s / total
    return {"shares": shares, "seconds": seconds, "by_program": by_program,
            "total_s": total, "no_path_s": no_path_s}


def _note(result: Dict[str, Any]) -> str:
    parts = []
    for share in list(SCOPES.values()) + [UNNAMED]:
        split = " ".join(
            f"{d[:3]} {result['seconds'][(share, d)]:.3f}"
            for d in ("forward", "backward", "recomputed")
            if (share, d) in result["seconds"]
        )
        parts.append(f"{share} [{split}]")
    programs = ", ".join(
        f"{name or '(no program)'} {s:.3f}" for name, s in
        sorted(result["by_program"].items(), key=lambda kv: -kv[1])[:6]
    )
    return (f"device seconds by scope (self time, all chips, of "
            f"{result['total_s']:.3f}): " + "; ".join(parts)
            + f" | {result['no_path_s']:.3f} of unnamed in operations the "
            f"tables give no path for | by program: {programs}")


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_device_scopes" not in record:
        record["_device_scopes"] = None
        from torchft_tpu.utils import profiling

        scope_tables = getattr(profiling, "scope_tables", None)
        path = newest_trace()
        if scope_tables is not None and path is not None:
            from jax.profiler import ProfileData

            profile = ProfileData.from_file(path)
            result = reduce(trace_reduce.device_lines(profile),
                            module_lines(profile), scope_tables())
            if result is not None:
                record.setdefault("notes", []).append(_note(result))
            record["_device_scopes"] = result
    return record["_device_scopes"]


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    result = _reduction(record)
    return None if result is None else float(result["shares"][spec["what"]])
