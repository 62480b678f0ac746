"""The kill cell's heal, from the inside: ``benchmark/run.py``'s own run
of ``c111m-x4-kill`` (its result line is printed as the driver reads it)
plus what no manifest entry holds — the joiner's and the donor's
``heal_*`` keys of ``Metrics.snapshot()``, the tiling of ``heal_wall_ms``
with its residual and, from a traced run whose window holds the heal,
the heal laid out on the trace's clock from the victim's ``tft.shutdown``.

    python scripts/heal_timeline.py --seed 2147700401 --trace 1 \
        [--window 3:27] [--sums] [--out chiprun_out/heal.json]

TPU only, four chips (~3 min warm). ``--window FROM:FOR`` moves the traced
window (seconds into the measured one; the cell's own is 2:20 and cuts a
slow recovery's end) in this process's copy of the traffic file.
``--sums`` widens the sinks' timing windows to hold every leaf of a heal,
so a span's ``_sum_ms`` is its true sum (the library keeps the newest 128
observations; a long run's percentiles then read the whole run too).
Without either, the run is ``benchmark/run.py``'s to the letter.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

_CELL = "c111m-x4-kill"
_TILES = ("heal_meta_max_ms", "heal_fetch_ms", "heal_apply_wait_ms",
          "heal_apply_max_ms")


def _heal_keys(group: Any) -> Dict[str, Any]:
    """A group's ``heal_*`` / ``shutdown*`` / ``episode_*_failed_wire``
    keys, with each timing's count and true sum beside its percentiles."""
    metrics = group.manager.metrics
    out = {k: v for k, v in metrics.snapshot().items()
           if k.startswith(("heal_", "shutdown")) or "failed_wire" in k}
    with metrics._lock:
        for name, window in metrics._timings.items():
            if name.startswith("heal_"):
                out[name + "_n"] = len(window)
                out[name + "_sum_ms"] = sum(window) * 1e3
    return out


def _timeline(path: str, victim: str, joiner: str) -> Dict[str, Any]:
    """Every ``tft.heal_*`` / ``tft.shutdown*`` event of the trace and the
    joiner's ``tft.wire_wait`` / ``tft.commit_barrier`` after its fetch,
    in seconds from the victim's ``tft.shutdown``: a row a span name and
    replica with its count, first start, last end and summed duration."""
    from jax.profiler import ProfileData

    events: List[Dict[str, Any]] = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("tft.heal_", "tft.shutdown",
                                      "tft.wire_wait", "tft.commit_barrier",
                                      "tft.quorum_wait")):
                    events.append(dict(
                        e.stats, name=e.name[4:], t0=e.start_ns * 1e-9,
                        t1=(e.start_ns + e.duration_ns) * 1e-9))
    zero = [e["t0"] for e in events
            if e["name"] == "shutdown" and e.get("replica") == victim]
    if not zero:
        return {"note": "the victim's tft.shutdown is not in the window"}
    fetched = [e["t1"] for e in events if e["name"] == "heal_fetch"]
    applied = [e["t1"] for e in events if e["name"] == "heal_apply"]
    # the joiner's own first step: from its fetch to the commit that
    # follows its apply
    commit = min((e["t1"] for e in events
                  if e["name"] == "commit_barrier"
                  and e.get("replica") == joiner
                  and applied and e["t0"] >= applied[0]), default=None)
    rows: Dict[str, Dict[str, Any]] = {}
    for e in sorted(events, key=lambda e: e["t0"]):
        who = str(e.get("replica", "?"))
        if e["name"] in ("wire_wait", "commit_barrier", "quorum_wait") and (
                who != joiner or not fetched or e["t1"] < fetched[0]
                or commit is None or e["t1"] > commit):
            continue
        if e["name"].startswith("shutdown") and who != victim:
            continue
        row = rows.setdefault(f"{e['name']} {who[:8]}", {
            "n": 0, "first_start_s": round(e["t0"] - zero[0], 4),
            "sum_s": 0.0})
        row["n"] += 1
        row["last_end_s"] = round(e["t1"] - zero[0], 4)
        row["sum_s"] = round(row["sum_s"] + e["t1"] - e["t0"], 4)
    # a fetch's body by its size: what a request costs whatever it moves
    sizes: Dict[str, Dict[str, Any]] = {}
    for wire in (e for e in events if e["name"] == "heal_wire"):
        nbytes = int(wire.get("bytes", 0))
        bucket = next(name for limit, name in (
            (1, "object"), (1 << 16, "under 64 KB"), (1 << 20, "under 1 MB"),
            (1 << 24, "under 16 MB"), (1 << 62, "16 MB and more"))
            if nbytes < limit)
        row = sizes.setdefault(bucket, {"n": 0, "bytes": 0, "wire_ms": []})
        row["n"] += 1
        row["bytes"] += nbytes
        row["wire_ms"].append(round((wire["t1"] - wire["t0"]) * 1e3, 2))
    for row in sizes.values():
        ms = sorted(row.pop("wire_ms"))
        row.update(sum_s=round(sum(ms) * 1e-3, 4), p50_ms=ms[len(ms) // 2],
                   min_ms=ms[0], max_ms=ms[-1])
    return {"rows": rows, "wire_by_size": sizes,
            "heal_events": sum(1 for e in events
                               if e["name"].startswith("heal_"))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=48.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--window", default=None, help="FROM:FOR, seconds")
    ap.add_argument("--sums", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from benchmark import run as bench_run
    from benchmark.jobs import kill_cadence
    from torchft_tpu.utils.metrics import Metrics

    if args.sums:
        Metrics.__init__.__defaults__ = (1 << 16,)  # every leaf of a heal
    seen: Dict[str, Any] = {}
    job_run = kill_cadence.run

    def run(ctx: Any) -> Dict[str, Any]:
        if args.window:
            start, length = (float(x) for x in args.window.split(":"))
            ctx.traffic = dict(ctx.traffic, trace_from_s=start,
                               trace_for_s=length)
        everyone: List[Any] = []
        build = kill_cadence._Cohort.build

        def building(self, *a, **kw):
            group = build(self, *a, **kw)
            everyone.append(group)
            return group

        kill_cadence._Cohort.build = building
        try:
            record = job_run(ctx)
        finally:
            kill_cadence._Cohort.build = build
        seen.update(ctx=ctx, kills=record["kills"], groups={
            g.manager.replica_id(): dict(
                _heal_keys(g), gid=g.gid, incarnation=g.incarnation)
            for g in everyone})
        return record

    kill_cadence.run = run
    rc = bench_run.main(["--workload", _CELL, "--seed", str(args.seed),
                         "--seconds", str(args.seconds),
                         "--trace", str(args.trace)])
    groups = seen["groups"]
    out: Dict[str, Any] = {"seed": args.seed, "trace": args.trace,
                           "groups": groups}
    # the replacements: a first incarnation heals too (from group 0, as
    # the run begins), but its window opened after that
    joiners = [r for r, g in groups.items()
               if g["incarnation"] and "heal_wall_ms" in g]
    for replica in joiners:
        g = groups[replica]
        tiles = {k: g.get(k) for k in _TILES}
        out.setdefault("tiling", {})[replica] = dict(
            tiles, heal_wall_ms=g["heal_wall_ms"],
            residual_ms=g["heal_wall_ms"] - sum(v or 0.0
                                                for v in tiles.values()))
    ctx = seen["ctx"]
    if ctx.trace_file and joiners and seen["kills"]:
        victim = next(r for r, g in groups.items()
                      if g["gid"] == seen["kills"][0]["gid"]
                      and g["incarnation"] == 0)
        out["timeline"] = _timeline(ctx.trace_file, victim, joiners[0])
    text = json.dumps(out, indent=1, default=str)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print("heal_timeline " + json.dumps(
        {k: out.get(k) for k in ("tiling", "timeline")}, default=str))
    return rc


if __name__ == "__main__":
    sys.exit(main())
