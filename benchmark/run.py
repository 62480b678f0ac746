"""One cell, one run:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Claims the TPU by name (no chip, or fewer chips than the cell asks for:
non-zero exit and no result line), places the compile cache at
``<checkout>/.jax_cache``, sets up, measures for ``--seconds`` and prints
one JSON line last: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``checks`` (each number compared beside its limit, also the
last lines on standard error) and, traced, ``breakdown``. With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.

This file holds no table of cells, metrics, families or jobs. The cell is
an entry of ``BENCHMARK.json``; its configuration names a family
(``families/<family>.py``), its traffic file names a job
(``jobs/<job>.py``), and each per-layer metric is
``layer_metrics/<metric>.json``, which names either a key of a
``Metrics.snapshot()`` or a reader (``readers/<reader>.py``). Which cells
report a metric is the manifest's alone to say (``_in_cell``): no file
under ``layer_metrics/`` names a cell. A later PR adds files and manifest
entries and edits nothing here. No environment variable is read.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # before any heavy import: "process start"

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)


def _load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(_ROOT, *parts)) as f:
        return json.load(f)


def _in_cell(metric: Dict[str, Any], cell: str,
             end_to_end: Sequence[Dict[str, Any]] = ()) -> bool:
    """Whether ``cell`` reports ``metric``: named in its ``workloads`` or,
    where the metric names no cells, reporting the end-to-end metric it
    ``moves`` (an end-to-end metric without a list is every cell's)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return all(_in_cell(m, cell) for m in end_to_end
               if m["name"] == metric.get("moves"))


def claim_devices(chips: int) -> Sequence[Any]:
    """The TPU, by name, and at least ``chips`` of it; the cell uses the
    first ``chips``. Raises where jax finds no accelerator."""
    from torchft_tpu.utils.device import place_compile_cache, require_tpu

    devices = require_tpu()
    if len(devices) < chips:
        raise RuntimeError(
            f"the cell needs {chips} chips, jax reports {len(devices)}"
        )
    place_compile_cache()
    return devices[:chips]


def layer_metric_value(name: str, record: Dict[str, Any]) -> Optional[float]:
    """The value of per-layer metric ``name`` from this run's record, or
    None where there is nothing to read (the metric is then left out)."""
    spec = _load_json("benchmark", "layer_metrics", name + ".json")
    if "reader" in spec:
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        return reader.read(record, spec)
    # a key of Metrics.snapshot(), of the manager's or the optimizer
    # wrapper's sink: the median over the groups that lived in the window
    # ("groups": "replacements" keeps those born in it)
    values = [s[spec["sink"]][spec["key"]] for s in record["sinks"]
              if spec["key"] in s[spec["sink"]]
              and (spec.get("groups") != "replacements" or s["replacement"])]
    if not values:
        return None
    values.sort()
    return float(values[len(values) // 2]) * float(spec.get("scale", 1.0))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, _ROOT)
    manifest = _load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = _load_json(entry["file"])
    traffic = _load_json("benchmark", "traffic", cell["traffic"] + ".json")
    family = importlib.import_module("benchmark.families." + config["family"])
    job = importlib.import_module("benchmark.jobs." + traffic["job"])

    from benchmark import harness, trace_reduce
    from benchmark.group import CompileCounter

    devices = claim_devices(int(cell["chips"]))
    ctx = harness.Context(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), chips=int(cell["chips"]), config=config,
        traffic=traffic, family=family, devices=devices, t_start=_T_START,
        boot_s=time.perf_counter() - _T_START, counter=CompileCounter(),
    )
    record = job.run(ctx)

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": harness.peak_hbm_bytes(devices),
    }
    out: Dict[str, Any] = {
        "correct": all(c["ok"] for c in record["checks"].values()),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    if not ctx.trace:
        for m in manifest["end_to_end"]:
            if _in_cell(m, args.workload):
                value = record["end_to_end"].get(m["name"])
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reduced = trace_reduce.reduce_file(
            ctx.trace_file, ctx.trace_span[1] - ctx.trace_span[0]
        )
        record["trace"] = reduced
        record["device_kind"] = device["kind"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        for m in manifest["per_layer"]:
            if _in_cell(m, args.workload, manifest["end_to_end"]):
                value = layer_metric_value(m["name"], record)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {
            "device_ops": reduced["device_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
    out["metrics"] = metrics
    out["device"] = device
    # every number compared beside its limit: before the notes for whoever
    # reads the run by hand, last in the result's line, and once more as
    # the last lines on standard error (a record of a run that is not
    # correct keeps only the two ends)
    out["checks"] = record["checks"]
    compared = [
        f"check {name}: {'ok' if check['ok'] else 'FAILED'} " + json.dumps(
            {k: v for k, v in check.items() if k != "ok"}, default=str)[:600]
        for name, check in record["checks"].items()
    ]
    print("\n".join(compared), flush=True)
    for note in record.get("notes", []):
        print("note " + note, flush=True)
    print(json.dumps(out, default=str), flush=True)
    print("\n".join(compared), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
