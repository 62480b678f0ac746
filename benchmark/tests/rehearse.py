"""Rehearsal on the CPU: any cell's job end to end through the real
``run.py`` at the test-only tiny configuration, in a temporary copy of
the benchmark whose manifest names tiny cells. Not a measurement: nothing
it prints may be written under a device metric's name.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python benchmark/tests/rehearse.py --traffic x4-kill60 --chips 4 \
        --seconds 20 --trace 1

The steering happens here, in the test, not through an option of the
benchmark: ``run.claim_devices`` is replaced by one that hands out the
CPU's virtual devices, the trace reduction is told where the CPU backend
puts its operations, and the CPU gets an entry in the copy's peaks.json.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
from typing import Any, Dict, List, Optional, Tuple

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO = os.path.dirname(_BENCH)


def make_copy(dst: str, cells: List[Dict[str, Any]],
              extra_metrics: Optional[List[Dict[str, Any]]] = None) -> str:
    """A copy of ``benchmark/`` under ``dst`` with a manifest whose cells
    are ``cells`` on the tiny configuration (every metric kept, its
    ``workloads`` filter dropped). Returns the copy's root."""
    shutil.copytree(
        _BENCH, os.path.join(dst, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", ".bench_trace"),
    )
    with open(os.path.join(_REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-test", "source": "test only",
        "file": "benchmark/tests/tiny-test.json", "reduced": [],
        "why": "rehearsal",
    }]
    manifest["workloads"] = cells
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    manifest["per_layer"] += extra_metrics or []
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    peaks = os.path.join(dst, "benchmark", "peaks.json")
    with open(peaks) as f:
        table = json.load(f)
    table["kinds"]["cpu"] = {"bf16_flops": 1e12}
    with open(peaks, "w") as f:
        json.dump(table, f)
    return dst


def cell_metrics(cell: str) -> set:
    """The per-layer metrics the real manifest lists for ``cell``."""
    with open(os.path.join(_REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    moved = {m["name"]: m for m in manifest["end_to_end"]}
    # run.py::_in_cell's rule: a metric that names no cells is reported
    # wherever the end-to-end metric it moves is
    return {m["name"] for m in manifest["per_layer"]
            if cell in m.get("workloads", moved[m["moves"]].get(
                "workloads", [cell]))}


def run_in_copy(root: str, argv: List[str]) -> Tuple[int, Dict[str, Any]]:
    """``run.main(argv)`` of the copy at ``root``, steered onto the CPU.
    Returns the exit code and the parsed last line."""
    import contextlib
    import io

    for name in [m for m in sys.modules if m.split(".")[0] == "benchmark"]:
        del sys.modules[name]
    sys.path[:0] = [root, _REPO]
    try:
        import jax

        from benchmark import run, trace_reduce

        def claim(chips: int):
            devices = jax.devices()
            if len(devices) < chips:
                raise RuntimeError(f"need {chips} devices")
            return devices[:chips]

        def cpu_device_lines(profile: Any):
            # the CPU client runs every virtual device's thunks on host
            # threads: all of it stands in for "chip 0"
            ops = [
                (e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                for plane in profile.planes for line in plane.lines
                for e in line.events if "hlo_op" in dict(e.stats)
            ]
            return {0: ops}

        run.claim_devices = claim
        trace_reduce.device_lines = cpu_device_lines
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run.main(argv)
        lines = out.getvalue().strip().splitlines()
        sys.stderr.write(out.getvalue())
        return rc, json.loads(lines[-1])
    finally:
        del sys.path[:2]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    with tempfile.TemporaryDirectory() as tmp:
        root = make_copy(tmp, [{
            "name": "tiny-cell", "config": "tiny-test",
            "traffic": args.traffic, "chips": args.chips, "why": "rehearsal",
        }])
        rc, line = run_in_copy(root, [
            "--workload", "tiny-cell", "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace),
        ])
    print(json.dumps(line, indent=1))
    return rc


if __name__ == "__main__":
    sys.exit(main())
