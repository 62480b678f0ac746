"""The Keye family's kernels' shares of their rooflines and the shares of
the chip's busy time under the four scopes of its sparse attention
(``torchft_tpu/models/keye.py``: ``dsa_index``, ``dsa_select``,
``dsa_core``, ``dsa_kl``). The shares this family reports besides are
other readers', unedited: ``gqa`` (``ssm_scopes``), the sparse
sublayer's (``moe_scopes``). What to read is the caller's to name:
``{"reader": "keye_scopes", "what": "dsa_index" | "dsa_select" |
"dsa_core" | "dsa_kl" | "dsa_select_roofline" | "dsa_fwd_roofline" |
"dsa_dq_roofline" | "dsa_dkv_roofline" | "dsa_kl_roofline" |
"selected_share"}``. THE MANIFEST'S 128 PER-LAYER PLACES ARE FULL
(``CHANGES.md``, PRs 63 and 66), so no file under ``layer_metrics/``
names this reader yet: ``benchmark/tests/keye_rooflines.py`` prints all
ten from a traced run, by hand.

Read with ``device_scopes``' own functions (the newest trace, self
times, the programs line, the program's instruction -> ``op_name``
tables), so a share here has the denominator of the six shares there:
the busy time of the chip.

``*_roofline``: the least time the chip could take for what the MODEL
needs of that kernel in a layer of a step — ``benchmark/keye_flops.py``'s
operations over the bf16 peak or its bytes over the HBM peak of
``peaks.json``, whichever is larger: the PUBLISHED work, chosen pairs for
the core and for the indexer's target, every causal pair for the scores,
whatever the kernel computes to get it — times the layers, over the
device self time of ALL the kernel's events (``dsa_fwd.3``: the kernels'
own names; the forward run again under remat and both calls of
``dsa_kl`` count as time) in the steps the trace holds whole: a
``tft_train_step`` program event that holds one ``dsa_dq`` call a layer.
Batch and sequence are those the step program itself recorded on its
first call (``profiling.step_args``); widths and depth are the traced
cell's configuration's.

``selected_share``: chosen pairs over causal pairs, as the run's own
check of the reference counted them on the system's packed sets
(``record["checks"]["reference"]["dsa_selected_share"]``).

A program without these scopes (every other family, and any parent of
PR 66) yields nothing, and the metric is left out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from benchmark import flops, harness, keye_flops, trace_reduce
from benchmark.readers import device_scopes

SCOPES = ("dsa_index", "dsa_select", "dsa_core", "dsa_kl")
KERNELS = keye_flops.KERNELS


def _tokens(path: Optional[str]) -> set:
    return set(path.replace("(", "/").replace(")", "/").split("/")) \
        if path else set()


def reduce(ops: Dict[int, List[device_scopes.Op]],
           modules: Dict[int, List[device_scopes.Op]],
           tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """On plain data: seconds under each of ``SCOPES``, and the kernels
    by the program event they ran in, one train step each (``{kernel:
    seconds}`` and ``{kernel: calls}``; a trace without a programs line
    has one bucket). ``None`` where neither a scope nor a kernel of this
    family is found."""
    seconds = {scope: 0.0 for scope in SCOPES}
    steps: Dict[Any, Dict[str, Dict[str, float]]] = {}
    total = 0.0
    for chip, events in ops.items():
        programs = sorted(modules.get(chip, []), key=lambda m: m[1])
        at = 0
        for name, start, self_s in device_scopes.self_times(events):
            while at < len(programs) and programs[at][2] <= start:
                at += 1
            inside = at < len(programs) and programs[at][1] <= start
            program = programs[at][0] if inside else ""
            total += self_s
            path = tables.get(program, {}).get(name)
            if path is None and not program:
                # a trace without a programs line (the CPU rehearsal)
                path = next((t[name] for t in tables.values() if name in t),
                            None)
            tokens = _tokens(path)
            for scope in SCOPES:
                if scope in tokens:
                    seconds[scope] += self_s
            kernel = name.split(".")[0]
            if kernel in KERNELS:
                step = steps.setdefault(
                    (chip, at if inside else None),
                    {"seconds": {k: 0.0 for k in KERNELS},
                     "calls": {k: 0 for k in KERNELS}})
                step["seconds"][kernel] += self_s
                step["calls"][kernel] += 1
    if total <= 0 or not (steps or any(seconds.values())):
        return None
    return {"shares": {k: s / total for k, s in seconds.items()},
            "seconds": seconds, "steps": list(steps.values()),
            "total_s": total}


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_keye_scopes" not in record:
        record["_keye_scopes"] = None
        from torchft_tpu.utils import profiling

        scope_tables = getattr(profiling, "scope_tables", None)
        path = device_scopes.newest_trace()
        if scope_tables is not None and path is not None:
            from jax.profiler import ProfileData

            profile = ProfileData.from_file(path)
            result = reduce(trace_reduce.device_lines(profile),
                            device_scopes.module_lines(profile),
                            scope_tables())
            if result is not None:
                kernels = {k: (sum(s["seconds"][k] for s in result["steps"]),
                               sum(s["calls"][k] for s in result["steps"]))
                           for k in KERNELS}
                record.setdefault("notes", []).append(
                    "keye device seconds: " + ", ".join(
                        f"{k} {s:.3f}" for k, s in
                        sorted(result["seconds"].items())
                    ) + "; kernels " + ", ".join(
                        f"{k} {s:.3f} in {n} calls"
                        for k, (s, n) in kernels.items()
                    ) + f" in {len(result['steps'])} step programs, of "
                    f"{result['total_s']:.3f} busy"
                )
            record["_keye_scopes"] = result
    return record["_keye_scopes"]


def cell_shapes(trace_path: str) -> Optional[Dict[str, Any]]:
    """Batch and sequence as the traced step program ran them (the
    argument shapes ``StepProgram`` noted on its first call); heads,
    widths and the layers of each kind from the configuration of the cell
    the harness wrote the trace for (``<TRACE_DIR>/<cell>/``). ``None``
    for a configuration without this family's keys."""
    from torchft_tpu.utils import profiling

    step_args = getattr(profiling, "step_args", None)
    args = step_args("tft_train_step") if step_args else None
    if args is None:
        return None
    tokens = args[2]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell_name = os.path.relpath(trace_path, harness.TRACE_DIR).split(os.sep)[0]
    cell = {w["name"]: w for w in manifest["workloads"]}[cell_name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(harness.ROOT, entry["file"])) as f:
        config = json.load(f)
    if "sa_config" not in config:
        return None
    dims = keye_flops.config_dims(config)
    return dict(dims, batch=tokens.shape[0], seq_len=tokens.shape[1])


def least_seconds(kernel: str, shapes: Dict[str, Any],
                  device_kind: str) -> float:
    """The least the chip could take for what ONE layer of a step needs
    of ``kernel`` at the cell's shapes."""
    peaks = flops.peaks(device_kind)
    return max(keye_flops.kernel_flops(kernel, **shapes)
               / peaks["bf16_flops"],
               keye_flops.kernel_bytes(kernel, **shapes)
               / peaks["hbm_bytes_per_s"])


def roofline(result: Dict[str, Any], kernel: str, shapes: Dict[str, Any],
             device_kind: str) -> Optional[float]:
    """``kernel``'s share of its roofline, in per cent, over the steps
    the trace holds whole."""
    layers = shapes["n_layers"]
    whole = [s for s in result["steps"] if s["calls"]["dsa_dq"] == layers]
    kernel_s = sum(s["seconds"][kernel] for s in whole)
    if kernel_s <= 0 or not layers:
        return None
    return 100.0 * len(whole) * layers * least_seconds(
        kernel, shapes, device_kind) / kernel_s


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    what = spec["what"]
    if what == "selected_share":
        seen = record.get("checks", {}).get("reference", {})
        share = seen.get("dsa_selected_share")
        return None if share is None else float(share)
    result = _reduction(record)
    if result is None:
        return None
    if not what.endswith("_roofline"):
        return float(result["shares"][what])
    shapes = cell_shapes(device_scopes.newest_trace())
    if shapes is None:
        return None
    return roofline(result, what[:-len("_roofline")], shapes,
                    record["device_kind"])
