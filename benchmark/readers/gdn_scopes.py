"""The Olmo Hybrid family's device time by the inner scopes of
``torchft_tpu/models/olmo_hybrid.py``'s linear-attention mixer, and its
kernels' shares of their rooflines. What ``device_scopes`` files whole
under ``attn`` — both sequence mixers stand there — is split into the
Gated DeltaNet mixer's five scopes: ``gdn_in`` + ``gdn_out`` (the five
projections, the output matmul, the output's norm), ``gdn_conv`` +
``gdn_gate`` (the pointwise stages around the scan: the convolution's
kernels, the l2 norms, the step and the decay, the head norm and the
gate) and ``gdn_core`` (the kernels ``gdn_fwd`` / ``gdn_bwd`` and
whatever XLA leaves around them: the cumulative sums, the chunk padding);
the full-attention mixer's scopes are ``ssm_scopes``' ``gqa`` and
``phi4flash_scopes``' ``full_core``. The metric's file names which:
``{"reader": "gdn_scopes", "what": "gdn" | "gdn_core" |
"gdn_fwd_roofline" | "gdn_bwd_roofline"}``; the projections' and the
pointwise stages' shares (``gdn_proj``, ``gdn_conv_gate``) stand in the
reader's note alone.

Read with ``device_scopes``' own functions (the newest trace, self
times, the programs line, the program's instruction -> ``op_name``
tables), so a share here has the denominator of the six shares there:
the busy time of the chip.

``gdn_*_roofline``: the least time the chip could take for what the
model needs of that kernel — ``benchmark/olmo_hybrid_flops.py``'s
operations (the recurrence's, whatever the chunk) over the bf16 peak or
its bytes (at the model's own widths, whatever the kernels pad) over the
HBM peak of ``peaks.json``, whichever is larger: the bytes, at 96 / 192
— once a linear layer a step, over the device self time of the kernel's
events (``gdn_fwd.3``, ``gdn_bwd.1``: the kernels' own names) in the
steps the trace holds whole: a ``tft_train_step`` program event that
holds one ``gdn_bwd`` a linear layer and one or (under
``jax.checkpoint``) two ``gdn_fwd``. The forward run again under remat
is time that counts and work that does not. Sequence and batch are those
the step program itself recorded on its first call
(``profiling.step_args``); heads, widths and the number of linear layers
are the traced cell's configuration's.

A program without these scopes (every other family, and any parent of
PR 56) yields nothing, and the metric is left out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from benchmark import flops, harness, olmo_hybrid_flops, trace_reduce
from benchmark.readers import device_scopes

# inner scope as it stands in an op_name path -> the shares it counts in
INNER = {
    "gdn_in": ("gdn", "gdn_proj"), "gdn_out": ("gdn", "gdn_proj"),
    "gdn_conv": ("gdn", "gdn_conv_gate"), "gdn_gate": ("gdn", "gdn_conv_gate"),
    "gdn_core": ("gdn", "gdn_core"),
}
SHARES = sorted({s for shares in INNER.values() for s in shares})
KERNELS = olmo_hybrid_flops.KERNELS


def inner_scopes(path: Optional[str]) -> tuple:
    """``("gdn", "gdn_core")`` for
    ``jit(tft_train_step)/jvp(attn)/gdn_core/...``; ``()`` outside the
    scopes this reader splits."""
    if not path:
        return ()
    tokens = path.replace("(", "/").replace(")", "/").split("/")
    return next((INNER[t] for t in tokens if t in INNER), ())


def reduce(ops: Dict[int, List[device_scopes.Op]],
           modules: Dict[int, List[device_scopes.Op]],
           tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """On plain data. ``None`` where no event lies in a scope of the
    delta-rule mixer."""
    seconds = {share: 0.0 for share in SHARES}
    total = 0.0
    # the kernels by the program event they ran in: one train step each
    # ({kernel: seconds} and {kernel: calls}); a trace without a programs
    # line has one bucket
    steps: Dict[Any, Dict[str, Dict[str, float]]] = {}
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
            for share in inner_scopes(path):
                seconds[share] += self_s
            kernel = name.split(".")[0]
            if kernel in KERNELS:
                step = steps.setdefault(
                    (chip, at if inside else None),
                    {"seconds": {k: 0.0 for k in KERNELS},
                     "calls": {k: 0 for k in KERNELS}})
                step["seconds"][kernel] += self_s
                step["calls"][kernel] += 1
    if total <= 0 or not any(seconds.values()):
        return None
    return {"shares": {k: s / total for k, s in seconds.items()},
            "seconds": seconds, "steps": list(steps.values()),
            "total_s": total}


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_gdn_scopes" not in record:
        record["_gdn_scopes"] = None
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
                    "device seconds by gated-delta scope: " + ", ".join(
                        f"{k} {s:.3f}" for k, s in
                        sorted(result["seconds"].items())
                    ) + "; kernels " + ", ".join(
                        f"{k} {s:.3f} in {n} calls"
                        for k, (s, n) in kernels.items()
                    ) + f" in {len(result['steps'])} step programs, of "
                    f"{result['total_s']:.3f} busy"
                )
            record["_gdn_scopes"] = result
    return record["_gdn_scopes"]


def cell_shapes(trace_path: str) -> Optional[Dict[str, int]]:
    """Batch and sequence as the traced step program ran them (the
    argument shapes ``StepProgram`` noted on its first call); heads,
    widths and the number of linear layers from the configuration of the
    cell the harness wrote the trace for (``<TRACE_DIR>/<cell>/``).
    ``None`` for a configuration without this family's keys."""
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
    if "linear_key_head_dim" not in config:
        return None
    return {
        "batch": tokens.shape[0], "seq_len": tokens.shape[1],
        "n_heads": config["linear_num_key_heads"],
        "key_dim": config["linear_key_head_dim"],
        "value_dim": config["linear_value_head_dim"],
        "layers": config["layer_types"].count(olmo_hybrid_flops.LINEAR),
    }


def roofline(result: Dict[str, Any], kernel: str, shapes: Dict[str, Any],
             device_kind: str) -> Optional[float]:
    """``kernel``'s share of its roofline, in per cent, over the steps
    the trace holds whole."""
    layers = shapes["layers"]
    whole = [s for s in result["steps"]
             if s["calls"]["gdn_bwd"] == layers
             and s["calls"]["gdn_fwd"] in (layers, 2 * layers)]
    kernel_s = sum(s["seconds"][kernel] for s in whole)
    if kernel_s <= 0 or not layers:
        return None
    peaks = flops.peaks(device_kind)
    dims = dict(n_heads=shapes["n_heads"], key_dim=shapes["key_dim"],
                value_dim=shapes["value_dim"])
    least_s = len(whole) * layers * shapes["batch"] * shapes["seq_len"] * max(
        olmo_hybrid_flops.gdn_flops_per_token(kernel, **dims) / peaks["bf16_flops"],
        olmo_hybrid_flops.gdn_bytes_per_token(kernel, **dims)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    result = _reduction(record)
    if result is None:
        return None
    what = spec["what"]
    if not what.endswith("_roofline"):
        return float(result["shares"][what])
    shapes = cell_shapes(device_scopes.newest_trace())
    if shapes is None:
        return None
    return roofline(result, what[:-len("_roofline")], shapes,
                    record["device_kind"])
