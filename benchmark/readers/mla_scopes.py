"""The latent-attention family's device time by the inner scopes of
``torchft_tpu/models/joyai.py``, and the flash kernels' share of their
roofline. What ``device_scopes`` files whole under ``attn`` is split
into the projections (``mla_q`` + ``mla_kv`` + ``mla_out``: down, norm,
up, RoPE, laying ``k`` out, the output matmul) and ``mla_core`` (the
flash calls); ``mtp`` is every operation whose path holds the ``mtp``
scope, whatever else it lies in (the sparse sublayer's inner scopes, the
shared expert's among them, are ``moe_scopes``'). The metric's file
names which: ``{"reader": "mla_scopes", "what": "proj" | "core" | "mtp" |
"flash_fwd_roofline" | "flash_dq_roofline" | "flash_dkv_roofline"}``.

Read with ``device_scopes``' own functions (the newest trace, self
times, the programs line, the program's instruction -> ``op_name``
tables), so a share here has the denominator of the six shares there:
the busy time of the chip.

``flash_*_roofline``: the least time the chip could take for what causal
attention needs of that kernel (``benchmark/mla_flops.py``: operations
over the bf16 peak or bytes over the HBM peak of ``peaks.json``,
whichever is larger; the first, at 8k sequences), once a layer a step,
over the device self time of the kernel's events (``flash_fwd.3``,
``flash_dq.1``, ``flash_dkv.2``: the kernels' own names) in the steps the
trace holds whole: a ``tft_train_step`` program event that holds one
``flash_dq`` and one ``flash_dkv`` a layer and one or (under
``jax.checkpoint``) two ``flash_fwd``. The forward run again under remat
is time that counts and work that does not. Sequence and batch are those
the step program itself recorded on its first call
(``profiling.step_args``); heads, widths and depth are the traced
cell's configuration's.

A program without these scopes (every other family, and any parent of
PR 31) yields nothing, and the metric is left out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from benchmark import flops, harness, mla_flops, trace_reduce
from benchmark.readers import device_scopes

# inner scope as it stands in an op_name path -> the share's name
INNER = {"mla_q": "proj", "mla_kv": "proj", "mla_out": "proj",
         "mla_core": "core"}
MTP = "mtp"
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def _tokens(path: Optional[str]) -> List[str]:
    return path.replace("(", "/").replace(")", "/").split("/") if path else []


def inner_scope(path: Optional[str]) -> Optional[str]:
    """``"core"`` for ``jit(tft_train_step)/jvp(attn)/mla_core/...``;
    ``None`` outside the scopes this reader splits."""
    return next((INNER[t] for t in _tokens(path) if t in INNER), None)


def reduce(ops: Dict[int, List[device_scopes.Op]],
           modules: Dict[int, List[device_scopes.Op]],
           tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """On plain data. ``None`` where no event lies in an inner scope."""
    seconds = {share: 0.0 for share in set(INNER.values()) | {MTP}}
    total = 0.0
    # the flash kernels by the program event they ran in: one train step
    # each ({kernel: seconds} and {kernel: calls}); a trace without a
    # programs line has one bucket
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
            if MTP in _tokens(path):
                seconds[MTP] += self_s
            share = inner_scope(path)
            if share is not None:
                seconds[share] += self_s
            kernel = name.split(".")[0]
            if kernel in KERNELS:
                step = steps.setdefault(
                    (chip, at if inside else None),
                    {"seconds": {k: 0.0 for k in KERNELS},
                     "calls": {k: 0 for k in KERNELS}})
                step["seconds"][kernel] += self_s
                step["calls"][kernel] += 1
    if total <= 0 or not any(seconds[s] for s in set(INNER.values())):
        return None
    return {"shares": {k: s / total for k, s in seconds.items()},
            "seconds": seconds, "steps": list(steps.values()),
            "total_s": total}


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_mla_scopes" not in record:
        record["_mla_scopes"] = None
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
                kernels = {k: sum(s["seconds"][k] for s in result["steps"])
                           for k in KERNELS}
                record.setdefault("notes", []).append(
                    "device seconds by latent-attention scope: " + ", ".join(
                        f"{k} {s:.3f}" for k, s in
                        sorted(result["seconds"].items())
                    ) + "; flash kernels " + ", ".join(
                        f"{k} {s:.3f}" for k, s in kernels.items()
                    ) + f" in {len(result['steps'])} step programs, of "
                    f"{result['total_s']:.3f} busy"
                )
            record["_mla_scopes"] = result
    return record["_mla_scopes"]


def cell_shapes(trace_path: str) -> Optional[Dict[str, int]]:
    """Batch and sequence as the traced step program ran them (the
    argument shapes ``StepProgram`` noted on its first call); heads,
    widths and the number of attention layers from the configuration of
    the cell the harness wrote the trace for (``<TRACE_DIR>/<cell>/``).
    ``None`` for a configuration without latent attention's keys."""
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
    if "qk_head_dim" not in config or "v_head_dim" not in config:
        return None
    return {
        "batch_heads": tokens.shape[0] * config["num_attention_heads"],
        "seq_len": tokens.shape[1], "d_qk": config["qk_head_dim"],
        "d_v": config["v_head_dim"],
        "n_layers": config["num_hidden_layers"]
        + config.get("num_nextn_predict_layers", 0),
    }


def roofline(result: Dict[str, Any], kernel: str, shapes: Dict[str, int],
             device_kind: str) -> Optional[float]:
    """``kernel``'s share of its roofline, in per cent, over the steps
    the trace holds whole."""
    layers = shapes["n_layers"]
    whole = [s for s in result["steps"]
             if s["calls"]["flash_dq"] == s["calls"]["flash_dkv"] == layers
             and s["calls"]["flash_fwd"] in (layers, 2 * layers)]
    kernel_s = sum(s["seconds"][kernel] for s in whole)
    if kernel_s <= 0:
        return None
    peaks = flops.peaks(device_kind)
    dims = {k: shapes[k] for k in ("batch_heads", "seq_len", "d_qk", "d_v")}
    least_s = len(whole) * layers * max(
        mla_flops.flash_flops_per_call(**dims) / peaks["bf16_flops"],
        mla_flops.flash_bytes_per_call(kernel, **dims)
        / peaks["hbm_bytes_per_s"],
    )
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
