"""The Phi-4-mini-flash family's device time by the inner scopes of
``torchft_tpu/models/phi4flash.py``, and its new kernels' shares of their
rooflines. What ``device_scopes`` files whole under ``attn`` — all five
kinds of mixer stand there — is split: the Mamba-1 mixer's ``ssm_*``
scopes are Nemotron-H's names and ``ssm_scopes`` reads them unedited; this
reader takes the rest — ``diff_attn`` (the three attention mixers whole),
inside it ``swa_core`` and ``full_core`` (the windowed flash calls; layer
17's and the cross layer's) and ``diff_combine`` (``λ``, the 128-wide norm,
the scale), ``gmu``, and ``memory_grad`` (the sums of the gradients that
come back into ``m``, ``k``, ``v``). An operation counts in every one of
these scopes its path holds. The metric's file names which: ``{"reader":
"phi4flash_scopes", "what": "diff_attn" | "swa_core" | "full_core" |
"diff_combine" | "gmu" | "memory_grad" | "s6_fwd_roofline" |
"s6_bwd_roofline" | "swa_flash_fwd_roofline" | "swa_flash_dq_roofline" |
"swa_flash_dkv_roofline"}``.

Read with ``device_scopes``' own functions (the newest trace, self
times, the programs line, the program's instruction -> ``op_name``
tables), so a share here has the denominator of the six shares there:
the busy time of the chip.

``*_roofline``: the least time the chip could take for what the model
needs of that kernel (``benchmark/phi4flash_flops.py``) — the scan's
bytes over the HBM peak of ``peaks.json`` (it has no matmul operations);
the windowed flash kernels' live pairs × ``2 (Dqk + Dv)`` operations over
the bf16 peak or their bytes, whichever is larger — once a layer of the
kernel's kind a step, over the device self time of the kernel's events
(``s6_fwd.3``, ``flash_dq.1``: the kernels' own names; a flash event is a
WINDOWED one where its path holds ``swa_core``) in the steps the trace
holds whole: a ``tft_train_step`` program event that holds one backward
call a layer (two a differential layer: a flash call a half of its pairs)
and as many or (under ``jax.checkpoint``) twice as many forward calls. The
forward run again under remat is time that counts and work that does
not. Batch and sequence are those the step program itself recorded on
its first call (``profiling.step_args``); widths, the window and the
layers of each kind are the traced cell's configuration's.

A program without these scopes (every other family, and any parent of
PR 47) yields nothing, and the metric is left out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from benchmark import flops, harness, phi4flash_flops, trace_reduce
from benchmark.readers import device_scopes

SHARES = ("diff_attn", "swa_core", "full_core", "diff_combine", "gmu",
          "memory_grad")
# kernel as this reader counts it -> the kind of layer that calls it once
# (backward) or once or twice (forward) a step
KERNELS = {"s6_fwd": "n_mamba", "s6_bwd": "n_mamba",
           "swa_flash_fwd": "n_swa", "swa_flash_dq": "n_swa",
           "swa_flash_dkv": "n_swa"}
# a differential layer makes TWO flash calls, one a half of its pairs
CALLS_A_LAYER = {"n_mamba": 1, "n_swa": 2}


def scopes_of(path: Optional[str]) -> set:
    """The scopes of ``SHARES`` (and the others) an ``op_name`` path
    holds: ``jit(tft_train_step)/jvp(attn)/diff_attn/swa_core/...``."""
    if not path:
        return set()
    return set(path.replace("(", "/").replace(")", "/").split("/"))


def reduce(ops: Dict[int, List[device_scopes.Op]],
           modules: Dict[int, List[device_scopes.Op]],
           tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """On plain data. ``None`` where no event lies in a scope of
    ``SHARES`` and none is a scan kernel's."""
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
            held = scopes_of(path)
            for share in SHARES:
                if share in held:
                    seconds[share] += self_s
            kernel = name.split(".")[0]
            if kernel.startswith("flash_") and "swa_core" in held:
                kernel = "swa_" + kernel
            if kernel in KERNELS:
                step = steps.setdefault(
                    (chip, at if inside else None),
                    {"seconds": {k: 0.0 for k in KERNELS},
                     "calls": {k: 0 for k in KERNELS}})
                step["seconds"][kernel] += self_s
                step["calls"][kernel] += 1
    if total <= 0 or not (any(seconds.values()) or steps):
        return None
    return {"shares": {k: s / total for k, s in seconds.items()},
            "seconds": seconds, "steps": list(steps.values()),
            "total_s": total}


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_phi4flash_scopes" not in record:
        record["_phi4flash_scopes"] = None
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
                    "device seconds by phi4flash scope: " + ", ".join(
                        f"{k} {s:.3f}" for k, s in
                        sorted(result["seconds"].items())
                    ) + "; kernels " + ", ".join(
                        f"{k} {s:.3f} in {n} calls"
                        for k, (s, n) in kernels.items()
                    ) + f" in {len(result['steps'])} step programs, of "
                    f"{result['total_s']:.3f} busy"
                )
            record["_phi4flash_scopes"] = result
    return record["_phi4flash_scopes"]


def cell_shapes(trace_path: str) -> Optional[Dict[str, Any]]:
    """Batch and sequence as the traced step program ran them (the
    argument shapes ``StepProgram`` noted on its first call); widths, the
    window and the layers of each kind from the configuration of the cell
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
    if "mamba" not in config or "layer_ids" not in config:
        return None
    dims = phi4flash_flops.config_dims(config)
    return dict(dims, batch=tokens.shape[0], seq_len=tokens.shape[1])


def least_seconds(kernel: str, shapes: Dict[str, Any],
                  device_kind: str) -> float:
    """The least the chip could take for ONE call of ``kernel`` at the
    cell's shapes."""
    peaks = flops.peaks(device_kind)
    batch, seq_len = shapes["batch"], shapes["seq_len"]
    if kernel in phi4flash_flops.S6_KERNELS:
        return batch * seq_len * phi4flash_flops.s6_bytes_per_token(
            kernel, channels=shapes["d_inner"], state=shapes["state"]
        ) / peaks["hbm_bytes_per_s"]
    dims = dict(batch_heads=batch * shapes["n_heads"] // 2, seq_len=seq_len,
                d_qk=shapes["head_dim"], d_v=2 * shapes["head_dim"])
    return max(
        phi4flash_flops.swa_flash_flops_per_call(
            window=shapes["window"], **dims) / peaks["bf16_flops"],
        phi4flash_flops.flash_bytes_per_call(kernel[len("swa_"):], **dims)
        / peaks["hbm_bytes_per_s"])


def roofline(result: Dict[str, Any], kernel: str, shapes: Dict[str, Any],
             device_kind: str) -> Optional[float]:
    """``kernel``'s share of its roofline, in per cent, over the steps
    the trace holds whole."""
    calls = shapes[KERNELS[kernel]] * CALLS_A_LAYER[KERNELS[kernel]]
    forward, *backward = [k for k, kind in KERNELS.items()
                          if kind == KERNELS[kernel]]
    whole = [s for s in result["steps"]
             if all(s["calls"][k] == calls for k in backward)
             and s["calls"][forward] in (calls, 2 * calls)]
    kernel_s = sum(s["seconds"][kernel] for s in whole)
    if kernel_s <= 0 or not calls:
        return None
    return 100.0 * len(whole) * calls * least_seconds(
        kernel, shapes, device_kind) / kernel_s


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    result = _reduction(record)
    if result is None:
        return None
    what = spec["what"]
    if not what.endswith("_roofline"):
        return float(result["shares"][what]) if any(
            result["seconds"].values()) else None
    shapes = cell_shapes(device_scopes.newest_trace())
    if shapes is None:
        return None
    return roofline(result, what[:-len("_roofline")], shapes,
                    record["device_kind"])
