"""The SmallThinker family's flash kernels' shares of their rooflines, by
the kind of call: ``torchft_tpu/models/smallthinker.py`` makes ONE flash
call a layer, under ``gqa_core/swa_core`` in a windowed layer and under
``gqa_core/full_core`` in a full one, and the two are different work (the
band's ``S·W − W(W − 1)/2`` live pairs a head against ``S(S + 1)/2``), so
each has its own three metrics. The shares of device time this family
reports are other readers', unedited: ``swa_core`` and ``full_core``
(``phi4flash_scopes``), ``gqa`` (``ssm_scopes``), the sparse sublayer's
(``moe_scopes``). The metric's file names which: ``{"reader":
"smallthinker_scopes", "what": "swa_flash_fwd_roofline" |
"swa_flash_dq_roofline" | "swa_flash_dkv_roofline" |
"full_flash_fwd_roofline" | "full_flash_dq_roofline" |
"full_flash_dkv_roofline"}``.

Read with ``device_scopes``' own functions (the newest trace, self
times, the programs line, the program's instruction -> ``op_name``
tables).

``*_roofline``: the least time the chip could take for what the model
needs of that kernel (``benchmark/smallthinker_flops.py``: the live pairs
× ``2 (Dqk + Dv)`` operations over the bf16 peak of ``peaks.json``, or
every operand and result once over the HBM peak, whichever is longer) —
once a layer of the kernel's kind a step, over the device self time of
the kernel's events (``flash_fwd.3``, ``flash_dq.1``: the kernels' own
names; a flash event is a WINDOWED one where its path holds ``swa_core``,
a full one where it holds ``full_core``) in the steps the trace holds
whole: a ``tft_train_step`` program event that holds one backward call a
layer of the kind and one or (under ``jax.checkpoint``) two forward
calls. Tiles above the band, padding, the rebuilt score tile and the
forward run again under remat are time that counts and work that does
not. Batch and sequence are those the step program itself recorded on
its first call (``profiling.step_args``); widths, the window and the
layers of each kind are the traced cell's configuration's. A note says
what the tile rule cost: the tiles ``ops/flash.py::_choose_blocks`` picks
at that call, the grid steps a head they take and their area over the
live pairs.

A program without these scopes (every other family, and any parent of
PR 50) yields nothing, and the metric is left out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from benchmark import flops, harness, smallthinker_flops, trace_reduce
from benchmark.readers import device_scopes

# the kind of call -> the scope its flash events stand under, and the
# configuration's count of layers that make it
KINDS = {"swa": ("swa_core", "n_swa"), "full": ("full_core", "n_full")}
KERNELS = tuple(f"{kind}_{k}" for kind in KINDS
                for k in smallthinker_flops.KERNELS)


def kind_of(path: Optional[str]) -> Optional[str]:
    """``"swa"`` for ``jit(tft_train_step)/jvp(attn)/gqa_core/swa_core/
    ...``; ``None`` outside both scopes."""
    if not path:
        return None
    tokens = set(path.replace("(", "/").replace(")", "/").split("/"))
    return next((kind for kind, (scope, _) in KINDS.items()
                 if scope in tokens), None)


def reduce(ops: Dict[int, List[device_scopes.Op]],
           modules: Dict[int, List[device_scopes.Op]],
           tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """On plain data: the flash kernels by the program event they ran in,
    one train step each (``{kernel: seconds}`` and ``{kernel: calls}``; a
    trace without a programs line has one bucket). ``None`` where no
    flash event lies in either scope."""
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
            kernel = name.split(".")[0]
            if kernel not in smallthinker_flops.KERNELS:
                continue
            path = tables.get(program, {}).get(name)
            if path is None and not program:
                # a trace without a programs line (the CPU rehearsal)
                path = next((t[name] for t in tables.values() if name in t),
                            None)
            kind = kind_of(path)
            if kind is None:
                continue
            step = steps.setdefault(
                (chip, at if inside else None),
                {"seconds": {k: 0.0 for k in KERNELS},
                 "calls": {k: 0 for k in KERNELS}})
            step["seconds"][f"{kind}_{kernel}"] += self_s
            step["calls"][f"{kind}_{kernel}"] += 1
    if total <= 0 or not steps:
        return None
    return {"steps": list(steps.values()), "total_s": total}


def tile_note(shapes: Dict[str, Any]) -> str:
    """What the tile rule costs at the cell's two calls: the tiles, the
    grid steps a head and the tiles' area over the live pairs."""
    from torchft_tpu.ops import flash

    said = []
    for kind, window in (("swa", shapes["window"]), ("full", None)):
        blocks = flash._choose_blocks(shapes["seq_len"], shapes["head_dim"],
                                      2, window=window)
        steps, _ = flash._grid_steps(shapes["seq_len"], *blocks, window)
        area = steps * blocks[0] * blocks[1] / smallthinker_flops.live_pairs(
            shapes["seq_len"], window)
        said.append(f"{kind} {blocks[0]} x {blocks[1]} tiles, {steps} grid "
                    f"steps a head, tile area {area:.3f} x the live pairs")
    return "; ".join(said)


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_smallthinker_scopes" not in record:
        record["_smallthinker_scopes"] = None
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
                shapes = cell_shapes(path)
                record.setdefault("notes", []).append(
                    "flash kernels by kind of call: " + ", ".join(
                        f"{k} {s:.3f} s in {n} calls"
                        for k, (s, n) in kernels.items()
                    ) + f" in {len(result['steps'])} step programs, of "
                    f"{result['total_s']:.3f} busy"
                    + ("; " + tile_note(shapes) if shapes else "")
                )
            record["_smallthinker_scopes"] = result
    return record["_smallthinker_scopes"]


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
    if ("sliding_window_layout" not in config
            or "moe_num_primary_experts" not in config):
        return None
    dims = smallthinker_flops.config_dims(config)
    return dict(dims, batch=tokens.shape[0], seq_len=tokens.shape[1])


def least_seconds(kernel: str, shapes: Dict[str, Any],
                  device_kind: str) -> float:
    """The least the chip could take for ONE call of ``kernel``
    (``swa_flash_fwd`` … ``full_flash_dkv``) at the cell's shapes."""
    peaks = flops.peaks(device_kind)
    kind, flash_kernel = kernel.split("_", 1)
    dims = dict(batch_heads=shapes["batch"] * shapes["n_heads"],
                seq_len=shapes["seq_len"], d_qk=shapes["head_dim"],
                d_v=shapes["head_dim"])
    return max(
        smallthinker_flops.flash_flops_per_call(
            window=shapes["window"] if kind == "swa" else None, **dims)
        / peaks["bf16_flops"],
        smallthinker_flops.flash_bytes_per_call(flash_kernel, **dims)
        / peaks["hbm_bytes_per_s"])


def roofline(result: Dict[str, Any], kernel: str, shapes: Dict[str, Any],
             device_kind: str) -> Optional[float]:
    """``kernel``'s share of its roofline, in per cent, over the steps
    the trace holds whole."""
    kind = kernel.split("_", 1)[0]
    calls = shapes[KINDS[kind][1]]
    forward, *backward = [k for k in KERNELS if k.startswith(kind + "_")]
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
    shapes = cell_shapes(device_scopes.newest_trace())
    if shapes is None:
        return None
    return roofline(result, spec["what"][:-len("_roofline")], shapes,
                    record["device_kind"])
