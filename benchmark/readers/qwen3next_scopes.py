"""The Qwen3-Next family's kernels' shares of their rooflines and the
price of the two stages ``torchft_tpu/models/qwen3_next.py`` leaves to
XLA beside them. The shares of device time this family reports besides
are other readers', unedited: ``gdn`` and ``gdn_core`` (``gdn_scopes``,
which splits by scope name and reads no width), ``gqa`` (``ssm_scopes``),
``full_core`` (``phi4flash_scopes``), the sparse sublayer's
(``moe_scopes``). ``gdn_scopes``' own rooflines are NOT this family's:
that reader counts ``linear_num_key_heads`` state heads, and here the
state heads are the value heads, twice as many. The metric's file names
which: ``{"reader": "qwen3next_scopes", "what": "gdn_fwd_roofline" |
"gdn_bwd_roofline" | "flash_fwd_roofline" | "flash_dq_roofline" |
"flash_dkv_roofline" | "gdn_repeat" | "attn_gate"}``.

Read with ``device_scopes``' own functions (the newest trace, self
times, the programs line, the program's instruction -> ``op_name``
tables), so a share here has the denominator of the six shares there:
the busy time of the chip.

``gdn_repeat`` / ``attn_gate``: the share of the chip's busy time under
the scope of that name — the copy of q and k to the value heads' count
(what a scan that takes the key heads as they are would save) and the
element-wise gate on the attention's output with its logits' matmul.

``*_roofline``: the least time the chip could take for what the MODEL
needs of that kernel — ``benchmark/qwen3_next_flops.py``'s operations
over the bf16 peak or its bytes over the HBM peak of ``peaks.json``,
whichever is larger — once a layer of the kernel's kind a step, over the
device self time of the kernel's events (``gdn_fwd.3``, ``flash_dq.1``:
the kernels' own names; a flash event counts where its path holds
``full_core``) in the steps the trace holds whole: a ``tft_train_step``
program event that holds one backward call a layer of the kind and one
or (under ``jax.checkpoint``) two forward calls. The forward run again
under remat, the broadcast operands, padding and rebuilt tiles are time
that counts and work that does not. Batch and sequence are those the
step program itself recorded on its first call
(``profiling.step_args``); heads, widths and the layers of each kind are
the traced cell's configuration's.

A program without these scopes (every other family, and any parent of
PR 63) yields nothing, and the metric is left out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from benchmark import flops, harness, qwen3_next_flops, trace_reduce
from benchmark.readers import device_scopes

SCOPES = ("gdn_repeat", "attn_gate")
# a kernel's events count where their path holds this scope (None: any)
KERNEL_SCOPE = {**{k: None for k in qwen3_next_flops.GDN_KERNELS},
                **{k: "full_core" for k in qwen3_next_flops.FLASH_KERNELS}}
# the kernel -> (its forward, its backward kernels, the key of the count
# of layers that call it)
KINDS = {
    **{k: ("gdn_fwd", ("gdn_bwd",), "n_linear")
       for k in qwen3_next_flops.GDN_KERNELS},
    **{k: ("flash_fwd", ("flash_dq", "flash_dkv"), "n_full")
       for k in qwen3_next_flops.FLASH_KERNELS},
}


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
            if kernel in KERNEL_SCOPE and (
                    KERNEL_SCOPE[kernel] is None
                    or KERNEL_SCOPE[kernel] in tokens):
                step = steps.setdefault(
                    (chip, at if inside else None),
                    {"seconds": {k: 0.0 for k in KERNEL_SCOPE},
                     "calls": {k: 0 for k in KERNEL_SCOPE}})
                step["seconds"][kernel] += self_s
                step["calls"][kernel] += 1
    if total <= 0 or not (steps or any(seconds.values())):
        return None
    return {"shares": {k: s / total for k, s in seconds.items()},
            "seconds": seconds, "steps": list(steps.values()),
            "total_s": total}


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_qwen3next_scopes" not in record:
        record["_qwen3next_scopes"] = None
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
                           for k in KERNEL_SCOPE}
                record.setdefault("notes", []).append(
                    "qwen3-next device seconds: " + ", ".join(
                        f"{k} {s:.3f}" for k, s in
                        sorted(result["seconds"].items())
                    ) + "; kernels " + ", ".join(
                        f"{k} {s:.3f} in {n} calls"
                        for k, (s, n) in kernels.items()
                    ) + f" in {len(result['steps'])} step programs, of "
                    f"{result['total_s']:.3f} busy"
                )
            record["_qwen3next_scopes"] = result
    return record["_qwen3next_scopes"]


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
    if ("linear_num_value_heads" not in config
            or "full_attention_interval" not in config):
        return None
    dims = qwen3_next_flops.config_dims(config)
    return dict(dims, batch=tokens.shape[0], seq_len=tokens.shape[1])


def least_seconds(kernel: str, shapes: Dict[str, Any],
                  device_kind: str) -> float:
    """The least the chip could take for ONE call of ``kernel`` at the
    cell's shapes."""
    peaks = flops.peaks(device_kind)
    if kernel in qwen3_next_flops.GDN_KERNELS:
        n = shapes["batch"] * shapes["seq_len"]
        dims = {k: shapes[k] for k in ("n_value_heads", "key_dim", "value_dim")}
        ops = n * qwen3_next_flops.gdn_flops_per_token(kernel, **dims)
        moved = n * qwen3_next_flops.gdn_bytes_per_token(
            kernel, n_key_heads=shapes["n_key_heads"], **dims)
    else:
        dims = dict(batch_heads=shapes["batch"] * shapes["n_heads"],
                    seq_len=shapes["seq_len"], d_qk=shapes["head_dim"],
                    d_v=shapes["head_dim"])
        ops = qwen3_next_flops.flash_flops_per_call(**dims)
        moved = qwen3_next_flops.flash_bytes_per_call(kernel, **dims)
    return max(ops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])


def roofline(result: Dict[str, Any], kernel: str, shapes: Dict[str, Any],
             device_kind: str) -> Optional[float]:
    """``kernel``'s share of its roofline, in per cent, over the steps
    the trace holds whole."""
    forward, backward, count = KINDS[kernel]
    calls = shapes[count]
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
        return float(result["shares"][what])
    shapes = cell_shapes(device_scopes.newest_trace())
    if shapes is None:
        return None
    return roofline(result, what[:-len("_roofline")], shapes,
                    record["device_kind"])
