"""The sparse-expert sublayer's device time by its inner scopes, and the
grouped matmuls' share of the chip's peak: what ``device_scopes`` files
whole under ``mlp`` is split by the ``jax.named_scope``s of
the sparse families' models and ``ops/moe.py`` — ``moe_router``,
``moe_dispatch`` + ``moe_combine`` (the data movement), ``moe_experts``
and, where the family has one, ``moe_shared`` (the shared expert, a
sibling of the other four). The metric's file names which: ``{"reader":
"moe_scopes", "what": "router" | "dispatch" | "experts" | "shared" |
"experts_roofline"}``.

Read with ``device_scopes``' own functions (the newest trace, self
times, the programs line, the program's instruction -> ``op_name``
tables), so a share here has the denominator of the six shares there:
the busy time of the chip.

``experts_roofline``: the operations the grouped matmuls need
(``benchmark/moe_flops.py``, from the record's shapes: 18 x d x f a row,
forward and backward, recomputation not credited) over the device time
of the grouped-matmul kernels themselves — the events under
``moe_experts`` whose instruction is a ``gmm`` / ``tgmm`` kernel —
over the bf16 peak of ``peaks.json``, on the steps the trace holds
whole: a step is one ``tft_train_step`` program event, whole when it
holds its ``6 x layers`` ``gmm`` and ``3 x layers`` ``tgmm`` calls (one
cut by the window's edge would add kernel time without its step). The
shapes are those the step program itself recorded on its first call
(``profiling.StepProgram``); ``top_k`` alone is no argument's shape and
is the traced cell's configuration's.

A program without these scopes (every other family, and any parent of
PR 26) yields nothing, and the metric is left out.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from benchmark import flops, harness, moe_flops, trace_reduce
from benchmark.readers import device_scopes

# inner scope as it stands in an op_name path -> the share's name
INNER = {"moe_router": "router", "moe_dispatch": "dispatch",
         "moe_combine": "dispatch", "moe_experts": "experts",
         "moe_shared": "shared"}
# the grouped-matmul kernels' instructions: ``gmm.5``, ``tgmm.2`` (the
# second is the gradient of the weights)
KERNELS = ("gmm", "tgmm")
# calls a layer a step: gate, up and down forward and their row gradients
# are ``gmm``, the three weight gradients ``tgmm``
CALLS_PER_LAYER = {"gmm": 6, "tgmm": 3}


def inner_scope(path: Optional[str]) -> Optional[str]:
    """``"experts"`` for ``jit(tft_train_step)/transpose(jvp(mlp))/
    moe_experts/...``; ``None`` outside the sparse sublayer."""
    if not path:
        return None
    tokens = path.replace("(", "/").replace(")", "/").split("/")
    return next((INNER[t] for t in tokens if t in INNER), None)


def reduce(ops: Dict[int, List[device_scopes.Op]],
           modules: Dict[int, List[device_scopes.Op]],
           tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """On plain data. ``None`` where no event lies in an inner scope."""
    seconds = {share: 0.0 for share in set(INNER.values())}
    total = 0.0
    # the grouped-matmul kernels by the program event they ran in: one
    # train step each ({"kernel_s", "gmm", "tgmm"}); a trace without a
    # programs line has one bucket
    steps: Dict[Any, Dict[str, float]] = {}
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
            share = inner_scope(path)
            if share is None:
                continue
            seconds[share] += self_s
            kernel = name.split(".")[0]
            if share == "experts" and kernel in KERNELS:
                step = steps.setdefault(
                    (chip, at if inside else None),
                    {"kernel_s": 0.0, **{k: 0 for k in KERNELS}})
                step["kernel_s"] += self_s
                step[kernel] += 1
    if total <= 0 or not any(seconds.values()):
        return None
    # the shares whose scope some program has: one it has not is not read
    # (no shared expert), one it has and that took no time reads 0
    present = {inner_scope(path) for table in tables.values()
               for path in table.values()} - {None}
    return {"shares": {k: s / total for k, s in seconds.items()},
            "seconds": seconds, "steps": list(steps.values()),
            "total_s": total, "present": present}


def _reduction(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if "_moe_scopes" not in record:
        record["_moe_scopes"] = None
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
                record.setdefault("notes", []).append(
                    "device seconds in the sparse sublayer: " + ", ".join(
                        f"{k} {s:.3f}" for k, s in
                        sorted(result["seconds"].items())
                    ) + "; grouped-matmul kernels "
                    f"{sum(s['kernel_s'] for s in result['steps']):.3f} in "
                    f"{len(result['steps'])} step programs, of "
                    f"{result['total_s']:.3f} busy"
                )
            record["_moe_scopes"] = result
    return record["_moe_scopes"]


def cell_shapes(trace_path: str) -> Optional[Dict[str, int]]:
    """The expert layer's shapes as the traced step program ran them:
    from the argument shapes ``StepProgram`` noted on its first call
    (params, optimizer state, tokens, targets). ``top_k`` is no
    argument's shape: it is read from the configuration of the cell the
    harness wrote the trace for (``<TRACE_DIR>/<cell>/``)."""
    import json

    from torchft_tpu.utils import profiling

    step_args = getattr(profiling, "step_args", None)
    args = step_args("tft_train_step") if step_args else None
    if args is None:
        return None
    params, tokens = args[0], args[2]
    layers = [v for k, v in params.items() if k.startswith("layers_")]
    n_experts, d_model, d_expert = layers[0]["moe"]["gate_proj"]["kernel"].shape
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell_name = os.path.relpath(trace_path, harness.TRACE_DIR).split(os.sep)[0]
    cell = {w["name"]: w for w in manifest["workloads"]}[cell_name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(harness.ROOT, entry["file"])) as f:
        top_k = int(json.load(f)["num_experts_per_tok"])
    return {"tokens": tokens.shape[0] * tokens.shape[1], "top_k": top_k,
            "n_layers": len(layers), "n_experts": n_experts,
            "d_model": d_model, "d_expert": d_expert}


def roofline(result: Dict[str, Any], shapes: Dict[str, int],
             device_kind: str) -> Optional[float]:
    """The grouped-matmul kernels' share of their roofline, in per cent,
    over the steps the trace holds whole."""
    whole = [s for s in result["steps"] if all(
        s[k] == n * shapes["n_layers"] for k, n in CALLS_PER_LAYER.items())]
    kernel_s = sum(s["kernel_s"] for s in whole)
    if kernel_s <= 0:
        return None
    peaks = flops.peaks(device_kind)
    dims = {k: v for k, v in shapes.items() if k != "n_experts"}
    # the least time the chip could take: the larger of operations over
    # peak FLOP/s and bytes over peak bytes/s (the first, at these shapes)
    least_s = len(whole) * max(
        moe_flops.expert_flops_per_step(**dims) / peaks["bf16_flops"],
        moe_flops.expert_bytes_per_step(**shapes) / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / kernel_s


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    result = _reduction(record)
    if result is None:
        return None
    if spec["what"] != "experts_roofline":
        if spec["what"] not in result["present"]:
            return None
        return float(result["shares"][spec["what"]])
    shapes = cell_shapes(device_scopes.newest_trace())
    if shapes is None:
        return None
    return roofline(result, shapes, record["device_kind"])
