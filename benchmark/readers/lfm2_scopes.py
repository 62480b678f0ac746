"""The LFM2 family's device time by the inner scopes of
``torchft_tpu/models/lfm2.py``, and its kernels' shares of their
rooflines. What ``device_scopes`` files whole under ``attn`` — both
sequence mixers stand there — is split into the gated short
convolution's ``sconv_in`` + ``sconv_out`` (norm, ``W_in`` 2048 -> 6144,
``W_out``) and ``sconv_core`` (the kernels ``sconv_fwd`` / ``sconv_bwd``
and whatever XLA leaves around them); the attention mixer's
``gqa_proj`` + ``gqa_core`` are ``ssm_scopes``' ``gqa`` (the same
scopes' names, the same measurement) and the sparse sublayer's inner
scopes ``moe_scopes``'. The metric's file names which: ``{"reader":
"lfm2_scopes", "what": "sconv" | "sconv_proj" | "sconv_core" |
"sconv_fwd_roofline" | "sconv_bwd_roofline" | "flash_fwd_roofline" |
"flash_dq_roofline" | "flash_dkv_roofline"}``.

Read with ``device_scopes``' own functions (the newest trace, self
times, the programs line, the program's instruction -> ``op_name``
tables), so a share here has the denominator of the six shares there:
the busy time of the chip.

``*_roofline``: the least time the chip could take for what the model
needs of that kernel — operations over the bf16 peak or bytes over the
HBM peak of ``peaks.json``, whichever is larger — once a layer of the
kernel's kind a step, over the device self time of the kernel's events
(``sconv_fwd.3``, ``flash_dq.1``: the kernels' own names) in the steps
the trace holds whole: a ``tft_train_step`` program event that holds one
backward call a layer (``sconv_bwd``; ``flash_dq`` and ``flash_dkv``)
and one or (under ``jax.checkpoint``) two forward calls. The forward run
again under remat is time that counts and work that does not. The
convolution counts with ``benchmark/lfm2_flops.py`` (the bytes bind);
the flash kernels through ``mla_scopes.roofline`` itself
(``mla_flops.py``'s ``BH · S(S+1)/2`` causal pairs × ``2 (Dqk + Dv)``
operations and its bytes), here at ``Dqk = Dv = head_dim``. Sequence and batch are those the
step program itself recorded on its first call
(``profiling.step_args``); widths, taps and the number of layers of each
kind are the traced cell's configuration's.

A program without these scopes (every other family, and any parent of
PR 38) yields nothing, and the metric is left out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from benchmark import flops, harness, lfm2_flops, trace_reduce
from benchmark.readers import device_scopes, mla_scopes

# inner scope as it stands in an op_name path -> the shares it counts in
INNER = {
    "sconv_in": ("sconv", "sconv_proj"), "sconv_out": ("sconv", "sconv_proj"),
    "sconv_core": ("sconv", "sconv_core"),
}
SHARES = sorted({s for shares in INNER.values() for s in shares})
# kernel -> the kind of layer that calls it once (backward) or once or
# twice (forward) a step
KERNELS = {"sconv_fwd": "conv", "sconv_bwd": "conv", "flash_fwd": "attn",
           "flash_dq": "attn", "flash_dkv": "attn"}


def inner_scopes(path: Optional[str]) -> tuple:
    """``("sconv", "sconv_core")`` for
    ``jit(tft_train_step)/jvp(attn)/sconv_core/...``; ``()`` outside the
    scopes this reader splits."""
    if not path:
        return ()
    tokens = path.replace("(", "/").replace(")", "/").split("/")
    return next((INNER[t] for t in tokens if t in INNER), ())


def reduce(ops: Dict[int, List[device_scopes.Op]],
           modules: Dict[int, List[device_scopes.Op]],
           tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """On plain data. ``None`` where no event lies in a scope of the
    gated convolution."""
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
    if "_lfm2_scopes" not in record:
        record["_lfm2_scopes"] = None
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
                    "device seconds by gated-convolution scope: " + ", ".join(
                        f"{k} {s:.3f}" for k, s in
                        sorted(result["seconds"].items())
                    ) + "; kernels " + ", ".join(
                        f"{k} {s:.3f} in {n} calls"
                        for k, (s, n) in kernels.items()
                    ) + f" in {len(result['steps'])} step programs, of "
                    f"{result['total_s']:.3f} busy"
                )
            record["_lfm2_scopes"] = result
    return record["_lfm2_scopes"]


def cell_shapes(trace_path: str) -> Optional[Dict[str, int]]:
    """Batch and sequence as the traced step program ran them (the
    argument shapes ``StepProgram`` noted on its first call); widths,
    taps and the layers of each kind from the configuration of the cell
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
    if "conv_L_cache" not in config or "layer_types" not in config:
        return None
    kinds = config["layer_types"]
    return {
        "batch": tokens.shape[0], "seq_len": tokens.shape[1],
        "channels": config["hidden_size"], "taps": config["conv_L_cache"],
        "n_heads": config["num_attention_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "layers": {"conv": kinds.count("conv"),
                   "attn": kinds.count("full_attention")},
    }


def roofline(result: Dict[str, Any], kernel: str, shapes: Dict[str, Any],
             device_kind: str) -> Optional[float]:
    """``kernel``'s share of its roofline, in per cent, over the steps
    the trace holds whole. The flash kernels' is ``mla_scopes``' own, at
    one head width."""
    if KERNELS[kernel] == "attn":
        return mla_scopes.roofline(result, kernel, {
            "batch_heads": shapes["batch"] * shapes["n_heads"],
            "seq_len": shapes["seq_len"], "d_qk": shapes["head_dim"],
            "d_v": shapes["head_dim"], "n_layers": shapes["layers"]["attn"],
        }, device_kind) if shapes["layers"]["attn"] else None
    layers = shapes["layers"]["conv"]
    whole = [s for s in result["steps"]
             if s["calls"]["sconv_bwd"] == layers
             and s["calls"]["sconv_fwd"] in (layers, 2 * layers)]
    kernel_s = sum(s["seconds"][kernel] for s in whole)
    if kernel_s <= 0 or not layers:
        return None
    peaks = flops.peaks(device_kind)
    dims = dict(channels=shapes["channels"])
    least_s = len(whole) * layers * shapes["batch"] * shapes["seq_len"] * max(
        lfm2_flops.sconv_flops_per_token(kernel, taps=shapes["taps"], **dims)
        / peaks["bf16_flops"],
        lfm2_flops.sconv_bytes_per_token(kernel, **dims)
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
