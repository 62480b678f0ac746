"""The Mamba-2 scan kernels' share of their roofline at Granite 4.0-H's
shapes — ONE group of 64 heads of 64, state 128 — from the device trace:
``{"reader": "granite_scopes", "what": "ssd_g1_fwd_roofline" |
"ssd_g1_bwd_roofline"}``.

The reduction is ``ssm_scopes``' own (``reduce`` over the newest trace,
cached on the record by ``_reduction``; ``roofline``: the least time for
what the scan needs of that kernel — ``benchmark/ssd_flops.py`` as it
stands, the same work whatever grid computes it: operations over the bf16
peak or bytes over the HBM peak of ``peaks.json``, whichever is larger;
the bytes, at these shapes: 17.2 and 26.1 KB a token —, once a Mamba-2
layer a step, over the device self time of the kernels' events
(``ssd_fwd``, ``ssd_bwd``) in the steps the trace holds whole: one
``ssd_bwd`` a Mamba-2 layer and one or, under ``jax.checkpoint``, two
``ssd_fwd``. The forward run again under remat is time that counts and
work that does not). What is this file's is where the shapes come from:
``ssm_scopes.cell_shapes`` reads Nemotron-H's key names (``n_groups``,
``hybrid_override_pattern``) and yields nothing for this family, so the
accepted ``ssd_fwd_roofline`` / ``ssd_bwd_roofline`` keep to their cell;
:func:`cell_shapes` here reads ``mamba_*`` and ``layer_types`` and yields
nothing for any other family's configuration.

A program without the scan's kernels (every other family, and the parent
of PR 68, which cannot run this configuration) yields nothing, and the
metric is left out.

``BENCHMARK.json`` holds 128 of the contract's 128 per-layer entries, so
no file under ``layer_metrics/`` names this reader yet:
``benchmark/tests/granite_rooflines.py`` prints both numbers from a traced
run it makes.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

from benchmark import granite_flops, harness
from benchmark.readers import device_scopes, ssm_scopes

READS = {"ssd_g1_fwd_roofline": "ssd_fwd", "ssd_g1_bwd_roofline": "ssd_bwd"}


def config_shapes(config: Dict[str, Any], tokens: int
                  ) -> Optional[Dict[str, int]]:
    """``ssm_scopes.roofline``'s shapes from a configuration of this
    family and the tokens a step ran; ``None`` for another family's
    keys."""
    if "mamba_n_groups" not in config or "layer_types" not in config:
        return None
    return dict(granite_flops.scan_dims(config), tokens=tokens,
                n_layers=config["layer_types"].count(granite_flops.MAMBA))


def cell_shapes(trace_path: str) -> Optional[Dict[str, int]]:
    """Tokens a step as the traced step program ran them (the argument
    shapes ``StepProgram`` noted on its first call); the scan's widths,
    the chunk counted and the number of Mamba-2 layers from the
    configuration of the cell the harness wrote the trace for
    (``<TRACE_DIR>/<cell>/``)."""
    from torchft_tpu.utils import profiling

    step_args = getattr(profiling, "step_args", None)
    args = step_args("tft_train_step") if step_args else None
    if args is None:
        return None
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell_name = os.path.relpath(trace_path, harness.TRACE_DIR).split(os.sep)[0]
    cell = {w["name"]: w for w in manifest["workloads"]}[cell_name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(harness.ROOT, entry["file"])) as f:
        config = json.load(f)
    return config_shapes(config, args[2].shape[0] * args[2].shape[1])


def read(record: Dict[str, Any], spec: Dict[str, Any]) -> Optional[float]:
    result = ssm_scopes._reduction(record)
    if result is None:
        return None
    shapes = cell_shapes(device_scopes.newest_trace())
    if shapes is None:
        return None
    return ssm_scopes.roofline(result, READS[spec["what"]], shapes,
                               record["device_kind"])
