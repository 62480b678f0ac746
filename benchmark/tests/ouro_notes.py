"""The Ouro cell's traced run with the readings that wait for a per-layer
place (the manifest's 128 are taken: PERF.md section 7): ``benchmark/
run.py``'s own run of ``ouro-l8-solo-steady`` with ``--trace 1`` — its
result line is printed as the driver reads it — and three more ``note``
lines before it:

- the head's achieved rate: ``6·T·N·d·V`` a step (``ouro_flops.
  head_flops_per_token``) over the device self time under ``lm_head_xent``
  in the step programs the trace holds whole, as a share of the bf16 peak;
- the device seconds a step under ``exit_gate`` and ``exit_mix`` (both read
  under ``unnamed_device_share``: neither is one of ``device_scopes``' six
  names) and under ``ut_pass`` outside ``attn`` and ``mlp`` (the pass's
  final norm and the scan's own copies and f32 gradient sums);
- the three gauges of the optimizer's sink (``ut_exit_mean_pass``,
  ``ut_exit_entropy``, ``ut_last_pass_loss``) at the window's end.

    python benchmark/tests/ouro_notes.py --seed 2147483659

TPU only, one chip (~5 min). The run is ``benchmark/run.py``'s to the
letter: the job is wrapped, not edited (as ``scripts/heal_timeline.py``).
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Any, Dict, List, Optional

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

CELL = "ouro-l8-solo-steady"
SCOPES = ("lm_head_xent", "exit_gate", "exit_mix", "ut_pass")


def scope_seconds(ops: Dict[int, List[Any]], modules: Dict[int, List[Any]],
                  tables: Dict[str, Dict[str, str]]) -> Optional[Dict[str, Any]]:
    """On plain data: device self seconds under each of ``SCOPES`` (an
    operation counts in every one its path holds; ``ut_pass_alone`` is
    ``ut_pass`` outside ``attn``, ``mlp`` and ``exit_gate``) over the
    ``whole`` step programs the trace holds. ``None`` without one."""
    from benchmark.readers import device_scopes

    held = device_scopes.whole_programs(ops, modules,
                                        device_scopes.STEP_PROGRAM)
    seconds = {s: 0.0 for s in SCOPES + ("ut_pass_alone",)}
    for key, _name, self_s, path in device_scopes.scoped_events(
            ops, modules, tables):
        if key not in held:
            continue
        tokens = device_scopes.scope_tokens(path)
        for scope in SCOPES:
            if scope in tokens:
                seconds[scope] += self_s
        if "ut_pass" in tokens and not tokens & {"attn", "mlp", "exit_gate"}:
            seconds["ut_pass_alone"] += self_s
    return dict(seconds, whole=len(held)) if held else None


def notes(record: Dict[str, Any], config: Dict[str, Any]) -> List[str]:
    """The three notes from a traced run's record."""
    import jax

    from benchmark import flops, ouro_flops
    from benchmark.readers import device_scopes
    from torchft_tpu.models.ouro import EXIT_GAUGES

    out = []
    found = device_scopes.trace_inputs(record)
    shape = device_scopes.step_tokens_shape()
    seen = scope_seconds(*found) if found is not None else None
    if seen is not None and shape is not None:
        dims = ouro_flops.config_dims(config)
        work = shape[0] * shape[1] * ouro_flops.head_flops_per_token(
            d_model=dims["d_model"], vocab=dims["vocab"],
            ut_steps=dims["ut_steps"])
        a_step = {k: v / seen["whole"] for k, v in seen.items()
                  if k != "whole"}
        peak = flops.peaks(jax.devices()[0].device_kind)["bf16_flops"]
        out.append(
            f"ouro head: {work / 1e12:.3f} TFLOP a step (6 T N d V) in "
            f"{a_step['lm_head_xent'] * 1e3:.2f} ms under lm_head_xent = "
            f"{100 * work / a_step['lm_head_xent'] / peak:.1f} % of the "
            f"bf16 peak, over {seen['whole']} whole step programs")
        out.append(
            "ouro scopes, device ms a step (all under unnamed_device_share)"
            f": exit_gate {a_step['exit_gate'] * 1e3:.3f}, exit_mix "
            f"{a_step['exit_mix'] * 1e3:.3f}, ut_pass outside attn / mlp / "
            f"exit_gate {a_step['ut_pass_alone'] * 1e3:.3f} of ut_pass "
            f"{a_step['ut_pass'] * 1e3:.2f}")
    sink = record["sinks"][0]["optimizer"]
    out.append("ouro gauges (optimizer's sink, the window's last reading): "
               + ", ".join(f"{k} {sink[k]:.4f}" if k in sink else f"{k} -"
                           for k in EXIT_GAUGES))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=48.0)
    args = ap.parse_args()

    from benchmark import run as bench_run
    from benchmark.jobs import steady

    job_run = steady.run

    def run(ctx: Any) -> Dict[str, Any]:
        record = job_run(ctx)
        record.setdefault("notes", []).extend(notes(record, ctx.config))
        return record

    steady.run = run
    return bench_run.main(["--workload", CELL, "--seed", str(args.seed),
                           "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
