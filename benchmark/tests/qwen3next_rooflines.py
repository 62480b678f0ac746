"""What ``readers/qwen3next_scopes.py`` reads of one traced run of the
Qwen3-Next cell, on the chip, by hand (PERF.md sections 5 and 7, PR 63):
the manifest holds its 128 per-layer metrics, so the family's seven
(``gva_gdn_fwd_roofline``, ``gva_gdn_bwd_roofline``,
``full256_flash_fwd_roofline``, ``full256_flash_dq_roofline``,
``full256_flash_dkv_roofline`` in per cent; ``gdn_repeat_device_share``,
``attn_gate_device_share`` as shares of the chip's busy time) have no
entry there yet, and this tool prints them from the run it makes:

    python benchmark/tests/qwen3next_rooflines.py --seed 2147483659

It runs ``benchmark/run.py``'s own ``main`` for the cell with ``--trace
1`` in this process (the reader needs the step program's own record of
its arguments and scopes, which live with the process), then hands the
reader the trace that run wrote. Prints the run's lines, then one JSON
line with the seven numbers, also written to
``chiprun_out/qwen3next_rooflines.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

CELL = "qwen3next-ep16-solo-steady"
READS = {
    "gva_gdn_fwd_roofline": "gdn_fwd_roofline",
    "gva_gdn_bwd_roofline": "gdn_bwd_roofline",
    "full256_flash_fwd_roofline": "flash_fwd_roofline",
    "full256_flash_dq_roofline": "flash_dq_roofline",
    "full256_flash_dkv_roofline": "flash_dkv_roofline",
    "gdn_repeat_device_share": "gdn_repeat",
    "attn_gate_device_share": "attn_gate",
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=48.0)
    args = ap.parse_args()

    from benchmark import run

    rc = run.main(["--workload", CELL, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if rc:
        return rc
    import jax

    from benchmark.readers import qwen3next_scopes

    record = {"device_kind": jax.devices()[0].device_kind}
    seen = {name: qwen3next_scopes.read(record, {"what": what})
            for name, what in READS.items()}
    for note in record.get("notes", []):
        print("note " + note, flush=True)
    print(json.dumps({"qwen3next_scopes": seen}), flush=True)
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "qwen3next_rooflines.json"), "w") as f:
        json.dump(seen, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
