"""What ``readers/granite_scopes.py`` reads of one traced run of the
Granite cell, on the chip, by hand (PERF.md sections 3 and 5, PR 68): the
manifest holds its 128 per-layer metrics, so ``ssd_g1_fwd_roofline`` and
``ssd_g1_bwd_roofline`` (per cent of the kernels' bytes roofline at ONE
group of 64 heads) have no entry there yet, and this tool prints them from
the run it makes:

    python benchmark/tests/granite_rooflines.py --seed 2147483659

It runs ``benchmark/run.py``'s own ``main`` for the cell with ``--trace
1`` in this process (the reader needs the step program's own record of
its arguments and scopes, which live with the process), then hands the
reader the trace that run wrote. Prints the run's lines, then one JSON
line with the numbers, also written to
``chiprun_out/granite_rooflines.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

CELL = "granite4h-vp8-solo-steady"


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

    from benchmark.readers import granite_scopes

    record = {"device_kind": jax.devices()[0].device_kind}
    seen = {name: granite_scopes.read(record, {"what": name})
            for name in granite_scopes.READS}
    for note in record.get("notes", []):
        print("note " + note, flush=True)
    print(json.dumps({"granite_scopes": seen}), flush=True)
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "granite_rooflines.json"), "w") as f:
        json.dump(seen, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
