"""What ``readers/keye_scopes.py`` reads of one traced run of the Keye
cell, on the chip, by hand (PERF.md sections 5 and 7, PR 66): the
manifest holds its 128 per-layer metrics, so the family's ten
(``dsa_select_roofline``, ``dsa_fwd_roofline``, ``dsa_dq_roofline``,
``dsa_dkv_roofline``, ``dsa_kl_roofline`` in per cent;
``dsa_index_device_share``, ``dsa_select_device_share``,
``dsa_core_device_share``, ``dsa_kl_device_share`` as shares of the
chip's busy time; ``dsa_selected_share``, chosen pairs over causal
pairs) have no entry there yet, and this tool prints them from the run it
makes:

    python benchmark/tests/keye_rooflines.py --seed 2147483659

It runs ``benchmark/run.py``'s own ``main`` for the cell with ``--trace
1`` in this process (the reader needs the step program's own record of
its arguments and scopes, which live with the process), then hands the
reader the trace that run wrote. Prints the run's lines, then one JSON
line with the numbers, also written to
``chiprun_out/keye_rooflines.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

CELL = "keye2-ep8-solo-steady"
READS = {
    **{k + "_roofline": k + "_roofline" for k in
       ("dsa_select", "dsa_fwd", "dsa_dq", "dsa_dkv", "dsa_kl")},
    **{k + "_device_share": k for k in
       ("dsa_index", "dsa_select", "dsa_core", "dsa_kl")},
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

    from benchmark.readers import keye_scopes

    record = {"device_kind": jax.devices()[0].device_kind}
    seen = {name: keye_scopes.read(record, {"what": what})
            for name, what in READS.items()}
    for note in record.get("notes", []):
        print("note " + note, flush=True)
    print(json.dumps({"keye_scopes": seen}), flush=True)
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "keye_rooflines.json"), "w") as f:
        json.dump(seen, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
