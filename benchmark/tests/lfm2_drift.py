"""What the router of ``lfm2-ep4-solo-steady`` does inside the window
(run by hand on the chip; PERF.md section 4, PR 38): the cell's donated
fused step on the cell's own batches (``BatchSource(seed, 0, 0, ...)``),
and every ``--every`` steps, on the next batch, the share of each expert
layer's assignments that fall on the held experts and its load max /
mean (``models/lfm2.py::loss_terms``), with the step's wall time:

    python benchmark/tests/lfm2_drift.py --steps 48 --every 8 --seeds 3

One JSON line a reading; all of them to ``chiprun_out/lfm2_drift.json``.
The expected share is 8 / 32 = 0.25 a layer.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--every", type=int, default=8)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2147489001)
    args = ap.parse_args()

    import jax

    from benchmark.families import lfm2 as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import lfm2
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(os.path.join(_BENCH, "configs", "lfm2-8b-a1b-ep4.json")) as f:
        model = family.build(json.load(f))
    device = jax.devices()[0]
    step = family.make_train_step(model)

    def routing(params, tokens, targets):
        t = lfm2.loss_terms(model.cfg, params, tokens, targets)
        return t["held_share"], t["load_max_over_mean"]

    routing = jax.jit(routing)
    readings = []
    for n in range(args.seeds):
        seed = args.seed + 7919 * n
        source = BatchSource(seed, 0, 0, model.rows, model.seq_len,
                             model.vocab_draw)
        state = family.init_state(model, seed, device)
        params, opt = state["params"], state["opt"]
        for i in range(args.steps + 1):
            if i % args.every == 0:
                held, skew = jax.device_get(
                    routing(params, *source.device_batch(i, device)))
                readings.append({
                    "seed": seed, "step": i,
                    "held_share": [round(float(x), 4) for x in held],
                    "load_max_over_mean": [round(float(x), 2) for x in skew],
                })
                print(json.dumps(readings[-1]), flush=True)
            t = time.perf_counter()
            params, opt, loss = step(params, opt,
                                     *source.device_batch(i, device))
            loss = float(loss)
            if i % args.every == 0:
                print(json.dumps({"seed": seed, "step": i, "loss": loss,
                                  "step_s": time.perf_counter() - t}),
                      flush=True)
        del params, opt, state
    path = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "lfm2_drift.json"), "w") as f:
        json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
