"""What the router of ``kimi-ep32-solo-steady`` does inside the window
(run by hand on the chip; PERF.md section 4, PR 40; sibling of
``lfm2_drift.py``): the cell's donated fused step on the cell's own
batches (``BatchSource(seed, 0, 0, ...)``), and every ``--every`` steps,
on the next batch, the share of each expert layer's assignments that
fall on the held experts and its load max / mean
(``models/kimi_linear.py::loss_terms``), with the step's wall time:

    python benchmark/tests/kimi_drift.py --steps 24 --every 8 --seeds 4

One JSON line a reading; all of them to ``chiprun_out/kimi_drift.json``.
The expected share is 8 / 256 = 0.03125 a layer; ISSUE 40's band is
0.8 - 1.25 of it (0.025 - 0.039) in all four expert layers throughout.
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
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--every", type=int, default=8)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--seed", type=int, default=2147489001)
    ap.add_argument("--bias-rate", type=float, default=None,
                    help="another balance_bias_rate than the configuration's "
                         "(how ISSUE 40's lever was read)")
    args = ap.parse_args()

    import jax

    from benchmark.families import kimi_linear as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import kimi_linear
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(os.path.join(_BENCH, "configs",
                           "kimi-linear-48b-a3b-ep32.json")) as f:
        config = json.load(f)
    if args.bias_rate is not None:
        config["optimizer"]["balance_bias_rate"] = args.bias_rate
    model = family.build(config)
    device = jax.devices()[0]
    step = family.make_train_step(model)

    def routing(params, tokens, targets):
        t = kimi_linear.loss_terms(model.cfg, params, tokens, targets)
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
    name = ("kimi_drift.json" if args.bias_rate is None
            else f"kimi_drift_{args.bias_rate:g}.json")
    with open(os.path.join(path, name), "w") as f:
        json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
