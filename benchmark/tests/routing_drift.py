"""What the router of a cell that holds a share of its experts does
inside the window (run by hand on the chip; PERF.md section 4): the
cell's donated fused step on the cell's own batches
(``BatchSource(seed, 0, 0, ...)``), and every ``--every`` steps, on the
next batch, the share of each expert layer's assignments that fall on the
held experts and its load max / mean (the model file's ``loss_terms``),
with the step's wall time:

    python benchmark/tests/routing_drift.py --workload nemo3-ep16-solo-steady \\
        --steps 56 --every 4 --seeds 2

One JSON line a reading, with ``in_band``: whether every expert layer
holds 0.8 - 1.25 x ``held / router_width`` of its assignments at load
max / mean <= 2.0 (the rule a share cell's balance-bias rate is set by:
each configuration's ``assumed.balance_rule``); all of them to
``chiprun_out/routing_drift_<cell>.json``. The window of a 48 s run opens
at step 3 and closes near 48 s / the step's time later.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ROOT = os.path.dirname(_BENCH)
sys.path.insert(0, _ROOT)

BAND = (0.8, 1.25)        # of held / router_width, every layer
MAX_OVER_MEAN = 2.0


def cell_config(workload: str) -> dict:
    """The configuration ``workload`` names in ``BENCHMARK.json``, found
    as ``run.py`` finds it."""
    from benchmark.run import _load_json

    manifest = _load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}[
        cells[workload]["config"]]
    config = _load_json(entry["file"])
    if "share" not in config:
        raise SystemExit(f"{entry['name']} holds no share of its experts")
    return config


def in_band(held_share, load_max_over_mean, expected: float) -> bool:
    return (all(BAND[0] * expected <= s <= BAND[1] * expected
                for s in held_share)
            and all(m <= MAX_OVER_MEAN for m in load_max_over_mean))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json whose configuration "
                         "holds a share")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--every", type=int, default=8)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--seed", type=int, default=2147489001)
    ap.add_argument("--bias-rate", type=float, default=None,
                    help="another balance_bias_rate than the configuration's "
                         "(how a rate is chosen, once)")
    args = ap.parse_args()
    config = cell_config(args.workload)
    if args.bias_rate is not None:
        config["optimizer"]["balance_bias_rate"] = args.bias_rate

    import jax
    import jax.numpy as jnp

    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.utils.device import place_compile_cache

    family = importlib.import_module("benchmark.families." + config["family"])
    net = importlib.import_module("torchft_tpu.models." + config["family"])
    place_compile_cache()
    model = family.build(config)
    expected = model.cfg.n_experts_held / model.cfg.n_routed_experts
    device = jax.devices()[0]
    step = family.make_train_step(model)

    def routing(params, tokens, targets):
        t = net.loss_terms(model.cfg, params, tokens, targets)
        return (t["rows_held"] / jnp.sum(t["loads"], axis=-1),
                t["load_max_over_mean"])

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
                held = [round(float(x), 4) for x in held]
                skew = [round(float(x), 2) for x in skew]
                readings.append({
                    "seed": seed, "step": i, "held_share": held,
                    "load_max_over_mean": skew,
                    "in_band": in_band(held, skew, expected),
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
    path = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    name = f"routing_drift_{args.workload}"
    if args.bias_rate is not None:
        name += f"_{args.bias_rate:g}"
    with open(os.path.join(path, name + ".json"), "w") as f:
        json.dump({"expected": expected, "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
