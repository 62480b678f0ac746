"""The faults ``families/kimi_linear.py``'s limits must catch, and the
readings the limits are set from, on the chip (run by hand; PERF.md
section 4, PR 40): the cell's own comparisons at the configuration's
widths, depth and share on sound weights over many seeds, and under each
fault, a few seeds each:

    python benchmark/tests/kimi_faults.py --sound 12 --faulty 3 --seed 9000
    python benchmark/tests/kimi_faults.py --scan --sound 12 --faulty 3

Each variant is one compiled program run on every seed. :func:`fault` is
also what ``tests/test_kimi_linear.py`` runs at the small size on the
CPU. The faults (ISSUE 40's list): one decay a head instead of one a
channel; ``β`` dropped (the rule writes at full strength); either l2
norm dropped; the delta rule's state kept in bf16; the decays ``exp g``
rounded to bf16; the taps in reverse order; the output gate BEFORE the
head norm; rotation applied in MLA (theta 10000, the config's unused
key); one held expert dropped; fp8 (e4m3, rounded on the host) in the
held experts alone. A fault inside the scan is also put through the
scan's own comparison (``families/kimi_linear.py::kda_comparison``), and
the reading is sound only if both are; ``--scan`` reads that comparison
alone (seconds a reading). Prints one JSON line a reading and writes
them all to ``chiprun_out/kimi_faults.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Callable, Optional, Tuple

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark.tests.lfm2_faults import _to_bf16, patched  # noqa: E402,F401
from benchmark.tests.nemotron_faults import (  # noqa: E402,F401
    _round_to_fp8,
    with_leaf,
)

FAULTS = ("decay_per_head", "beta_dropped", "q_l2_dropped", "k_l2_dropped",
          "state_bf16", "decays_bf16", "taps_reversed", "gate_before_norm",
          "mla_rotated", "expert_dropped", "fp8_experts")
# those inside the scan: also put through the scan's own comparison
IN_THE_SCAN = ("decay_per_head", "beta_dropped", "state_bf16", "decays_bf16")
# those that only round: a lower precision in one place
ROUNDING = ("state_bf16", "decays_bf16", "fp8_experts")


def jnp_scan(q, k, v, g, beta, state_bf16: bool = False):
    """The delta rule as one ``lax.scan`` over the positions in jnp, in
    the kernels' place (f32 inside, the result in ``v``'s dtype);
    ``state_bf16`` rounds the state to bf16 after every position."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    rnd = _to_bf16 if state_bf16 else (lambda a: a)
    B, _, H, K = q.shape

    def step(S, at):
        qt, kt, vt, gt, bt = at
        S = S * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.sum(S * kt[..., None], axis=-2))
        S = rnd(S + kt[..., None] * u[..., None, :])
        return S, jnp.sum(S * qt[..., None], axis=-2)

    _, o = jax.lax.scan(
        step, jnp.zeros((B, H, K, v.shape[3]), f32),
        tuple(jnp.moveaxis(z.astype(f32), 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def fault(name: str, cfg: Any, params: Any
          ) -> Tuple[tuple, Optional[Any], Optional[Any], Optional[Callable]]:
    """``(patches, weights, system_cfg, scan_fn)`` of one fault: what to
    put in the place of the model's pieces while the system is traced
    (``(module, attribute, replacement)`` each), the faulty weights,
    another system config, and — for a fault inside the scan — what
    stands in ``kda_scan``'s place in the scan's own comparison; ``None``
    where the fault leaves that alone. Weight faults strike the first
    layer of the kind."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import common, kimi_linear
    from torchft_tpu.ops.kda import kda_scan

    first_kda = f"layers_{min(cfg.kda_layers) - 1}"
    experts = [f"layers_{i}" for i in range(cfg.n_dense_layers, cfg.n_layers)]
    real_l2 = kimi_linear._l2_normed
    patches: tuple = ()
    weights = system_cfg = scan_fn = None

    def scan_patch(fn):
        return ((kimi_linear, "_kda_scan", fn),), fn

    def l2_dropped(which: int):
        # the mixer norms q, then k: calls 0, 2, 4, ... are q's
        calls = [0]

        def normed(x):
            calls[0] += 1
            if (calls[0] - 1) % 2 == which:
                return x.astype(jnp.float32)
            return real_l2(x)
        return ((kimi_linear, "_l2_normed", normed),)

    if name == "decay_per_head":
        patches, scan_fn = scan_patch(lambda q, k, v, g, beta: kda_scan(
            q, k, v, jnp.broadcast_to(
                jnp.mean(g, axis=-1, keepdims=True), g.shape), beta))
    elif name == "beta_dropped":
        patches, scan_fn = scan_patch(lambda q, k, v, g, beta: kda_scan(
            q, k, v, g, jnp.ones_like(beta)))
    elif name == "q_l2_dropped":
        patches = l2_dropped(0)
    elif name == "k_l2_dropped":
        patches = l2_dropped(1)
    elif name == "state_bf16":
        patches, scan_fn = scan_patch(
            lambda *a: jnp_scan(*a, state_bf16=True))
    elif name == "decays_bf16":
        # exp(g) in bf16: a slow channel's 0.999 rounds to 1 or 0.996
        patches, scan_fn = scan_patch(lambda q, k, v, g, beta: kda_scan(
            q, k, v, jnp.log(_to_bf16(jnp.exp(g))), beta))
    elif name == "taps_reversed":
        weights = with_leaf(params, first_kda, ("kda", "conv", "kernel"),
                            lambda w: w[::-1])
    elif name == "gate_before_norm":
        def gated_first(o, scale, gate, eps):
            f32 = jnp.float32
            return common.rms_norm(
                o.astype(f32) * jax.nn.sigmoid(gate.astype(f32)),
                scale, eps).astype(o.dtype)
        patches = ((kimi_linear, "_gated_head_norm", gated_first),)
    elif name == "mla_rotated":
        system_cfg = dataclasses.replace(cfg, rope_theta=10000.0)
    elif name == "expert_dropped":
        weights = with_leaf(params, experts[0],
                            ("moe", "down_proj", "kernel"),
                            lambda w: w.at[1].set(0))
    elif name == "fp8_experts":
        weights = params
        for layer in experts:
            for leaf in ("gate_proj", "up_proj", "down_proj"):
                weights = with_leaf(weights, layer, ("moe", leaf, "kernel"),
                                    _round_to_fp8)
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, weights, system_cfg, scan_fn


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=12)
    ap.add_argument("--faulty", type=int, default=3)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--scan", action="store_true",
                    help="the scan's own comparison alone")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "kimi-linear-48b-a3b-ep32.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import kimi_linear as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import kimi_linear
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: kimi_linear.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + (IN_THE_SCAN if args.scan else FAULTS):
        if args.only and name not in args.only:
            continue
        n = args.sound if name == "sound" else args.faulty
        fn = scan = moe = None
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            reading = {"variant": name, "seed": seed, "ok": True}
            params = None
            if not args.scan:
                params = family.seed_balance_bias(
                    init(np.uint32(seed & 0xFFFFFFFF)), seed)
            patches, weights, system_cfg, scan_fn = (
                ((), None, None, None) if name == "sound"
                else fault(name, cfg, params))
            if not args.scan:
                tokens, targets = BatchSource(
                    seed, 0x7265, 0, family.REFERENCE_SEQUENCES,
                    model.seq_len, model.vocab_draw).device_batch(0, device)
                system = params if weights is None else weights
                if fn is None:  # one program a variant: traced on its
                    fn = jax.jit(family.comparison(     # first seed, patched
                        cfg, system_cfg=system_cfg))
                with patched(patches):
                    seen = jax.device_get(fn(system, params, tokens, targets))
                reading.update(family.judge(seen))
                if moe is None:
                    moe = jax.jit(family.moe_comparison(cfg))
                judged = family.judge_moe(jax.device_get(moe(
                    system, params, np.uint32(seed & 0xFFFFFFFF))))
                reading.update(judged, ok=reading["ok"] and judged["ok"])
                del params, system
            if name == "sound" or scan_fn is not None:
                if scan is None:
                    scan = jax.jit(family.kda_comparison(scan_fn))
                alone = jax.device_get(scan(*family.kda_inputs(cfg, seed)))
                judged = family.judge_kda(alone)
                reading.update(
                    judged, ok=reading["ok"] and judged["ok"],
                    kda_rel_l2={k: float(v) for k, v in alone.items()})
            readings.append(reading)
            print(json.dumps(reading), flush=True)
    name = "kimi_faults_scan.json" if args.scan else "kimi_faults.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
