"""The faults ``families/ouro.py``'s limits must catch, and the readings
the limits are set from, on the chip (run by hand; PERF.md section 4,
PR 73): the cell's own comparison at the configuration's widths, depth,
passes and length on sound weights over many seeds, and under each fault,
a few seeds each:

    python benchmark/tests/ouro_faults.py --sound 6 --faulty 2 --seed 9000

Each variant is one compiled program run on every seed. :func:`fault` is
also what ``tests/test_ouro_family.py`` runs at the small size on the CPU.
The faults, each a stand-in patched into the SYSTEM (the reference imports
nothing of it): three passes for four; the un-normed stream re-entering
(the final norm feeds the head and the gate only); the MLP branch's
outgoing norm left out (``common.dense_sublayer``, the pre-norm block);
the gate on the un-normed stream; ``p_T = λ_T S_{T−1}`` (no remainder: the
mass does not add up); β's term dropped; its sign turned; the four losses
averaged unweighted; θ 1e4 for 1e6; the stream between layer-steps rounded
to fp8 (e4m3: the nearest precision below the configuration's bf16).
Prints one JSON line a reading and writes them all to
``chiprun_out/ouro_faults.json``.
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

FAULTS = ("three_passes", "unnormed_stream_reenters", "mlp_out_norm_left_out",
          "gate_on_unnormed_stream", "last_pass_not_the_remainder",
          "entropy_term_dropped", "entropy_sign_turned",
          "losses_averaged_unweighted", "theta_1e4", "fp8_stream")


def fault(name: str, cfg: Any) -> Tuple[tuple, Optional[Any]]:
    """``(patches, system_cfg)`` of one fault: what to put in the place of
    the model's pieces while the system is traced (``(module, attribute,
    replacement)`` each) and another system config; ``()`` / ``None`` where
    the fault leaves that alone."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import common, ouro

    real_end, real_layer, real_mix = ouro._pass_end, ouro._layer, ouro._mix
    patches: tuple = ()
    system_cfg = None
    if name == "three_passes":
        system_cfg = dataclasses.replace(cfg, ut_steps=cfg.ut_steps - 1)
    elif name == "unnormed_stream_reenters":
        def pass_end(c, params, h):
            return h, real_end(c, params, h)[1]
        patches = ((ouro, "_pass_end", pass_end),)
    elif name == "mlp_out_norm_left_out":
        patches = ((ouro, "_mlp_sublayer",
                    lambda c, layer, h: common.dense_sublayer(
                        c, h, layer["mlp_norm"]["scale"], layer["mlp"])),)
    elif name == "gate_on_unnormed_stream":
        def pass_end(c, params, h):
            x, (_, _g) = real_end(c, params, h)
            gate = params["exit_gate"]
            g = jnp.dot(h, gate["kernel"].astype(c.dtype),
                        preferred_element_type=jnp.float32)[..., 0]
            return x, (x, g + gate["bias"].astype(jnp.float32))
        patches = ((ouro, "_pass_end", pass_end),)
    elif name == "last_pass_not_the_remainder":
        def distribution(gate):
            log_exit = jax.nn.log_sigmoid(gate)
            log_stay = jnp.cumsum(jax.nn.log_sigmoid(-gate), axis=0)
            log_p = log_exit + jnp.concatenate(
                [jnp.zeros_like(gate[:1]), log_stay[:-1]])
            return jnp.exp(log_p), log_p
        patches = ((ouro, "exit_distribution", distribution),)
    elif name == "entropy_term_dropped":
        system_cfg = dataclasses.replace(cfg, exit_entropy_weight=0.0)
    elif name == "entropy_sign_turned":
        system_cfg = dataclasses.replace(
            cfg, exit_entropy_weight=-cfg.exit_entropy_weight)
    elif name == "losses_averaged_unweighted":
        def mix(c, p, log_p, weighted, nll):
            return jnp.mean(nll), real_mix(c, p, log_p, weighted, nll)[1]
        patches = ((ouro, "_mix", mix),)
    elif name == "theta_1e4":
        system_cfg = dataclasses.replace(cfg, rope_theta=1e4)
    elif name == "fp8_stream":
        def layer(c, weights, h, *, attn_fn):
            return jax.lax.reduce_precision(
                real_layer(c, weights, h, attn_fn=attn_fn),
                exponent_bits=4, mantissa_bits=3)
        patches = ((ouro, "_layer", layer),)
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, system_cfg


def _seed(base: int, i: int, sound: bool) -> int:
    # sound seeds and faulty seeds do not overlap; some pass 2^31
    return base + i + (0 if sound else 1000) + (2**31 if i % 2 else 0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=6)
    ap.add_argument("--faulty", type=int, default=2)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "ouro-2.6b-l8.json"))
    args = ap.parse_args()

    import jax

    from benchmark.families import ouro as family
    from benchmark.tests.lfm2_faults import patched
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import ouro
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: ouro.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + FAULTS:
        if args.only and name not in args.only:
            continue
        patches, system_cfg = ((), None) if name == "sound" else fault(
            name, cfg)
        fn: Optional[Callable] = jax.jit(
            family.comparison(cfg, system_cfg=system_cfg))
        for i in range(args.sound if name == "sound" else args.faulty):
            seed = _seed(args.seed, i, name == "sound")
            params = init(family._low_bits(seed))
            tokens, targets = BatchSource(
                seed, 0x6f75, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            with patched(patches):      # traced on the variant's first seed
                seen = family.per_token_errors(
                    cfg, params, params, tokens, targets, seed, fn=fn)
            reading = dict(family.judge(seen), variant=name, seed=seed)
            reading.pop("limits")
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "ouro_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
