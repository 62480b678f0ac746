"""The readings ``families/joyai.py``'s limits are set from, on the chip
(run by hand; PERF.md section 6, PR 31): the cell's own comparison at the
configuration's widths, depth and share on sound weights over many seeds,
and under each fault the limits must catch, a few seeds each:

    python benchmark/tests/joyai_faults.py --sound 20 --faulty 4 --seed 9000

Each variant is one compiled program run on every seed. The faults:
fp8 (e4m3) in the held routed experts alone; one held expert dropped;
weights not renormalised; the 2.5 left out; the balance bias ignored in
the selection; ``rotate_half`` for the interleaved pairs; the score
scaled by 1/sqrt(128); ``v`` cut to its first half; the MTP term left
out of the loss. Prints one JSON line a reading and writes them all to
``chiprun_out/joyai_faults.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=20)
    ap.add_argument("--faulty", type=int, default=4)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "joyai-llm-flash-ep16.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import joyai as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import joyai, llama
    from torchft_tpu.ops import moe
    from torchft_tpu.ops.attention import causal_attention
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: joyai.init_params(cfg, jax.random.key(s)))

    def experts_only(fn):
        def leaf(path, x):
            names = [getattr(k, "key", None) for k in path]
            routed = ("moe" in names and "shared" not in names
                      and names[-2] in ("gate_proj", "up_proj", "down_proj"))
            return fn(x) if routed else x
        return lambda p: jax.tree_util.tree_map_with_path(leaf, p)

    def round_to_fp8(x):
        # on the host: inside one jitted computation the TPU compiler
        # keeps an f32 -> e4m3 -> f32 pair in f32 and nothing is rounded
        # (my chip run, PR 31: readings equal to the sound ones)
        import ml_dtypes

        rounded = np.asarray(x).astype(ml_dtypes.float8_e4m3fn).astype(
            np.float32)
        return jax.device_put(rounded, x.sharding)

    fp8 = experts_only(round_to_fp8)
    drop = jax.jit(experts_only(lambda x: x.at[3].set(0)
                                if x.shape[-1] == cfg.d_model else x))
    no_bias = jax.jit(lambda p: jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if joyai.is_balance_bias(path) else x, p))

    real_routing, real_rope = moe.top_k_routing, joyai._rope_pairs

    sound = jax.jit(family.comparison(cfg))
    # variant -> (jitted comparison, weight fault, what replaces the
    # model's pieces while it runs: jit traces on the first call)
    variants = {
        "sound": (sound, None, {}),
        "fp8_experts": (sound, fp8, {}),
        "expert_dropped": (sound, drop, {}),
        "bias_ignored": (sound, no_bias, {}),
        "no_renormalise": (jax.jit(family.comparison(cfg)), None, dict(
            routing=lambda s, k, **kw: real_routing(
                s, k, **dict(kw, renormalise=False)))),
        "no_scale": (jax.jit(family.comparison(
            cfg, system_cfg=dataclasses.replace(cfg, routed_scale=1.0))),
            None, {}),
        "rotate_half": (jax.jit(family.comparison(cfg)), None,
                        dict(rope=llama._rope)),
        "score_scale_128": (jax.jit(family.comparison(
            cfg, attn_fn=lambda q, k, v: causal_attention(
                q, k, v, scale=cfg.qk_nope_dim ** -0.5))), None, {}),
        "v_truncated": (jax.jit(family.comparison(
            cfg, attn_fn=lambda q, k, v: causal_attention(
                q, k, v.at[..., cfg.v_head_dim // 2:].set(0)))), None, {}),
        "mtp_left_out": (jax.jit(family.comparison(
            cfg, system_cfg=dataclasses.replace(cfg, mtp_coef=0.0))),
            None, {}),
    }
    readings = []
    for name, (fn, weight_fault, patches) in variants.items():
        if args.only and name not in args.only:
            continue
        n = args.sound if name == "sound" else args.faulty
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            params = family.seed_balance_bias(
                init(np.uint32(seed & 0xFFFFFFFF)), seed)
            tokens, targets = BatchSource(
                seed, 0x7265, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            system = params if weight_fault is None else weight_fault(params)
            moe.top_k_routing = patches.get("routing", real_routing)
            joyai._rope_pairs = patches.get("rope", real_rope)
            try:
                seen = jax.device_get(fn(system, params, tokens, targets))
            finally:
                moe.top_k_routing, joyai._rope_pairs = real_routing, real_rope
            verdict = family.judge(seen)
            reading = dict(verdict, variant=name, seed=seed)
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params, system
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "joyai_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
