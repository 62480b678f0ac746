"""The faults ``families/qwen3_next.py``'s limits must catch, and the
readings the limits are set from, on the chip (run by hand; PERF.md
section 4, PR 63): the cell's own comparisons at the configuration's
widths, depth and share on sound weights over many seeds, and under each
fault, a few seeds each:

    python benchmark/tests/qwen3next_faults.py --sound 8 --faulty 2

Each variant is one compiled program run on every seed. :func:`fault` is
also what ``tests/test_qwen3_next.py`` runs at the small size on the CPU.
The faults: the delta rule's state rounded to bf16 at chunk boundaries;
value head ``h`` reading key head ``h % H_k`` instead of ``h // r``; ``β =
2σ``; the head norm's gate before the norm; norms with ``w`` for ``1 +
w``; the rotation over half the head (128 lanes of 256); the attention
gate a head instead of an element, and left out; the shared expert's gate
left out; the router's logits in bf16; the attention operands rounded to
8 bits (e4m3). A fault inside the scan, the flash call or the sparse
sublayer is also put through that part's own comparison, and the reading
is sound only if all are. Prints one JSON line a reading and writes them all to
``chiprun_out/qwen3next_faults.json``.
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

FAULTS = ("state_bf16", "value_heads_modulo", "beta_doubled",
          "gate_before_norm", "norm_plain_weight", "rope_half_head",
          "attn_gate_a_head", "attn_gate_dropped", "shared_gate_dropped",
          "router_bf16", "attention_fp8")
# those inside the sparse sublayer: also put through its own comparison
IN_THE_SUBLAYER = ("shared_gate_dropped", "router_bf16")
CHUNK = 128     # where ``state_bf16`` rounds: ``ops/kda.py``'s chunk


def fault(name: str, cfg: Any) -> Tuple[
        tuple, Optional[Any], Optional[Callable], Optional[Callable],
        Optional[Callable]]:
    """``(patches, system_cfg, attn_fn, scan_fn, flash_fn)`` of one fault:
    what to put in the place of the model's pieces while the system is
    traced (``(module, attribute, replacement)`` each), another system
    config, what stands in ``causal_attention``'s place in the whole
    model, and — for a fault inside a kernel — what stands in
    ``gdn_scan``'s or in ``causal_attention``'s place in that kernel's own
    comparison; ``None`` where the fault leaves that alone."""
    import jax
    import jax.numpy as jnp

    from benchmark.families.qwen3_next import recurrence_in_blocks
    from torchft_tpu.models import common, qwen3_next
    from torchft_tpu.ops import moe
    from torchft_tpu.ops.attention import causal_attention

    real_routing, real_step = moe.top_k_routing, qwen3_next.decay_and_step
    patches: tuple = ()
    system_cfg = attn_fn = scan_fn = flash_fn = None
    f32 = jnp.float32

    if name == "state_bf16":
        def scan_fn(q, k, v, g, beta):
            # position by position in jnp, f32 inside, the state rounded
            # to bf16 where the kernels' chunks end
            return recurrence_in_blocks(
                q, k, v, g, beta, CHUNK, _to_bf16).astype(v.dtype)
        patches = ((qwen3_next, "_gdn_scan", scan_fn),)
    elif name == "value_heads_modulo":
        patches = ((qwen3_next, "_value_groups", lambda x, n: jnp.tile(
            x, (1, 1, n // x.shape[2], 1))),)
    elif name == "beta_doubled":
        def doubled(cfg, m, n):
            g, beta = real_step(cfg, m, n)
            return g, 2.0 * beta
        patches = ((qwen3_next, "decay_and_step", doubled),)
    elif name == "gate_before_norm":
        def gate_first(o, scale, gate, eps):
            return common.rms_norm(
                o.astype(f32) * jax.nn.silu(gate.astype(f32)), scale,
                eps).astype(o.dtype)
        patches = ((qwen3_next, "_gated_head_norm", gate_first),)
    elif name == "norm_plain_weight":
        patches = ((qwen3_next, "unit_plus", lambda w: w.astype(f32)),)
    elif name == "rope_half_head":
        system_cfg = dataclasses.replace(cfg, partial_rotary=0.5)
    elif name == "attn_gate_a_head":
        def a_head(o, logits):
            B, S, H, D = o.shape
            gate = jax.nn.sigmoid(jnp.mean(
                logits.reshape(B, S, H, D), axis=-1, keepdims=True))
            return (o.astype(f32) * gate).astype(o.dtype).reshape(B, S, H * D)
        patches = ((qwen3_next, "attn_gate", a_head),)
    elif name == "attn_gate_dropped":
        patches = ((qwen3_next, "attn_gate", lambda o, logits: o.reshape(
            *o.shape[:2], -1)),)
    elif name == "shared_gate_dropped":
        patches = ((qwen3_next, "_shared_expert", lambda cfg, m, h:
                    common.swiglu(h, m, cfg.dtype)),)
    elif name == "router_bf16":
        patches = ((moe, "top_k_routing", lambda s, k, **kw: real_routing(
            _to_bf16(s), k, **kw)),)
    elif name == "attention_fp8":
        def fp8(x):
            return jax.lax.reduce_precision(x, exponent_bits=4,
                                            mantissa_bits=3)

        def rounded(q, k, v):
            return causal_attention(fp8(q), fp8(k), fp8(v))
        attn_fn = flash_fn = rounded
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, system_cfg, attn_fn, scan_fn, flash_fn


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=8)
    ap.add_argument("--faulty", type=int, default=2)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "qwen3-next-80b-a3b-ep16.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import qwen3_next as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import qwen3_next
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: qwen3_next.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + FAULTS:
        if args.only and name not in args.only:
            continue
        n = args.sound if name == "sound" else args.faulty
        fn, alone, moe = None, {}, None
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            bits = np.uint32(seed & 0xFFFFFFFF)
            params = init(bits)
            tokens, targets = BatchSource(
                seed, 0x7133, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            patches, system_cfg, attn_fn, scan_fn, flash_fn = (
                ((), None, None, None, None) if name == "sound"
                else fault(name, cfg))
            if fn is None:      # one program a variant: traced on its
                fn = jax.jit(family.comparison(     # first seed, patched
                    cfg, system_cfg=system_cfg, attn_fn=attn_fn))
                if name == "sound" or scan_fn is not None:
                    alone["gdn"] = (family.judge_gdn, family.GDN_LEAVES, jax.jit(
                        lambda b, scan_fn=scan_fn: family.gdn_comparison(
                            scan_fn)(*family.gdn_inputs(
                                cfg, b, model.rows, model.seq_len))))
                if name == "sound" or flash_fn is not None:
                    alone["flash"] = (
                        family.judge_flash, family.FLASH_LEAVES,
                        jax.jit(family.flash_comparison(
                            cfg, model.rows, model.seq_len, flash_fn)))
                if name in ("sound",) + IN_THE_SUBLAYER:
                    moe = jax.jit(family.moe_comparison(cfg),
                                  static_argnums=2)
            with patched(patches):
                seen = family.per_token_errors(
                    cfg, params, params, tokens, targets, seed, fn=fn)
                if name in ("sound",) + IN_THE_SUBLAYER:
                    sublayer = jax.device_get(moe(params, bits,
                                                  model.seq_len))
            reading = dict(family.judge(seen), variant=name, seed=seed)
            if name in ("sound",) + IN_THE_SUBLAYER:
                judged = family.judge_moe(sublayer)
                reading.update(
                    judged, ok=reading["ok"] and judged["ok"],
                    moe_flips=float(sublayer["flips"]),
                    moe_rel_l2=float(sublayer["rel_l2"]))
            for kernel, (judge, leaves, compare) in alone.items():
                errors = jax.device_get(compare(bits))
                judged = judge(errors)
                reading.update(
                    judged, ok=reading["ok"] and judged["ok"],
                    **{f"{kernel}_rel_l2": [float(errors[leaf])
                                            for leaf in leaves]})
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params
    with open(os.path.join(out, "qwen3next_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
