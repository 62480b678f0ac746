"""The faults ``families/laguna.py``'s limits must catch, and the
readings the limits are set from, on the chip (run by hand; PERF.md
section 4, PR 59): the cell's own comparisons at the configuration's
widths, depth and share on sound weights over many seeds, and under each
fault, a few seeds each:

    python benchmark/tests/laguna_faults.py --sound 8 --faulty 2

Each variant is one compiled program run on every seed. :func:`fault` is
also what ``tests/test_laguna_family.py`` runs at the small size on the
CPU. The faults: a window of 511 and of 513 keys; the sliding layers'
query heads grouped on key/value heads by 6 and the full layers' by 8
(the other kind's group); the full layers turned over the whole head; the
YaRN ramp dropped (plain theta 5e5); ``attention_factor`` dropped; the
sliding layers turned at theta 5e5; the gate dropped; the gate a softmax
over heads; softmax scores in the router; the 2.5 dropped; the shared
expert scaled by 2.5; the weight applied to the expert's input; the
router's scores in bf16; the attention operands rounded to 8 bits (e4m3).
A fault of a flash call is also put through that call's own comparison
(``families/laguna.py::flash_comparison``), and the reading is sound only
if both are. Prints one JSON line a reading and writes them all to
``chiprun_out/laguna_faults.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Callable, Dict, Optional, Tuple

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark.tests.lfm2_faults import _to_bf16, patched  # noqa: E402,F401

FAULTS = ("window_511", "window_513", "groups_swapped", "rope_full_whole",
          "yarn_ramp_dropped", "attention_factor_dropped",
          "rope_swa_theta_5e5", "gate_dropped", "gate_softmax",
          "router_softmax", "scale_dropped", "shared_scaled",
          "weight_on_input", "router_bf16", "attention_fp8")


def fault(name: str, cfg: Any) -> Tuple[
        tuple, Optional[Any], Optional[Callable], Dict[str, Callable]]:
    """``(patches, system_cfg, attn_fn, flash_fns)`` of one fault: what to
    put in the place of the model's pieces while the system is traced
    (``(module, attribute, replacement)`` each), another system config,
    what stands in ``causal_attention``'s place in the whole model, and —
    for a fault of a flash call — what stands in its place in that call's
    own comparison (``{"swa" | "full": attn_fn}``); ``None`` / empty where
    the fault leaves that alone."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import laguna
    from torchft_tpu.ops import moe
    from torchft_tpu.ops.attention import causal_attention

    real_routing, real_sublayer = moe.top_k_routing, laguna.routed_sublayer
    real_swiglu = laguna.swiglu
    patches: tuple = ()
    system_cfg = attn_fn = None
    flash_fns: Dict[str, Callable] = {}

    def other_window(faulty):
        # the comparison passes the configuration's window; the stand-in
        # takes another where there is one
        def attn(q, k, v, window=None):
            return causal_attention(
                q, k, v, window=None if window is None else faulty)
        return attn

    def other_rope(**kinds):
        return dataclasses.replace(cfg, **{
            k: dataclasses.replace(getattr(cfg, k), **v)
            for k, v in kinds.items()})

    if name in ("window_511", "window_513"):
        faulty = cfg.window + (1 if name == "window_513" else -1)
        system_cfg = dataclasses.replace(cfg, window=faulty)
        flash_fns = {"swa": other_window(faulty)}
    elif name == "groups_swapped":
        # query head i on key/value head i // (the OTHER kind's group),
        # by a copy; the call then runs at equal head counts
        groups = sorted({h // cfg.n_kv_heads for h in cfg.heads})

        def regrouped(q, k, v, window=None):
            group = q.shape[2] // k.shape[2]
            wrong = next(g for g in groups if g != group)
            at = jnp.minimum(jnp.arange(q.shape[2]) // wrong, k.shape[2] - 1)
            return causal_attention(q, k[:, :, at], v[:, :, at],
                                    window=window)
        attn_fn = regrouped
        flash_fns = {"swa": regrouped, "full": regrouped}
    elif name == "rope_full_whole":
        system_cfg = other_rope(rope_full=dict(partial=1.0))
    elif name == "yarn_ramp_dropped":
        system_cfg = other_rope(rope_full=dict(yarn_factor=None))
    elif name == "attention_factor_dropped":
        system_cfg = other_rope(rope_full=dict(attention_factor=1.0))
    elif name == "rope_swa_theta_5e5":
        system_cfg = other_rope(rope_swa=dict(theta=cfg.rope_full.theta))
    elif name == "gate_dropped":
        patches = ((laguna, "head_gate", lambda n, w: jnp.ones(
            (*n.shape[:-1], w.shape[-1]), jnp.float32)),)
    elif name == "gate_softmax":
        patches = ((laguna, "head_gate", lambda n, w: jax.nn.softmax(jnp.dot(
            n, w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)),)
    elif name == "router_softmax":
        patches = ((laguna, "routed_sublayer", lambda *a, **kw:
                    real_sublayer(*a, **dict(kw, score="softmax"))),)
    elif name == "scale_dropped":
        system_cfg = dataclasses.replace(cfg, routed_scale=1.0)
    elif name == "shared_scaled":
        patches = ((laguna, "swiglu", lambda h, m, dt:
                    cfg.routed_scale * real_swiglu(h, m, dt)),)
    elif name == "weight_on_input":
        def on_input(h, weights, experts, gate, up, down, *, n_routed,
                     first_expert, activation=None):
            # every held expert on every row, the row scaled by its weight
            # BEFORE the expert (0 where the expert was not chosen)
            dense = jnp.zeros((h.shape[0], n_routed), jnp.float32).at[
                jnp.arange(h.shape[0])[:, None], experts].set(weights)
            held = dense[:, first_expert:first_expert + up.shape[0]]

            def add(y, args):
                g, u, d, w = args
                x = h * w[:, None].astype(h.dtype)
                dt = h.dtype
                return y + (jax.nn.silu(x @ g.astype(dt))
                            * (x @ u.astype(dt))) @ d.astype(dt), None

            y, _ = jax.lax.scan(add, jnp.zeros_like(h),
                                (gate, up, down, held.T))
            return y
        patches = ((moe, "moe_mlp", on_input),)
    elif name == "router_bf16":
        patches = ((moe, "top_k_routing", lambda s, k, **kw: real_routing(
            _to_bf16(s), k, **kw)),)
    elif name == "attention_fp8":
        def fp8(x):
            return jax.lax.reduce_precision(x, exponent_bits=4,
                                            mantissa_bits=3)

        def rounded(q, k, v, window=None):
            return causal_attention(fp8(q), fp8(k), fp8(v), window=window)
        attn_fn = rounded
        flash_fns = {"swa": rounded, "full": rounded}
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, system_cfg, attn_fn, flash_fns


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=8)
    ap.add_argument("--faulty", type=int, default=2)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "laguna-xs2-ep8.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import laguna as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import laguna
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: laguna.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + FAULTS:
        if args.only and name not in args.only:
            continue
        n = args.sound if name == "sound" else args.faulty
        fn, alone = None, {}
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            params = family.seed_balance_bias(
                init(np.uint32(seed & 0xFFFFFFFF)), seed)
            tokens, targets = BatchSource(
                seed, 0x7265, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            patches, system_cfg, attn_fn, flash_fns = (
                ((), None, None, {}) if name == "sound" else fault(name, cfg))
            if fn is None:      # one program a variant: traced on its
                fn = jax.jit(family.comparison(     # first seed, patched
                    cfg, system_cfg=system_cfg, attn_fn=attn_fn))
                alone = {call: jax.jit(family.flash_comparison(
                    cfg, call, model.rows, model.seq_len,
                    flash_fns.get(call)))
                    for call in family.FLASH_CALLS
                    if name == "sound" or call in flash_fns}
            with patched(patches):
                seen = jax.device_get(fn(params, params, tokens, targets))
            reading = dict(family.judge(seen), variant=name, seed=seed)
            for call, compare in alone.items():
                errors = jax.device_get(compare(
                    np.uint32(seed & 0xFFFFFFFF)))
                judged = family.judge_flash(call, errors)
                reading.update(
                    judged, ok=reading["ok"] and judged["ok"],
                    **{f"{call}_rel_l2": [float(errors[leaf])
                                          for leaf in family.FLASH_LEAVES]})
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params
    with open(os.path.join(out, "laguna_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
