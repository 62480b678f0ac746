"""The faults ``families/smallthinker.py``'s limits must catch, and the
readings the limits are set from, on the chip (run by hand; PERF.md
section 4, PR 50): the cell's own comparisons at the configuration's
widths, depth and share on sound weights over many seeds, and under each
fault, a few seeds each:

    python benchmark/tests/smallthinker_faults.py --sound 20 --faulty 2

Each variant is one compiled program run on every seed. :func:`fault` is
also what ``tests/test_smallthinker.py`` runs at the small size on the
CPU. The faults: the router reading ``n2`` (the experts' input) instead
of ``n1``; silu for relu in the experts; the window dropped, halved
(2048) and one tile short (3584 = 4096 − 512); RoPE on the full layer
too, dropped from the windowed ones, at theta 1e4, over interleaved
pairs; query head ``i`` on key/value head ``i % 4`` for ``i // 7``; the
balance bias weighting instead of only selecting; the weights the softmax
over all 64, not renormalised over the chosen; one held expert dropped;
fp8 (e4m3, rounded on the host) in the held experts alone. A fault of the
window is also put through the windowed call's own comparison
(``families/smallthinker.py::swa_comparison``), and the reading is sound
only if both are. ``UNLISTED`` variants are read and recorded but no
limit is claimed to hold them (``--unlisted``; the family file's header
has their readings). Prints one JSON line a reading and writes them all
to ``chiprun_out/smallthinker_faults.json``.
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

FAULTS = ("router_reads_n2", "silu_experts", "window_dropped",
          "window_halved", "window_tile_short", "rope_on_full",
          "rope_dropped", "rope_theta_1e4", "rope_interleaved",
          "kv_heads_modulo", "bias_weighting", "no_renormalise",
          "expert_dropped", "fp8_experts")
# read and recorded, held by no limit (the family file's header)
UNLISTED = ("router_bf16",)
# those that only round: a lower precision in one place
ROUNDING = ("fp8_experts", "router_bf16")
# those that change the windowed call itself: ``fault`` hands back what
# stands in its place in the call's own comparison
WINDOW = ("window_dropped", "window_halved", "window_tile_short")


def fault(name: str, cfg: Any, params: Any
          ) -> Tuple[tuple, Optional[Any], Optional[Any], Optional[Callable]]:
    """``(patches, weights, system_cfg, swa_fn)`` of one fault: what to
    put in the place of the model's pieces while the system is traced
    (``(module, attribute, replacement)`` each), the faulty weights,
    another system config, and — for a fault of the window — what stands
    in ``causal_attention``'s place in the windowed call's own
    comparison; ``None`` where the fault leaves that alone. Weight faults
    strike layer 0."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import joyai, smallthinker
    from torchft_tpu.ops import moe
    from torchft_tpu.ops.attention import causal_attention

    real_routing, real_sublayer = moe.top_k_routing, smallthinker.routed_sublayer
    real_mlp = smallthinker._moe_mlp
    patches: tuple = ()
    weights = system_cfg = swa_fn = None

    def other_window(faulty):
        # the comparison passes the configuration's window; the stand-in
        # takes another (None: the causal mask alone)
        def attn(q, k, v, window=None):
            return causal_attention(q, k, v, window=faulty)
        return attn

    if name == "router_reads_n2":
        def late(cfg_, layer, x, n1):
            n2 = smallthinker.rms_norm(
                x.astype(jnp.float32), layer["norm_2"]["scale"], cfg_.rms_eps)
            return real_mlp(cfg_, layer, x, n2)
        patches = ((smallthinker, "_moe_mlp", late),)
    elif name == "silu_experts":
        patches = ((smallthinker, "routed_sublayer", lambda *a, **kw:
                    real_sublayer(*a, **dict(kw, activation=None))),)
    elif name == "window_dropped":
        system_cfg = dataclasses.replace(
            cfg, windowed=(0,) * cfg.n_layers)
        swa_fn = other_window(None)
    elif name == "window_halved":
        system_cfg = dataclasses.replace(cfg, window=cfg.window // 2)
        swa_fn = other_window(cfg.window // 2)
    elif name == "window_tile_short":
        short = cfg.window - max(1, cfg.window // 8)      # 4096 - 512
        system_cfg = dataclasses.replace(cfg, window=short)
        swa_fn = other_window(short)
    elif name == "rope_on_full":
        system_cfg = dataclasses.replace(cfg, rotated=(1,) * cfg.n_layers)
    elif name == "rope_dropped":
        system_cfg = dataclasses.replace(cfg, rotated=(0,) * cfg.n_layers)
    elif name == "rope_theta_1e4":
        system_cfg = dataclasses.replace(cfg, rope_theta=1e4)
    elif name == "rope_interleaved":
        patches = ((smallthinker, "_rope", joyai._rope_pairs),)
    elif name == "kv_heads_modulo":
        patches = ((smallthinker, "repeat_kv", lambda kv, n: jnp.tile(
            kv, (1, 1, n // kv.shape[2], 1))),)
    elif name == "bias_weighting":
        def weighting(scores, k, bias=None, **kw):
            return real_routing(scores + bias.astype(scores.dtype), k,
                                bias=jnp.zeros_like(bias), **kw)
        patches = ((moe, "top_k_routing", weighting),)
    elif name == "no_renormalise":
        def over_all(scores, k, bias=None, softmax=False, scale=1.0, **kw):
            _, experts = real_routing(scores, k, bias=bias)
            return scale * jnp.take_along_axis(
                jax.nn.softmax(scores, axis=-1), experts, axis=-1), experts
        patches = ((moe, "top_k_routing", over_all),)
    elif name == "expert_dropped":
        weights = with_leaf(params, "layers_0",
                            ("moe", "down_proj", "kernel"),
                            lambda w: w.at[1].set(0))
    elif name == "fp8_experts":
        weights = params
        for i in range(cfg.n_layers):
            for leaf in ("gate_proj", "up_proj", "down_proj"):
                weights = with_leaf(weights, f"layers_{i}",
                                    ("moe", leaf, "kernel"), _round_to_fp8)
    elif name == "router_bf16":
        patches = ((moe, "top_k_routing", lambda s, k, **kw: real_routing(
            _to_bf16(s), k, **kw)),)
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, weights, system_cfg, swa_fn


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=20)
    ap.add_argument("--faulty", type=int, default=2)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--unlisted", action="store_true",
                    help="also read the variants of UNLISTED")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "smallthinker-21b-a3b-ep4.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import smallthinker as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import smallthinker
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(
        lambda s: smallthinker.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + FAULTS + (UNLISTED if args.unlisted else ()):
        if args.only and name not in args.only:
            continue
        n = args.sound if name == "sound" else args.faulty
        fn = swa = None
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            params = family.seed_balance_bias(
                init(np.uint32(seed & 0xFFFFFFFF)), seed)
            tokens, targets = BatchSource(
                seed, 0x7265, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            patches, weights, system_cfg, swa_fn = (
                ((), None, None, None) if name == "sound"
                else fault(name, cfg, params))
            system = params if weights is None else weights
            if fn is None:      # one program a variant: traced on its
                fn = jax.jit(family.comparison(     # first seed, patched
                    cfg, system_cfg=system_cfg))
                if name == "sound" or swa_fn is not None:
                    swa = jax.jit(family.swa_comparison(cfg, swa_fn))
            with patched(patches):
                seen = jax.device_get(fn(system, params, tokens, targets))
            reading = dict(family.judge(seen), variant=name, seed=seed)
            if swa is not None:
                alone = jax.device_get(swa(*family.swa_inputs(
                    cfg, seed, model.seq_len)))
                judged = family.judge_swa(alone)
                reading.update(
                    judged, ok=reading["ok"] and judged["ok"],
                    swa_rel_l2={k: float(v) for k, v in alone.items()})
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params, system
    with open(os.path.join(out, "smallthinker_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["variant"] not in UNLISTED
           and r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
