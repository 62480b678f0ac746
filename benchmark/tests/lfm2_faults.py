"""The faults ``families/lfm2.py``'s limits must catch, and the readings
the limits are set from, on the chip (run by hand; PERF.md section 4, PR
38): the cell's own comparisons at the configuration's widths, depth and
share on sound weights over many seeds, and under each fault, a few
seeds each:

    python benchmark/tests/lfm2_faults.py --sound 20 --faulty 3 --seed 9000

Each variant is one compiled program run on every seed. :func:`fault` is
also what ``tests/test_lfm2.py`` runs at the small size on the CPU. The
faults: the taps in reverse order; the convolution's window shifted by
one (a look ahead); ``B`` and ``C`` swapped; the gate ``C ⊙`` dropped;
the QK-norm dropped; RoPE dropped, at theta 1e4, over interleaved pairs;
the key/value heads paired with the wrong query heads; a head of its own
(an independent draw) in the table's place; the balance bias weighting
instead of only selecting, or ignored in the selection; the weights not
renormalised; one held expert dropped; fp8 (e4m3, rounded on the host) in
the held experts alone; the gated convolution's insides in bf16 where the
configuration says f32. A fault in the convolution is also put through
the convolution's own comparison (``families/lfm2.py::conv_comparison``),
and the reading is sound only if both are. ``UNLISTED`` variants are read
and recorded but no limit is claimed to hold them (``--unlisted``): the
router's scores rounded to bf16 flips 0.0521 - 0.0528 of the (token,
layer) pairs against the sound 0.0460 - 0.0490, too close for a limit
with room on both sides. Prints one JSON line a reading and writes them
all to ``chiprun_out/lfm2_faults.json``.
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

from benchmark.tests.nemotron_faults import (  # noqa: E402,F401
    _round_to_fp8,
    with_leaf,
)

FAULTS = ("taps_reversed", "window_shifted", "b_c_swapped", "gate_dropped",
          "qk_norm_dropped", "rope_dropped", "rope_theta_1e4",
          "rope_interleaved", "kv_heads_swapped", "untied_head",
          "bias_weighting", "bias_ignored", "no_renormalise",
          "expert_dropped", "fp8_experts", "conv_bf16")
# read and recorded, held by no limit (the module's docstring)
UNLISTED = ("router_bf16",)
# those that only round: a lower precision in one place
ROUNDING = ("fp8_experts", "router_bf16", "conv_bf16")


def _to_bf16(a):
    # ``reduce_precision``, not a pair of casts: inside one jitted
    # computation the TPU compiler keeps an f32 -> bf16 -> f32 pair in f32
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def jnp_conv(bcx, taps, shift: int = 0, gate: bool = True,
             rounded: bool = False):
    """The gated convolution as jnp passes, in the kernels' place, with
    what the fault changes: ``shift`` 1 reads one position ahead,
    ``gate`` False leaves ``C ⊙`` out, ``rounded`` rounds ``B ⊙ X``, the
    taps and every partial sum to bf16."""
    import jax.numpy as jnp

    f32 = jnp.float32
    K, C = taps.shape
    S = bcx.shape[1]
    b, c, x = (bcx[..., i * C:(i + 1) * C].astype(f32) for i in range(3))
    rnd = _to_bf16 if rounded else (lambda a: a)
    u = jnp.pad(rnd(b * x), ((0, 0), (K - 1 - shift, shift), (0, 0)))
    v = jnp.zeros_like(b)
    for j in range(K):
        v = rnd(v + rnd(rnd(taps[j].astype(f32)) * u[:, j:j + S]))
    return ((c * v) if gate else v).astype(bcx.dtype)


def fault(name: str, cfg: Any, params: Any
          ) -> Tuple[tuple, Optional[Any], Optional[Any], Optional[Callable]]:
    """``(patches, weights, system_cfg, conv_fn)`` of one fault: what to
    put in the place of the model's pieces while the system is traced
    (``(module, attribute, replacement)`` each), the faulty weights,
    another system config, and — for a fault inside the convolution —
    what stands in ``gated_conv``'s place in the convolution's own
    comparison; ``None`` where the fault leaves that alone. Weight faults
    strike the first layer of the kind."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import joyai, lfm2
    from torchft_tpu.ops import moe

    first = {kind: f"layers_{cfg.layer_types.index(kind)}"
             for kind in set(cfg.layer_types)}
    experts = [f"layers_{i}" for i in range(cfg.n_dense_layers, cfg.n_layers)]
    real_routing, real_norm = moe.top_k_routing, lfm2.rms_norm
    real_ce = lfm2.ce_from_hidden
    patches: tuple = ()
    weights = system_cfg = conv_fn = None

    def conv_patch(fn):
        return ((lfm2, "_gated_conv", lambda m, bcx, dt: fn(
            bcx, m["conv"]["kernel"]).astype(dt)),), fn

    d = cfg.d_model
    if name == "taps_reversed":
        weights = with_leaf(params, first["conv"], ("conv", "conv", "kernel"),
                            lambda w: w[::-1])
    elif name == "window_shifted":
        patches, conv_fn = conv_patch(
            lambda bcx, taps: jnp_conv(bcx, taps, shift=1))
    elif name == "b_c_swapped":
        weights = with_leaf(
            params, first["conv"], ("conv", "in_proj", "kernel"),
            lambda w: jnp.concatenate(
                [w[:, d:2 * d], w[:, :d], w[:, 2 * d:]], axis=1))
    elif name == "gate_dropped":
        patches, conv_fn = conv_patch(
            lambda bcx, taps: jnp_conv(bcx, taps, gate=False))
    elif name == "qk_norm_dropped":
        # q and k are the only 4-D operands of the model's rms_norm
        patches = ((lfm2, "rms_norm", lambda x, scale, eps: x
                    if x.ndim == 4 else real_norm(x, scale, eps)),)
    elif name == "rope_dropped":
        patches = ((lfm2, "_rope", lambda x, theta: x),)
    elif name == "rope_theta_1e4":
        system_cfg = dataclasses.replace(cfg, rope_theta=1e4)
    elif name == "rope_interleaved":
        patches = ((lfm2, "_rope", joyai._rope_pairs),)
    elif name == "kv_heads_swapped":
        hd = cfg.head_dim

        def rolled(w):          # key head j takes key head j + 1's place
            return jnp.concatenate([w[:, hd:], w[:, :hd]], axis=1)
        weights = with_leaf(params, first["full_attention"],
                            ("attn", "k_proj", "kernel"), rolled)
    elif name == "untied_head":
        # a head of its own, as a family with two leaves initialises it
        patches = ((lfm2, "ce_from_hidden", lambda h, w, t, chunks: real_ce(
            h, cfg.init_std * jax.random.normal(
                jax.random.key(0), w.shape, w.dtype), t, chunks)),)
    elif name == "bias_weighting":
        def weighting(scores, k, bias=None, **kw):
            return real_routing(scores + bias.astype(scores.dtype), k,
                                bias=jnp.zeros_like(bias), **kw)
        patches = ((moe, "top_k_routing", weighting),)
    elif name == "bias_ignored":
        weights = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.zeros_like(x)
            if lfm2.is_balance_bias(p) else x, params)
    elif name == "no_renormalise":
        patches = ((moe, "top_k_routing", lambda s, k, **kw: real_routing(
            s, k, **dict(kw, renormalise=False))),)
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
    elif name == "router_bf16":
        patches = ((moe, "top_k_routing", lambda s, k, **kw: real_routing(
            _to_bf16(s), k, **kw)),)
    elif name == "conv_bf16":
        patches, conv_fn = conv_patch(
            lambda bcx, taps: jnp_conv(bcx, taps, rounded=True))
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, weights, system_cfg, conv_fn


class patched:
    """The model's pieces replaced while a program is traced."""

    def __init__(self, patches: tuple) -> None:
        self.patches = patches

    def __enter__(self) -> None:
        self.saved = [(mod, attr, getattr(mod, attr))
                      for mod, attr, _new in self.patches]
        for mod, attr, new in self.patches:
            setattr(mod, attr, new)

    def __exit__(self, *exc: Any) -> None:
        for mod, attr, old in self.saved:
            setattr(mod, attr, old)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=20)
    ap.add_argument("--faulty", type=int, default=3)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--unlisted", action="store_true",
                    help="also read the variants of UNLISTED")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "lfm2-8b-a1b-ep4.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import lfm2 as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import lfm2
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: lfm2.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + FAULTS + (UNLISTED if args.unlisted else ()):
        if args.only and name not in args.only:
            continue
        n = args.sound if name == "sound" else args.faulty
        fn = conv = None
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            params = family.seed_balance_bias(
                init(np.uint32(seed & 0xFFFFFFFF)), seed)
            tokens, targets = BatchSource(
                seed, 0x7265, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            patches, weights, system_cfg, conv_fn = (
                ((), None, None, None) if name == "sound"
                else fault(name, cfg, params))
            system = params if weights is None else weights
            if fn is None:      # one program a variant: traced on its
                fn = jax.jit(family.comparison(     # first seed, patched
                    cfg, system_cfg=system_cfg))
                if name == "sound" or conv_fn is not None:
                    conv = jax.jit(family.conv_comparison(conv_fn))
            with patched(patches):
                seen = jax.device_get(fn(system, params, tokens, targets))
            reading = dict(family.judge(seen), variant=name, seed=seed)
            if conv is not None:
                alone = jax.device_get(conv(*family.conv_inputs(cfg, seed)))
                judged = family.judge_conv(alone)
                reading.update(
                    judged, ok=reading["ok"] and judged["ok"],
                    conv_rel_l2={k: float(v) for k, v in alone.items()})
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params, system
    with open(os.path.join(out, "lfm2_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["variant"] not in UNLISTED
           and r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
