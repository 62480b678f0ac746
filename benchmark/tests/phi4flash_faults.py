"""The faults ``families/phi4flash.py``'s limits must catch, and the
readings the limits are set from, on the chip (run by hand; PERF.md
section 4, PR 47): the cell's own comparisons at the configuration's
widths, depth and share on sound weights over many seeds, and under each
fault, a few seeds each:

    python benchmark/tests/phi4flash_faults.py --sound 20 --faulty 2 --seed 9000

Each variant is one compiled program run on every seed. The faults: the
window 511 or 513 keys, or absent; ``λ``, the 128-wide norm or ``(1 −
λ_init)`` left out; ``λ_init`` from the layer's place in the cut and not
its published index; the value halves swapped; the cross layer reading
layer 1's ``k, v``; the GMU reading the gated ``y ⊙ silu(z)``, or ``m``
without ``D·x̃``; softplus left out; the taps reversed; the LayerNorms'
biases left out; the scan's state, or its decays, rounded to bf16; bf16
parameters. (ISSUE 47 also lists "a cross layer reading its own
projections of k, v": the tree holds none for it to read — as the
published model holds none — so it cannot be written.) A fault in the
scan is also put through the scan's own comparison
(``families/phi4flash.py::scan_comparison``), and the reading is sound
only if both are. ``UNLISTED`` variants are read and recorded but no limit
is claimed to hold them (``--unlisted``): bf16 parameters read rms 0.0197
- 0.0200 on the hidden state, inside the sound range 0.0189 - 0.0218 (the
seeds differ by more than the rounding does). Prints one JSON line a
reading and writes them all to ``chiprun_out/phi4flash_faults.json``;
``--scan`` reads the scan's comparison alone.
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

from benchmark.tests.lfm2_faults import patched  # noqa: E402,F401
from benchmark.tests.nemotron_faults import with_leaf  # noqa: E402,F401

FAULTS = ("window_511", "window_513", "window_absent", "no_lambda",
          "no_head_norm", "no_out_scale", "lambda_init_of_the_cut",
          "value_halves_swapped", "cross_reads_layer_1", "gmu_reads_gated_y",
          "memory_without_d", "no_softplus", "taps_reversed",
          "ln_bias_dropped", "scan_state_bf16", "scan_decay_bf16")
# read and recorded, held by no limit (the module's docstring)
UNLISTED = ("bf16_params",)
# those that only round: a lower precision in one place
ROUNDING = ("scan_state_bf16", "scan_decay_bf16", "bf16_params")
# those that stand in for the scan, and go through its own comparison too
IN_THE_SCAN = ("scan_state_bf16", "scan_decay_bf16")


def _to_bf16(a):
    # ``reduce_precision``, not a pair of casts: inside one jitted
    # computation the TPU compiler keeps an f32 -> bf16 -> f32 pair in f32
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def rounding_scan(x, delta, a, bm, cm, d, state: bool = False,
                  decay: bool = False):
    """The recurrence position by position in the kernels' place (f32
    inside, ``y`` in ``x``'s type), with what the fault rounds to bf16:
    the ``state`` after every position, or every ``decay`` ``exp(Δ A)``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    xs, bs, cs = (z.astype(f32) for z in (x, bm, cm))

    def step(s, at):
        xt, dt, bt, ct = at
        da = jnp.exp(dt[..., None] * a)
        s = (_to_bf16(da) if decay else da) * s + (
            dt * xt)[..., None] * bt[:, None, :]
        s = _to_bf16(s) if state else s
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), f32),
        tuple(jnp.moveaxis(z, 1, 0) for z in (xs, delta, bs, cs)))
    return (jnp.moveaxis(y, 0, 1) + d * xs).astype(x.dtype)


def fault(name: str, cfg: Any, params: Any
          ) -> Tuple[tuple, Optional[Any], Optional[Any], Optional[Callable]]:
    """``(patches, weights, system_cfg, scan_fn)`` of one fault: what to
    put in the place of the model's pieces while the system is traced
    (``(module, attribute, replacement)`` each), the faulty weights,
    another system config, and — for a fault inside the scan — what stands
    in ``s6_scan``'s place in the scan's own comparison; ``None`` where the
    fault leaves that alone. Weight faults strike the first layer of the
    kind."""
    import functools

    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import phi4flash as M

    first = {kind: f"layers_{cfg.kinds.index(kind)}" for kind in set(cfg.kinds)}
    real_lambda_init, real_layer_norm = M.lambda_init, M._layer_norm
    real_attn = M._attn_mixer
    patches: tuple = ()
    weights = system_cfg = scan_fn = None

    def combine(lam: bool = True, norm: bool = True, scale: bool = True):
        def faulty(cfg_, a, a1, a2, init):
            B, S, pairs, W = a1.shape
            d = a1.astype(jnp.float32) - (
                M._lambda(a, init) if lam else 0.0) * a2.astype(jnp.float32)
            if norm:
                d = M.rms_norm(d, a["subln"]["scale"], cfg_.ln_eps)
            d = d * (1.0 - init) if scale else d
            return d.astype(cfg_.dtype).reshape(B, S, pairs * W)
        return ((M, "_combine", faulty),)

    if name in ("window_511", "window_513"):
        system_cfg = dataclasses.replace(cfg, window=int(name[-3:]))
    elif name == "window_absent":
        system_cfg = dataclasses.replace(cfg, window=1 << 30)
    elif name == "no_lambda":
        patches = combine(lam=False)
    elif name == "no_head_norm":
        patches = combine(norm=False)
    elif name == "no_out_scale":
        patches = combine(scale=False)
    elif name == "lambda_init_of_the_cut":
        patches = ((M, "lambda_init", lambda i: real_lambda_init(
            cfg.layer_ids.index(i))),)
    elif name == "value_halves_swapped":
        D = cfg.head_dim
        kv = cfg.n_kv_heads * D

        def swapped(w):         # [..., q | k | v]: v's pairs as [v2 ; v1]
            v = w[..., -kv:].reshape(*w.shape[:-1], kv // (2 * D), 2, D)
            return jnp.concatenate(
                [w[..., :-kv], v[..., ::-1, :].reshape(*w.shape[:-1], kv)],
                axis=-1)
        weights = with_leaf(params, first["swa"],
                            ("attn", "qkv_proj", "kernel"), swapped)
        weights = with_leaf(weights, first["swa"],
                            ("attn", "qkv_proj", "bias"), swapped)
    elif name == "cross_reads_layer_1":
        # the windowed layer hands its k, v on and layer 17 does not
        patches = ((M, "_attn_mixer", lambda cfg_, kind, init, layer, x,
                    memory, hand_on, *, attn_fn: real_attn(
                        cfg_, kind, init, layer, x, memory, kind == "swa",
                        attn_fn=attn_fn)),)
    elif name == "gmu_reads_gated_y":
        patches = ((M, "_memory",
                    lambda y, xs, z, d: y * jax.nn.silu(z)),)
    elif name == "memory_without_d":
        patches = ((M, "_memory", lambda y, xs, z, d: (
            y.astype(jnp.float32) - d.astype(jnp.float32)
            * xs.astype(jnp.float32)).astype(y.dtype)),)
    elif name == "no_softplus":
        patches = ((M, "_softplus", lambda pre: pre),)
    elif name == "taps_reversed":
        weights = with_leaf(params, first["mamba"], ("ssm", "conv", "kernel"),
                            lambda w: w[::-1])
    elif name == "ln_bias_dropped":
        patches = ((M, "_layer_norm", lambda x, scale, bias, eps=1e-5:
                    real_layer_norm(x, scale, jnp.zeros_like(bias), eps)),)
    elif name in IN_THE_SCAN:
        scan_fn = functools.partial(
            rounding_scan, state=name == "scan_state_bf16",
            decay=name == "scan_decay_bf16")
        patches = ((M, "_scan", scan_fn),)
    elif name == "bf16_params":
        weights = jax.tree_util.tree_map(_to_bf16, params)
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, weights, system_cfg, scan_fn


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=20)
    ap.add_argument("--faulty", type=int, default=2)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--scan", action="store_true",
                    help="the scan's own comparison alone")
    ap.add_argument("--unlisted", action="store_true",
                    help="also read the variants of UNLISTED")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "phi-4-mini-flash-reasoning-vp8.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import phi4flash as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import phi4flash
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: phi4flash.init_params(cfg, jax.random.key(s)))
    scan_seq = min(family.SCAN_SEQ, model.seq_len)

    readings = []
    for name in ("sound",) + FAULTS + (UNLISTED if args.unlisted else ()):
        if args.only and name not in args.only:
            continue
        if args.scan and name != "sound" and name not in IN_THE_SCAN:
            continue
        n = args.sound if name == "sound" else args.faulty
        fn = scan = None
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            params = family.seed_biases(
                init(np.uint32(seed & 0xFFFFFFFF)), seed)
            tokens, targets = BatchSource(
                seed, 0x7068, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            patches, weights, system_cfg, scan_fn = (
                ((), None, None, None) if name == "sound"
                else fault(name, cfg, params))
            system = params if weights is None else weights
            if fn is None:      # one program a variant: traced on its
                fn = jax.jit(family.comparison(     # first seed, patched
                    cfg, system_cfg=system_cfg))
                if name == "sound" or scan_fn is not None:
                    scan = jax.jit(family.scan_comparison(scan_fn))
            reading = {"variant": name, "seed": seed, "ok": True}
            if not args.scan:
                with patched(patches):
                    seen = jax.device_get(fn(system, params, tokens, targets))
                reading.update(family.judge(seen))
            if scan is not None:
                alone = jax.device_get(
                    scan(*family.scan_inputs(cfg, seed, scan_seq)))
                judged = family.judge_scan(alone)
                reading.update(
                    judged, ok=reading["ok"] and judged["ok"],
                    scan_rel_l2={k: float(v) for k, v in alone.items()})
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params, system
    with open(os.path.join(out, "phi4flash_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["variant"] not in UNLISTED
           and r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
