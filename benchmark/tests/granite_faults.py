"""The faults ``families/granite_hybrid.py``'s limits must catch, and the
readings the limits are set from, on the chip (run by hand; PERF.md
section 4, PR 68): the cell's own comparison at the configuration's
widths, depth and share on sound weights over many seeds, and under each
fault, a few seeds each:

    python benchmark/tests/granite_faults.py --sound 8 --faulty 2 --seed 9000

Each variant is one compiled program run on every seed. :func:`fault` is
also what ``tests/test_granite_hybrid_family.py`` runs at the small size
on the CPU. The faults: the softmax scaled ``head_dim^-1/2`` (1/8) instead
of ``attention_multiplier`` (1/64); ``residual_multiplier`` left out of
one branch (the first layer's MLP); ``embedding_multiplier`` left out;
``logits_scaling`` left out (the loss alone sees it); a rotary embedding
applied; the gate applied after the norm; the convolution's bias dropped;
a head block's ``x`` scanned under another block's ``Δ``, ``A`` and ``D``
(a wrong block index in the scan's grid); the scan's state rounded to bf16
every position; fp8 (e4m3, rounded on the host) in every MLP. ``--scan``
reads instead the scan's own comparison
(``families/granite_hybrid.py::scan_comparison``: ``ssd_scan`` and its six
gradients against the recurrence) over the sound seeds and under each
stand-in of ``SCAN_VARIANTS`` — among them ``dB`` and ``dC`` summed over
one head block only, which no forward pass sees. Prints one JSON line a
reading and writes them all to ``chiprun_out/granite_faults.json`` /
``granite_scan.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
from typing import Any, Callable, Optional, Tuple

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark.tests.nemotron_faults import (  # noqa: E402
    _gate_after_norm,
    _round_to_fp8,
    position_by_position,
    with_leaf,
)

FAULTS = ("softmax_scale_inv_sqrt_d", "residual_multiplier_left_out",
          "embedding_multiplier_left_out", "logits_scaling_left_out",
          "rotary_applied", "gate_after_norm", "conv_bias_left_out",
          "wrong_head_block", "scan_state_bf16", "fp8_mlp")
# heads a grid step of ``ops/ssd.py`` takes at the published widths
BLOCK_HEADS = 8


def _wrong_head_block(x, dt, A, B, C, D, block: int = BLOCK_HEADS):
    """Head block ``j``'s ``x`` scanned under block ``j + 1``'s ``Δ``,
    ``A`` and ``D``: what a wrong block index on the scalar operands would
    compute."""
    import jax.numpy as jnp

    from torchft_tpu.ops.ssd import ssd_scan

    block = min(block, x.shape[2] // 2)
    return ssd_scan(x, jnp.roll(dt, -block, axis=2), jnp.roll(A, -block),
                    B, C, jnp.roll(D, -block))


def _db_of_one_block(x, dt, A, B, C, D, block: int = BLOCK_HEADS):
    """The scan itself, with ``dB`` and ``dC`` from the first head block
    alone: the other blocks read ``B`` and ``C`` behind a
    ``stop_gradient`` (the forward pass is the sound one to the bit)."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.ssd import ssd_scan

    block = min(block, x.shape[2] // 2)
    held = jax.lax.stop_gradient
    return jnp.concatenate([
        ssd_scan(x[:, :, :block], dt[:, :, :block], A[:block], B, C,
                 D[:block]),
        ssd_scan(x[:, :, block:], dt[:, :, block:], A[block:], held(B),
                 held(C), D[block:])], axis=2)


def fault(name: str, cfg: Any, params: Any
          ) -> Tuple[tuple, Optional[Any], Optional[Any], Optional[Callable]]:
    """``(patches, weights, system_cfg, attn_fn)`` of one fault: what to
    put in the place of the model's pieces while the system is traced
    (``(module, attribute, replacement)`` each), the faulty weights,
    another system config, another attention; ``None`` where the fault
    leaves that alone. Weight faults strike the first layer of the kind."""
    import jax.numpy as jnp

    from torchft_tpu.models import granite_hybrid, llama
    from torchft_tpu.ops.attention import causal_attention

    first_mamba = f"layers_{cfg.layer_types.index(granite_hybrid.MAMBA)}"
    patches: tuple = ()
    weights = system_cfg = attn_fn = None
    if name == "softmax_scale_inv_sqrt_d":
        attn_fn = causal_attention
    elif name == "residual_multiplier_left_out":
        weights = with_leaf(params, "layers_0", ("mlp", "down_proj", "kernel"),
                            lambda w: w / cfg.residual_multiplier)
    elif name == "embedding_multiplier_left_out":
        system_cfg = dataclasses.replace(cfg, embedding_multiplier=1.0)
    elif name == "logits_scaling_left_out":
        system_cfg = dataclasses.replace(cfg, logits_scaling=1.0)
    elif name == "rotary_applied":
        def attn_fn(q, k, v):
            return causal_attention(llama._rope(q, 10000.0),
                                    llama._rope(k, 10000.0), v,
                                    scale=cfg.attention_multiplier)
    elif name == "gate_after_norm":
        patches = ((granite_hybrid, "_gated_norm", _gate_after_norm),)
    elif name == "conv_bias_left_out":
        weights = with_leaf(params, first_mamba, ("mamba", "conv", "bias"),
                            jnp.zeros_like)
    elif name == "wrong_head_block":
        patches = ((granite_hybrid, "ssd_scan", _wrong_head_block),)
    elif name == "scan_state_bf16":
        patches = ((granite_hybrid, "ssd_scan", functools.partial(
            position_by_position, round_state=1)),)
    elif name == "fp8_mlp":
        weights = params
        for i in range(cfg.n_layers):
            for leaf in ("gate_proj", "up_proj", "down_proj"):
                weights = with_leaf(weights, f"layers_{i}",
                                    ("mlp", leaf, "kernel"), _round_to_fp8)
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, weights, system_cfg, attn_fn


# the scan alone (``families/granite_hybrid.py::scan_comparison``): what
# stands in ``ssd_scan``'s place, and whether the limits are to pass it
SCAN_VARIANTS = {
    "sound": (None, True),
    "scan_state_bf16": (functools.partial(position_by_position,
                                          round_state=1), False),
    "wrong_head_block": (_wrong_head_block, False),
    "db_of_one_block": (_db_of_one_block, False),
    # the loop that rounds nothing: the two sides differ by the order of
    # f32 sums alone
    "loop_f32": (position_by_position, True),
}


def _seed(base: int, i: int, sound: bool) -> int:
    # sound seeds and faulty seeds do not overlap; some pass 2^31
    return base + i + (0 if sound else 1000) + (2**31 if i % 2 else 0)


def scan_readings(model: Any, sound: int, faulty: int, seed: int,
                  only: Optional[list] = None) -> list:
    """The scan's own comparison over ``sound`` seeds, and ``faulty`` other
    seeds each stand-in of ``SCAN_VARIANTS``."""
    import jax

    from benchmark.families import granite_hybrid as family

    readings = []
    for name, (scan_fn, passes) in SCAN_VARIANTS.items():
        if only and name not in only:
            continue
        fn = jax.jit(lambda bits, scan_fn=scan_fn: family.scan_comparison(
            scan_fn)(*family.scan_inputs(model.cfg, bits, model.rows,
                                         model.seq_len)))
        for i in range(sound if name == "sound" else faulty):
            s = _seed(seed, i, name == "sound")
            seen = jax.device_get(fn(family._low_bits(s)))
            reading = dict(family.judge_scan(seen), variant=name, seed=s,
                           expected_ok=passes)
            readings.append(reading)
            print(json.dumps(reading), flush=True)
    return readings


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scan", action="store_true",
                    help="the scan's own comparison only (no weights)")
    ap.add_argument("--sound", type=int, default=8)
    ap.add_argument("--faulty", type=int, default=2)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "granite-4.0-h-micro-vp8.json"))
    args = ap.parse_args()

    import jax

    from benchmark.families import granite_hybrid as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import granite_hybrid
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    if args.scan:
        readings = scan_readings(model, args.sound, args.faulty, args.seed,
                                 args.only)
        with open(os.path.join(out, "granite_scan.json"), "w") as f:
            json.dump(readings, f, indent=1)
        bad = [r for r in readings if r["ok"] != r["expected_ok"]]
        print(f"{len(readings)} readings; {len(bad)} on the wrong side of "
              f"the limits: {[(r['variant'], r['seed']) for r in bad]}")
        return 0
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: granite_hybrid.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + FAULTS:
        if args.only and name not in args.only:
            continue
        fn = None
        for i in range(args.sound if name == "sound" else args.faulty):
            seed = _seed(args.seed, i, name == "sound")
            params = init(family._low_bits(seed))
            tokens, targets = BatchSource(
                seed, 0x6772, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            patches, weights, system_cfg, attn_fn = (
                ((), None, None, None) if name == "sound"
                else fault(name, cfg, params))
            system = params if weights is None else weights
            if fn is None:      # one program a variant: traced on its
                fn = jax.jit(family.comparison(     # first seed, patched
                    cfg, system_cfg=system_cfg, attn_fn=attn_fn))
            saved = [(mod, attr, getattr(mod, attr))
                     for mod, attr, _new in patches]
            for mod, attr, new in patches:
                setattr(mod, attr, new)
            try:
                seen = family.per_token_errors(
                    cfg, system, params, tokens, targets, seed, fn=fn)
            finally:
                for mod, attr, old in saved:
                    setattr(mod, attr, old)
            reading = dict(family.judge(seen), variant=name, seed=seed)
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params, system
    with open(os.path.join(out, "granite_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
