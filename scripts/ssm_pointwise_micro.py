"""The two stages around the Mamba-2 scan alone, at the shapes of
``nemo3-ep16-solo-steady`` (run by hand on the chip; PERF.md section 6,
PR 34): ``ops/ssm_pointwise.py``'s kernels against the jnp formulation
they replaced in ``models/nemotron_h.py`` (now the oracle of
``tests/test_ssm_pointwise.py``, imported from there), ms a call forward
and forward + backward, with the least the chip could take for the bytes
beside each — every operand read once and every result written once in
bf16, over the HBM peak of ``benchmark/peaks.json`` — and how far the two
sides differ on the chip, value and every gradient.

    python scripts/ssm_pointwise_micro.py
    python scripts/ssm_pointwise_micro.py --blocks 512x512 1024x256 --chunk 8 16

``--blocks rows x lanes`` and ``--chunk`` run the kernels at other blocks
than the ones they choose from the shape (the gate's lanes are rounded to
whole norm groups). Prints one JSON object and writes it to
``chiprun_out/ssm_pointwise_micro.json``. A CPU run (the interpreter, a
small shape) gives agreement only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tests"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", nargs="*", default=[],
                    help="rows x lanes, e.g. 512x512; default: from the shape")
    ap.add_argument("--chunk", nargs="*", type=int, default=[])
    ap.add_argument("--calls", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import test_ssm_pointwise as oracle
    from torchft_tpu.ops import ssm_pointwise as sp
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    on_chip = jax.default_backend() == "tpu"
    # the cell: 4 rows of 8192, xBC 6144 wide with 4 taps, 4096 in 8 groups
    rows, seq, conv_dim, taps_n, inner, groups = (
        (4, 8192, 6144, 4, 4096, 8) if on_chip else (2, 64, 256, 4, 256, 2))
    eps, bf16 = 1e-5, jnp.bfloat16
    with open(os.path.join(_ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    hbm = peaks["kinds"][kind]["hbm_bytes_per_s"] if on_chip else None
    out = {"device": kind, "rows": rows, "seq": seq, "conv_dim": conv_dim,
           "inner": inner, "groups": groups}

    x, taps, bias, dy = oracle.conv_inputs(34, rows, seq, conv_dim, taps_n,
                                           bf16)
    y, z, scale, dout = oracle.gate_inputs(35, rows, seq, inner, bf16)
    interpret = sp._interpret()

    def time_ms(fn, *a):
        jax.block_until_ready(fn(*a))
        seen = []
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(args.calls):
                r = fn(*a)
            jax.block_until_ready(r)
            seen.append((time.perf_counter() - t) / args.calls)
        return 1e3 * sorted(seen)[1]

    @jax.jit
    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.linalg.norm((a - b).ravel()) / jnp.maximum(
            jnp.linalg.norm(b.ravel()), 1e-30)

    def both(fn):
        """Value and gradients, so that neither pass is dead code; the
        cotangent is an argument (a closed-over array is a constant of
        the program: 400 MB in the executable and in the compile
        cache)."""
        def run(cot, *a):
            value, pull = jax.vjp(fn, *a)
            return value, pull(cot)
        return jax.jit(run)

    def measure(fn, operands, cot):
        """Value and gradients, and on the chip ms a call of each."""
        seen = {"out": both(fn)(cot, *operands)}
        if on_chip:
            seen["fwd_ms"] = time_ms(jax.jit(fn), *operands)
            seen["fwd_bwd_ms"] = time_ms(both(fn), cot, *operands)
        return seen

    n = rows * seq
    group = inner // groups
    # stage -> (operands, cotangent, the leaves' names, the jnp side,
    # bytes forward, bytes backward: bf16, each array once)
    stages = {
        "conv_silu": ((x, taps, bias), dy, ("x", "taps", "bias"),
                      oracle.conv_silu_formula,
                      2 * n * conv_dim * 2, 3 * n * conv_dim * 2),
        "gated_norm": ((y, z, scale), dout, ("y", "z", "scale"),
                       lambda *a: oracle.gated_norm_formula(*a, groups, eps),
                       3 * n * inner * 2, 5 * n * inner * 2),
    }
    jnp_side = {}
    for name, (operands, cot, _, formula, fwd_b, bwd_b) in stages.items():
        jnp_side[name] = measure(formula, operands, cot)
        if on_chip:
            out[name + ".jnp"] = {
                "fwd_ms": jnp_side[name]["fwd_ms"],
                "fwd_bwd_ms": jnp_side[name]["fwd_bwd_ms"],
                "bytes_floor_fwd_ms": 1e3 * fwd_b / hbm,
                "bytes_floor_fwd_bwd_ms": 1e3 * (fwd_b + bwd_b) / hbm}
            print(name + ".jnp", json.dumps(out[name + ".jnp"]), flush=True)

    plans = [(None, None)] + [
        (b and tuple(int(e) for e in b.split("x")), c)
        for b in (args.blocks or [None]) for c in (args.chunk or [None])
        if b is not None or c is not None]
    for blocks, chunk in plans:
        if chunk is not None:
            sp._CONV_CHUNK = sp._GATE_CHUNK = chunk
        tag = ("" if blocks is None else f"@{blocks[0]}x{blocks[1]}") + (
            "" if chunk is None else f"/chunk{chunk}")
        if blocks is None:
            lanes = sp._lane_block(conv_dim), sp._lane_block(inner, group)
            cb, gb = ((sp._row_block(seq, w), w) for w in lanes)
        else:
            cb, gb = blocks, (blocks[0], max(blocks[1] // group, 1) * group)
        kernels = {
            "conv_silu": (cb, lambda *a: sp._conv(*a, cb, interpret)),
            "gated_norm": (gb, lambda *a: sp._gate(
                *a, group, eps, gb, interpret)),
        }
        for name, (used, fn) in kernels.items():
            operands, cot, leaves = stages[name][:3]
            seen = measure(fn, operands, cot)
            want = jnp_side[name]["out"]
            entry = {"blocks": used,
                     "chunk": chunk or (sp._CONV_CHUNK, sp._GATE_CHUNK),
                     "rel_l2_value": float(rel(seen["out"][0], want[0]))}
            for leaf, a, b in zip(leaves, seen["out"][1], want[1]):
                entry["rel_l2_d" + leaf] = float(rel(a, b))
            entry.update({k: v for k, v in seen.items() if k != "out"})
            out[name + tag] = entry
            print(name + tag, json.dumps(entry), flush=True)
    path = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "ssm_pointwise_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
