"""The two stages around the Mamba-2 scan alone, at the shapes of
``nemo3-ep16-solo-steady``, and the two around the delta rule at those of
``kimi-ep32-solo-steady`` (run by hand on the chip; PERF.md section 6,
PRs 34 and 44): ``ops/ssm_pointwise.py``'s kernels against the jnp formulation
they replaced in ``models/nemotron_h.py`` / ``models/kimi_linear.py`` (now the oracle of
``tests/test_ssm_pointwise.py``, imported from there), ms a call forward
and forward + backward, with the least the chip could take for the bytes
beside each — every operand read once and every result written once in
bf16 (the decays and their cotangent in f32), over the HBM peak of ``benchmark/peaks.json`` — and how far the two
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
    ap.add_argument("--stages", nargs="*", default=[],
                    help="conv_silu gated_norm kda_qkg kda_ogate; default: "
                         "all four")
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
    # kimi's: the same 4096 channels in 32 heads of 128
    head = 128
    heads = inner // head
    eps, bf16 = 1e-5, jnp.bfloat16
    with open(os.path.join(_ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    hbm = peaks["kinds"][kind]["hbm_bytes_per_s"] if on_chip else None
    out = {"device": kind, "rows": rows, "seq": seq, "conv_dim": conv_dim,
           "inner": inner, "groups": groups}

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

    def conv_stage():
        x, taps, bias, dy = oracle.conv_inputs(34, rows, seq, conv_dim,
                                               taps_n, bf16)
        return (x, taps, bias), dy

    def gate_stage(scale_width):
        y, z, scale, dout = oracle.gate_inputs(35, rows, seq, inner, bf16)
        return (y, z, scale[:scale_width]), dout

    # stage -> (its operands and cotangent, made when the stage runs: the
    # four stages' together do not fit the chip; the leaves' names, the
    # jnp side, bytes forward, bytes backward: each array once, bf16 but
    # the decays and their cotangent; the kernels at blocks (conv, gate,
    # kda))
    stages = {
        "conv_silu": (conv_stage, ("x", "taps", "bias"),
                      oracle.conv_silu_formula,
                      2 * n * conv_dim * 2, 3 * n * conv_dim * 2,
                      lambda cb, gb, kb: (cb, lambda *a: sp._conv(
                          *a, cb, interpret))),
        "gated_norm": (lambda: gate_stage(inner), ("y", "z", "scale"),
                       lambda *a: oracle.gated_norm_formula(*a, groups, eps),
                       3 * n * inner * 2, 5 * n * inner * 2,
                       lambda cb, gb, kb: (gb, lambda *a: sp._gate(
                           *a, group, eps, gb, interpret))),
        # q̃, k̃, v, f in and q, k, v out in bf16, g out in f32; backward
        # the same operands, three bf16 cotangents and g's in f32 in,
        # [dq̃ ; dk̃ ; dv] and df out
        "kda_qkg": (lambda: oracle.qkg_inputs(44, rows, seq, heads, head,
                                              bf16),
                    ("qkv", "f", "dt_bias", "a_log"), oracle.kda_qkg_formula,
                    n * inner * (4 * 2 + 3 * 2 + 4),
                    n * inner * (4 * 2 + 3 * 2 + 4 + 4 * 2),
                    lambda cb, gb, kb: (kb, lambda qkv, f, b, a: sp._qkg(
                        qkv, f, b, jnp.repeat(a, head), head,
                        oracle.l2_normed, kb, interpret))),
        "kda_ogate": (lambda: gate_stage(head), ("o", "gate", "scale"),
                      lambda *a: oracle.kda_ogate_formula(*a, eps),
                      3 * n * inner * 2, 5 * n * inner * 2,
                      lambda cb, gb, kb: (kb, lambda *a: sp._ogate(
                          *a, head, eps, oracle.head_norm_then_gate, kb,
                          interpret))),
    }
    plans = [(None, None)] + [
        (b and tuple(int(e) for e in b.split("x")), c)
        for b in (args.blocks or [None]) for c in (args.chunk or [None])
        if b is not None or c is not None]
    chunks = sp._CONV_CHUNK, sp._GATE_CHUNK
    for name in args.stages or stages:
        make, leaves, formula, fwd_b, bwd_b, kernel = stages[name]
        operands, cot = make()
        jnp_side = measure(formula, operands, cot)
        want = jnp_side.pop("out")
        if on_chip:
            out[name + ".jnp"] = dict(
                jnp_side, bytes_floor_fwd_ms=1e3 * fwd_b / hbm,
                bytes_floor_fwd_bwd_ms=1e3 * (fwd_b + bwd_b) / hbm)
            print(name + ".jnp", json.dumps(out[name + ".jnp"]), flush=True)
        for blocks, chunk in plans:
            sp._CONV_CHUNK, sp._GATE_CHUNK = (
                chunks if chunk is None else (chunk, chunk))
            jax.clear_caches()   # the kda kernels' builders are jitted
            tag = ("" if blocks is None else f"@{blocks[0]}x{blocks[1]}") + (
                "" if chunk is None else f"/chunk{chunk}")
            if blocks is None:
                cb, gb, kb = ((sp._row_block(seq, w), w) for w in (
                    sp._lane_block(conv_dim), sp._lane_block(inner, group),
                    sp._lane_block(inner, head)))
            else:
                cb = kb = blocks
                gb = blocks[0], max(blocks[1] // group, 1) * group
            used, fn = kernel(cb, gb, kb)
            seen = measure(fn, operands, cot)
            got = seen.pop("out")
            entry = {"blocks": used,
                     "chunk": (sp._CONV_CHUNK, sp._GATE_CHUNK),
                     "rel_l2_value": max(
                         float(rel(a, b)) for a, b in zip(
                             jax.tree_util.tree_leaves(got[0]),
                             jax.tree_util.tree_leaves(want[0])))}
            for leaf, a, b in zip(leaves, got[1], want[1]):
                entry["rel_l2_d" + leaf] = float(rel(a, b))
            entry.update(seen)
            out[name + tag] = entry
            print(name + tag, json.dumps(entry), flush=True)
            del got, seen
        del operands, cot, want
    path = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "ssm_pointwise_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
