"""The two stages around the Mamba-2 scan alone, at the shapes of
``nemo3-ep16-solo-steady``, the two around the delta rule at those of
``kimi-ep32-solo-steady`` and the two around the attention call at those of
``laguna-ep8-solo-steady`` (run by hand on the chip; PERF.md section 6,
PRs 34, 44 and 62): ``ops/ssm_pointwise.py``'s kernels against the jnp formulation
they replaced in ``models/nemotron_h.py`` / ``models/kimi_linear.py`` /
``models/laguna.py`` (now the oracle of
``tests/test_ssm_pointwise.py``, imported from there), ms a call forward
and forward + backward, with the least the chip could take for the bytes
beside each — every operand read once and every result written once in
bf16 (the decays and their cotangent in f32), over the HBM peak of ``benchmark/peaks.json`` — and how far the two
sides differ on the chip, value and every gradient.

    python scripts/ssm_pointwise_micro.py
    python scripts/ssm_pointwise_micro.py --blocks 512x512 1024x256 --chunk 8 16
    python scripts/ssm_pointwise_micro.py --stages laguna --parent

``--blocks rows x lanes`` and ``--chunk`` run the kernels at other blocks
than the ones they choose from the shape (the gate's lanes are rounded to
whole norm groups; the gate a head takes its rows alone: all the heads are
a block). ``--stages laguna`` stands for the cell's calls around its
attention: ``[4, 8192, H, 128]`` turned over the whole head at 64 heads and
over half a head with YaRN's factor at 48, both at the 8 key/value heads,
and the gate at 64 and at 48 heads. Those run the kernels alone unless
``--parent`` is given: then the jnp form the model had (with the flash
call's turn to the heads first, and its table rebuilt a call) is timed
beside each and the two sides' results are compared to the bit — the share
of elements that differ and by how many places of bf16 at most
(``differ_*``, ``max_ulp_*``). Prints one JSON object and writes it to
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
                    help="conv_silu gated_norm kda_qkg kda_ogate, laguna or "
                         "its six by name; default: the first four")
    ap.add_argument("--parent", action="store_true",
                    help="laguna's stages: time the jnp form the model had "
                         "beside the kernel and compare the results")
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
    # kda, and the plan's own where they were given by hand))
    stages = {
        "conv_silu": (conv_stage, ("x", "taps", "bias"),
                      oracle.conv_silu_formula,
                      2 * n * conv_dim * 2, 3 * n * conv_dim * 2,
                      lambda cb, gb, kb, _: (cb, lambda *a: sp._conv(
                          *a, cb, interpret))),
        "gated_norm": (lambda: gate_stage(inner), ("y", "z", "scale"),
                       lambda *a: oracle.gated_norm_formula(*a, groups, eps),
                       3 * n * inner * 2, 5 * n * inner * 2,
                       lambda cb, gb, kb, _: (gb, lambda *a: sp._gate(
                           *a, group, eps, gb, interpret))),
        # q̃, k̃, v, f in and q, k, v out in bf16, g out in f32; backward
        # the same operands, three bf16 cotangents and g's in f32 in,
        # [dq̃ ; dk̃ ; dv] and df out
        "kda_qkg": (lambda: oracle.qkg_inputs(44, rows, seq, heads, head,
                                              bf16),
                    ("qkv", "f", "dt_bias", "a_log"), oracle.kda_qkg_formula,
                    n * inner * (4 * 2 + 3 * 2 + 4),
                    n * inner * (4 * 2 + 3 * 2 + 4 + 4 * 2),
                    lambda cb, gb, kb, _: (kb, lambda qkv, f, b, a: sp._qkg(
                        qkv, f, b, jnp.repeat(a, head), head,
                        oracle.l2_normed, kb, interpret))),
        "kda_ogate": (lambda: gate_stage(head), ("o", "gate", "scale"),
                      lambda *a: oracle.kda_ogate_formula(*a, eps),
                      3 * n * inner * 2, 5 * n * inner * 2,
                      lambda cb, gb, kb, _: (kb, lambda *a: sp._ogate(
                          *a, head, eps, oracle.head_norm_then_gate, kb,
                          interpret))),
    }
    # laguna's: [4, 8192, H, 128]; name -> (heads, half the turned lanes,
    # the factor on the table); the tables are made once a step
    l_head = 128 if on_chip else 32
    l_rows, l_seq = (4, 8192) if on_chip else (2, 64)
    turns = {"rotary_swa_64": (64, l_head // 2, 1.0),
             "rotary_yarn_48": (48, l_head // 4, 1.4158883),
             "rotary_swa_8": (8, l_head // 2, 1.0),
             "rotary_yarn_8": (8, l_head // 4, 1.4158883)}
    l_n = l_rows * l_seq

    def rotary_stage(name):
        h, half, factor = turns[name]
        width = h * l_head

        made = {}     # the frequencies and the table, with the operands

        def make():
            x, freqs, dy = oracle.rotary_inputs(62, l_rows, l_seq, h, l_head,
                                                half)
            made.update(freqs=freqs, table=jax.jit(lambda f: sp.rotary_tables(
                f, l_seq, l_head, factor))(freqs))
            return (x,), dy

        def kernel(cb, gb, kb, by_hand):
            # blocks given by hand hold whole heads
            lanes = sp._lane_block(width, l_head, sp._ROTARY_LANES)
            blocks = (sp._row_block(l_seq, lanes, sp._ATTN_BLOCK_ELEMS),
                      lanes) if by_hand is None else (
                by_hand[0],
                min(max(by_hand[1] // l_head, 1) * l_head, width))
            return blocks, lambda x: sp._rotary(
                x, *made["table"], half, blocks, interpret)

        # x in and out forward, dy in and dx out backward, the table's two
        # arrays a pass
        passes = 2 * l_n * width * 2 + 2 * l_seq * l_head * 4
        return (make, ("x",), None if not args.parent else
                lambda x: oracle.rotary_formula(x, made["freqs"], factor,
                                                l_head),
                passes, passes, kernel)

    def head_gate_stage(h):
        width = h * l_head

        def make():
            o, gate, dy = oracle.head_gate_inputs(63, l_rows, l_seq, h, l_head)
            return (o, gate), dy

        def kernel(cb, gb, kb, by_hand):
            bs = (sp._row_block(l_seq, width, sp._ATTN_BLOCK_ELEMS)
                  if by_hand is None else by_hand[0])
            return (bs, width), lambda o, g: sp._hgate(o, g, bs, interpret)

        # o in and y out, the gate in; backward dy and o in, do out, the
        # gate in and its cotangent out
        return (make, ("o", "gate"),
                oracle.gate_heads_formula if args.parent else None,
                2 * l_n * width * 2 + l_n * h * 4,
                3 * l_n * width * 2 + 2 * l_n * h * 4, kernel)

    laguna = {name: rotary_stage(name) for name in turns}
    laguna.update(gate_heads_64=head_gate_stage(64),
                  gate_heads_48=head_gate_stage(48))
    stages.update(laguna)
    named = []
    for name in args.stages or [s for s in stages if s not in laguna]:
        named.extend(laguna if name == "laguna" else [name])

    @jax.jit
    def places_apart(a, b):
        return oracle.ulps_apart_traced(a, b)

    plans = [(None, None)] + [
        (b and tuple(int(e) for e in b.split("x")), c)
        for b in (args.blocks or [None]) for c in (args.chunk or [None])
        if b is not None or c is not None]
    chunks = sp._CONV_CHUNK, sp._GATE_CHUNK, sp._ROTARY_CHUNK
    for name in named:
        make, leaves, formula, fwd_b, bwd_b, kernel = stages[name]
        operands, cot = make()
        floors = {} if not on_chip else dict(
            bytes_floor_fwd_ms=1e3 * fwd_b / hbm,
            bytes_floor_fwd_bwd_ms=1e3 * (fwd_b + bwd_b) / hbm)
        want = None
        if formula is not None:
            jnp_side = measure(formula, operands, cot)
            want = jnp_side.pop("out")
            if on_chip:
                out[name + ".jnp"] = dict(jnp_side, **floors)
                print(name + ".jnp", json.dumps(out[name + ".jnp"]),
                      flush=True)
        for blocks, chunk in plans:
            sp._CONV_CHUNK, sp._GATE_CHUNK, sp._ROTARY_CHUNK = (
                chunks if chunk is None else (chunk, chunk, chunk))
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
            used, fn = kernel(cb, gb, kb, blocks)
            try:
                seen = measure(fn, operands, cot)
            except Exception as e:  # noqa: BLE001 — Mosaic's refusal of a
                # block given by hand is a reading
                out[name + tag] = {"blocks": used, "refused": str(e)[:300]}
                print(name + tag, json.dumps(out[name + tag]), flush=True)
                continue
            got = seen.pop("out")
            entry = {"blocks": used,
                     "chunk": (sp._CONV_CHUNK, sp._GATE_CHUNK,
                               sp._ROTARY_CHUNK)}
            if want is not None:
                entry["rel_l2_value"] = max(
                    float(rel(a, b)) for a, b in zip(
                        jax.tree_util.tree_leaves(got[0]),
                        jax.tree_util.tree_leaves(want[0])))
                for leaf, a, b in zip(leaves, got[1], want[1]):
                    entry["rel_l2_d" + leaf] = float(rel(a, b))
            if want is not None and name in laguna:
                for leaf, a, b in zip(("value",) + tuple(
                        "d" + leaf for leaf in leaves),
                        (got[0],) + tuple(got[1]),
                        (want[0],) + tuple(want[1])):
                    if a.dtype == bf16:
                        share, most = places_apart(a, b)
                        entry["differ_" + leaf] = float(share)
                        entry["max_ulp_" + leaf] = int(most)
            entry.update(seen, **floors)
            if on_chip:
                entry["bytes_floor_share_fwd"] = (
                    floors["bytes_floor_fwd_ms"] / seen["fwd_ms"])
                entry["bytes_floor_share_fwd_bwd"] = (
                    floors["bytes_floor_fwd_bwd_ms"] / seen["fwd_bwd_ms"])
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
