"""The delta-rule scan's two kernels at the cell's call, by the heads a grid
step holds (run by hand on the chip; PERF.md section 6, PR 48): ``kda_fwd``
and ``kda_bwd`` of ``ops/kda.py`` at ``kimi-ep32-solo-steady``'s ``[4, 8192,
32, 128]``, ms a call forward and forward + backward, once for each rung
given (``ops/kda.py::_LADDER`` set to that rung alone).

    python scripts/kda_micro.py --heads 1 2 4 8
    python scripts/kda_micro.py --parent _scratch/parent/torchft_tpu/ops/kda.py

``--parent`` names other ``ops/kda.py`` files (``name=path``, or a path
alone, called ``parent``) that are read in the same process: their kernels
run on the same operands turn about with this tree's, and ``o``, ``dq``,
``dk``, ``dv``, ``dg`` and ``dβ`` are compared with this tree's at every
rung bit for bit. The operands are the cell's own check's
(``benchmark/families/kimi_linear.py::kda_inputs``), a seed a batch row.
Prints one JSON object and writes it to ``chiprun_out/kda_heads.json``. A
CPU run (the interpreter, ``[2, 256]`` of 8 heads of 16) gives agreement
and the grids only.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

_LEAVES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", type=int, nargs="*", default=[1, 2, 4],
                    help="the rungs: heads a grid step")
    ap.add_argument("--parent", nargs="*", default=[],
                    help="other ops/kda.py files, compared in this process")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.families import kimi_linear as family
    from torchft_tpu.ops import kda
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    on_chip = jax.default_backend() == "tpu"
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b-ep32.json")) as f:
        cfg = family.build(json.load(f)).cfg
    rows, seq = (4, 8192) if on_chip else (2, 256)
    if not on_chip:
        cfg = dataclasses.replace(cfg, n_heads=8, kda_head_dim=16)
    drawn = [family.kda_inputs(cfg, 1234567891 + i, seq) for i in range(rows)]
    ops = tuple(jnp.concatenate(leaves)
                for leaves in zip(*(a for a, _ in drawn)))
    do = jnp.concatenate([g for _, g in drawn])
    h, kd, vd = ops[0].shape[2], ops[0].shape[3], ops[2].shape[3]
    chunk = kda._choose_chunk(seq)

    def kernels(mod):
        def scan(*a):
            return mod._kda(*a, chunk, not on_chip)

        def both(do, *a):               # an argument: a closed-over
            o, pull = jax.vjp(scan, *a)     # array is a constant
            return (o,) + pull(do)
        return {"fwd": (jax.jit(scan), ops), "fwd_bwd": (jax.jit(both),
                                                         (do,) + ops)}

    def time_ms(fn, a):
        jax.block_until_ready(fn(*a))
        seen = []
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(args.calls):
                r = fn(*a)
            jax.block_until_ready(r)
            seen.append((time.perf_counter() - t) / args.calls)
        return 1e3 * sorted(seen)[1]

    sides = {}
    for n in args.heads:
        def rung(n=n):
            kda._LADDER = (n,)
            # jit keeps a traced body a shape: another rung is another trace
            jax.clear_caches()
            return kernels(kda)
        sides[f"heads_{n}"] = rung
    for i, named in enumerate(args.parent):
        name, _, path = named.rpartition("=")
        spec = importlib.util.spec_from_file_location(f"kda_other_{i}", path)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        sides[name or "parent"] = lambda other=other: kernels(other)

    out = {"device": jax.devices()[0].device_kind, "q": list(ops[0].shape),
           "chunk": chunk, "calls": args.calls}
    results, ms = {}, {side: {"fwd": [], "fwd_bwd": []} for side in sides}
    order = list(sides)
    for turn in range(args.rounds if on_chip else 1):
        for side in (order if turn % 2 == 0 else order[::-1]):
            built = sides[side]()
            if side.startswith("heads_"):
                n = int(side[len("heads_"):])
                assert kda._heads_a_step(h, chunk, kd, vd) == n, side
            if side not in results:
                fn, a = built["fwd_bwd"]
                results[side] = jax.device_get(fn(*a))
            if on_chip:
                for name, (fn, a) in built.items():
                    ms[side][name].append(time_ms(fn, a))
                    print(turn, side, name, ms[side][name][-1], flush=True)
    first = f"heads_{args.heads[0]}"
    out["bit_for_bit_with_" + first] = {
        side: {leaf: bool((a == b).all())
               for leaf, a, b in zip(_LEAVES, got, results[first])}
        for side, got in results.items() if side != first}
    if on_chip:
        out["ms_a_call"] = {
            side: {name: sorted(seen)[len(seen) // 2]
                   for name, seen in per.items()} for side, per in ms.items()}
        out["ms_a_call_every_round"] = ms
    print(json.dumps(out, indent=1))
    path = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "kda_heads.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
