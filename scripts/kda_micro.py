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

    python scripts/kda_micro.py --gdn --parent _scratch/parent/torchft_tpu/ops/kda.py

``--gdn`` (PERF.md section 6, PR 65) is the scalar-decay scan at
``qwen3next-ep16-solo-steady``'s ``[4, 8192, 16 | 32, 128]``, two value
heads a key head, on the cell's own check's operands
(``benchmark/families/qwen3_next.py::gdn_inputs``) with q and k taken at the
KEY heads: ``grouped``, this tree's ``gdn_scan`` handed them there;
``copied``, this tree's on ``jnp.repeat``-ed q and k, the copy and the
pair-sum of ``dq`` / ``dk`` XLA's, inside the timed program; and each
``--parent`` file's, copied likewise (a file from before PR 65 takes nothing
else). ms a call forward and forward + backward, turn about; ``o``, ``dv``,
``dg``, ``dβ`` bit for bit with ``grouped``; ``dq`` and ``dk`` of every side
as relative L2 against the f32 sum over a key head's value heads of what
this tree's equal-head kernels give on the same numbers held in f32
(unrounded a value head). Written to ``chiprun_out/gdn_grouped.json``; a CPU
run gives agreement only (``[2, 256, 2 | 4, 16 | 32]``).
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


def _other_kda(i: int, named: str):
    """``(name, module)`` of ``--parent``'s ``name=path`` or path."""
    name, _, path = named.rpartition("=")
    spec = importlib.util.spec_from_file_location(f"kda_other_{i}", path)
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    return name or "parent", other


def _time_ms(fn, a, calls: int) -> float:
    import jax

    jax.block_until_ready(fn(*a))
    seen = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(calls):
            r = fn(*a)
        jax.block_until_ready(r)
        seen.append((time.perf_counter() - t) / calls)
    return 1e3 * sorted(seen)[1]


def _write(out: dict, name: str) -> int:
    print(json.dumps(out, indent=1))
    path = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, name), "w") as f:
        json.dump(out, f, indent=1)
    return 0


def gdn_grouped(args) -> int:
    """``--gdn``: the module's docstring."""
    import jax
    import jax.numpy as jnp

    from benchmark.families import qwen3_next as family
    from torchft_tpu.ops import kda
    from torchft_tpu.utils.device import place_compile_cache
    from torchft_tpu.utils.metrics import TRACED

    place_compile_cache()
    on_chip = jax.default_backend() == "tpu"
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-ep16.json")) as f:
        cfg = family.build(json.load(f)).cfg
    rows, seq = (4, 8192) if on_chip else (2, 256)
    if not on_chip:
        cfg = dataclasses.replace(cfg, n_key_heads=2, n_value_heads=4,
                                  key_dim=16, value_dim=32)
    r = cfg.n_value_heads // cfg.n_key_heads
    (q, k, v, g, beta), do = family.gdn_inputs(cfg, 1234567891, rows, seq)
    q, k = q[:, :, ::r], k[:, :, ::r]            # as the model has them
    ops = (q, k, v, g, beta)

    def copied(scan):
        return lambda q, k, *rest: scan(
            jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), *rest)

    def programs(scan):
        def both(do, *a):               # an argument: a closed-over
            o, pull = jax.vjp(scan, *a)     # array is a constant
            return (o,) + pull(do)
        return {"fwd": (jax.jit(scan), ops),
                "fwd_bwd": (jax.jit(both), (do,) + ops)}

    sides = {"grouped": programs(kda.gdn_scan),
             "copied": programs(copied(kda.gdn_scan))}
    for i, named in enumerate(args.parent):
        name, other = _other_kda(i, named)
        sides[name] = programs(copied(other.gdn_scan))

    out = {"device": jax.devices()[0].device_kind, "q": list(q.shape),
           "v": list(v.shape), "calls": args.calls,
           "heads_a_step": kda._gdn_heads_a_step(
               v.shape[2], kda._choose_chunk(seq), q.shape[3], v.shape[3],
               not on_chip, r)}
    before = TRACED.snapshot().get("gdn_value_group_copies", 0)
    results = {}
    for side, built in sides.items():
        fn, a = built["fwd_bwd"]
        results[side] = fn(*a)
    out["gdn_value_group_copies"] = TRACED.snapshot().get(
        "gdn_value_group_copies", 0) - before

    def same(a, b):
        return bool(jnp.array_equal(a, b))

    out["bit_for_bit_with_grouped"] = {
        side: {leaf: same(a, b) for leaf, a, b in zip(
            _LEAVES, got, results["grouped"])}
        for side, got in results.items() if side != "grouped"}
    # the unrounded value heads: the same numbers, held in f32
    f32 = jnp.float32
    exact = jax.jit(lambda do, q, k, v, g, beta: jax.vjp(
        kda.gdn_scan, jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2),
        v, g, beta)[1](do)[:2])(
            do.astype(f32), q.astype(f32), k.astype(f32), v.astype(f32), g,
            beta)
    want = [z.reshape(z.shape[:2] + (q.shape[2], r, -1)).sum(axis=3)
            for z in exact]

    def rel(a, b):
        return float(jnp.linalg.norm(a.astype(f32) - b) / jnp.linalg.norm(b))

    out["rel_l2_to_the_f32_sum"] = {
        side: {"dq": rel(got[1], want[0]), "dk": rel(got[2], want[1])}
        for side, got in results.items()}
    if on_chip:
        ms = {side: {"fwd": [], "fwd_bwd": []} for side in sides}
        order = list(sides)
        for turn in range(args.rounds):
            for side in (order if turn % 2 == 0 else order[::-1]):
                for name, (fn, a) in sides[side].items():
                    ms[side][name].append(_time_ms(fn, a, args.calls))
                    print(turn, side, name, ms[side][name][-1], flush=True)
        out["ms_a_call"] = {
            side: {name: sorted(seen)[len(seen) // 2]
                   for name, seen in per.items()} for side, per in ms.items()}
        out["ms_a_call_every_round"] = ms
    return _write(out, "gdn_grouped.json")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", type=int, nargs="*", default=[1, 2, 4],
                    help="the rungs: heads a grid step")
    ap.add_argument("--parent", nargs="*", default=[],
                    help="other ops/kda.py files, compared in this process")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--gdn", action="store_true",
                    help="the scalar-decay scan at two value heads a key "
                         "head: grouped against copied")
    args = ap.parse_args()
    if args.gdn:
        return gdn_grouped(args)

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

    sides = {}
    for n in args.heads:
        def rung(n=n):
            kda._LADDER = (n,)
            # jit keeps a traced body a shape: another rung is another trace
            jax.clear_caches()
            return kernels(kda)
        sides[f"heads_{n}"] = rung
    for i, named in enumerate(args.parent):
        name, other = _other_kda(i, named)
        sides[name] = lambda other=other: kernels(other)

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
                    ms[side][name].append(_time_ms(fn, a, args.calls))
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
    return _write(out, "kda_heads.json")


if __name__ == "__main__":
    sys.exit(main())
