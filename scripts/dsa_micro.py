"""The four attention kernels of ``ops/dsa.py`` alone at the cell's call
(run by hand on the chip; PERF.md section 6, PR 69): ``dsa_fwd``,
``dsa_dq``, ``dsa_dkv`` and ``dsa_kl`` at ``keye2-ep8-solo-steady``'s ``[2,
32 | 4, 16384, 128]``, the sets ``dsa_select``'s 2 048 best keys a query,
ms a call — the reading taken BEFORE the cell's.

    python scripts/dsa_micro.py --chunks 1 2 4 8
    python scripts/dsa_micro.py --parent _scratch/parent/torchft_tpu/ops/dsa.py

``--chunks`` times this tree's ``dsa_fwd`` once for each count of k tiles a
grid step given (``ops/dsa.py::_choose_chunk`` answering that count) and
each of ``--spans`` (tiles an update of the softmax statistics takes,
``ops/dsa.py::_SPAN`` set to it: 1 is the order the one-tile kernel meets
them in, bit for bit) beside the rule's own choice, ``chosen``.
``--parent`` names other
``ops/dsa.py`` files (``name=path``, or a path alone, called ``parent``)
read in the same process: their kernels run on the same operands and the
same sets turn about with this tree's; ``o`` and ``lse`` of every side are
compared with the first side's bit for bit and as a relative L2, and ``dq``,
``dk``, ``dv`` (which read the side's own ``lse``) likewise. ``dsa_dq`` and
``dsa_dkv`` are one jitted call (``_backward``) each of whose results is
asked for alone, so the other kernel is dead code to XLA. The operands are
the cell's own check's (``benchmark/families/keye.py::kernel_inputs``).
Prints one JSON object and writes it to ``chiprun_out/dsa_micro.json``. A
CPU run (the interpreter, ``[2, 16 | 2, 64, 16]``, 16 rows a step, every
``--chunks`` count a real chunk) gives agreement only.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

_LEAVES = ("o", "lse", "dq", "dk", "dv")


def _other_dsa(i: int, named: str):
    """``(name, module)`` of ``--parent``'s ``name=path`` or path."""
    name, _, path = named.rpartition("=")
    spec = importlib.util.spec_from_file_location(f"dsa_other_{i}", path)
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chunks", type=int, nargs="*", default=[],
                    help="k tiles a grid step of dsa_fwd, one side each")
    ap.add_argument("--parent", nargs="*", default=[],
                    help="other ops/dsa.py files, compared in this process")
    ap.add_argument("--spans", type=int, nargs="*", default=[2],
                    help="tiles a softmax update, one side a chunk each")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.families import keye as family
    from torchft_tpu.models.keye import KEYE_CONFIGS
    from torchft_tpu.ops import dsa
    from torchft_tpu.utils.device import place_compile_cache
    from torchft_tpu.utils.metrics import TRACED

    place_compile_cache()
    on_chip = jax.default_backend() == "tpu"
    if on_chip:
        with open(os.path.join(_ROOT, "benchmark", "configs",
                               "keye-vl-2.0-30b-a3b-ep8.json")) as f:
            model = family.build(json.load(f))
        cfg, rows, seq, kw = model.cfg, model.rows, model.seq_len, {}
    else:
        cfg, rows, seq = KEYE_CONFIGS["keye_tiny"], 2, 64
        kw = dict(block_q=16, interpret=True)
    x = family.kernel_inputs(cfg, args.seed, rows, seq)
    sel, lse_i = jax.jit(lambda qi, ki, w: dsa.select(
        qi, ki, w, cfg.index_topk, **kw))(x["qi"], x["ki"], x["w"])
    heads, scale = x["q"].shape[1], float(x["q"].shape[-1] ** -0.5)
    block_q = kw.get("block_q", dsa._rows(seq, dsa._ATTEND_ROWS, None))
    qkv = (x["q"], x["k"], x["v"])

    def kernels(mod):
        """``{kernel: (jitted call, its operands)}`` of one ``ops/dsa.py``;
        the backward's and the KL's read the module's own ``lse``."""
        fwd = jax.jit(lambda q, k, v, sel: mod.attend(q, k, v, sel, **kw))
        o, lse = fwd(*qkv, sel)
        merge = mod._merge
        do = merge(x["do"])
        delta = jnp.sum(do.astype(jnp.float32) * merge(o).astype(
            jnp.float32), axis=-1)[:, None, :]
        back = (merge(x["q"]), merge(x["k"]), merge(x["v"]), do,
                merge(lse)[:, None, :], delta, sel)

        def backward(*a):
            return mod._backward(*a, heads, scale, block_q, not on_chip)

        return {
            "dsa_fwd": (fwd, qkv + (sel,)),
            "dsa_dq": (jax.jit(lambda *a: backward(*a)[0]), back),
            "dsa_dkv": (jax.jit(lambda *a: backward(*a)[1:]), back),
            "dsa_kl": (jax.jit(lambda *a: mod._kl_call(
                *a, scale, block_q, True, not on_chip)),
                (x["q"], x["k"], lse, x["qi"], x["ki"], x["w"], sel, lse_i)),
        }

    rule, span = dsa._choose_chunk, dsa._SPAN

    def this_tree(n=None, s=span):
        def build():
            # ``_forward`` takes the count as a static argument: another
            # count is another trace
            dsa._choose_chunk = rule if n is None else (lambda *a: n)
            if dsa._SPAN != s:
                dsa._SPAN = s
                jax.clear_caches()
            return kernels(dsa)
        return build

    sides = {"chosen": this_tree()}
    sides.update({f"chunk_{n}_span_{s}": this_tree(n, s)
                  for n in args.chunks for s in args.spans})
    others = dict(_other_dsa(i, named) for i, named in enumerate(args.parent))
    sides.update({name: (lambda other=other: kernels(other))
                  for name, other in others.items()})

    out = {"device": jax.devices()[0].device_kind, "q": list(x["q"].shape),
           "k": list(x["k"].shape), "topk": cfg.index_topk,
           "block_q": block_q, "calls": args.calls, "seed": args.seed,
           "chunk_tiles": {}}
    results = {}
    ms = {side: {} for side in sides}
    order = list(sides)
    for turn in range(args.rounds if on_chip else 1):
        for side in (order if turn % 2 == 0 else order[::-1]):
            built = sides[side]()
            if side not in results:
                o, lse = built["dsa_fwd"][0](*built["dsa_fwd"][1])
                dq = built["dsa_dq"][0](*built["dsa_dq"][1])
                dk, dv = built["dsa_dkv"][0](*built["dsa_dkv"][1])
                results[side] = jax.device_get((o, lse, dq, dk, dv))
                if side not in others:      # a gauge: this tree's alone
                    out["chunk_tiles"][side] = TRACED.snapshot().get(
                        "dsa_fwd_chunk_tiles")
            if not on_chip:
                continue
            # a chunk's side differs from ``chosen`` in the forward alone
            names = (["dsa_fwd"] if side.startswith("chunk_")
                     else list(built))
            for name in names:
                fn, a = built[name]
                ms[side].setdefault(name, []).append(
                    _time_ms(fn, a, args.calls))
                print(turn, side, name, ms[side][name][-1], flush=True)
    dsa._choose_chunk, dsa._SPAN = rule, span

    import numpy as np

    first = order[0]

    def against(a, b):
        a, b = (np.asarray(z, np.float32) for z in (a, b))
        return {"bit_for_bit": bool(np.array_equal(a, b)),
                "rel_l2": float(np.linalg.norm(a - b) / np.linalg.norm(b))}

    out["against_" + first] = {
        side: {leaf: against(a, b) for leaf, a, b in zip(
            _LEAVES, got, results[first])}
        for side, got in results.items() if side != first}
    if on_chip:
        out["ms_a_call"] = {
            side: {name: sorted(seen)[len(seen) // 2]
                   for name, seen in per.items()} for side, per in ms.items()}
        out["ms_a_call_every_round"] = ms
    print(json.dumps(out, indent=1))
    path = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "dsa_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
