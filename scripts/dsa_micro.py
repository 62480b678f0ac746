"""The four attention kernels of ``ops/dsa.py`` alone at the cell's call
(run by hand on the chip; PERF.md section 6, PRs 69 and 72): ``dsa_fwd``,
``dsa_dq``, ``dsa_dkv`` and ``dsa_kl`` at ``keye2-ep8-solo-steady``'s ``[2,
32 | 4, 16384, 128]``, the sets ``dsa_select``'s 2 048 best keys a query,
ms a call — the reading taken BEFORE the cell's.

    python scripts/dsa_micro.py --chunks 1 2 4 8 --dkv 2x1 2x4 --kl 4 8
    python scripts/dsa_micro.py --parent _scratch/parent/torchft_tpu/ops/dsa.py

A side is this tree with one kernel's count forced through the rule that
chooses it (``ops/dsa.py::_choose_chunk``, ``_choose_backward``,
``_choose_kl`` answering it), timed beside the rules' own choice,
``chosen``: ``--chunks`` the k tiles a grid step of ``dsa_fwd`` AND of
``dsa_dq``; ``--dkv`` ``dsa_dkv``'s k tiles x q blocks a grid step;
``--kl`` ``dsa_kl``'s heads a straight-line group; each of ``--chunks`` and
``--dkv`` once for each of ``--spans`` (tiles a matmul takes side by side,
``ops/dsa.py::_SPAN`` set to it: 1 is the order the one-tile kernels meet
them in, bit for bit). ``--only`` names the kernels to time. ``--parent``
names other ``ops/dsa.py`` files (``name=path``, or a path alone, called
``parent``; a file from before PR 72 takes no counts for its three sweeps)
read in the same process: their kernels run on the same operands and the
same sets turn about with this tree's; every result of every side — ``o``,
``lse``; ``dq``, ``dk``, ``dv`` (which read the side's own ``lse``); the
rows' ``kl`` and ``dqi``, ``dki``, ``dw`` — is compared with the first
side's bit for bit and as a relative L2. ``dsa_dq`` and ``dsa_dkv`` are one
jitted call (``_backward``) each of whose results is asked for alone, so
the other kernel is dead code to XLA; ``dsa_kl`` runs with its gradients.
The operands are the cell's own check's
(``benchmark/families/keye.py::kernel_inputs``). Prints one JSON object and
writes it to ``chiprun_out/dsa_micro.json``. A CPU run (the interpreter,
``[2, 16 | 2, 64, 16]``, 16 rows a step, every count a real one) gives
agreement only.

Readings (my chip runs, PR 72; TPU v5 lite, seed 2147483659, parent
``6ed3603``; ms a call, three rounds' median, rounds within 0.1):

    dsa_dq   parent 56.91; 2 / 4 / 8 / 16 / 32 k tiles a step 51.5 / 47.3 /
             45.6 / 41.7 / 37.5, a tile or a pair a matmul alike
    dsa_dkv  parent 74.93; k tiles x q blocks 2x1 62.4, 4x1 59.1, 8x1 61.7,
             1x2 68.2, 1x4 64.9, 1x8 63.2, 2x2 59.0, 4x2 57.5, 2x4 57.3,
             2x8 56.5 (taken), 4x4 56.5
    dsa_kl   parent 65.74; 2 / 4 / 8 / 16 heads a group 54.0 / 48.3 / 45.5 /
             42.1 (taken); two k tiles a step 63.7 / 53.9 / 49.1 / 47.2 at
             1 / 2 / 4 / 8 heads: no gain, not kept
    dsa_fwd  35.4 on both trees (PR 69: 83.6 at one tile a step)
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

_LEAVES = ("o", "lse", "dq", "dk", "dv", "kl", "dqi", "dki", "dw")


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
                    help="k tiles a grid step of dsa_fwd and dsa_dq, one "
                    "side each")
    ap.add_argument("--dkv", nargs="*", default=[], metavar="TILESxBLOCKS",
                    help="k tiles x q blocks a grid step of dsa_dkv")
    ap.add_argument("--kl", type=int, nargs="*", default=[],
                    help="heads a straight-line group of dsa_kl")
    ap.add_argument("--parent", nargs="*", default=[],
                    help="other ops/dsa.py files, compared in this process")
    ap.add_argument("--spans", type=int, nargs="*", default=[2],
                    help="tiles a matmul, one side a count each")
    ap.add_argument("--only", nargs="*", default=[],
                    help="the kernels to time (all)")
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
        the backward's and the KL's read the module's own ``lse``. A file
        from before PR 72 takes no counts: its three sweeps are one tile
        a grid step."""
        fwd = jax.jit(lambda q, k, v, sel: mod.attend(q, k, v, sel, **kw))
        o, lse = fwd(*qkv, sel)
        merge = mod._merge
        do = merge(x["do"])
        delta = jnp.sum(do.astype(jnp.float32) * merge(o).astype(
            jnp.float32), axis=-1)[:, None, :]
        back = (merge(x["q"]), merge(x["k"]), merge(x["v"]), do,
                merge(lse)[:, None, :], delta, sel)
        d, dv, size = x["q"].shape[-1], x["v"].shape[-1], x["q"].dtype.itemsize
        counted = hasattr(mod, "_choose_backward")
        tiles = ((mod._choose_backward(seq, d, dv, size, block_q),)
                 if counted else ())
        kl_tiles = ((mod._choose_kl(
            seq, heads, x["k"].shape[1], d, x["qi"].shape[1],
            x["qi"].shape[-1], size, block_q),) if counted else ())

        def backward(*a):
            return mod._backward(*a, heads, scale, block_q, *tiles,
                                 not on_chip)

        return {
            "dsa_fwd": (fwd, qkv + (sel,)),
            "dsa_dq": (jax.jit(lambda *a: backward(*a)[0]), back),
            "dsa_dkv": (jax.jit(lambda *a: backward(*a)[1:]), back),
            "dsa_kl": (jax.jit(lambda *a: mod._kl_call(
                *a, scale, block_q, True, *kl_tiles, not on_chip)),
                (x["q"], x["k"], lse, x["qi"], x["ki"], x["w"], sel, lse_i)),
        }

    rules = {name: getattr(dsa, name) for name in (
        "_choose_chunk", "_choose_backward", "_choose_kl", "_SPAN")}

    span_traced = [rules["_SPAN"]]

    def this_tree(times, s=rules["_SPAN"], fwd=None, dq=None, dkv=None,
                  kl=None):
        """This tree with counts forced (``None``: the rule's), and the
        kernels a forced count reaches (``times``: all for none)."""
        def forced_backward(*shape):
            was = rules["_choose_backward"](*shape)
            return ((was[0] if dq is None else dq,)
                    + (was[1:] if dkv is None else dkv))

        def build():
            # the counts are static arguments of the jitted calls: another
            # count is another trace; the span is read where a body is
            # traced, so another span clears jax's caches
            dsa._choose_chunk = (rules["_choose_chunk"] if fwd is None
                                 else (lambda *shape: fwd))
            dsa._choose_backward = forced_backward
            dsa._choose_kl = (rules["_choose_kl"] if kl is None
                              else (lambda *shape: kl))
            dsa._SPAN = s
            if s != span_traced[0]:
                span_traced[0] = s
                jax.clear_caches()
            return {name: call for name, call in kernels(dsa).items()
                    if not times or name in times}
        return build

    sides = {"chosen": this_tree(())}
    for s in args.spans:
        for n in args.chunks:
            sides[f"chunk_{n}_span_{s}"] = this_tree(
                ("dsa_fwd", "dsa_dq"), s, fwd=n, dq=n)
        for pair in args.dkv:
            sides[f"dkv_{pair}_span_{s}"] = this_tree(
                ("dsa_dkv",), s, dkv=tuple(map(int, pair.split("x"))))
    for n in args.kl:
        sides[f"kl_{n}"] = this_tree(("dsa_kl",), kl=n)
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
    gauges = {"dsa_fwd": "dsa_fwd_chunk_tiles", "dsa_dq": "dsa_dq_chunk_tiles",
              "dsa_dkv": "dsa_dkv_chunk_tiles", "dsa_kl": "dsa_kl_group_heads"}
    for turn in range(args.rounds if on_chip else 1):
        for side in (order if turn % 2 == 0 else order[::-1]):
            built = sides[side]()
            if side not in results:
                got = {}
                for name, leaves in (("dsa_fwd", ("o", "lse")),
                                     ("dsa_dq", ("dq",)),
                                     ("dsa_dkv", ("dk", "dv")),
                                     ("dsa_kl", ("kl", "dqi", "dki", "dw"))):
                    if name in built:
                        value = built[name][0](*built[name][1])
                        value = value if isinstance(value, tuple) else (value,)
                        got.update(zip(leaves, jax.device_get(value)))
                results[side] = got
                if side not in others:      # gauges: this tree's alone
                    traced = TRACED.snapshot()
                    out["chunk_tiles"][side] = {
                        gauge: traced.get(gauge)
                        for name, gauge in gauges.items() if name in built}
            if not on_chip:
                continue
            for name, (fn, a) in built.items():
                if args.only and name not in args.only:
                    continue
                ms[side].setdefault(name, []).append(
                    _time_ms(fn, a, args.calls))
                print(turn, side, name, ms[side][name][-1], flush=True)
    for name, rule in rules.items():
        setattr(dsa, name, rule)

    import numpy as np

    first = order[0]

    def against(a, b):
        a, b = (np.asarray(z, np.float32) for z in (a, b))
        return {"bit_for_bit": bool(np.array_equal(a, b)),
                "rel_l2": float(np.linalg.norm(a - b) / np.linalg.norm(b))}

    out["against_" + first] = {
        side: {leaf: against(got[leaf], results[first][leaf])
               for leaf in _LEAVES if leaf in got}
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
