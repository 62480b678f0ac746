"""The head's loss alone at the eight solo cells' shapes (run by hand on the
chip; PERF.md section 6, PR 49): ``jax.value_and_grad`` of
``ops/xent.py::hidden_cross_entropy`` over the hidden states and the head,
ms a call, as a share of 6 T d V at the MXU's peak and as bytes an element
of the logits at the HBM's peak.

    python scripts/xent_micro.py
    python scripts/xent_micro.py --parent _scratch/parent

``--parent`` names a second tree (or its ``ops/xent.py``) that is read in
the same process: its loss runs on the same operands, turn about with this
tree's, and the loss, ``dh`` and ``dW`` are compared. ``--cells`` picks the
shapes (T rows of the step, d, V, ``xent_chunks``, whether the head is the
embedding's transpose), ``--chunks`` overrides the configurations' chunk
counts: the builder's tool for choosing the tile. Prints one JSON object and
writes it to ``chiprun_out/xent_micro.json``. A CPU run (each shape cut to a
sixty-fourth) gives agreement only.
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

# cell -> (T, d, V, xent_chunks, tied head): benchmark/configs/*.json
_CELLS = {
    "c111m": (32768, 768, 50304, 3, False),
    "c1p3b": (16384, 2048, 50304, 3, False),
    "olmoe": (24576, 2048, 50304, 3, False),
    "joyai": (32768, 2048, 16160, 4, False),
    "nemo3": (32768, 2688, 16384, 4, False),
    "lfm2": (32768, 2048, 16384, 4, True),
    "kimi": (32768, 2304, 20480, 4, False),
    "phi4flash": (32768, 2560, 25088, 4, True),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", default=None,
                    help="a second tree or ops/xent.py, compared in this "
                         "process")
    ap.add_argument("--cells", nargs="*", default=list(_CELLS))
    ap.add_argument("--chunks", type=int, default=0,
                    help="override every cell's xent_chunks")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.ops import xent
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    on_chip = jax.default_backend() == "tpu"
    sides = {"this": xent}
    if args.parent:
        path = args.parent
        if os.path.isdir(path):
            path = os.path.join(path, "torchft_tpu", "ops", "xent.py")
        spec = importlib.util.spec_from_file_location("xent_parent", path)
        sides["parent"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sides["parent"])

    kind = jax.devices()[0].device_kind
    out = {"device": kind, "calls": args.calls}
    if on_chip:
        with open(os.path.join(_ROOT, "benchmark", "peaks.json")) as f:
            peak = json.load(f)["kinds"][kind]

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

    for cell in args.cells:
        n, d, v, chunks, tied = _CELLS[cell]
        if not on_chip:
            n, d, v = n // 64, d // 64, v // 64
        chunks = args.chunks or chunks
        rng = np.random.default_rng(49)
        h = jnp.asarray(rng.standard_normal((1, n, d)), jnp.bfloat16)
        # the head as the parameter tree holds it: [d, V], or the
        # embedding's [V, d] where the two are tied
        head = jnp.asarray(
            0.02 * rng.standard_normal((v, d) if tied else (d, v)),
            jnp.float32)
        t = jnp.asarray(rng.integers(0, v, (1, n)), jnp.int32)
        entry = {"T": n, "d": d, "V": v, "chunks": chunks, "tied": tied}
        entry["row_tiles"] = list(xent._row_tiles(n, chunks))

        def build(mod):
            def loss(h, head, t):
                return mod.hidden_cross_entropy(
                    h, head.T if tied else head, t, chunks)
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

        built = {side: build(mod) for side, mod in sides.items()}
        if "parent" in built:
            (la, (dha, dwa)), (lb, (dhb, dwb)) = (
                built[side](h, head, t) for side in ("this", "parent"))

            def off(a, b):
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                return float(jnp.max(jnp.abs(a - b))
                             / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))
            entry["loss"] = {"this": float(la), "parent": float(lb)}
            entry["off_by_share_of_largest"] = {
                "dh": off(dha, dhb), "dW": off(dwa, dwb)}
            del dha, dwa, dhb, dwb
        if on_chip:
            ms = {side: [] for side in built}
            order = list(built)
            for turn in range(args.rounds):
                for side in (order if turn % 2 == 0 else order[::-1]):
                    ms[side].append(time_ms(built[side], h, head, t))
            entry["ms_a_call_every_round"] = ms
            entry["ms_a_call"] = {
                side: sorted(seen)[len(seen) // 2]
                for side, seen in ms.items()}
            entry["share_of_mxu_peak_on_6TdV"] = {
                side: 6.0 * n * d * v / peak["bf16_flops"] / (1e-3 * m)
                for side, m in entry["ms_a_call"].items()}
            entry["hbm_bytes_an_element_at_peak"] = {
                side: 1e-3 * m * peak["hbm_bytes_per_s"] / (n * v)
                for side, m in entry["ms_a_call"].items()}
            if "parent" in ms:
                entry["gain_ms_a_call"] = (entry["ms_a_call"]["parent"]
                                           - entry["ms_a_call"]["this"])
        out[cell] = entry
        print(cell, json.dumps(entry), flush=True)
        del built
    path = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "xent_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
