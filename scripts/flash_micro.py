"""The three flash kernels alone at a cell's call shape (run by hand on the
chip; PERF.md section 6, PRs 45 and 51): ``flash_fwd``, ``flash_dq`` and
``flash_dkv`` of ``ops/flash.py``, ms a call each, with the grid steps a
head the call takes and the rectangle of blocks would (``_grid_steps``).

    python scripts/flash_micro.py
    python scripts/flash_micro.py --parent _scratch/parent/torchft_tpu/ops/flash.py

``--parent`` names a second ``ops/flash.py`` that is read in the same
process: its kernels run on the same operands, turn about with this tree's,
and ``out``, ``lse``, ``dq``, ``dk`` and ``dv`` are compared bit for bit. A
timed call is the jitted wrapper: the kernel and whatever XLA lays out
around it. ``--cells`` picks the shapes (``_CELLS``: a cell's call as ``[BH,
S, Dqk / Dv]``, bf16, the blocks the kernels choose from the shape; a name
ending in ``-swa`` is under a window, in ``-unmasked`` without the mask).
Prints one JSON object and writes it to ``chiprun_out/flash_micro.json``. A
CPU run (the interpreter, a small shape) gives agreement and step counts
only.
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

_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")

# cell -> ((heads x rows, sequence, Dqk, Dv, causal, window) on the chip,
# the same on the CPU with the regime's threshold last): there the same
# width ratio a quarter the size, at a tile ratio of 1 : 2; the resident
# cell keeps its regime, the others are streamed by force
_CELLS = {
    "joyai": ((128, 8192, 192, 128, True, None),
              (2, 512, 48, 32, True, None, 0)),
    "nemo3": ((128, 8192, 128, 128, True, None),
              (2, 512, 32, 32, True, None, 0)),
    # the calls PR 51 was held to, bit for bit: resident at 64 wide,
    # streamed at 192 / 128 and at 128, masked, windowed and not
    "c111m": ((192, 2048, 64, 64, True, None),
              (2, 512, 16, 16, True, None, None)),
    "c111m-unmasked": ((192, 2048, 64, 64, False, None),
                       (2, 512, 16, 16, False, None, None)),
    "joyai-ep16": ((80, 8192, 192, 128, True, None),
                   (2, 512, 48, 32, True, None, 0)),
    "smallthinker": ((56, 16384, 128, 128, True, None),
                     (2, 512, 32, 32, True, None, 0)),
    "smallthinker-swa": ((56, 16384, 128, 128, True, 4096),
                         (2, 512, 32, 32, True, 256, 0)),
    "smallthinker-unmasked": ((56, 16384, 128, 128, False, None),
                              (2, 512, 32, 32, False, None, 0)),
    # the shortest sweeps of any cell (512 keys: one or two tiles a q
    # block), where whatever a kernel does once a q block shows most
    "phi4flash-swa": ((80, 8192, 64, 128, True, 512),
                      (2, 512, 16, 32, True, 128, 0)),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", default=None,
                    help="a second ops/flash.py, compared in this process")
    ap.add_argument("--cells", nargs="*", default=["joyai", "nemo3"])
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.ops import flash
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    on_chip = jax.default_backend() == "tpu"
    sides = {"this": flash}
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "flash_parent", args.parent)
        sides["parent"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sides["parent"])

    out = {"device": jax.devices()[0].device_kind, "calls": args.calls}

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
        chip, cpu = _CELLS[cell]
        bh, seq, dqk, dv, causal, window, threshold = (
            (*chip, None) if on_chip else cpu)
        rng = np.random.default_rng(45)
        q, k, v, g = (
            jnp.asarray(rng.standard_normal((bh, seq, w)), jnp.bfloat16)
            for w in (dqk, dqk, dv, dv))
        scale = 1.0 / dqk ** 0.5
        blocks = (flash._choose_blocks(seq, dqk, 2, v_dim=dv, window=window)
                  if on_chip else (64, 128))
        live, rectangular = flash._grid_steps(seq, *blocks, window)
        entry = {"q": [bh, seq, dqk], "v": [bh, seq, dv], "blocks": blocks,
                 "causal": causal, "window": window,
                 "grid_steps_a_head": {
                     "live": live if causal else rectangular,
                     "rectangular": rectangular}}

        # the operands are arguments (a closed-over array is a constant of
        # the program); dq and dkv are two results of one builder, and the
        # one a function does not return is dead code to XLA
        def kernels(mod):
            common = (causal, scale, *blocks, not on_chip, threshold)

            def forward(q, k, v):
                return mod._flash_forward(q, k, v, *common, window=window)

            def backward(q, k, v, g, lse, delta):
                return mod._flash_backward_core(q, k, v, g, lse, delta,
                                                *common, window=window)
            return {
                "flash_fwd": jax.jit(forward),
                "flash_dq": jax.jit(lambda *a: backward(*a)[0]),
                "flash_dkv": jax.jit(lambda *a: backward(*a)[1:]),
            }

        built = {side: kernels(mod) for side, mod in sides.items()}
        results, statistics = {}, {}
        for side, fns in built.items():
            o, lse = fns["flash_fwd"](q, k, v)
            delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1)
            statistics[side] = lse, delta
            dk, dvv = fns["flash_dkv"](q, k, v, g, lse, delta)
            results[side] = {"out": o, "lse": lse,
                             "dq": fns["flash_dq"](q, k, v, g, lse, delta),
                             "dk": dk, "dv": dvv}
        if "parent" in results:
            entry["bit_for_bit"] = {
                name: bool(jnp.array_equal(a, results["parent"][name]))
                for name, a in results["this"].items()}
        if on_chip:
            # both sides' backward kernels on ONE side's statistics: the
            # times do not depend on them, and two sets of results do not
            # fit beside the operands at every shape
            lse, delta = statistics["this"]
            del results, statistics
            ms = {side: {name: [] for name in _KERNELS} for side in built}
            order = list(built)
            for turn in range(args.rounds):
                for side in (order if turn % 2 == 0 else order[::-1]):
                    for name in _KERNELS:
                        a = ((q, k, v) if name == "flash_fwd"
                             else (q, k, v, g, lse, delta))
                        ms[side][name].append(
                            time_ms(built[side][name], *a))
            entry["ms_a_call"] = {
                side: {name: sorted(seen)[len(seen) // 2]
                       for name, seen in per.items()}
                for side, per in ms.items()}
            entry["ms_a_call_every_round"] = ms
            if "parent" in ms:
                entry["gain_ms_a_call"] = {
                    name: entry["ms_a_call"]["parent"][name]
                    - entry["ms_a_call"]["this"][name] for name in _KERNELS}
        out[cell] = entry
        print(cell, json.dumps(entry), flush=True)
        del built
    path = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "flash_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
