"""The flash kernels alone at a cell's call shape (run by hand on the
chip; PERF.md section 6, PRs 45, 51 and 74): ``flash_fwd`` and the backward
of ``ops/flash.py`` — ``flash_bwd``, a side's WHOLE backward: the one kernel
where the shape takes it (``fused_backward``: ``flash._fuses_backward``),
else ``flash_dq`` + ``flash_dkv`` in one program, which are then timed alone
as well (what ``--parent``'s side, a tree before PR 74, reads) —, ms a call
each, with the grid steps a head the call takes and the rectangle of blocks
would (``_grid_steps``).

    python scripts/flash_micro.py
    python scripts/flash_micro.py --parent _scratch/parent/torchft_tpu/ops/flash.py
    python scripts/flash_micro.py --parent ... --cells streamed --chunks 1 2 4 8
    python scripts/flash_micro.py --parent ... --cells fused --plain-tile

``--parent`` names a second ``ops/flash.py`` that is read in the same
process: its kernels run on the same operands, turn about with this tree's,
and ``out``, ``lse``, ``dq``, ``dk`` and ``dv`` are compared bit for bit. A
timed call is the jitted wrapper: the kernel and whatever XLA lays out
around it. ``--cells`` picks the shapes (``_CELLS``: a cell's call as ``[BH,
S, Dqk / Dv]``, bf16, the blocks the kernels choose from the shape; a name
ending in ``-swa`` is under a window, in ``-unmasked`` without the mask;
``streamed`` stands for the nine calls of the seven cells whose K and V
stream, ``_STREAMED``, ``fused`` for the eleven streamed calls whose
backward is ``flash_bwd``, ``_FUSED``, ``resident`` for the five cells'
calls whose K and V stay in VMEM, ``_RESIDENT``). ``--plain-tile`` times ``flash_bwd`` again with
its tile in the other orientation (``_plain_tile``, swapped in for
``flash._bwd_tile``: the script's, not an option of the library) and holds
its results to the library's. ``chunk`` is the k tiles a grid step of the streamed
forward sweeps (``_choose_chunk``) and ``grid_steps_a_head.forward`` what its
grid takes; ``--chunks`` times the forward again at each given chunk and
holds its ``out`` and ``lse`` to the other side's bit for bit
(``flash_fwd_by_chunk``: the readings beside ``flash._CHUNK_LADDER``, PR 60;
a chunk Mosaic's VMEM does not hold is ``refused``).
A cell of ``_GROUPED`` is another cell's call with the key/value heads the
model has (``name: (cell, query heads a key/value head, sequences a
step)``): there the two sides are THIS file's kernels on k and v as they
lie (``grouped``, ``[BH / group, S, D]``) and on their copies
(``repeated``, what ``repeat_kv`` fed the kernels until PR 55). ``out``,
``lse`` and ``dq`` are compared bit for
bit, ``dk`` and ``dv`` against the float32 sum over the copies' gradients,
and ``layer_call_ms`` is the whole of ``value_and_grad(flash_attention)`` on
``[B, S, H, D]`` operands either way: the kernels with the repeat, the
layouts and the sum over the copies that XLA puts around them.
Prints one JSON object and writes it to ``chiprun_out/flash_micro.json``. A
CPU run (the interpreter, a small shape) gives agreement and step counts
only.
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv", "flash_bwd")

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
    "phi4flash": ((80, 8192, 64, 128, True, None),
                  (2, 512, 16, 32, True, None, 0)),
    "lfm2": ((128, 8192, 64, 64, True, None),
             (2, 512, 16, 16, True, None, None)),
    # the other resident cells' calls (PR 74)
    "c1p3b": ((128, 2048, 128, 128, True, None),
              (2, 512, 32, 32, True, None, None)),
    "olmoe": ((96, 4096, 128, 128, True, None),
              (2, 512, 32, 32, True, None, None)),
    "granite4h": ((64, 8192, 64, 64, True, None),
                  (2, 512, 16, 16, True, None, None)),
    # the other streamed cells' calls (PR 60; kimi's is joyai's)
    "olmohybrid": ((30, 8192, 128, 128, True, None),
                   (2, 512, 32, 32, True, None, 0)),
    "laguna": ((192, 8192, 128, 128, True, None),
               (2, 512, 32, 32, True, None, 0)),
    "laguna-swa": ((256, 8192, 128, 128, True, 512),
                   (2, 512, 32, 32, True, 128, 0)),
    # PR 74: the two streamed cells no entry stood for (256-wide heads on
    # 512 x 512 tiles; one sequence of sixteen heads)
    "qwen3next": ((64, 8192, 256, 256, True, None),
                  (2, 512, 64, 64, True, None, 0)),
    "ouro": ((16, 8192, 128, 128, True, None),
             (2, 512, 32, 32, True, None, 0)),
    # no cell's: the longest call ``flash._fuses_backward`` admits at
    # 128-wide heads, and the shortest it sends to the pair at 256
    "long32k": ((28, 32768, 128, 128, True, None),
                (2, 512, 32, 32, True, None, 0)),
    "long32k-256": ((8, 32768, 256, 256, True, None),
                    (2, 512, 64, 64, True, None, 0)),
}

# the calls of the seven cells whose K and V stream (PR 60)
_STREAMED = ("joyai", "nemo3", "olmohybrid", "phi4flash", "phi4flash-swa",
             "smallthinker", "smallthinker-swa", "laguna", "laguna-swa")
# every streamed call whose backward is ``flash_bwd`` (PR 74): those and the
# two above; and the five cells' calls whose K and V are resident
_FUSED = _STREAMED + ("qwen3next", "ouro")
_RESIDENT = ("c111m", "c1p3b", "olmoe", "lfm2", "granite4h")

# the four cells whose key/value heads serve several query heads (PR 55)
_GROUPED = {
    "smallthinker-gqa": ("smallthinker", 7, 2),
    "smallthinker-swa-gqa": ("smallthinker-swa", 7, 2),
    "nemo3-gqa": ("nemo3", 16, 4),
    "lfm2-gqa": ("lfm2", 4, 4),
    "phi4flash-gqa": ("phi4flash", 2, 4),
    "phi4flash-swa-gqa": ("phi4flash-swa", 2, 4),
    "qwen3next-gqa": ("qwen3next", 8, 4),
    "laguna-gqa": ("laguna", 6, 4),
    "laguna-swa-gqa": ("laguna-swa", 8, 4),
    "long32k-gqa": ("long32k", 7, 1),
    "granite4h-gqa": ("granite4h", 4, 2),
}


def _plain_tile(flash):
    """``flash._bwd_tile`` in the OTHER orientation (``--plain-tile``): P and
    dS ``[BQ, BK]`` on the statistics' columns, dq the plain product, dk
    and dv each contracted over the tile's rows — two turns of a score tile
    through the transpose unit where the library's tile takes one."""
    import jax
    import jax.numpy as jnp

    def rows_contracted(a, b):
        return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def tile(q, k, v, do, lse, delta, qi, ki, masked, window=None):
        cols = flash._rows_to_cols(lse, delta)
        p, ds = flash._bwd_p_ds(q, k, v, do, cols[:, :1], cols[:, 1:2], qi,
                                ki, masked, window=window)
        dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return dq, rows_contracted(ds, q), rows_contracted(p, do)
    return tile


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", default=None,
                    help="a second ops/flash.py, compared in this process")
    ap.add_argument("--cells", nargs="*", default=["joyai", "nemo3"],
                    help="names of _CELLS / _GROUPED; 'streamed' is the "
                    "seven streamed cells' calls")
    ap.add_argument("--chunks", nargs="*", type=int, default=[],
                    help="time the forward again at these k tiles a grid "
                    "step (the rule's is what every other figure is at)")
    ap.add_argument("--plain-tile", action="store_true",
                    help="time flash_bwd again with its tile in the other "
                    "orientation (_plain_tile), held to the library's")
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
    modules = {"this": flash}
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "flash_parent", args.parent)
        modules["parent"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(modules["parent"])

    out = {"device": jax.devices()[0].device_kind, "calls": args.calls}

    def max_abs(a, b):
        return float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))

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

    cells = [c for name in args.cells
             for c in {"streamed": _STREAMED, "fused": _FUSED,
                       "resident": _RESIDENT}.get(name, (name,))]
    for cell in cells:
        base, group, batch = _GROUPED.get(cell, (cell, 1, 1))
        chip, cpu = _CELLS[base]
        bh, seq, dqk, dv, causal, window, threshold = (
            (*chip, None) if on_chip else cpu)
        if not on_chip:
            bh *= group
        rng = np.random.default_rng(45)
        q, k, v, g = (
            jnp.asarray(rng.standard_normal((rows, seq, w)), jnp.bfloat16)
            for rows, w in ((bh, dqk), (bh // group, dqk),
                            (bh // group, dv), (bh, dv)))
        # side -> (module, k, v): this tree against the parent's on the
        # same operands, or this tree on k and v as they lie against
        # itself on their copies
        if group > 1:
            sides = {"grouped": (flash, k, v), "repeated": (
                flash, jnp.repeat(k, group, axis=0),
                jnp.repeat(v, group, axis=0))}
        else:
            sides = {side: (mod, k, v) for side, mod in modules.items()}
        other = list(sides)[-1]
        scale = 1.0 / dqk ** 0.5
        blocks = (flash._choose_blocks(seq, dqk, 2, v_dim=dv, window=window)
                  if on_chip else (64, 128))
        chunk = flash._choose_chunk(seq, dqk, 2, *blocks, dv, window, causal,
                                    threshold)

        def steps_a_head(chunk):
            live, rectangular, chunked = flash._grid_steps(
                seq, *blocks, window, chunk)
            return {"live": live if causal else rectangular,
                    "rectangular": rectangular,
                    "forward": chunked if causal else rectangular // chunk}

        fused = flash._fuses_backward(seq, dqk, 2, *blocks, dv, threshold)
        entry = {"q": [bh, seq, dqk], "v": [bh // group, seq, dv],
                 "blocks": blocks, "causal": causal, "window": window,
                 "chunk": chunk, "grid_steps_a_head": steps_a_head(chunk),
                 "fused_backward": fused}

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
            fns = {"flash_fwd": jax.jit(forward),
                   "flash_bwd": jax.jit(backward)}
            if not (fused and hasattr(mod, "_fuses_backward")):
                fns["flash_dq"] = jax.jit(lambda *a: backward(*a)[0])
                fns["flash_dkv"] = jax.jit(lambda *a: backward(*a)[1:])
            return fns

        def layer_call(repeated):
            """``value_and_grad`` of the model's call on ``[B, S, H, D]``
            operands (``q``, ``k``, ``v``, ``g`` arrive merged and are laid
            out here, outside the timed function's hot part as little as
            the model's projections are): with ``repeated`` the key/value
            heads are copied first, as the models did."""
            b = batch if on_chip else 1

            def bshd(x):
                return x.reshape(b, -1, seq, x.shape[-1]).transpose(
                    0, 2, 1, 3)

            def loss(q, k, v, g):
                if repeated:
                    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
                o = flash.flash_attention(
                    q, k, v, causal=causal, window=window,
                    interpret=not on_chip, _resident_kv_bytes=threshold)
                return jnp.sum(o.astype(jnp.float32)
                               * g.astype(jnp.float32))

            grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
            return jax.jit(lambda q, k, v, g: grad(
                bshd(q), bshd(k), bshd(v), bshd(g)))

        built = {side: kernels(mod) for side, (mod, _, _) in sides.items()}
        results, statistics = {}, {}
        for side, fns in built.items():
            _, k_, v_ = sides[side]
            o, lse = fns["flash_fwd"](q, k_, v_)
            delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1)
            statistics[side] = lse, delta
            dq, dk, dvv = fns["flash_bwd"](q, k_, v_, g, lse, delta)
            results[side] = {"out": o, "lse": lse, "dq": dq, "dk": dk,
                             "dv": dvv}
        if group > 1:
            entry["bit_for_bit"] = {
                name: bool(jnp.array_equal(
                    results["grouped"][name], results["repeated"][name]))
                for name in ("out", "lse", "dq")}
            # the copies' gradients, each rounded to bf16, summed in f32
            entry["max_abs_from_the_copies_sum"] = {
                name: max_abs(
                    results["grouped"][name],
                    results["repeated"][name].astype(jnp.float32).reshape(
                        bh // group, group, seq, -1).sum(axis=1))
                for name in ("dk", "dv")}
        elif "parent" in results:
            entry["bit_for_bit"] = {
                name: bool(jnp.array_equal(a, results["parent"][name]))
                for name, a in results["this"].items()}
            entry["max_abs_from_the_parent"] = {
                name: max_abs(a, results["parent"][name])
                for name, a in results["this"].items()
                if not entry["bit_for_bit"][name]}
        if args.plain_tile and fused:
            # the other orientation of the tile, on this tree's kernel
            # and the first side's operands and statistics
            first = next(iter(sides))
            operands = (q, *sides[first][1:], g, *statistics[first])
            library, flash._bwd_tile = flash._bwd_tile, _plain_tile(flash)
            jax.clear_caches()
            plain = jax.jit(
                lambda *a: flash._flash_backward_core(
                    *a, causal, scale, *blocks, not on_chip, threshold,
                    window=window))
            try:
                got = plain(*operands)
                entry["plain_tile"] = {"max_abs_from_the_library": {
                    name: max_abs(a, results[first][name])
                    for name, a in zip(("dq", "dk", "dv"), got)}}
                del got
                if on_chip:
                    entry["plain_tile"]["flash_bwd_ms_a_call"] = time_ms(
                        plain, *operands)
            except Exception as e:     # Mosaic refuses the other tile
                entry["plain_tile"] = {"refused": str(e)[-300:]}
            finally:
                flash._bwd_tile = library
                jax.clear_caches()
            del plain, operands
        # the forward again at other chunks: out and lse against the last
        # side's (the parent's, where one is given), then ms a call
        by_chunk = {}
        for n in args.chunks:
            if (seq // blocks[1]) % n:
                continue
            fn = jax.jit(functools.partial(
                flash._flash_forward, causal=causal, scale=scale,
                block_q=blocks[0], block_k=blocks[1], interpret=not on_chip,
                resident_kv_bytes=threshold, window=window, chunk=n))
            _, k_, v_ = sides[other]
            try:
                o, lse = fn(q, k_, v_)
            except jax.errors.JaxRuntimeError as e:    # Mosaic's VMEM limit
                text = str(e)
                by_chunk[n] = {"refused": text[max(text.find(
                    "Scoped allocation"), 0):][:160]}
                continue
            by_chunk[n] = {
                "grid_steps_a_head": steps_a_head(n)["forward"],
                "bit_for_bit": bool(
                    jnp.array_equal(o, results[other]["out"])
                    and jnp.array_equal(lse, results[other]["lse"]))}
            del o, lse
            if on_chip:
                by_chunk[n]["ms_a_call"] = time_ms(fn, q, k_, v_)
        if by_chunk:
            entry["flash_fwd_by_chunk"] = by_chunk
        if on_chip:
            # both sides' backward kernels on ONE side's statistics: the
            # times do not depend on them, and two sets of results do not
            # fit beside the operands at every shape
            lse, delta = statistics[other]
            del results, statistics
            timed = dict(built)
            if group > 1:
                timed = {side: dict(fns, layer_call=layer_call(
                    side == "repeated")) for side, fns in timed.items()}
            ms = {side: {name: [] for name in fns}
                  for side, fns in timed.items()}
            order = list(timed)
            for turn in range(args.rounds):
                for side in (order if turn % 2 == 0 else order[::-1]):
                    _, k_, v_ = sides[side]
                    for name, fn in timed[side].items():
                        a = ((q, k, v, g) if name == "layer_call"
                             else (q, k_, v_) if name == "flash_fwd"
                             else (q, k_, v_, g, lse, delta))
                        ms[side][name].append(time_ms(fn, *a))
            entry["ms_a_call"] = {
                side: {name: sorted(seen)[len(seen) // 2]
                       for name, seen in per.items()}
                for side, per in ms.items()}
            entry["ms_a_call_every_round"] = ms
            if len(ms) == 2:
                first = order[0]
                entry["gain_ms_a_call"] = {
                    name: entry["ms_a_call"][other][name]
                    - entry["ms_a_call"][first][name]
                    for name in ms[first]}
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
