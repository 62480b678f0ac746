"""The windowed flash call of ``smallthinker-ep4-solo-steady`` on the chip,
by the tile (run by hand; PERF.md section 6, PR 50): ``flash_fwd`` /
``flash_dq`` / ``flash_dkv`` at [2, 16384, 28, 128] under a window of 4096
keys,

- ms a call with the SQUARE 512 x 512 tile and with the streamed 512 x
  1024 tile the causal calls use (and any further ``BQxBK`` given), the
  grid steps a head each takes and its tiles' area over the band's live
  pairs;
- what ``ops/flash.py::_choose_blocks`` picks at that shape, and the full
  causal call of the same shape beside it at the rule's tiles;
- each kernel's floor (``benchmark/smallthinker_flops.py``: the live
  pairs' operations over the bf16 peak, or the bytes, whichever is
  longer);
- every candidate's forward, ``dq``, ``dk``, ``dv`` on two heads of one
  sequence against the square tile's, bit for bit or by the largest
  difference, and the rule's call against the band-masked softmax
  (``families/smallthinker.py::swa_comparison``, the cell's own).

    python benchmark/tests/swa_micro.py [1024x1024 ...] [w1024 w2048 ...]

``wN`` reads the square and the streamed tile at a window of N keys too
(where the rule's threshold between 512 and 4096 keys comes from).

Prints one JSON object and writes it to ``chiprun_out/swa_micro.json``. A
CPU run (the interpreter, a short sequence) gives agreement only.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

TILES = [(512, 512), (512, 1024)]


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import flops, smallthinker_flops
    from benchmark.families import smallthinker as family
    from torchft_tpu.ops import flash
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    tiles = TILES + [tuple(int(e) for e in a.split("x"))
                     for a in sys.argv[1:] if "x" in a]
    windows = [int(a[1:]) for a in sys.argv[1:] if a.startswith("w")]
    with open(os.path.join(_BENCH, "configs",
                           "smallthinker-21b-a3b-ep4.json")) as f:
        config = json.load(f)
    cfg = family.build(config).cfg
    on_chip = jax.default_backend() == "tpu"
    kind = jax.devices()[0].device_kind
    rows, seq, W = (2, 16384, cfg.window) if on_chip else (1, 2048, 1000)
    H, D, dt = cfg.n_heads, cfg.head_dim, cfg.dtype
    if not on_chip:
        H = 2

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(5):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t)
        return 1e3 * sorted(times)[2]

    out = {"device": kind, "rows": rows, "seq": seq, "heads": H,
           "window": W}
    key = jax.random.split(jax.random.key(7), 4)
    merged = [jax.random.normal(k, (rows * H, seq, D), jnp.float32
                                ).astype(dt) for k in key]
    live = smallthinker_flops.live_pairs(seq, W)

    def kernels(blocks, window):
        common = (True, D ** -0.5, *blocks, not on_chip, None)

        def forward(q, k, v):
            return flash._flash_forward(q, k, v, *common, window=window)

        def backward(q, k, v, g, lse, delta):
            return flash._flash_backward_core(
                q, k, v, g, lse, delta, *common, window=window)

        return forward, backward

    def run(label, blocks, window, compare=True):
        forward, backward = kernels(blocks, window)
        o, lse = jax.jit(forward)(*merged[:3])
        delta = jnp.sum(merged[3].astype(jnp.float32)
                        * o.astype(jnp.float32), axis=-1)
        steps = flash._grid_steps(seq, *blocks, window)[0]
        seen = {"blocks": list(blocks), "grid_steps_a_head": steps,
                "tile_area_over_live_pairs": steps * blocks[0] * blocks[1]
                / smallthinker_flops.live_pairs(seq, window)}
        if on_chip:
            seen["flash_fwd_ms"] = timed(jax.jit(forward), *merged[:3])
            seen["flash_dq_ms"] = timed(
                jax.jit(lambda *a: backward(*a)[0]), *merged, lse, delta)
            seen["flash_dkv_ms"] = timed(
                jax.jit(lambda *a: backward(*a)[1:]), *merged, lse, delta)
        out[label] = seen
        if not compare:
            return None
        # two heads' results, to compare the tiles with one another
        two = [m[:2] for m in merged]
        o2, lse2 = jax.jit(forward)(*two[:3])
        d2 = jnp.sum(two[3].astype(jnp.float32) * o2.astype(jnp.float32),
                     axis=-1)
        return (o2, *jax.jit(backward)(*two, lse2, d2))

    base = None
    for blocks in tiles:
        label = "swa_%dx%d" % blocks
        got = run(label, blocks, W)
        if base is None:
            base = got
        else:
            out[label]["max_abs_diff_to_" + "%dx%d" % tiles[0]] = [
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(got, base)]
    for other in windows if on_chip else ():
        for blocks in TILES:
            run("w%d_%dx%d" % (other, *blocks), blocks, other, compare=False)
    out["rule_swa_blocks"] = {str(w): list(flash._choose_blocks(
        seq, D, 2, window=w)) for w in [W] + windows}
    full = flash._choose_blocks(seq, D, 2)
    run("full_%dx%d" % full, full, None, compare=False)
    if on_chip:
        peaks = flops.peaks(kind)
        for label, window in (("swa", W), ("full", None)):
            dims = dict(batch_heads=rows * H, seq_len=seq, d_qk=D, d_v=D)
            out[label + "_floor_ms"] = {k: 1e3 * max(
                smallthinker_flops.flash_flops_per_call(window=window, **dims)
                / peaks["bf16_flops"],
                smallthinker_flops.flash_bytes_per_call(k, **dims)
                / peaks["hbm_bytes_per_s"])
                for k in smallthinker_flops.KERNELS}
    # the cell's own comparison of the windowed call, at the rule's tiles
    swa_cfg = cfg if on_chip else dataclasses.replace(
        cfg, n_heads=2, n_kv_heads=2, window=W)
    seen = jax.device_get(jax.jit(family.swa_comparison(swa_cfg))(
        *family.swa_inputs(swa_cfg, 987654321, seq)))
    out["swa_rel_l2"] = {k: float(v) for k, v in seen.items()}
    out["live_pairs_a_head"] = live
    print(json.dumps(out, indent=1))
    path = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "swa_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
