"""``ops/s6.py::s6_scan`` and the windowed flash call on the chip at the
cell's shapes (5120 channels, 16 states; 20 heads a call of 64-wide q and
k and 128-wide v under a window of 512 keys; run by hand; PERF.md section
6, PR 47):

- the scan's agreement with the position-by-position recurrence, leaf by
  leaf, as the cell's own check takes it (``families/phi4flash.py::
  scan_comparison``), for each chunk given;
- ms a call of ``s6_fwd`` and of forward + backward at [4, 8192, 5120]
  with the XLA layouts around the kernels, and their bytes floor;
- ms a call of ``flash_fwd`` / ``flash_dq`` / ``flash_dkv`` at [4, 8192,
  20, 64 / 128] under the window and under the causal mask alone, the
  tiles the rule picks, the grid steps a head and the windowed kernels'
  floor; the windowed forward against ``reference_attention`` with the
  band mask at [1, 2048].

    python benchmark/tests/s6_micro.py 64 128

Prints one JSON object and writes it to ``chiprun_out/s6_micro.json``. A
CPU run (the interpreter) gives agreement only, and slowly.
"""

from __future__ import annotations

import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import phi4flash_flops
    from benchmark.families import phi4flash as family
    from benchmark.readers import phi4flash_scopes
    from torchft_tpu.ops import flash, s6
    from torchft_tpu.ops.attention import reference_attention
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    chunks = [int(c) for c in sys.argv[1:]] or [s6._CHUNK]
    with open(os.path.join(_BENCH, "configs",
                           "phi-4-mini-flash-reasoning-vp8.json")) as f:
        config = json.load(f)
    cfg = family.build(config).cfg
    on_chip = jax.default_backend() == "tpu"
    kind = jax.devices()[0].device_kind
    rows, seq = (4, 8192) if on_chip else (1, 128)

    def at(q):
        return lambda *a: s6._s6(*a, q, s6._interpret())

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(5):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t)
        return 1e3 * sorted(times)[2]

    out = {"device": kind, "rows": rows, "seq": seq}
    one = family.scan_inputs(cfg, 987654321, family.SCAN_SEQ if on_chip
                             else 128)
    for q in chunks:
        seen = jax.device_get(jax.jit(family.scan_comparison(at(q)))(*one))
        out[f"scan_rel_l2_q{q}"] = {k: float(v) for k, v in seen.items()}
    shapes = dict(phi4flash_flops.config_dims(config), batch=rows,
                  seq_len=seq)
    if on_chip:
        # [rows, seq]: a sequence a seed, one A and D
        drawn = [family.scan_inputs(cfg, 1234567891 + i, seq)
                 for i in range(rows)]
        (_, _, A, _, _, D), _ = drawn[0]
        x, delta, Bm, Cm, dy = (
            jnp.concatenate(leaves) for leaves in zip(*(
                (a[0], a[1], a[3], a[4], g) for a, g in drawn)))
        args = (x, delta, A, Bm, Cm, D)
        for q in chunks:
            out[f"s6_fwd_ms_q{q}"] = timed(jax.jit(at(q)), *args)
            out[f"s6_fwd_bwd_ms_q{q}"] = timed(jax.jit(
                lambda *a, q=q: jax.vjp(at(q), *a[:-1])[1](a[-1])), *args, dy)
        for k in phi4flash_flops.S6_KERNELS:
            out[f"{k}_floor_ms"] = 1e3 * phi4flash_scopes.least_seconds(
                k, shapes, kind)

    # -- the windowed flash call, and the causal one beside it
    H, D, W = cfg.n_heads // 2, cfg.head_dim, cfg.window
    key = jax.random.split(jax.random.key(7), 4)
    dt = cfg.dtype
    q_, k_ = (jax.random.normal(key[i], (rows, seq, H, D), jnp.float32
                                ).astype(dt) for i in (0, 1))
    v_, do_ = (jax.random.normal(key[i], (rows, seq, H, 2 * D), jnp.float32
                                 ).astype(dt) for i in (2, 3))
    small = min(seq, 2048)
    got = flash.flash_attention(
        q_[:1, :small], k_[:1, :small], v_[:1, :small], window=W,
        interpret=not on_chip)
    want = reference_attention(
        *(z[:1, :small].astype(jnp.float32) for z in (q_, k_, v_)),
        window=W)
    out["swa_fwd_max_abs_err"] = float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want)))
    if on_chip:
        merged = [z.transpose(0, 2, 1, 3).reshape(rows * H, seq, z.shape[-1])
                  for z in (q_, k_, v_, do_)]
        for label, window in (("swa", W), ("causal", None)):
            blocks = flash._choose_blocks(seq, D, 2, v_dim=2 * D,
                                          window=window)
            out[f"{label}_blocks"] = list(blocks)
            out[f"{label}_grid_steps"] = flash._grid_steps(
                seq, *blocks, window)[0]
            common = (True, D ** -0.5, *blocks, False, None)

            def forward(q, k, v):
                return flash._flash_forward(q, k, v, *common, window=window)

            def backward(q, k, v, g, lse, delta):
                return flash._flash_backward_core(
                    q, k, v, g, lse, delta, *common, window=window)

            o, lse = jax.jit(forward)(*merged[:3])
            delta = jnp.sum(merged[3].astype(jnp.float32)
                            * o.astype(jnp.float32), axis=-1)
            out[f"{label}_flash_fwd_ms"] = timed(jax.jit(forward),
                                                 *merged[:3])
            out[f"{label}_flash_dq_ms"] = timed(
                jax.jit(lambda *a: backward(*a)[0]), *merged, lse, delta)
            out[f"{label}_flash_dkv_ms"] = timed(
                jax.jit(lambda *a: backward(*a)[1:]), *merged, lse, delta)
        for k in ("swa_flash_fwd", "swa_flash_dq", "swa_flash_dkv"):
            out[f"{k}_floor_ms"] = 1e3 * phi4flash_scopes.least_seconds(
                k, shapes, kind)
    print(json.dumps(out, indent=1))
    path = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "s6_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
