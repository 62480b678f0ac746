"""``ops/ssd.py::ssd_scan`` on the chip at the cell's widths (64 heads of
64, 8 groups, a state of 128; run by hand; PERF.md section 6, PR 33): for
each chunk given, agreement with the position-by-position recurrence
(``reference/nemotron_h_f32.recurrence``, f32 ``highest``, on the same
rounded inputs) — forward at [4, 8192], bf16 and f32 operands; every
gradient at [1, 2048], leaf by leaf, as the cell's own check takes them
(``families/nemotron_h.py::scan_comparison``) — and ms a call forward and
forward + backward at [4, 8192] with the XLA layouts around the kernels:

    python benchmark/tests/ssd_micro.py 128 256

Prints one JSON object and writes it to ``chiprun_out/ssd_micro.json``.
A CPU run (the interpreter) gives agreement only, and slowly.
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

    from benchmark.families import nemotron_h as family
    from benchmark.reference import nemotron_h_f32
    from torchft_tpu.ops import ssd
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    chunks = [int(c) for c in sys.argv[1:]] or [128, 256]
    with open(os.path.join(_BENCH, "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        cfg = family.build(json.load(f)).cfg
    on_chip = jax.default_backend() == "tpu"
    rows, seq = (4, 8192) if on_chip else (1, 512)

    def at(q):
        return lambda *a: ssd._ssd(*a, q, ssd._interpret())

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    out = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq}
    # [rows, seq]: a sequence a seed, one A and D
    drawn = [family.scan_inputs(cfg, 1234567891 + i, seq) for i in range(rows)]
    (_, _, A, _, _, D), _ = drawn[0]
    x, delta, Bm, Cm, dy = (
        jnp.concatenate(leaves) for leaves in zip(*(
            (a[0], a[1], a[3], a[4], g) for a, g in drawn)))
    args = (x, delta, A, Bm, Cm, D)
    args32 = tuple(a.astype(jnp.float32) for a in args)
    want = jax.jit(nemotron_h_f32.recurrence)(*args32)
    for q in chunks:
        fwd = jax.jit(at(q))
        out[f"fwd_rel_l2_q{q}"] = rel(fwd(*args), want)
        out[f"fwd_rel_l2_f32_operands_q{q}"] = rel(fwd(*args32), want)
    del want
    one = family.scan_inputs(cfg, 987654321)
    for q in chunks:
        seen = jax.device_get(jax.jit(family.scan_comparison(at(q)))(*one))
        out[f"rel_l2_q{q}"] = {k: float(v) for k, v in seen.items()}
    if on_chip:
        for q in chunks:
            def both(*a, q=q):
                return jax.vjp(at(q), *a)[1](dy)
            for name, fn in (("fwd", jax.jit(at(q))),
                             ("fwd_bwd", jax.jit(both))):
                jax.block_until_ready(fn(*args))
                times = []
                for _ in range(5):
                    t = time.perf_counter()
                    jax.block_until_ready(fn(*args))
                    times.append(time.perf_counter() - t)
                out[f"{name}_ms_q{q}"] = 1e3 * sorted(times)[2]
    print(json.dumps(out, indent=1))
    path = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "ssd_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
