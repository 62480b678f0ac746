"""``ops/kda.py::kda_scan`` on the chip at the cell's widths (32 heads of
128 key and 128 value channels; run by hand; PERF.md section 6, PR 40):
for each chunk given, agreement with the position-by-position recurrence
(``reference/kimi_linear_f32.kda_recurrence``, f32 ``highest``, on the
same rounded inputs) at [1, 2048], forward and every gradient, leaf by
leaf, the worst head's, as the cell's own check takes them
(``families/kimi_linear.py::kda_comparison``) — once with the kernels'
matmuls as they are and once with every one at ``Precision.HIGHEST``
(what the MXU's one-pass rounding of f32 operands costs) — and ms a call
forward and forward + backward at [4, 8192] with the XLA layouts around
the kernels:

    python benchmark/tests/kda_micro.py 64 128

Prints one JSON object and writes it to ``chiprun_out/kda_micro.json``.
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

    from benchmark.families import kimi_linear as family
    from torchft_tpu.ops import kda
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    chunks = [int(c) for c in sys.argv[1:]] or [64, 128]
    with open(os.path.join(_BENCH, "configs",
                           "kimi-linear-48b-a3b-ep32.json")) as f:
        cfg = family.build(json.load(f)).cfg
    on_chip = jax.default_backend() == "tpu"
    rows, seq, check_seq = (4, 8192, family.KDA_SEQ) if on_chip else (1, 256, 256)

    def at(c):
        return lambda *a: kda._kda(*a, c, kda._interpret())

    out = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq}
    one = family.kda_inputs(cfg, 987654321, check_seq)
    plain = kda._dot

    def highest(a, b, contract=((1,), (0,)), precision=None):
        return plain(a, b, contract, kda._HIGHEST)

    for c in chunks:
        for label, dot in (("", plain), ("_highest", highest)):
            kda._dot = dot
            try:
                seen = jax.device_get(
                    jax.jit(family.kda_comparison(at(c)))(*one))
            finally:
                kda._dot = plain
            out[f"rel_l2_c{c}{label}"] = {k: float(v) for k, v in seen.items()}
            print(c, label, out[f"rel_l2_c{c}{label}"], flush=True)
    if on_chip:
        drawn = [family.kda_inputs(cfg, 1234567891 + i, seq)
                 for i in range(rows)]
        args = tuple(jnp.concatenate(leaves)
                     for leaves in zip(*(a for a, _ in drawn)))
        do = jnp.concatenate([g for _, g in drawn])
        for c in chunks:
            def both(do, *a, c=c):      # an argument: a closed-over
                return jax.vjp(at(c), *a)[1](do)    # array is a constant
            for name, fn, ins in (("fwd", jax.jit(at(c)), args),
                                  ("fwd_bwd", jax.jit(both), (do,) + args)):
                jax.block_until_ready(fn(*ins))
                times = []
                for _ in range(5):
                    t = time.perf_counter()
                    jax.block_until_ready(fn(*ins))
                    times.append(time.perf_counter() - t)
                out[f"{name}_ms_c{c}"] = 1e3 * sorted(times)[2]
                print(c, name, out[f"{name}_ms_c{c}"], flush=True)
    print(json.dumps(out, indent=1))
    path = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "kda_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
