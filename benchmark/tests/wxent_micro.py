"""Ouro's four heads alone at the cell's shape (run by hand on the chip;
PERF.md section 6, PR 73): ``jax.value_and_grad`` of ``Σ_t Σ_i p_t,i ℓ_t,i
/ N`` over the ``T`` stacked streams, the head and the weights, three ways:

- ``one_call``: ``ops/xent.py::weighted_cross_entropy`` once over the
  ``T·N`` rows (what ``models/ouro.py`` runs);
- ``a_call_a_pass``: the same sweep ``T`` times, ``N`` rows each (``T`` f32
  ``dW`` residuals alive at once);
- ``recompute``: ``chunked_lse_and_target`` (generic cotangents, the
  vocabulary in chunks, both scans) over the ``T·N`` rows.

    python benchmark/tests/wxent_micro.py [--chunks 8] [--vocab-chunks 12]

Prints ms a call, the share of ``6·T·N·d·V`` at the bf16 peak, the
compiler's temporaries (GiB) and the three ways' agreement, one JSON line
each. A CPU run (the shape cut to a sixty-fourth) gives agreement only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chunks", type=int, default=8)
    ap.add_argument("--vocab-chunks", type=int, default=12)
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import flops, ouro_flops
    from torchft_tpu.ops.xent import (chunked_lse_and_target,
                                      weighted_cross_entropy)
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(os.path.join(_BENCH, "configs", "ouro-2.6b-l8.json")) as f:
        dims = ouro_flops.config_dims(json.load(f))
    on_chip = jax.default_backend() == "tpu"
    T, n, d, v = dims["ut_steps"], dims["seq_len"], dims["d_model"], dims[
        "vocab"]
    if not on_chip:
        n, d, v = n // 64, d // 64, v // 64
    rng = np.random.default_rng(73)
    streams = jnp.asarray(rng.standard_normal((T, n, d)), jnp.bfloat16)
    head = jnp.asarray(0.02 * rng.standard_normal((d, v)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    p = jax.nn.softmax(jnp.asarray(rng.standard_normal((T, n)), jnp.float32),
                       axis=0)

    def rows(x):
        return x.astype(jnp.float32).reshape(-1, d), jnp.tile(targets, T)

    def one_call(x, w, p):
        flat, t = rows(x)
        return weighted_cross_entropy(flat, w, t, p.reshape(-1) / n,
                                      args.chunks)[0]

    def a_call_a_pass(x, w, p):
        return sum(weighted_cross_entropy(
            x[i].astype(jnp.float32), w, targets, p[i] / n,
            max(1, args.chunks // T))[0] for i in range(T))

    def recompute(x, w, p):
        flat, t = rows(x)
        lse, tl = chunked_lse_and_target(flat, w, t, jnp.ones(t.shape, bool),
                                         args.vocab_chunks)
        return jnp.sum(p.reshape(-1) / n * (lse - tl))

    work = T * n * ouro_flops.head_flops_per_token(
        d_model=d, vocab=v, ut_steps=1)
    seen = {}
    for name, fn in (("one_call", one_call), ("a_call_a_pass", a_call_a_pass),
                     ("recompute", recompute)):
        step = jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2)))
        compiled = step.lower(streams, head, p).compile()
        out = {"way": name, "T": T, "N": n, "d": d, "V": v,
               "temp_gib": compiled.memory_analysis().temp_size_in_bytes
               / 2**30}
        seen[name] = jax.block_until_ready(step(streams, head, p))
        if on_chip:
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    r = step(streams, head, p)
                jax.block_until_ready(r)
                times.append((time.perf_counter() - t0) / args.calls)
            ms = 1e3 * sorted(times)[1]
            peak = flops.peaks(jax.devices()[0].device_kind)["bf16_flops"]
            out.update(ms=ms, share_of_peak=work / (ms * 1e-3) / peak)
        if name != "one_call":
            (value, grads), (want, want_grads) = seen[name], seen["one_call"]
            out["loss_diff"] = abs(float(value) - float(want))
            out["grad_rel_l2"] = [float(
                jnp.linalg.norm((g - w_).astype(jnp.float32))
                / jnp.linalg.norm(w_.astype(jnp.float32)))
                for g, w_ in zip(grads, want_grads)]
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
