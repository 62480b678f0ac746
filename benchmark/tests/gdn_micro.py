"""``ops/kda.py::gdn_scan`` on the chip at the cell's shape ([1, 8192]
positions, 30 heads of 96 key and 192 value channels; run by hand;
PERF.md section 6, PR 56), after ``kda_micro.py``: the scalar-decay
kernels (``gdn_fwd`` / ``gdn_bwd``), at each number of heads a grid step
given, against the baseline they have to beat — ``kda_scan``, the
channel-wise kernels, fed ``g`` broadcast over the key channels
(``olmo_hybrid_faults.channelwise``: each head padded to whole lane
tiles there, as those kernels need) — as ms a
call forward and forward + backward with the XLA layouts around the
kernels, the shares of the rooflines those are
(``benchmark/olmo_hybrid_flops.py``: the unpadded bytes over the HBM
peak; the backward's time is forward + backward less forward), and the
two paths' agreement with the recurrence, leaf by leaf, the worst
head's, as the cell's own check takes them:

    python benchmark/tests/gdn_micro.py 4

(of 96 / 192 channels only four heads fill whole lane tiles).

Prints one JSON object and writes it to ``chiprun_out/gdn_micro.json``.
A CPU run (the interpreter) gives agreement only, at a small shape. The
kernels' matmuls take three bf16 passes (``ops/kda.py::_gdot``); what one
and six read and cost was measured with this script while the op still had
those branches, and stands in PERF.md section 6, PR 56.
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

    from benchmark import flops, olmo_hybrid_flops
    from benchmark.families import olmo_hybrid as family
    from benchmark.tests.olmo_hybrid_faults import channelwise
    from torchft_tpu.ops import kda
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    rungs = [int(c) for c in sys.argv[1:]] or [kda._GDN_LADDER[0]]
    with open(os.path.join(_BENCH, "configs", "olmo-hybrid-7b-vp8.json")) as f:
        model = family.build(json.load(f))
    cfg = model.cfg
    on_chip = jax.default_backend() == "tpu"
    seq, check_seq = (model.seq_len, 2048) if on_chip else (256, 256)
    out = {"device": jax.devices()[0].device_kind, "rows": 1, "seq": seq}
    dims = dict(n_heads=cfg.n_heads, key_dim=cfg.key_dim,
                value_dim=cfg.value_dim)

    def broadcast(q, k, v, g, beta):
        return channelwise(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                           beta)

    # (what runs, the ladder)
    paths = {"kda_broadcast_g": (broadcast, None)}
    paths.update({f"gdn_{n}_heads": (kda.gdn_scan, (n, 1)) for n in rungs})
    one = family.gdn_inputs(cfg, 987654321, check_seq)
    args, do = family.gdn_inputs(cfg, 1234567891, seq)
    ladder = kda._GDN_LADDER
    for label, (fn, rung) in paths.items():
        if rung is not None:
            kda._GDN_LADDER = rung
            jax.clear_caches()
        try:
            if rung is not None:
                # what the op really takes: a rung that does not fit falls
                out[f"heads_a_step_{label}"] = kda._gdn_heads_a_step(
                    cfg.n_heads, kda._CHUNK, cfg.key_dim, cfg.value_dim,
                    not on_chip)
            seen = jax.device_get(jax.jit(family.gdn_comparison(fn))(*one))
            out[f"rel_l2_{label}"] = {k: float(v) for k, v in seen.items()}
            print(label, out[f"rel_l2_{label}"], flush=True)
            if not on_chip:
                continue

            def both(do, *a, fn=fn):    # an argument: a closed-over
                return jax.vjp(fn, *a)[1](do)   # array is a constant
            ms = {}
            for name, f, ins in (("fwd", jax.jit(fn), args),
                                 ("fwd_bwd", jax.jit(both), (do,) + args)):
                jax.block_until_ready(f(*ins))
                times = []
                for _ in range(7):
                    t = time.perf_counter()
                    jax.block_until_ready(f(*ins))
                    times.append(time.perf_counter() - t)
                ms[name] = 1e3 * sorted(times)[3]
            out[f"ms_{label}"] = ms
            peaks = flops.peaks(jax.devices()[0].device_kind)
            for kernel, spent in (("gdn_fwd", ms["fwd"]),
                                  ("gdn_bwd", ms["fwd_bwd"] - ms["fwd"])):
                least = seq * max(
                    olmo_hybrid_flops.gdn_flops_per_token(kernel, **dims)
                    / peaks["bf16_flops"],
                    olmo_hybrid_flops.gdn_bytes_per_token(kernel, **dims)
                    / peaks["hbm_bytes_per_s"])
                out[f"{kernel}_roofline_{label}"] = 100.0 * least / (
                    spent * 1e-3)
            print(label, ms, flush=True)
        finally:
            kda._GDN_LADDER = ladder
            jax.clear_caches()
    print(json.dumps(out, indent=1))
    path = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "gdn_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
