"""``ops/ssm_pointwise.py::gated_conv`` and the flash call of the
``lfm2-ep4-solo-steady`` cell on the chip at the cell's shapes (run by
hand; PERF.md section 6, PR 38):

- the gated short convolution at ``[4, 8192, 3 x 2048]`` bf16, 3 taps:
  agreement with the reference's shifted products leaf by leaf (the
  cell's own check, ``families/lfm2.py::conv_comparison``), and ms a
  call forward and forward + backward for the kernels, for the jnp
  formulation XLA fuses by itself (``lfm2_faults.jnp_conv``), and the
  bytes floor of
  ``benchmark/lfm2_flops.py`` over the HBM peak;
- ``flash_attention`` at ``[4, 8192, 32, 64]`` bf16, causal, forward and
  forward + backward: in the regime ``_choose_blocks`` picks (a head's K
  and V are exactly ``_RESIDENT_KV_BYTES``: resident) and, for the
  record, streamed at 512 x 1024.

    python benchmark/tests/sconv_micro.py

Prints one JSON object and writes it to ``chiprun_out/sconv_micro.json``.
A CPU run (the interpreter) gives agreement only, at a small shape.
"""

from __future__ import annotations

import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))


def _median_ms(fn, *args, repeats: int = 7) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return 1e3 * sorted(times)[repeats // 2]


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import flops, lfm2_flops
    from benchmark.families import lfm2 as family
    from benchmark.tests.lfm2_faults import jnp_conv as jnp_gated_conv
    from torchft_tpu.ops import flash
    from torchft_tpu.ops.ssm_pointwise import gated_conv
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(os.path.join(_BENCH, "configs", "lfm2-8b-a1b-ep4.json")) as f:
        cfg = family.build(json.load(f)).cfg
    on_chip = jax.default_backend() == "tpu"
    rows, seq = (4, 8192) if on_chip else (1, 256)
    out = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq}

    one = family.conv_inputs(cfg, 987654321, min(seq, family.CONV_SEQ))
    for name, fn in (("kernels", None), ("jnp", jnp_gated_conv)):
        seen = jax.device_get(jax.jit(family.conv_comparison(fn))(*one))
        out[f"conv_rel_l2_{name}"] = {k: float(v) for k, v in seen.items()}
    if not on_chip:
        print(json.dumps(out, indent=1))
        return 0

    drawn = [family.conv_inputs(cfg, 1234567891 + i, seq) for i in range(rows)]
    bcx = jnp.concatenate([a[0] for a, _ in drawn])
    dy = jnp.concatenate([g for _, g in drawn])
    taps = drawn[0][0][1]
    peak = flops.peaks(out["device"])["hbm_bytes_per_s"]
    for kernel in lfm2_flops.KERNELS:
        out[f"{kernel}_floor_ms"] = 1e3 * rows * seq * (
            lfm2_flops.sconv_bytes_per_token(kernel, channels=cfg.d_model)
            / peak)
    for name, fn in (("kernels", gated_conv), ("jnp", jnp_gated_conv)):
        def both(a, t, fn=fn):
            return jax.vjp(fn, a, t)[1](dy)
        out[f"conv_fwd_ms_{name}"] = _median_ms(jax.jit(fn), bcx, taps)
        out[f"conv_fwd_bwd_ms_{name}"] = _median_ms(jax.jit(both), bcx, taps)
    del bcx, dy

    H, D = cfg.n_heads, cfg.head_dim
    k = jax.random.split(jax.random.key(7), 4)
    q, kk, v, g = (jax.random.normal(k[i], (rows, seq, H, D), jnp.float32
                                     ).astype(jnp.bfloat16) for i in range(4))
    out["flash_rule_blocks"] = list(flash._choose_blocks(seq, D, 2))
    out["flash_rule_resident"] = bool(flash._resident(seq, 2 * D, 2))
    regimes = {"rule": {},
               "streamed_512x1024": dict(block_q=512, block_k=1024,
                                         _resident_kv_bytes=0)}
    results = {}
    for name, kw in regimes.items():
        def fwd(q, kk, v, kw=kw):
            return flash.flash_attention(q, kk, v, causal=True, **kw)

        def both(q, kk, v, fwd=fwd):
            return jax.vjp(fwd, q, kk, v)[1](g)
        out[f"flash_fwd_ms_{name}"] = _median_ms(jax.jit(fwd), q, kk, v)
        out[f"flash_fwd_bwd_ms_{name}"] = _median_ms(jax.jit(both), q, kk, v)
        results[name] = jax.jit(fwd)(q, kk, v).astype(jnp.float32)
    out["flash_streamed_vs_rule_max_abs"] = float(jnp.max(jnp.abs(
        results["rule"] - results["streamed_512x1024"])))
    print(json.dumps(out, indent=1))
    path = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "sconv_micro.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
