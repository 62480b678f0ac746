"""The faults ``families/olmo_hybrid.py``'s limits must catch, and the
readings the limits are set from, on the chip (run by hand; PERF.md
section 4, PR 56): the cell's own comparisons at the configuration's
widths, depth and share on sound weights over many seeds, and under each
fault, a few seeds each:

    python benchmark/tests/olmo_hybrid_faults.py --sound 12 --faulty 2 --seed 9000
    python benchmark/tests/olmo_hybrid_faults.py --scan --sound 12 --faulty 2

Each variant is one compiled program run on every seed. :func:`fault` is
also what ``tests/test_olmo_hybrid.py`` runs at the small size on the
CPU. The faults (ISSUE 56's list): ``β`` not doubled; the decay dropped
(``g = 0``); the decay applied a channel from a wrong broadcast (the
heads' decays laid along the key channels, through the channel-wise
kernels); ``q`` without its ``K^{-1/2}``; ``k`` not normalised; the gate
a sigmoid; the delta rule's state rounded to bf16 at chunk boundaries;
the whole mixer's operands in a lower precision than the file states
(e4m3 where it says bf16: ``q, k, v`` into the scan, the gate and the
scan's output into the head norm); the QK-norm a head instead of the
whole width; rotated full attention (theta 10000). A fault inside the
scan is also put through the scan's own comparison
(``families/olmo_hybrid.py::gdn_comparison``), and the reading is sound
only if both are; ``--scan`` reads that comparison alone. Prints one
JSON line a reading and writes them all to
``chiprun_out/olmo_hybrid_faults.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Callable, Optional, Tuple

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark.tests.lfm2_faults import _to_bf16, patched  # noqa: E402,F401

FAULTS = ("beta_not_doubled", "decay_dropped", "decay_wrong_broadcast",
          "q_scale_dropped", "k_l2_dropped", "gate_sigmoid", "state_bf16",
          "operands_fp8", "qk_norm_per_head", "rotated_attention")
# those inside the scan: also put through the scan's own comparison
IN_THE_SCAN = ("decay_dropped", "decay_wrong_broadcast", "state_bf16",
               "operands_fp8")
CHUNK = 128     # where ``state_bf16`` rounds: ``ops/kda.py``'s chunk


def _to_fp8(a):
    """Rounded to e4m3's 3 mantissa bits (``reduce_precision``: inside
    one jitted computation the TPU compiler keeps a pair of casts in the
    wider type); the exponent's range is not narrowed — the operands are
    of order one."""
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)


def jnp_scan(q, k, v, g, beta, state_bf16_every: int = 0):
    """The scalar-decay delta rule position by position in jnp, in the
    kernels' place (``families/olmo_hybrid.py::recurrence_in_blocks``:
    f32 inside, the result in ``v``'s dtype); ``state_bf16_every``
    rounds the state to bf16 every so many positions (the chunk
    boundaries)."""
    from benchmark.families.olmo_hybrid import recurrence_in_blocks

    return recurrence_in_blocks(
        q, k, v, g, beta, state_bf16_every or CHUNK,
        _to_bf16 if state_bf16_every else None).astype(v.dtype)


def channelwise(q, k, v, g4, beta):
    """``ops/kda.py::kda_scan`` (the channel-wise kernels) at any key and
    value width: each head padded with zeros to whole lane tiles, which
    those kernels need (``g`` with 0: a channel that holds nothing does
    not decay). ``g4 [B, S, H, K]``. What ``gdn_micro.py`` times the
    scalar path against, fed a broadcast ``g``."""
    import jax.numpy as jnp

    from torchft_tpu.ops.kda import _up, kda_scan

    K, V = q.shape[3], v.shape[3]

    def wide(z, width):
        return jnp.pad(z, ((0, 0),) * 3 + ((0, width - z.shape[3]),))

    return kda_scan(wide(q, _up(K)), wide(k, _up(K)), wide(v, _up(V)),
                    wide(g4, _up(K)), beta)[..., :V]


def fault(name: str, cfg: Any, params: Any
          ) -> Tuple[tuple, Optional[Any], Optional[Any], Optional[Callable]]:
    """``(patches, weights, system_cfg, scan_fn)`` of one fault: what to
    put in the place of the model's pieces while the system is traced
    (``(module, attribute, replacement)`` each), the faulty weights,
    another system config, and — for a fault inside the scan — what
    stands in ``gdn_scan``'s place in the scan's own comparison; ``None``
    where the fault leaves that alone."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import common, olmo_hybrid
    from torchft_tpu.ops.kda import gdn_scan

    del params
    real_l2 = olmo_hybrid._l2_normed
    patches: tuple = ()
    weights = system_cfg = scan_fn = None

    def scan_patch(fn):
        return ((olmo_hybrid, "_gdn_scan", fn),), fn

    if name == "beta_not_doubled":
        system_cfg = dataclasses.replace(cfg, allow_neg_eigval=False)
    elif name == "decay_dropped":
        patches, scan_fn = scan_patch(lambda q, k, v, g, beta: gdn_scan(
            q, k, v, jnp.zeros_like(g), beta))
    elif name == "decay_wrong_broadcast":
        def wrong(q, k, v, g, beta):
            # [B, S, H] tiled along the channels and read back as [H, K]:
            # channel c of head h takes head (h·K + c) mod H's decay
            B, S, H, K = q.shape
            return channelwise(
                q, k, v, jnp.tile(g, (1, 1, K)).reshape(B, S, H, K), beta)
        patches, scan_fn = scan_patch(wrong)
    elif name == "q_scale_dropped":
        patches = ((olmo_hybrid, "_gdn_scan", lambda q, k, v, g, beta:
                    gdn_scan((q.astype(jnp.float32) * q.shape[3] ** 0.5
                              ).astype(q.dtype), k, v, g, beta)),)
    elif name == "k_l2_dropped":
        # the mixer norms q, then k: calls 0, 2, 4, ... are q's
        calls = [0]

        def normed(x):
            calls[0] += 1
            if calls[0] % 2 == 0:
                return x.astype(jnp.float32)
            return real_l2(x)
        patches = ((olmo_hybrid, "_l2_normed", normed),)
    elif name == "gate_sigmoid":
        def sigmoid_gate(o, scale, gate, eps):
            f32 = jnp.float32
            return (common.rms_norm(o.astype(f32), scale, eps)
                    * jax.nn.sigmoid(gate.astype(f32))).astype(o.dtype)
        patches = ((olmo_hybrid, "_gated_head_norm", sigmoid_gate),)
    elif name == "state_bf16":
        patches, scan_fn = scan_patch(
            lambda *a: jnp_scan(*a, state_bf16_every=CHUNK))
    elif name == "operands_fp8":
        real_ogate = olmo_hybrid.kda_ogate

        def low(q, k, v, g, beta):
            return gdn_scan(_to_fp8(q), _to_fp8(k), _to_fp8(v), g, beta)
        patches, scan_fn = scan_patch(low)
        # rounded before the kernel (Mosaic lowers no reduce_precision)
        patches += ((olmo_hybrid, "kda_ogate",
                     lambda o, gate, *rest: real_ogate(
                         _to_fp8(o), _to_fp8(gate), *rest)),)
    elif name == "qk_norm_per_head":
        def per_head(z, scale, eps, n_heads):
            B, S, d = z.shape
            shape = (n_heads, d // n_heads)
            return common.rms_norm(z.reshape(B, S, *shape),
                                   scale.reshape(shape), eps)
        patches = ((olmo_hybrid, "_qk_normed", per_head),)
    elif name == "rotated_attention":
        system_cfg = dataclasses.replace(cfg, rope_theta=10000.0)
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, weights, system_cfg, scan_fn


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=12)
    ap.add_argument("--faulty", type=int, default=2)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--scan", action="store_true",
                    help="the scan's own comparison alone")
    ap.add_argument("--seq", type=int, default=0,
                    help="another sequence length than the job's")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "olmo-hybrid-7b-vp8.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import olmo_hybrid as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import olmo_hybrid
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    seq_len = args.seq or model.seq_len
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: olmo_hybrid.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + (IN_THE_SCAN if args.scan else FAULTS):
        if args.only and name not in args.only:
            continue
        n = args.sound if name == "sound" else args.faulty
        fn = scan = None
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            reading = {"variant": name, "seed": seed, "ok": True}
            patches, _, system_cfg, scan_fn = (
                ((), None, None, None) if name == "sound"
                else fault(name, cfg, None))
            if not args.scan:
                params = init(np.uint32(seed & 0xFFFFFFFF))
                tokens, targets = BatchSource(
                    seed, 0x6f68, 0, family.REFERENCE_SEQUENCES,
                    seq_len, model.vocab_draw).device_batch(0, device)
                if fn is None:  # one program a variant: traced on its
                    fn = jax.jit(family.comparison(     # first seed, patched
                        cfg, system_cfg=system_cfg))
                with patched(patches):
                    seen = family.per_token_errors(
                        cfg, params, params, tokens, targets, seed, fn=fn)
                reading.update(family.judge(seen))
                del params
            if name == "sound" or scan_fn is not None:
                if scan is None:
                    scan = jax.jit(family.gdn_comparison(scan_fn))
                alone = jax.device_get(scan(
                    *family.gdn_inputs(cfg, seed, seq_len)))
                judged = family.judge_gdn(alone)
                reading.update(
                    judged, ok=reading["ok"] and judged["ok"],
                    gdn_rel_l2={k: float(v) for k, v in alone.items()})
            readings.append(reading)
            print(json.dumps(reading), flush=True)
    name = ("olmo_hybrid_faults_scan.json" if args.scan
            else "olmo_hybrid_faults.json")
    with open(os.path.join(out, name), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
