"""The faults ``families/nemotron_h.py``'s limits must catch, and the
readings the limits are set from, on the chip (run by hand; PERF.md
section 4, PR 33): the cell's own comparison at the configuration's
widths, depth and share on sound weights over many seeds, and under each
fault, a few seeds each:

    python benchmark/tests/nemotron_faults.py --sound 20 --faulty 3 --seed 9000

Each variant is one compiled program run on every seed. :func:`fault`
is also what ``tests/test_nemotron_h_family.py`` runs at the small size
on the CPU. The faults: the scan's state, or its decays, rounded to bf16 every
position; the state not carried across a chunk boundary; ``D·x`` left
out; the convolution's bias, or its silu, left out; its taps in reverse
order; the gate applied after the grouped norm; one norm over all
channels instead of one a group; head ``h`` reading group ``h % G``;
relu in place of relu²; the 2.5 left out; the weights not renormalised;
the balance bias ignored in the selection; one held expert dropped; fp8
(e4m3, rounded on the host) in the held routed experts alone; a rotary
embedding applied; the key/value heads paired with the wrong query
heads. Prints one JSON line a reading and writes them all to
``chiprun_out/nemotron_faults.json``. ``--scan`` reads instead the
scan's own comparison (``families/nemotron_h.py::scan_comparison``:
``ssd_scan`` and its six gradients against the recurrence) over the
sound seeds and under each stand-in of ``SCAN_VARIANTS``
(``chiprun_out/nemotron_scan.json``; ~1.5 min).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
from typing import Any, Callable, Optional, Tuple

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

FAULTS = ("scan_state_bf16", "scan_decays_bf16", "state_not_carried",
          "d_left_out", "conv_bias_left_out", "conv_silu_left_out",
          "taps_reversed", "gate_after_norm", "one_norm_over_all",
          "wrong_group", "relu_for_relu2", "no_scale", "no_renormalise",
          "bias_ignored", "expert_dropped", "fp8_experts", "rotary_applied",
          "kv_heads_swapped")
# the three that only round: a lower precision in one place
ROUNDING = ("scan_state_bf16", "scan_decays_bf16", "fp8_experts")


def position_by_position(x, dt, A, B, C, D, round_state: int = 0,
                         round_decay: bool = False):
    """The scan as a loop over positions, in the place of the kernels:
    what is rounded to bf16 on the way is the fault (nothing: the scan
    itself). ``round_state`` n rounds the carried state after every n-th
    position (1: every position; 256: what a chunked scan with a bf16
    carry would do). ``reduce_precision``, not a pair of casts: inside one
    jitted computation the TPU compiler keeps an f32 -> bf16 -> f32 pair
    in f32 (my chip run, PR 33: readings equal to the loop that rounds
    nothing). Stretches of positions behind ``jax.checkpoint``, as the
    reference's recurrence has them, so that its vjp fits too."""
    import math

    import jax
    import jax.numpy as jnp

    rep = x.shape[2] // B.shape[2]
    Bh, Ch = jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2)

    def to_bf16(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def step(state, at):
        x_t, d_t, b_t, c_t = (a.astype(jnp.float32) for a in at[:4])
        decay = jnp.exp(d_t * A)
        if round_decay:
            decay = to_bf16(decay)
        state = (decay[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        if round_state:
            state = jnp.where((at[4] + 1) % round_state == 0,
                              to_bf16(state), state)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    @jax.checkpoint
    def stretch(state, ats):
        return jax.lax.scan(step, state, ats)

    b, s, h, p = x.shape
    n = math.gcd(s, 64)
    _, y = jax.lax.scan(
        stretch, jnp.zeros((b, h, p, B.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0).reshape(s // n, n, *a.shape[:1],
                                           *a.shape[2:])
              for a in (x, dt, Bh, Ch)) + (jnp.arange(s).reshape(s // n, n),))
    y = jnp.moveaxis(y.reshape(s, b, h, p), 0, 1)
    return (y + D[:, None] * x).astype(x.dtype)


def _each_chunk_from_zero(x, dt, A, B, C, D):
    """Every quarter of the sequence (at most 256 positions) scanned from
    a zero state: the carry between chunks dropped."""
    from torchft_tpu.ops.ssd import ssd_scan

    b, s = x.shape[:2]
    chunk = min(256, s // 4)

    def cut(a):
        return a.reshape(b * s // chunk, chunk, *a.shape[2:])

    return ssd_scan(cut(x), cut(dt), A, cut(B), cut(C), D).reshape(x.shape)


def _wrong_group(x, dt, A, B, C, D):
    """Head ``h`` reads group ``h % G`` instead of ``h // (H/G)``."""
    import jax.numpy as jnp

    from torchft_tpu.ops.ssd import ssd_scan

    h, g = x.shape[2], B.shape[2]
    order = jnp.array([i for r in range(g) for i in range(r, h, g)])
    y = ssd_scan(x[:, :, order], dt[:, :, order], A[order], B, C, D[order])
    return y[:, :, jnp.argsort(order)]


def _gate_after_norm(y, z, scale, groups, eps, dt):
    import jax
    import jax.numpy as jnp

    B, S, I = z.shape
    g = y.reshape(B, S, groups, I // groups).astype(jnp.float32)
    normed = (g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                                + eps)).reshape(B, S, I) * scale
    return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(dt)


def _conv_without_silu(m, xbc, dt):
    import jax.numpy as jnp

    taps = m["conv"]["kernel"]
    K = taps.shape[0]
    padded = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    return (m["conv"]["bias"] + sum(
        taps[j] * padded[:, j:j + xbc.shape[1]] for j in range(K))).astype(dt)


def _relu_mlp(h, m, dt):
    import jax

    return jax.nn.relu(h @ m["up_proj"]["kernel"].astype(dt)) @ m[
        "down_proj"]["kernel"].astype(dt)


def _relu_experts(x, up, down, sizes):
    import jax

    from torchft_tpu.ops import moe

    dt = x.dtype
    return moe.grouped_matmul(jax.nn.relu(moe.grouped_matmul(
        x, up.astype(dt), sizes)), down.astype(dt), sizes)


def with_leaf(params, layer: str, path: Tuple[str, ...], fn: Callable):
    """``params`` with the leaf at ``layer`` / ``path`` replaced by
    ``fn(leaf)``; every other leaf is the same array."""
    import jax

    out = jax.tree_util.tree_map(lambda a: a, params)
    node = out[layer]
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = fn(node[path[-1]])
    return out


def _round_to_fp8(x):
    # on the host: inside one jitted computation the TPU compiler keeps
    # an f32 -> e4m3 -> f32 pair in f32 and nothing is rounded (PR 31)
    import jax
    import ml_dtypes
    import numpy as np

    rounded = np.asarray(x).astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    return jax.device_put(rounded, getattr(x, "sharding", None))


def fault(name: str, cfg: Any, params: Any
          ) -> Tuple[tuple, Optional[Any], Optional[Any], Optional[Callable]]:
    """``(patches, weights, system_cfg, attn_fn)`` of one fault: what to
    put in the place of the model's pieces while the system is traced
    (``(module, attribute, replacement)`` each), the faulty weights,
    another system config, another attention; ``None`` where the fault
    leaves that alone. Weight faults strike the first layer of the kind."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import llama, nemotron_h
    from torchft_tpu.ops import moe
    from torchft_tpu.ops.attention import causal_attention

    first = {kind: f"layers_{cfg.pattern.index(letter)}"
             for letter, kind in nemotron_h.MIXERS.items()
             if letter in cfg.pattern}
    experts = [f"layers_{i}" for i, c in enumerate(cfg.pattern) if c == "E"]
    real_routing, real_norm = moe.top_k_routing, nemotron_h._gated_norm
    patches: tuple = ()
    weights = system_cfg = attn_fn = None
    if name == "scan_state_bf16":
        patches = ((nemotron_h, "ssd_scan", functools.partial(
            position_by_position, round_state=1)),)
    elif name == "scan_decays_bf16":
        patches = ((nemotron_h, "ssd_scan", functools.partial(
            position_by_position, round_decay=True)),)
    elif name == "state_not_carried":
        patches = ((nemotron_h, "ssd_scan", _each_chunk_from_zero),)
    elif name == "d_left_out":
        weights = with_leaf(params, first["mamba"], ("mamba", "D"),
                             jnp.zeros_like)
    elif name == "conv_bias_left_out":
        weights = with_leaf(params, first["mamba"],
                             ("mamba", "conv", "bias"), jnp.zeros_like)
    elif name == "conv_silu_left_out":
        patches = ((nemotron_h, "_conv_silu", _conv_without_silu),)
    elif name == "taps_reversed":
        weights = with_leaf(params, first["mamba"],
                             ("mamba", "conv", "kernel"), lambda w: w[::-1])
    elif name == "gate_after_norm":
        patches = ((nemotron_h, "_gated_norm", _gate_after_norm),)
    elif name == "one_norm_over_all":
        patches = ((nemotron_h, "_gated_norm",
                    lambda y, z, scale, groups, eps, dt: real_norm(
                        y, z, scale, 1, eps, dt)),)
    elif name == "wrong_group":
        patches = ((nemotron_h, "ssd_scan", _wrong_group),)
    elif name == "relu_for_relu2":
        patches = ((nemotron_h, "_relu2", _relu_mlp),
                   (moe, "relu2_experts", _relu_experts))
    elif name == "no_scale":
        system_cfg = dataclasses.replace(cfg, routed_scale=1.0)
    elif name == "no_renormalise":
        patches = ((moe, "top_k_routing", lambda s, k, **kw: real_routing(
            s, k, **dict(kw, renormalise=False))),)
    elif name == "bias_ignored":
        weights = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.zeros_like(x)
            if nemotron_h.is_balance_bias(p) else x, params)
    elif name == "expert_dropped":
        weights = with_leaf(params, first["moe"],
                             ("moe", "down_proj", "kernel"),
                             lambda w: w.at[1].set(0))
    elif name == "fp8_experts":
        weights = params
        for layer in experts:
            for leaf in ("up_proj", "down_proj"):
                weights = with_leaf(weights, layer, ("moe", leaf, "kernel"),
                                     _round_to_fp8)
    elif name == "rotary_applied":
        def attn_fn(q, k, v):
            return causal_attention(llama._rope(q, 10000.0),
                                    llama._rope(k, 10000.0), v)
    elif name == "kv_heads_swapped":
        d = cfg.head_dim

        def rolled(w):          # key head j takes key head j + 1's place
            return jnp.concatenate([w[:, d:], w[:, :d]], axis=1)
        weights = with_leaf(params, first["attn"],
                             ("attn", "k_proj", "kernel"), rolled)
    else:
        raise ValueError(f"no fault {name!r}")
    return patches, weights, system_cfg, attn_fn


# the scan alone (``families/nemotron_h.py::scan_comparison``): what stands
# in ``ssd_scan``'s place
SCAN_VARIANTS = {
    "sound": None,
    "scan_state_bf16": functools.partial(position_by_position, round_state=1),
    "scan_decays_bf16": functools.partial(position_by_position,
                                          round_decay=True),
    # not a listed fault: what a chunked scan with a bf16 carry would do
    "scan_carry_bf16": functools.partial(position_by_position,
                                         round_state=256),
    # the loop that rounds nothing: the two sides differ by the order of
    # f32 sums alone
    "loop_f32": position_by_position,
}


def scan_readings(model: Any, sound: int, faulty: int, seed: int) -> list:
    """The scan's own comparison over ``sound`` seeds, and ``faulty`` other
    seeds each stand-in of ``SCAN_VARIANTS``."""
    import jax

    from benchmark.families import nemotron_h as family

    readings = []
    for name, scan_fn in SCAN_VARIANTS.items():
        fn = jax.jit(family.scan_comparison(scan_fn))
        for i in range(sound if name == "sound" else faulty):
            s = seed + i + (0 if name == "sound" else 1000)
            s += 2**31 if i % 2 else 0
            seen = jax.device_get(fn(*family.scan_inputs(model.cfg, s)))
            reading = dict(
                family.judge_scan(seen), variant=name, seed=s,
                scan_rel_l2={k: float(v) for k, v in seen.items()})
            readings.append(reading)
            print(json.dumps(reading), flush=True)
    return readings


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scan", action="store_true",
                    help="the scan's own comparison only (no weights)")
    ap.add_argument("--sound", type=int, default=20)
    ap.add_argument("--faulty", type=int, default=3)
    ap.add_argument("--seed", type=int, default=9000)
    ap.add_argument("--only", nargs="*", help="variants to run (default all)")
    ap.add_argument("--config", default=os.path.join(
        _BENCH, "configs", "nemotron-3-nano-30b-a3b-ep16.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import nemotron_h as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import nemotron_h
    from torchft_tpu.utils.device import place_compile_cache

    place_compile_cache()
    with open(args.config) as f:
        model = family.build(json.load(f))
    out = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    if args.scan:
        readings = scan_readings(model, args.sound, args.faulty, args.seed)
        with open(os.path.join(out, "nemotron_scan.json"), "w") as f:
            json.dump(readings, f, indent=1)
        bad = [r for r in readings if r["ok"] != (
            r["variant"] in ("sound", "loop_f32", "scan_carry_bf16"))]
        print(f"{len(readings)} readings; {len(bad)} on the wrong side of "
              f"the limits: {[(r['variant'], r['seed']) for r in bad]}")
        return 0
    cfg, device = model.cfg, jax.devices()[0]
    init = jax.jit(lambda s: nemotron_h.init_params(cfg, jax.random.key(s)))

    readings = []
    for name in ("sound",) + FAULTS:
        if args.only and name not in args.only:
            continue
        n = args.sound if name == "sound" else args.faulty
        fn = None
        for i in range(n):
            # sound seeds and faulty seeds do not overlap; some pass 2^31
            seed = args.seed + i + (0 if name == "sound" else 1000)
            seed += 2**31 if i % 2 else 0
            params = family.seed_balance_bias(
                init(np.uint32(seed & 0xFFFFFFFF)), seed)
            tokens, targets = BatchSource(
                seed, 0x7265, 0, family.REFERENCE_SEQUENCES, model.seq_len,
                model.vocab_draw).device_batch(0, device)
            patches, weights, system_cfg, attn_fn = (
                ((), None, None, None) if name == "sound"
                else fault(name, cfg, params))
            system = params if weights is None else weights
            if fn is None:      # one program a variant: traced on its
                fn = jax.jit(family.comparison(     # first seed, patched
                    cfg, system_cfg=system_cfg, attn_fn=attn_fn))
            saved = [(mod, attr, getattr(mod, attr))
                     for mod, attr, _new in patches]
            for mod, attr, new in patches:
                setattr(mod, attr, new)
            try:
                seen = jax.device_get(fn(system, params, tokens, targets))
            finally:
                for mod, attr, old in saved:
                    setattr(mod, attr, old)
            reading = dict(family.judge(seen), variant=name, seed=seed)
            readings.append(reading)
            print(json.dumps(reading), flush=True)
            del params, system
    with open(os.path.join(out, "nemotron_faults.json"), "w") as f:
        json.dump(readings, f, indent=1)
    bad = [r for r in readings if r["ok"] != (r["variant"] == "sound")]
    print(f"{len(readings)} readings; {len(bad)} on the wrong side of the "
          f"limits: {[(r['variant'], r['seed']) for r in bad]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
