"""Faults the Keye cell's comparisons must catch, on the chip, by hand
(PERF.md section 6, PR 66), and the stand-ins ``tests/test_keye_family.py``
runs at the small size:

    python benchmark/tests/keye_faults.py [--sound N] [--faulty N]

Each fault is ``(what the system is handed, which comparison shows it)``:
``whole`` is ``families/keye.py::per_token_errors`` on one seeded
sequence at the configuration's widths, depth and share (on streams that
DIFFER: an image span's, so that the streams' order shows), ``kernels``
is ``kernel_comparison`` at the cell's rows and length. A fault must fail
at least one limit of its comparison on every seed; the sound system
must pass all on every seed.

- ``topk_2047``: one key fewer a query (the set sizes are counted).
- ``no_relu``: ``I = Σ_j w_j · (qI_j · kI)`` (``dsa._relu`` the identity).
- ``w_unscaled``: ``w = n̄·W_w`` without ``(HI · DI)^(-1/2)``.
- ``pbar_one_head``: the indexer's target from head 0 alone.
- ``window_4096``: the last 4 096 keys in place of ``S_t``.
- ``bf16_scores``: the index scores rounded to bf16 before the threshold.
- ``streams_swapped``: the height and width streams handed the other way.

NOT HERE, because no forward pass shows them (``tests/test_keye.py``
holds each on the CPU, on the gradient): the indexer's input not
detached (the cross entropy's gradient then reaches the indexer's
leaves), ``L_I`` left out (the indexer's leaves then get none), the
backward on a re-chosen set (the compiled program holds ONE selection a
layer).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import types

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

CELL_CONFIG = "keye-vl-2.0-30b-a3b-ep8.json"


def image_positions(seq_len: int):
    """Three streams that differ: text, then from a quarter of the
    sequence on an ``h x w`` grid (temporal fixed, height the row, width
    the column), then text again from where the largest left off."""
    import jax.numpy as jnp

    t = jnp.arange(seq_len)
    start, side = seq_len // 4, max(2, int((seq_len // 2) ** 0.5))
    span = side * side
    inside = (t >= start) & (t < start + span)
    after = t >= start + span
    offset = start + side - span
    i = t - start
    return jnp.stack([
        jnp.where(inside, start, jnp.where(after, t + offset, t)),
        jnp.where(inside, start + i // side, jnp.where(after, t + offset, t)),
        jnp.where(inside, start + i % side, jnp.where(after, t + offset, t)),
    ])


def _ops(**replaced):
    """``ops/dsa.py``'s three calls with some replaced."""
    from torchft_tpu.ops import dsa

    return types.SimpleNamespace(**{
        name: replaced.get(name, getattr(dsa, name))
        for name in ("select", "attend", "index_kl")})


@contextlib.contextmanager
def _patched(module, name, value):
    import jax

    was = getattr(module, name)
    setattr(module, name, value)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(module, name, was)
        jax.clear_caches()


def window_select(window: int):
    """A ``select`` that keeps the last ``window`` keys: packed straight
    from the words' positions (``ops/dsa.py``'s layout)."""
    import jax
    import jax.numpy as jnp

    def select(qi, ki, w, topk, **kw):
        from benchmark.families.keye import causal_words

        seq_len = qi.shape[2]
        width = seq_len // 32
        t = jnp.arange(seq_len, dtype=jnp.int32)[:, None]
        c = jnp.arange(width, dtype=jnp.int32)[None, :]
        # keys s = b * width + c with s < t - window + 1 are too old
        old = jnp.maximum(t - window + 1 - c + width - 1, 0) // width
        low = (jnp.uint32(1) << jnp.minimum(old, 31).astype(jnp.uint32)) - 1
        low = jnp.where(old >= 32, jnp.uint32(0xFFFFFFFF), low)
        sel = causal_words(seq_len) & ~jax.lax.bitcast_convert_type(
            low, jnp.int32)
        sel = jnp.broadcast_to(sel, (qi.shape[0],) + sel.shape)
        return sel, jnp.zeros(qi.shape[:1] + (seq_len,), jnp.float32)

    return select


def faults(cfg):
    """``{name: (comparison, context manager factory, kwargs)}``."""
    import dataclasses

    import jax.numpy as jnp

    from torchft_tpu.models import keye
    from torchft_tpu.ops import dsa

    sound_inputs = keye.indexer_inputs

    def unscaled(config, ix, n, table):
        qi, ki, w = sound_inputs(config, ix, n, table)
        return qi, ki, w * (config.index_heads * config.index_head_dim) ** 0.5

    def one_head(q, k, lse, *rest, **kw):
        return dsa.index_kl(q[:, :1], k[:, :1], lse[:, :1], *rest, **kw)

    sortable, scores = dsa._sortable, dsa.index_scores

    def rounded(x):
        return sortable(x.astype(jnp.bfloat16).astype(jnp.float32))

    def rounded_scores(qi, ki, w):
        return scores(qi, ki, w).astype(jnp.bfloat16).astype(jnp.float32)

    @contextlib.contextmanager
    def bf16_scores():
        with _patched(dsa, "_sortable", rounded), \
                _patched(dsa, "index_scores", rounded_scores):
            yield

    none = contextlib.nullcontext
    return {
        "topk_2047": ("whole", none, dict(system_cfg=dataclasses.replace(
            cfg, index_topk=cfg.index_topk - 1))),
        "no_relu": ("whole", lambda: _patched(dsa, "_relu", lambda s: s), {}),
        "w_unscaled": ("whole", lambda: _patched(
            keye, "indexer_inputs", unscaled), {}),
        "pbar_one_head": ("whole", none,
                          dict(attn_fn=_ops(index_kl=one_head))),
        "window_4096": ("whole", none, dict(attn_fn=_ops(
            select=window_select(2 * cfg.index_topk)))),
        "bf16_scores": ("kernels", bf16_scores, {}),
        "streams_swapped": ("whole", none, dict(swap=True)),
    }


def run_whole(family, cfg, params, tokens, targets, positions, **kw):
    if kw.pop("swap", False):       # height and width the other way
        kw["system_positions"] = positions[(0, 2, 1), :]
    return family.judge(family.per_token_errors(
        cfg, params, params, tokens, targets, positions=positions, **kw))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", type=int, default=2)
    ap.add_argument("--faulty", type=int, default=1)
    ap.add_argument("--seed", type=int, default=2147490001)
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()

    import jax

    from benchmark.families import keye as family
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.utils.device import place_compile_cache, require_tpu

    device = require_tpu()[0]
    place_compile_cache()
    with open(os.path.join(_BENCH, "configs", CELL_CONFIG)) as f:
        model = family.build(json.load(f))
    cfg = model.cfg
    positions = image_positions(model.seq_len)
    out = {}

    def at(seed):
        params = family.seed_check_params(
            family.init_state(model, seed, device)["params"], seed)
        tokens, targets = BatchSource(
            seed, 0x6b79, 0, 1, model.seq_len, model.vocab_draw
        ).device_batch(0, device)
        return params, tokens, targets

    def kernels(seed):
        with jax.default_device(device):
            return family.judge_kernels(jax.device_get(jax.jit(
                family.kernel_comparison(cfg))(family.kernel_inputs(
                    cfg, seed, model.rows, model.seq_len))))

    for i in range(args.sound):
        seed = args.seed + 7919 * i
        params, tokens, targets = at(seed)
        out[f"sound/{seed}"] = {
            "whole": run_whole(family, cfg, params, tokens, targets,
                               positions),
            "kernels": kernels(seed)}
        print(json.dumps({f"sound/{seed}": out[f"sound/{seed}"]}), flush=True)
        del params
    for name, (which, patch, kw) in faults(cfg).items():
        if args.only is not None and name not in args.only:
            continue
        for i in range(args.faulty):
            seed = args.seed + 104729 + 7919 * i
            with patch():
                if which == "whole":
                    params, tokens, targets = at(seed)
                    seen = run_whole(family, cfg, params, tokens, targets,
                                     positions, **kw)
                    del params
                else:
                    seen = kernels(seed)
            out[f"{name}/{seed}"] = seen
            print(json.dumps({f"{name}/{seed}": seen}), flush=True)
    path = os.path.join(os.path.dirname(_BENCH), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "keye_faults.json"), "w") as f:
        json.dump(out, f, indent=1)
    bad = [k for k, v in out.items()
           if (k.startswith("sound/")
               and not (v["whole"]["ok"] and v["kernels"]["ok"]))
           or (not k.startswith("sound/") and v["ok"])]
    print(json.dumps({"not_as_expected": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
