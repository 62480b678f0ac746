"""Adapter for the Granite 4.0-H family
(``torchft_tpu/models/granite_hybrid.py``): the six functions of
``families/olmo_hybrid.py`` — ``build``, ``init_state``,
``make_train_step``, ``make_grad_step``, ``flops_per_token``,
``check_reference`` — and nothing of any one configuration. The step
programs are the one step maker's (``models/transformer.py``) with this
family's loss; the optimizer is the configuration's AdamW behind a linear
warm-up (an optax schedule: its count is optimizer state); a dense model
has no balance bias. ``check_reference`` is ``judge(per_token_errors(...))``
and ``judge_scan(scan_comparison(...))``; each pair is apart so that a test
or ``tests/granite_faults.py`` can run a faulty system against the sound
reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, softplus, decays and scan state) against the f32
# reference — the recurrence position by position — on the same share (rows
# 0-12543 of the tied table), the same weights and REFERENCE_SEQUENCES
# seeded sequences of the timed length, ONE AT A TIME (each beside 8.6 GiB
# of training state), TOKEN BY TOKEN on the final-norm hidden state: per
# token ||h - h_ref||_2 / ||h_ref||_2, then its root mean square and its
# largest over all tokens; and |loss - loss_ref| (the only number the
# logits' scaling reaches).
#
# At initialisation every norm weight and ``D`` is one, ``A_log`` is
# log(1 .. 64) and nothing tells a weight that was left out from one that
# is there; and the attention layer attends to nothing: at 0.02 a logit
# ``q·k / 64`` has a standard deviation of 0.10, the softmax over
# thousands of keys is flat, its output a mean of thousands of values —
# and a rotation, or the scale 1/8, changes a flat softmax by nothing (my
# chip run, PR 68: a rotated system read rms 0.01812 / largest 0.0223
# where the sound one reads 0.01793 - 0.01805 / 0.0215 - 0.0224). The
# check therefore seeds, on both sides (:func:`seed_check_weights`): every
# norm weight and ``D`` 1 + CHECK_NORM_STD x normal, ``A_log`` and
# ``dt_bias`` + CHECK_NORM_STD x normal, and ``W_q`` and ``W_k`` times
# CHECK_QK_GAIN (the logits' standard deviation 2.6: a query's weight on a
# few keys, as a trained layer's); the convolution's bias is U(-1/2, 1/2)
# as initialised, which hides nothing.
#
# READINGS on the v5e at the cell's widths, depth and share, two sequences
# of 8192 (my chip runs, PR 68; ``benchmark/tests/granite_faults.py``:
# 8 sound seeds, half of them beyond 2^31, and the cell's own traced run;
# 2 other seeds each fault), as rms / largest of the per-token error:
#   sound            rms 0.01813 - 0.01821, largest 0.0217 - 0.0231,
#                    |loss diff| 5.7e-6 - 2.5e-5
#   (before the check sharpened W_q and W_k, 5 readings: rms 0.01793 -
#   0.01805, largest 0.0215 - 0.0224; a rotation then read 0.01812 / 0.0223
#   and the softmax at 1/8 0.0202 / 0.081: inside, and just outside)
#   the scan's state rounded to bf16 every position (a loop in the kernels'
#   place)           rms 0.0372 / largest 0.109 on one seed, 0.0184 / 0.0220
#                    on the other: NOT HELD HERE on every seed — the scan's
#                    own comparison below holds it on every one
#   fp8 (e4m3) in every MLP (rounded on the host)   0.0865 - 0.0867 / 0.106
#   a rotary embedding applied   0.0934 - 0.0936 / 0.188 - 0.190
#   the softmax at 64^-1/2 = 1/8 for 1/64    0.216 / 0.265 - 0.275
#   a head block under another block's decays and D   0.478 - 0.485 / 0.87 - 0.95
#   the convolution's bias dropped 0.804 - 0.808; the gate after the norm
#   0.821 - 0.826; residual_multiplier left out of ONE branch (the first
#   layer's MLP) 1.034 - 1.035; embedding_multiplier left out 1.094 - 1.096
#   logits_scaling left out   rms and largest as sound (the hidden state is
#                    before it), |loss diff| 0.886 - 0.898      -> the loss
# Every fault but the scan's state is on the wrong side of one of THESE
# limits on every seed tried. The sound rms barely moves (0.4 % over 9
# readings: every token's error is the sum of twenty sublayers' roundings),
# so 0.025 is 1.37 x the largest sound reading and 0.29 x the smallest
# faulty one it must catch on every seed (0.0865, fp8). The largest error
# of a token has no tail (1.2 - 1.3 x the rms): 0.05 is 2.2 x the largest
# sound reading and 0.47 x the least a held fault reads (0.106). The loss:
# the accepted cells' limit, 80 x the largest sound reading and 1/440 of
# the one fault it alone holds.
HIDDEN_REL_L2_RMS_MAX = 0.025
HIDDEN_REL_L2_MAX = 0.05
# |system loss - reference loss| (the cross entropy over the slice): the
# accepted JoyAI, LFM2, Kimi and Olmo Hybrid cells' limit
REFERENCE_LOSS_ATOL = 2e-3
REFERENCE_SEQUENCES = 2
CHECK_NORM_STD = 0.1
CHECK_QK_GAIN = 5.0
# rows of scores the reference's attention holds at a time
REFERENCE_ROW_BLOCK = 1024

# THE SCAN BY ITSELF, forward and backward (the whole-model comparison
# holds no gradient, so nothing above runs ``ssd_bwd``; and no limit on the
# final hidden state separates a scan computed below f32 from a sound one:
# ``families/nemotron_h.py``): ``ops/ssd.py::ssd_scan`` — the kernels the
# step runs, at the cell's widths (64 heads of 64 on ONE group, a state of
# 128), the timed rows and length (32 chunks a row, eight head blocks a
# chunk), bf16 operands as the model hands them — against
# ``granite_hybrid_f32.recurrence`` (position by position, f32) on the same
# rounded inputs and ``jax.vjp`` of it, LEAF BY LEAF: ``y`` and the
# gradients of ``x, Δ, A, B, C, D`` under one seeded cotangent, each as
# ||got - want||_2 / ||want||_2 — over the whole leaf for ``dA, dB, dC,
# dD``, and for ``y, dx, dΔ`` A HEAD AT A TIME, the worst head's. Inputs as
# the model's initialisation gives them (:func:`scan_inputs`).
# ``dA`` ALONE IS TAKEN UNDER A COTANGENT OF ITS OWN, ``dy = y`` (the
# gradient of ½‖y‖²: the reference's ``y``, on both sides). Under the
# seeded one ``dA_h = Σ_t da_t Δ_t`` sums 16 384 terms of both signs, the
# 64-vector's norm stands in two or three slow heads and cancels to
# anything, and the relative error of a SOUND scan read 0.0006 - 0.0082 on
# 18 seeds and 0.0311 on the nineteenth (my chip runs, PR 68: one of six
# runs of the cell printed ``correct: false`` by that leaf alone, under a
# limit 2.4 x the largest of the 13 readings it was set from): no limit
# stands between such a tail and a fault. A slower decay makes every ``y_t``
# larger, so under ``dy = y`` every position pulls ``A_h`` the same way
# and the sum does not cancel. What ``da`` is worth position by position
# is ``dΔ``'s to hold (``dΔ = x·g + A·da``), under the seeded cotangent.
#
# Readings on the v5e at [2, 8192, 64, 64], ONE group, state 128 (my chip
# runs, PR 68; ``granite_faults.py --scan``: 28 sound seeds, half beyond
# 2^31, and 14 runs of the cell; 7 other seeds a stand-in; ``dA`` under its
# own cotangent on the last 16 sound seeds, 7 runs and 3 seeds a stand-in):
#                     y         dx        dΔ        dA        dB, dC    dD
#   sound             .00392-   .00362-   .00385-   4.7e-6-   .00403-   < 4e-7
#                     .00418    .00382    .00441    2.9e-4    .00412
#   state rounded to  .00523-   .00521-   .00945-   4.7e-5-   .00435-   0
#   bf16 a position   .01411    .01362    .0176     1.2e-3    .00456
#   a head block under another block's decays: 1.0 - 16 in every leaf, dA .99
#   dB, dC of ONE head block of eight (the forward pass sound to the bit):
#                     as sound  as sound  as sound  as sound  .66 - .93  as sound
#   the loop rounding nothing: y .0029, dx .0029, dΔ .0030 - .0034, dA 1e-5 -
#   3e-5, dB, dC .0033 (the bf16 rounding of those outputs and the MXU's one
#   bf16 pass over f32 operands, inside the sound kernels by design, make
#   the rest)
# At ONE group every head's y, dx and dΔ stand a third higher than at
# Nemotron-H's eight (``families/nemotron_h.py``: .0029 - .0032, .0024 -
# .0026, .0018 - .0022) and dB, dC, sums over 64 heads, .0040 for .0029, so
# its limits do not carry over; these are set between THIS shape's two
# readings: y 0.0047 (1.12 x the largest sound reading, 0.90 x the smallest
# faulty), dx 0.0045 (1.18 x, 0.86 x), dΔ 0.0065 (1.47 x, 0.69 x): each of
# the three holds the rounded state on every seed (7 of 7). dA under
# ``dy = y`` no longer cancels but still moves sixty-fold with the seed, two
# orders under any fault that reaches it: 0.02 is the geometric middle of
# the largest sound reading (2.9e-4) and the wrong block's (0.99); the
# rounded state is not dA's to hold. dB, dC 0.0047 (1.14 x sound, which
# moves 2 % over 42 readings; 1/140 of the one-block sum's) and dD 1e-5
# guard the kernels' arithmetic.
SCAN_LEAVES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
SCAN_BY_HEAD = ("y", "dx", "ddt")
SCAN_REL_L2_MAX = {"y": 0.0047, "dx": 0.0045, "ddt": 0.0065, "dA": 0.02,
                   "dB": 0.0047, "dC": 0.0047, "dD": 1e-5}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's GraniteHybridConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # granite_flops.train_flops_per_token's total


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import granite_flops
    from torchft_tpu.models.granite_hybrid import GraniteHybridConfig

    kinds = tuple(config["layer_types"])
    cannot = {
        k: config[k] for k, v in (
            ("hidden_act", "silu"), ("attention_bias", False),
            ("tie_word_embeddings", True), ("num_local_experts", 0),
            ("num_experts_per_tok", 0), ("mamba_conv_bias", True),
            ("mamba_proj_bias", False), ("position_embedding_type", "nope"),
            ("normalization_function", "rmsnorm"),
            ("num_hidden_layers", len(kinds)),
            ("shared_intermediate_size", config["intermediate_size"]),
            ("mamba_expand", config["mamba_n_heads"] * config["mamba_d_head"]
             // config["hidden_size"]),
        ) if config[k] != v
    }
    if config["hidden_size"] % config["num_attention_heads"]:
        cannot["num_attention_heads"] = config["num_attention_heads"]
    if cannot:
        raise ValueError(f"models/granite_hybrid.py does not compute {cannot}")
    job, opt = config["job"], config["optimizer"]
    cfg = GraniteHybridConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=kinds, ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_groups=config["mamba_n_groups"],
        ssm_state=config["mamba_d_state"], conv_kernel=config["mamba_d_conv"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["shared_intermediate_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rms_eps=float(config["rms_norm_eps"]),
        init_std=float(config["initializer_range"]),
        remat=bool(job["remat"]), xent_chunks=int(job["xent_chunks"]),
    )
    peak, warm = float(opt["learning_rate"]), int(opt["warmup_steps"])
    tx = optax.adamw(
        # step c (from 0) runs at peak x (c + 1) / warm, then at peak
        optax.linear_schedule(peak / warm, peak, warm - 1),
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"],
        # matrices and the table only; the taps [4, 4352], the norms,
        # A_log, D and dt_bias take none
        mask=lambda params: jax.tree_util.tree_map_with_path(
            lambda path, x: x.ndim >= 2
            and getattr(path[-2], "key", None) != "conv", params))
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=granite_flops.train_flops_per_token(
            **granite_flops.config_dims(config))["total"],
    )


def _low_bits(seed: Any) -> Any:
    """``--seed`` may pass 2**31: a key takes its low 32 bits, unsigned
    (an array is those bits already)."""
    import numpy as np

    return np.uint32(seed & 0xFFFFFFFF) if isinstance(seed, int) else seed


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models.granite_hybrid import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        _low_bits(seed))


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.granite_hybrid import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.granite_hybrid import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/granite_hybrid_f32.terms`` from
    the program's config."""
    return dict(
        layer_types=cfg.layer_types, ssm_heads=cfg.ssm_heads,
        ssm_head_dim=cfg.ssm_head_dim, ssm_groups=cfg.ssm_groups,
        ssm_state=cfg.ssm_state, n_head=cfg.n_heads, n_kv=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling, eps=cfg.rms_eps,
    )


def seed_check_weights(params: Any, seed: Any) -> Any:
    """``params`` with every norm weight and ``D`` drawn ``1 +
    CHECK_NORM_STD x normal``, ``A_log``, ``dt_bias`` moved by
    ``CHECK_NORM_STD x normal`` and the attention's ``W_q``, ``W_k`` times
    ``CHECK_QK_GAIN`` (the module's header); every other leaf is the same
    array, not a copy. ``seed`` an int or, inside a program, its
    low 32 bits as a uint32."""
    import jax

    key = jax.random.key(_low_bits(seed))
    drawn = [0]     # the leaves come in the tree's own order: a stable index

    def leaf(path, x):
        name = getattr(path[-1], "key", None)
        drawn[0] += 1
        if [getattr(k, "key", None) for k in path[-3:-1]] in (
                ["attn", "q_proj"], ["attn", "k_proj"]):
            return x * CHECK_QK_GAIN
        noise = CHECK_NORM_STD * jax.random.normal(
            jax.random.fold_in(key, drawn[0]), x.shape, x.dtype)
        if name in ("scale", "D"):
            return 1.0 + noise
        if name in ("A_log", "dt_bias"):
            return x + noise
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None,
               row_block: Optional[int] = REFERENCE_ROW_BLOCK) -> Callable:
    """``(system_params, reference_params, tokens, targets, check_seed)
    -> small arrays``, to be jitted: ``models/granite_hybrid.py`` as it
    trains against ``reference/granite_hybrid_f32.py`` in ONE program, so
    that neither side's hidden states outlive it (``families/olmoe.py``).
    The cell passes the same weights twice; a fault passes faulty ones
    first, another ``system_cfg`` or another ``attn_fn``. Both sides'
    weights are seeded from ``check_seed`` (a uint32) INSIDE the program
    (:func:`seed_check_weights`: the seeded copies are the program's
    temporaries, no array beside the training state). What comes back:
    ``error`` [N], every token's ||h - h_ref||_2 / ||h_ref||_2 on the
    final-norm hidden state, and both losses."""
    import jax.numpy as jnp

    from benchmark.reference import granite_hybrid_f32
    from torchft_tpu.models.granite_hybrid import loss_terms

    def both(p, p_ref, tok, tgt, check_seed):
        p, p_ref = (seed_check_weights(z, check_seed) for z in (p, p_ref))
        got = loss_terms(system_cfg or cfg, p, tok, tgt, attn_fn)
        want = granite_hybrid_f32.terms(
            p_ref, tok, tgt, row_block=row_block, **reference_dims(cfg))
        h = got["hidden"].astype(jnp.float32).reshape(-1, cfg.d_model)
        h_ref = want["hidden"].reshape(-1, cfg.d_model)
        return {
            "error": (jnp.linalg.norm(h - h_ref, axis=-1)
                      / jnp.linalg.norm(h_ref, axis=-1)),
            "loss": got["loss"], "reference_loss": want["loss"],
        }

    return both


def per_token_errors(cfg: Any, system_params: Any, reference_params: Any,
                     tokens: Any, targets: Any, check_seed: int,
                     fn: Optional[Callable] = None,
                     **faults: Any) -> Dict[str, Any]:
    """:func:`comparison`, jitted (or ``fn``, already jitted) and run
    once a sequence — each beside the training state —, the sequences'
    errors joined and their losses averaged."""
    import jax
    import numpy as np

    fn = fn or jax.jit(comparison(cfg, **faults))
    bits = _low_bits(check_seed)
    seen = [jax.device_get(fn(system_params, reference_params,
                              tokens[i:i + 1], targets[i:i + 1], bits))
            for i in range(tokens.shape[0])]
    return {"error": np.concatenate([s["error"] for s in seen]),
            "loss": np.mean([s["loss"] for s in seen]),
            "reference_loss": np.mean([s["reference_loss"] for s in seen])}


def scan_inputs(cfg: Any, seed: Any, rows: int, seq_len: int):
    """``((x, Δ, A, B, C, D), dy)`` of ``rows`` sequences at ``cfg``'s
    widths, drawn as the model's initialisation and a unit-rms stream give
    them: ``A = -(1 .. H)``; ``Δ = softplus(n + dt_bias)`` with
    ``softplus(dt_bias)`` log-uniform over the config's ``time_step_*``
    and ``n`` standard normal (``h·W_in`` at init 0.02 over 2 048 unit
    inputs has a standard deviation of 0.91); ``x, B, C`` and the cotangent
    standard normal in the compute dtype; ``D`` normal around one. ``seed``
    as :func:`seed_check_weights` takes it."""
    import jax
    import jax.numpy as jnp

    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    k = jax.random.split(jax.random.key(_low_bits(seed)), 7)
    f32, dt = jnp.float32, cfg.dtype
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        k[0], (H,), f32, jnp.log(cfg.time_step_min),
        jnp.log(cfg.time_step_max))), cfg.time_step_floor)
    delta = jax.nn.softplus(
        jax.random.normal(k[1], (rows, seq_len, H), f32)
        + step + jnp.log(-jnp.expm1(-step)))
    return (
        jax.random.normal(k[2], (rows, seq_len, H, P), f32).astype(dt), delta,
        -jnp.arange(1, H + 1, dtype=f32),
        jax.random.normal(k[3], (rows, seq_len, G, N), f32).astype(dt),
        jax.random.normal(k[4], (rows, seq_len, G, N), f32).astype(dt),
        1.0 + jax.random.normal(k[5], (H,), f32),
    ), jax.random.normal(k[6], (rows, seq_len, H, P), f32).astype(dt)


def scan_comparison(scan_fn: Optional[Callable] = None) -> Callable:
    """``(args, dy) -> {leaf: relative L2 error}`` over ``SCAN_LEAVES``
    (the worst head's for the leaves of ``SCAN_BY_HEAD``), to be jitted:
    ``scan_fn`` (the program's ``ssd_scan``; a fault passes another) and
    its ``jax.vjp`` against the reference's recurrence and its own, on
    the same inputs; ``dA`` pulled back from ``dy = y``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import granite_hybrid_f32
    from torchft_tpu.ops.ssd import ssd_scan

    def both(args, dy):
        f32 = jnp.float32
        got, pull = jax.vjp(scan_fn or ssd_scan, *args)
        want, pull_ref = jax.vjp(granite_hybrid_f32.recurrence,
                                 *(a.astype(f32) for a in args))
        grads, grads_ref = list(pull(dy)), list(pull_ref(dy.astype(f32)))
        # dA under its own cotangent (the module's header): dy = y
        own = want.astype(dy.dtype)
        grads[2], grads_ref[2] = pull(own)[2], pull_ref(own.astype(f32))[2]

        def error(name, a, b):
            # y, dx [B, S, H, P] and dΔ [B, S, H]: a head at a time
            over = tuple(i for i in range(a.ndim) if i != 2) if (
                name in SCAN_BY_HEAD) else None
            gap = jnp.sum(jnp.square(a.astype(f32) - b), axis=over)
            return jnp.max(jnp.sqrt(gap / jnp.sum(jnp.square(b), axis=over)))

        return {name: error(name, a, b) for name, a, b in zip(
            SCAN_LEAVES, [got] + grads, [want] + grads_ref)}

    return both


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge_scan(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`scan_comparison`'s errors against ``SCAN_REL_L2_MAX``."""
    over = [n for n in SCAN_LEAVES if not float(seen[n]) <= SCAN_REL_L2_MAX[n]]
    return {"ok": not over, "scan_over": over,
            "scan_rel_l2": {n: _short(seen[n]) for n in SCAN_LEAVES}}


def judge(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`per_token_errors` against the limits at the head of this
    file."""
    import numpy as np

    rms = float(np.sqrt(np.mean(seen["error"] ** 2)))
    worst = float(seen["error"].max())
    loss, loss_ref = float(seen["loss"]), float(seen["reference_loss"])
    diff = abs(loss - loss_ref)
    return {
        "ok": bool(rms <= HIDDEN_REL_L2_RMS_MAX
                   and worst <= HIDDEN_REL_L2_MAX
                   and diff <= REFERENCE_LOSS_ATOL),
        "hidden_rel_l2_rms": _short(rms), "rms_limit": HIDDEN_REL_L2_RMS_MAX,
        "hidden_rel_l2_max": _short(worst), "max_limit": HIDDEN_REL_L2_MAX,
        "tokens": int(seen["error"].size),
        # where the largest error stands (tokens in the sequences' order)
        "worst_at": int(seen["error"].argmax()),
        "system_loss": round(loss, 5), "reference_loss": round(loss_ref, 5),
        "abs_diff": _short(diff), "atol": REFERENCE_LOSS_ATOL,
    }


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the norms'
    weights, ``D``, ``A_log`` and ``dt_bias`` seeded on both sides) and
    ``REFERENCE_SEQUENCES`` seeded sequences, at the configuration's
    widths, depth and share; then the scan alone, forward and backward,
    against the recurrence at the timed rows and length."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x6772, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    whole = judge(per_token_errors(model.cfg, params, params, tokens,
                                   targets, seed))
    # the scan's inputs are drawn inside its program, the seeded weights
    # inside the other: the check puts no array beside the training state
    # but its two sequences of ids
    alone = jax.jit(lambda bits: scan_comparison()(
        *scan_inputs(model.cfg, bits, model.rows, model.seq_len)))
    with jax.default_device(device):
        scan = judge_scan(jax.device_get(alone(_low_bits(seed))))
    return {**whole, **scan, "ok": whole["ok"] and scan["ok"]}
