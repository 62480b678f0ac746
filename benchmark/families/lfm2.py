"""Adapter for the LFM2-MoE family (``torchft_tpu/models/lfm2.py``): the
six functions of ``families/nemotron_h.py`` — ``build``, ``init_state``,
``make_train_step``, ``make_grad_step``, ``flops_per_token``,
``check_reference`` — and nothing of any one configuration. The step
programs are the one step maker's (``models/transformer.py``) with this
family's loss; the optimizer is the configuration's AdamW behind a linear
warm-up (an optax schedule: its count is optimizer state) with the
balance-bias rule on the bias leaves (``optim.with_balance_bias``, told
which experts are held so that the optimizer wrapper's sink carries
``moe_held_share``). ``check_reference`` is ``judge(per_token_errors(
...))`` and ``judge_conv(conv_comparison(...))``; each pair is apart so
that a test or ``tests/lfm2_faults.py`` can run a faulty system against
the sound reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# the balance bias is JoyAI's leaf under JoyAI's predicate
# (``models/common.py``), so the check seeds it with that family's
# function and spread
from benchmark.families.joyai import seed_balance_bias

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, router and convolution taps) against the f32 reference
# on the same share (experts 0-7, rows 0-16383 of the one table), the
# same weights and two sequences of 8192, TOKEN BY TOKEN on the
# final-norm hidden state: per token ||h - h_ref||_2 / ||h_ref||_2, then
# its root mean square and its largest over all 16 384 tokens. The
# balance bias is zero at initialisation, so the check seeds it (normal,
# JoyAI's ``CHECK_BIAS_STD`` 0.05) on both sides.
#
# A flipped top-4 set (a near-tie that rounds the other way in bf16) is
# treated as ``families/nemotron_h.py`` treats it, for that file's
# reason: the convolution remembers 2 positions and attention all of
# them, so a flipped token's jump reaches tokens that ARE compared. The
# reference is computed ON THE SYSTEM'S top-4 sets
# (``lfm2_f32.terms(selection=...)``: the weights are still the
# reference's own scores), every token is compared, and the reference's
# OWN choice on that stream is counted beside it (``top4_disagreement``,
# bounded by itself).
#
# Readings on the v5e at the cell's widths, depth and share (my chip
# runs, PR 38; ``benchmark/tests/lfm2_faults.py``: 24 sound seeds, half
# of them beyond 2^31, and the cell's own runs; 3 other seeds each
# fault):
#   sound            rms 0.01756 - 0.01777, max 0.0209 - 0.0222,
#                    disagreement 0.0460 - 0.0490, |loss diff| 2.9e-6 - 3.4e-4
#   the convolution's insides in bf16   rms 0.0195 - 0.0196, max 0.0236 -
#                    0.0242, disagreement 0.052 - 0.053
#                                       -> rms, and the convolution's own
#   the bias weighting, not only selecting   rms 0.0217 - 0.0227, max
#                    0.034 - 0.041, disagreement 0.054 - 0.056   -> rms, max
#   QK-norm dropped  rms 0.0255 - 0.0256, max 0.115 - 0.123,
#                    disagreement 0.063                          -> all three
#   fp8 (e4m3) in the held experts alone (rounded on the host)
#                    rms 0.0471 - 0.0480, max 0.071 - 0.072      -> rms, max
#   one held expert dropped   rms 0.067 - 0.078, max 0.195 - 0.202 -> rms
#   RoPE dropped 0.0678 - 0.0679; theta 1e4 0.0705 - 0.0706; interleaved
#   pairs 0.0739 - 0.0740; key/value heads swapped 0.0831 - 0.0833 (max
#   0.28 - 0.63, disagreement 0.155 - 0.184)                     -> rms
#   not renormalised 0.540 - 0.550; taps reversed 1.332 - 1.335; B and C
#   swapped 1.332 - 1.337; the window one ahead 1.404 - 1.407; the gate
#   dropped 1.406 (disagreement 0.64 - 1.0)                      -> rms
#   a head of its own (an independent draw)   the hidden state as sound;
#                    |loss diff| 1.9e-4 - 8.3e-3, |own loss diff| 3.29 -
#                    3.31 (sound 1.3e-5 - 4.8e-4)                -> own loss
#   the balance bias ignored   rms as sound (the reference follows the
#                    system's sets), disagreement 0.646 - 0.682  -> disagreement
#   NOT HELD (``lfm2_faults.UNLISTED``): the router's scores rounded to
#   bf16 read rms 0.01764 - 0.01769 and disagreement 0.0521 - 0.0528.
# Every listed fault is on the wrong side of one of THESE limits on every
# seed tried. The sound rms barely moves from seed to seed (a range of
# 1.1 % over 24 seeds, standard deviation 0.3 %), so its limit can stand
# close: 0.019 is 7 % above the largest sound reading and under the
# smallest faulty one that the rms must catch alone (0.0217: 12 % of
# room; 0.0195 is the convolution's own comparison's to catch, and it
# fails this one too). The largest error of a token has no tail here
# (1.2 - 1.25 x the rms on every seed): 0.03 is 1.35 x the largest sound
# reading and under the least a listed fault reads (0.034). The
# disagreement (standard deviation 0.0007 over 24 seeds): 0.06 is 1.22 x
# the largest sound reading, and well under what the fault it alone
# catches reads (0.65). The loss: below the disagreement's limits.
HIDDEN_REL_L2_RMS_MAX = 0.019
HIDDEN_REL_L2_MAX = 0.03
TOP_K_DISAGREEMENT_MAX = 0.06
# |system loss - reference loss| (the cross entropy over the slice), taken
# TWICE: against the next tokens, as the job trains, and against the
# tokens THEMSELVES (``own_*``). The second is the tied table's witness: a
# head that is not the table leaves the hidden state alone and, at
# initialisation, moves the next-token loss by no more than the seed does
# (an independent draw in the table's place: |diff| 1.9e-4, 8.8e-4, 7.8e-3
# on three seeds, my chip runs, PR 38 — the loss is log V + half the
# logits' variance whatever the head), but a token's own row is in its
# residual stream, so under the table its own logit stands 3.3 nats above
# where any other head puts it: the own-token loss reads 6.81 against
# 10.11, and the untied head 3.29 - 3.31 off on every one of 4 seeds
# (sound 1.3e-5 - 4.8e-4 over 24 seeds and the cell's 7 runs). The
# next-token limit is the accepted JoyAI cell's, 5.9 x the largest of 31
# sound readings (3.4e-4; the Nemotron-H cell's 1e-3 would leave 2.9 x);
# the own-token limit stands between its two readings with room on both
# sides: 10 x the largest sound one, 1 / 660 of the faulty one.
REFERENCE_LOSS_ATOL = 2e-3
OWN_LOSS_ATOL = 5e-3
REFERENCE_SEQUENCES = 2

# THE GATED CONVOLUTION BY ITSELF, forward and backward (the whole-model
# comparison holds no gradient, so nothing above runs ``sconv_bwd``):
# ``ops/ssm_pointwise.py::gated_conv`` — the kernels the step runs, at the
# cell's width (2048 channels, 3 taps), one seeded sequence of CONV_SEQ
# positions (4 sequence blocks: the halo crosses three edges), bf16
# operands as the model hands them — against
# ``lfm2_f32.short_conv`` (three shifted products, f32) on the same
# rounded inputs and ``jax.vjp`` of it, LEAF BY LEAF: ``y`` and the
# gradients ``dB, dC, dX`` (the thirds of the one cotangent) and
# ``dtaps`` under one seeded cotangent, each as ||got - want||_2 /
# ||want||_2. Inputs as the model's initialisation and a unit-rms stream
# give them (:func:`conv_inputs`).
CONV_SEQ = 2048
CONV_LEAVES = ("y", "dB", "dC", "dX", "dtaps")
#
# Readings on the v5e (my chip runs, PR 38; ``lfm2_faults.py``: 24 sound
# seeds and the cell's own runs; 3 seeds a stand-in):
#                     y          dB         dC         dX         dtaps
#   sound             .001655-   .001656-   .001657-   .001656-   1.57e-7-
#                     .001663    .001663    .001663    .001663    1.63e-7
#   the insides in    .00363-    .00333-    .00399-    .00333-    .00286-
#   bf16              .00365     .00334     .00400     .00335     .00293
#   the window one ahead, the gate dropped: 1.0 - 1.43 in every leaf
# The sound readings are the one bf16 rounding of each result and move
# 0.5 % over 25 seeds: 0.0025 is 1.5 x the largest sound reading and
# 0.75 x the smallest faulty one. ``dtaps`` is an f32 sum on both sides:
# 1e-5 is 60 x the largest sound reading and 1 / 290 of the faulty one.
CONV_REL_L2_MAX = {"y": 0.0025, "dB": 0.0025, "dC": 0.0025, "dX": 0.0025,
                   "dtaps": 1e-5}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's Lfm2Config
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # lfm2_flops.train_flops_per_token's total


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import lfm2_flops
    from torchft_tpu.models.lfm2 import MIXERS, Lfm2Config, is_balance_bias
    from torchft_tpu.optim import with_balance_bias

    kinds = tuple(config["layer_types"])
    cannot = {
        k: config[k] for k, v in (
            ("conv_bias", False), ("norm_topk_prob", True),
            ("use_expert_bias", True), ("num_hidden_layers", len(kinds)),
        ) if config[k] != v
    }
    if set(kinds) - set(MIXERS):
        cannot["layer_types"] = kinds
    if config["hidden_size"] % config["num_attention_heads"]:
        cannot["num_attention_heads"] = config["num_attention_heads"]
    if cannot:
        raise ValueError(f"models/lfm2.py does not compute {cannot}")
    job, opt, share = config["job"], config["optimizer"], config["share"]
    cfg = Lfm2Config(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=kinds, n_dense_layers=config["num_dense_layers"],
        init_depth=config["published"]["num_hidden_layers"],
        conv_kernel=config["conv_L_cache"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        rope_theta=float(config["rope_theta"]),
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_routed_experts=share["router_width"],
        first_expert=share["first_expert"],
        n_experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        routed_scale=float(config["routed_scaling_factor"]),
        rms_eps=float(config["norm_eps"]),
        init_std=float(config["initializer_range"]),
        remat=bool(job["remat"]), xent_chunks=int(job["xent_chunks"]),
    )
    peak, warm = float(opt["learning_rate"]), int(opt["warmup_steps"])
    tx = with_balance_bias(
        optax.adamw(
            # step c (from 0) runs at peak x (c + 1) / warm, then at peak
            optax.linear_schedule(peak / warm, peak, warm - 1),
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"],
            # matrices only (the taps [3, 2048] among them); norms take none
            mask=lambda params: jax.tree_util.tree_map(
                lambda x: x.ndim >= 2, params)),
        float(opt["balance_bias_rate"]), is_balance_bias,
        held=(cfg.first_expert, cfg.n_experts_held),
    )
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=lfm2_flops.train_flops_per_token(
            **lfm2_flops.config_dims(config))["total"],
    )


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models.lfm2 import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    # --seed may pass 2**31: the key takes its low 32 bits, unsigned
    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        np.uint32(seed & 0xFFFFFFFF)
    )


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.lfm2 import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.lfm2 import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/lfm2_f32.terms`` from the
    program's config."""
    return dict(
        layer_types=cfg.layer_types, n_dense=cfg.n_dense_layers,
        n_head=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        theta=cfg.rope_theta, top_k=cfg.top_k,
        first_expert=cfg.first_expert, routed_scale=cfg.routed_scale,
        eps=cfg.rms_eps,
    )


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None) -> Callable:
    """``(system_params, reference_params, tokens, targets) -> small
    arrays``, to be jitted: ``models/lfm2.py`` as it trains against
    ``reference/lfm2_f32.py`` in ONE program, so that neither side's
    hidden states outlive it (``families/olmoe.py``). The cell passes the
    same weights twice; a fault passes faulty ones first, another
    ``system_cfg`` or another ``attn_fn``. What comes back: ``error``
    [N], every token's ||h - h_ref||_2 / ||h_ref||_2 on the final-norm
    hidden state, the reference computed ON THE SYSTEM'S top-k sets;
    ``disagreement``, the share of (token, layer) pairs in which the
    reference's own set, on that stream, is another; both losses against
    the next tokens and both against the tokens themselves; and per
    expert layer ``rows_held``, ``held_share`` and ``load_max_over_mean``
    of the system's routing."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import lfm2_f32
    from torchft_tpu.models.lfm2 import loss_terms

    def both(p, p_ref, tok, tgt):
        got = loss_terms(system_cfg or cfg, p, tok, tgt, attn_fn)
        taken = jnp.any(jax.nn.one_hot(
            got["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        want = lfm2_f32.terms(p_ref, tok, tgt, selection=taken,
                              **reference_dims(cfg))
        h = got["hidden"].astype(jnp.float32).reshape(-1, cfg.d_model)
        h_ref = want["hidden"].reshape(-1, cfg.d_model)
        return {
            "error": (jnp.linalg.norm(h - h_ref, axis=-1)
                      / jnp.linalg.norm(h_ref, axis=-1)),
            "disagreement": jnp.mean(
                jnp.any(taken != want["chosen"], axis=-1)),
            "loss": got["loss"], "reference_loss": want["loss"],
            # the tokens as their own targets: the tied table's witness
            "own_loss": loss_terms(system_cfg or cfg, p, tok, tok,
                                   attn_fn)["loss"],
            "reference_own_loss": lfm2_f32.cross_entropy(
                want["hidden"], p_ref["wte"]["embedding"], tok),
            "rows_held": got["rows_held"], "held_share": got["held_share"],
            "load_max_over_mean": got["load_max_over_mean"],
        }

    return both


def per_token_errors(cfg: Any, system_params: Any, reference_params: Any,
                     tokens: Any, targets: Any, **faults: Any
                     ) -> Dict[str, Any]:
    """:func:`comparison`, jitted and run once."""
    import jax

    return jax.device_get(jax.jit(comparison(cfg, **faults))(
        system_params, reference_params, tokens, targets))


def conv_inputs(cfg: Any, seed: int, seq_len: int = CONV_SEQ):
    """``((bcx, taps), dy)`` of one sequence at ``cfg``'s width, drawn as
    the model's initialisation and a unit-rms stream give them: ``bcx``
    standard normal in the compute dtype (``n·W_in`` at init 0.02 over
    2048 inputs has a standard deviation of 0.9), the taps U(-1/sqrt(K),
    1/sqrt(K)) in float32, the cotangent standard normal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    C, K = cfg.d_model, cfg.conv_kernel
    k = jax.random.split(jax.random.key(np.uint32(seed & 0xFFFFFFFF)), 3)
    f32, dt = jnp.float32, cfg.dtype
    bound = 1.0 / K ** 0.5
    return (
        jax.random.normal(k[0], (1, seq_len, 3 * C), f32).astype(dt),
        jax.random.uniform(k[1], (K, C), f32, -bound, bound),
    ), jax.random.normal(k[2], (1, seq_len, C), f32).astype(dt)


def conv_comparison(conv_fn: Optional[Callable] = None) -> Callable:
    """``(args, dy) -> {leaf: relative L2 error}`` over ``CONV_LEAVES``,
    to be jitted: ``conv_fn`` (the program's ``gated_conv``; a fault
    passes another) and its ``jax.vjp`` against the reference's shifted
    products and their own, on the same inputs."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import lfm2_f32
    from torchft_tpu.ops.ssm_pointwise import gated_conv

    def both(args, dy):
        f32 = jnp.float32
        got, pull = jax.vjp(conv_fn or gated_conv, *args)
        want, pull_ref = jax.vjp(lfm2_f32.short_conv,
                                 *(a.astype(f32) for a in args))
        (d_bcx, d_taps), (r_bcx, r_taps) = pull(dy), pull_ref(dy.astype(f32))
        C = d_taps.shape[1]

        def error(a, b):
            return jnp.sqrt(jnp.sum(jnp.square(a.astype(f32) - b))
                            / jnp.sum(jnp.square(b)))

        thirds = [(d_bcx[..., i * C:(i + 1) * C], r_bcx[..., i * C:(i + 1) * C])
                  for i in range(3)]
        return {name: error(a, b) for name, (a, b) in zip(
            CONV_LEAVES, [(got, want)] + thirds + [(d_taps, r_taps)])}

    return both


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge_conv(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`conv_comparison`'s errors against ``CONV_REL_L2_MAX``."""
    over = [n for n in CONV_LEAVES if not float(seen[n]) <= CONV_REL_L2_MAX[n]]
    return {"ok": not over, "conv_over": over,
            "conv_rel_l2": {n: _short(seen[n]) for n in CONV_LEAVES}}


def judge(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`per_token_errors` against the limits at the head of this
    file."""
    import numpy as np

    rms = float(np.sqrt(np.mean(seen["error"] ** 2)))
    worst = float(seen["error"].max())
    differs = float(seen["disagreement"])
    loss, loss_ref = float(seen["loss"]), float(seen["reference_loss"])
    diff = abs(loss - loss_ref)
    own = abs(float(seen["own_loss"]) - float(seen["reference_own_loss"]))
    return {
        "ok": bool(rms <= HIDDEN_REL_L2_RMS_MAX
                   and worst <= HIDDEN_REL_L2_MAX
                   and differs <= TOP_K_DISAGREEMENT_MAX
                   and diff <= REFERENCE_LOSS_ATOL
                   and own <= OWN_LOSS_ATOL),
        "hidden_rel_l2_rms": _short(rms), "rms_limit": HIDDEN_REL_L2_RMS_MAX,
        "hidden_rel_l2_max": _short(worst), "max_limit": HIDDEN_REL_L2_MAX,
        "tokens": int(seen["error"].size),
        "top4_disagreement": _short(differs),
        "disagreement_limit": TOP_K_DISAGREEMENT_MAX,
        "system_loss": round(loss, 5), "reference_loss": round(loss_ref, 5),
        "abs_diff": _short(diff), "atol": REFERENCE_LOSS_ATOL,
        "own_abs_diff": _short(own), "own_atol": OWN_LOSS_ATOL,
        "rows_held": [int(x) for x in seen["rows_held"]],
        # 600 characters of a check are printed (``run.py``): short
        "held_share": [round(float(x), 3) for x in seen["held_share"]],
        "load_max_over_mean": [round(float(x), 1)
                               for x in seen["load_max_over_mean"]],
    }


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the balance
    biases seeded non-zero on both sides) and ``REFERENCE_SEQUENCES``
    seeded sequences, at the configuration's widths, depth and share;
    then the gated convolution alone, forward and backward, against the
    shifted products."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x7265, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    params = seed_balance_bias(params, seed)
    whole = judge(per_token_errors(model.cfg, params, params, tokens, targets))
    with jax.default_device(device):
        conv = judge_conv(jax.device_get(jax.jit(conv_comparison())(
            *conv_inputs(model.cfg, seed, CONV_SEQ))))
    return {**whole, **conv, "ok": whole["ok"] and conv["ok"]}
