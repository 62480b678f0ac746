"""Adapter for the Kimi Linear family
(``torchft_tpu/models/kimi_linear.py``): the six functions of
``families/lfm2.py`` — ``build``, ``init_state``, ``make_train_step``,
``make_grad_step``, ``flops_per_token``, ``check_reference`` — and
nothing of any one configuration. The step programs are the one step
maker's (``models/transformer.py``) with this family's loss; the
optimizer is the configuration's AdamW behind a linear warm-up (an optax
schedule: its count is optimizer state) with the balance-bias rule on
the bias leaves (``optim.with_balance_bias``, told which experts are
held so that the optimizer wrapper's sink carries ``moe_held_share``,
``moe_load_max_over_mean`` and ``moe_row_buffer_share``).
``check_reference`` is ``judge(per_token_errors(...))``,
``judge_kda(kda_comparison(...))`` and ``judge_moe(moe_comparison(...))``;
each pair is apart so that a test or
``tests/kimi_faults.py`` can run a faulty system against the sound
reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# the balance bias is JoyAI's leaf under JoyAI's predicate
# (``models/common.py``), so the check seeds it with that family's
# function and spread
from benchmark.families.joyai import seed_balance_bias

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, router, decays, step sizes and the delta rule's state)
# against the f32 reference on the same share (experts 0-7, rows 0-20479
# of table and head), the same weights and two sequences of 8192, TOKEN BY
# TOKEN on the final-norm hidden state: per token ||h - h_ref||_2 /
# ||h_ref||_2, then its root mean square and its largest over all 16 384
# tokens. The balance bias is zero at initialisation, so the check seeds
# it (normal, JoyAI's ``CHECK_BIAS_STD`` 0.05) on both sides.
#
# A flipped top-8 set (a near-tie that rounds the other way in bf16) is
# treated as ``families/nemotron_h.py`` treats it, for that file's
# reason: the delta rule's state and attention remember every position,
# so a flipped token's jump reaches tokens that ARE compared. The
# reference is computed ON THE SYSTEM'S top-8 sets
# (``kimi_linear_f32.terms(selection=...)``: the weights are still the
# reference's own scores), every token is compared, and the reference's
# OWN choice on that stream is counted beside it (``top8_disagreement``,
# bounded by itself).
#
# Readings on the v5e at the cell's widths, depth and share (my chip
# runs, PR 40; ``benchmark/tests/kimi_faults.py``):
#   sound (39 readings: 14 seeds, half of them beyond 2^31, and the
#   cell's own 25 runs)
#                    rms 0.01879 - 0.01951, max 0.0217 - 0.0234,
#                    disagreement 0.1407 - 0.1482, |loss diff| 3.8e-6 - 3.9e-4
#   fp8 (e4m3) in the held experts alone (rounded on the host)
#                    rms 0.01939 - 0.02008, max 0.0233 - 0.0242,
#                    disagreement 0.144 - 0.150      -> the experts' own
#   rotation applied in MLA (theta 10000, the config's unused key)
#                    rms 0.0212 - 0.0220, max 0.059 - 0.074,
#                    disagreement 0.150 - 0.156              -> rms, max
#   the delta rule's state in bf16   rms 0.0240 - 0.0247, max 0.0282 -
#                    0.0287, disagreement 0.175 - 0.185
#                                    -> rms, disagreement, the scan's own
#   one held expert dropped   rms 0.0241 - 0.0349, max 0.105 - 0.115
#                                    -> rms, max, the experts' own
#   the decays exp(g) in bf16   rms 0.0457 - 0.0486, max 0.071 - 0.080,
#                    disagreement 0.30 - 0.33      -> all, the scan's own
#   q's l2 norm dropped 0.272 - 0.275; the gate before the head norm
#   0.41 - 0.42; beta dropped 0.76; one decay a head 1.07 - 1.08; taps
#   reversed 1.31 (disagreement 0.92 - 1.0)                  -> rms
#   k's l2 norm dropped   nan (the rule no longer contracts: the state
#                    overflows)                              -> every limit
# Every listed fault is on the wrong side of one of THESE limits on every
# seed tried. The sound rms moves 4 % over 39 readings (its standard
# deviation is 0.8 %): 0.0205 is 5.1 % above the largest, seven standard
# deviations above the mean, and 3.3 % under the smallest faulty one that
# the rms catches (rotation in MLA, 0.0212, which the largest error
# catches too, by twice: 0.059 against 0.03). The largest error of a token
# has no tail (1.12 - 1.22 x the rms): 0.03 is 1.28 x the largest sound
# reading. The disagreement: 0.165 is 1.11 x the largest sound reading (a
# seventh of the pairs flip: sigmoid scores of 256 experts lie close, four
# mixers upstream) and under the state's fault (0.175). The loss: the
# accepted JoyAI and LFM2 cells' limit, 5 x the largest of 39 sound
# readings.
HIDDEN_REL_L2_RMS_MAX = 0.0205
HIDDEN_REL_L2_MAX = 0.03
TOP_K_DISAGREEMENT_MAX = 0.165
# |system loss - reference loss| (the cross entropy over the slice): the
# accepted JoyAI and LFM2 cells' limit
REFERENCE_LOSS_ATOL = 2e-3
REFERENCE_SEQUENCES = 2

# THE DELTA RULE BY ITSELF, forward and backward (the whole-model
# comparison holds no gradient, so nothing above runs ``kda_bwd``):
# ``ops/kda.py::kda_scan`` — the kernels the step runs, at the cell's
# widths (32 heads of 128 key and 128 value channels), one seeded
# sequence of KDA_SEQ positions (16 chunks: the state crosses 15 edges),
# bf16 operands as the model hands them — against
# ``kimi_linear_f32.kda_recurrence`` (position by position, f32) on the
# same rounded inputs and ``jax.vjp`` of it, LEAF BY LEAF: ``o`` and the
# gradients ``dq, dk, dv, dg, dbeta`` under one seeded cotangent, each as
# the WORST HEAD'S ||got - want||_2 / ||want||_2. Inputs as the model's
# initialisation and a unit-rms stream give them (:func:`kda_inputs`).
KDA_SEQ = 2048
KDA_LEAVES = ("o", "dq", "dk", "dv", "dg", "dbeta")
#
# Readings on the v5e (my chip runs, PR 40; ``kimi_faults.py --scan``):
#                     o        dq       dk       dv       dg       dbeta
#   sound (14 seeds   .00342-  .00385-  .00428-  .00433-  .00469-  .00421-
#   + the cell's 25)  .00389   .00417   .00474   .00473   .00509   .00486
#   state in bf16     .0087-   .0133-   .0140-   .0113-   .0211-   .0119-
#                     .0098    .0148    .0151    .0123    .0224    .0129
#   decays in bf16    .038-    .058-    .058-    .046-    .107-    .052-
#                     .059     .090     .091     .073     .188     .079
#   one decay a head, beta dropped: 0.64 - 1.23 in every leaf
# The sound readings are the one bf16 rounding of each result (0.00167)
# and the MXU's one-pass rounding of the kernels' f32 operands (with every
# kernel matmul at Precision.HIGHEST the same leaves read 0.00167, dg
# 0.00022, dbeta 0.00011: ``kda_micro.py``); they move 14 % over 39
# readings. Each limit is 1.5 - 1.7 x the largest sound reading and 0.4 -
# 0.7 x the smallest of the state's fault.
KDA_REL_L2_MAX = {"o": 0.006, "dq": 0.007, "dk": 0.0075, "dv": 0.007,
                  "dg": 0.008, "dbeta": 0.0075}


# THE HELD EXPERTS BY THEMSELVES. Eight of 256 experts are held, so what
# they add is a few per cent of a stream that four mixers dominate: on the
# whole model's hidden state fp8 (e4m3) in the held experts reads rms
# 0.01984 - 0.02008 against the sound 0.01902 - 0.01935 (my chip runs, PR
# 40), too close for a limit with room on both sides. So the first expert
# layer's ROUTED part is compared alone: ``models/joyai.py::_moe_sublayer``
# as the step runs it (the shared expert's output matrix zeroed on both
# sides, so that what is compared is ``Σ g_e · SwiGLU_e(n)`` over the held
# experts) on one seeded unit-rms stream of MOE_ROWS tokens in the compute
# dtype, against ``kimi_linear_f32._experts`` on the system's top-8 sets:
# per token that takes a held expert ||y - y_ref||_2 / ||y_ref||_2, then
# its root mean square.
MOE_ROWS = 8192
#
# Readings on the v5e (my chip runs, PR 40; ``kimi_faults.py``):
#   sound (6 seeds and the cell's 25 runs)   0.00590 - 0.00595 (656 - 2938
#                     tokens compared: the one bf16 rounding of each matmul)
#   fp8 in the held experts 0.0582 - 0.0583;  one held expert dropped
#   0.288 - 0.579
# 0.012 is twice the sound reading and a fifth of the least faulty one.
MOE_REL_L2_RMS_MAX = 0.012


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's KimiLinearConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # kda_flops.train_flops_per_token's total


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import kda_flops
    from torchft_tpu.models.kimi_linear import (
        KimiLinearConfig,
        is_balance_bias,
    )
    from torchft_tpu.optim import with_balance_bias

    linear = config["linear_attn_config"]
    kda, full = tuple(linear["kda_layers"]), tuple(linear["full_attn_layers"])
    cannot = {
        k: config[k] for k, v in (
            ("mla_use_nope", True), ("q_lora_rank", None),
            ("rope_scaling", None), ("num_shared_experts", 1),
            ("moe_router_activation_func", "sigmoid"),
            ("moe_renormalize", True), ("hidden_act", "silu"),
            ("tie_word_embeddings", False), ("moe_layer_freq", 1),
            ("num_expert_group", 1), ("topk_group", 1),
            ("num_nextn_predict_layers", 0),
            ("num_key_value_heads", config["num_attention_heads"]),
            ("num_hidden_layers", len(kda) + len(full)),
        ) if config[k] != v
    }
    if sorted(kda + full) != list(range(1, len(kda) + len(full) + 1)):
        cannot["linear_attn_config"] = {"kda_layers": kda,
                                        "full_attn_layers": full}
    if linear["num_heads"] != config["num_attention_heads"]:
        cannot["linear_attn_config.num_heads"] = linear["num_heads"]
    if cannot:
        raise ValueError(f"models/kimi_linear.py does not compute {cannot}")
    job, opt, share = config["job"], config["optimizer"], config["share"]
    cfg = KimiLinearConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        kda_layers=kda, full_attn_layers=full,
        n_dense_layers=config["first_k_dense_replace"],
        n_heads=config["num_attention_heads"],
        kda_head_dim=linear["head_dim"], kda_rank=linear["head_dim"],
        conv_kernel=linear["short_conv_kernel_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_routed_experts=share["router_width"],
        first_expert=share["first_expert"],
        n_experts_held=config["num_experts"],
        top_k=config["num_experts_per_token"],
        routed_scale=float(config["routed_scaling_factor"]),
        rms_eps=float(config["rms_norm_eps"]),
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
            # matrices only (the taps [4, 12288] among them); norms,
            # A_log and dt_bias take none
            mask=lambda params: jax.tree_util.tree_map(
                lambda x: x.ndim >= 2, params)),
        float(opt["balance_bias_rate"]), is_balance_bias,
        held=(cfg.first_expert, cfg.n_experts_held),
    )
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=kda_flops.train_flops_per_token(
            **kda_flops.config_dims(config))["total"],
    )


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models.kimi_linear import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    # --seed may pass 2**31: the key takes its low 32 bits, unsigned
    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        np.uint32(seed & 0xFFFFFFFF)
    )


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.kimi_linear import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.kimi_linear import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/kimi_linear_f32.terms`` from
    the program's config."""
    return dict(
        kda_layers=cfg.kda_layers, n_layer=cfg.n_layers,
        n_dense=cfg.n_dense_layers, n_head=cfg.n_heads,
        nope=cfg.qk_nope_dim, rope=cfg.qk_rope_dim, v_dim=cfg.v_head_dim,
        kv_rank=cfg.kv_lora_rank, top_k=cfg.top_k,
        first_expert=cfg.first_expert, routed_scale=cfg.routed_scale,
        eps=cfg.rms_eps,
    )


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None) -> Callable:
    """``(system_params, reference_params, tokens, targets) -> small
    arrays``, to be jitted: ``models/kimi_linear.py`` as it trains
    against ``reference/kimi_linear_f32.py`` in ONE program, so that
    neither side's hidden states outlive it (``families/olmoe.py``). The
    cell passes the same weights twice; a fault passes faulty ones
    first, another ``system_cfg`` or another ``attn_fn``. What comes
    back: ``error`` [N], every token's ||h - h_ref||_2 / ||h_ref||_2 on
    the final-norm hidden state, the reference computed ON THE SYSTEM'S
    top-k sets; ``disagreement``, the share of (token, layer) pairs in
    which the reference's own set, on that stream, is another; both
    losses; and per expert layer ``rows_held``, ``held_share`` and
    ``load_max_over_mean`` of the system's routing."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kimi_linear_f32
    from torchft_tpu.models.kimi_linear import loss_terms

    def both(p, p_ref, tok, tgt):
        got = loss_terms(system_cfg or cfg, p, tok, tgt, attn_fn)
        taken = jnp.any(jax.nn.one_hot(
            got["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        want = kimi_linear_f32.terms(p_ref, tok, tgt, selection=taken,
                                     **reference_dims(cfg))
        h = got["hidden"].astype(jnp.float32).reshape(-1, cfg.d_model)
        h_ref = want["hidden"].reshape(-1, cfg.d_model)
        return {
            "error": (jnp.linalg.norm(h - h_ref, axis=-1)
                      / jnp.linalg.norm(h_ref, axis=-1)),
            "disagreement": jnp.mean(
                jnp.any(taken != want["chosen"], axis=-1)),
            "loss": got["loss"], "reference_loss": want["loss"],
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


def kda_inputs(cfg: Any, seed: int, seq_len: int = KDA_SEQ):
    """``((q, k, v, g, beta), do)`` of one sequence at ``cfg``'s widths,
    drawn as the model's initialisation and a unit-rms stream give them:
    ``q̃, k̃, v`` the silu of a standard normal (``n·W_qkv`` at init 0.02
    over 2304 inputs has a standard deviation of 0.96), l2-normed and
    scaled as the mixer does, in the compute dtype; ``g = −A·softplus(
    dt_bias + 0.22 z)`` with ``A`` and ``dt_bias`` as
    ``models/kimi_linear.py::_kda_params`` draws them (0.22: the
    low-rank pair's output at init); ``β = σ(z)``; the cotangent
    standard normal."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    H, D = cfg.n_heads, cfg.kda_head_dim
    k = jax.random.split(jax.random.key(np.uint32(seed & 0xFFFFFFFF)), 8)
    f32, dt = jnp.float32, cfg.dtype

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    shape = (1, seq_len, H, D)
    qkv = [jax.nn.silu(jax.random.normal(k[i], shape, f32)) for i in range(3)]
    a = jax.random.uniform(k[3], (H, 1), f32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(
        k[4], (H, D), f32, math.log(1e-3), math.log(1e-1)))
    g = -a * jax.nn.softplus(step + jnp.log(-jnp.expm1(-step))
                             + 0.22 * jax.random.normal(k[5], shape, f32))
    return (
        (l2(qkv[0]) * D ** -0.5).astype(dt), l2(qkv[1]).astype(dt),
        qkv[2].astype(dt), g,
        jax.nn.sigmoid(jax.random.normal(k[6], shape[:3], f32)),
    ), jax.random.normal(k[7], shape, f32).astype(dt)


def kda_comparison(scan_fn: Optional[Callable] = None) -> Callable:
    """``(args, do) -> {leaf: the worst head's relative L2 error}`` over
    ``KDA_LEAVES``, to be jitted: ``scan_fn`` (the program's
    ``kda_scan``; a fault passes another) and its ``jax.vjp`` against the
    reference's recurrence and its own, a head at a time (the
    recurrence's backward keeps every position's state: 134 MB a head at
    2048 positions), on the same inputs."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kimi_linear_f32
    from torchft_tpu.ops.kda import kda_scan

    def one_head(head):
        *args, do = head                     # [S, D] each, beta [S]

        def run(q, k, v, g, beta):
            return kimi_linear_f32.kda_recurrence(
                q[None, :, None], k[None, :, None], v[None, :, None],
                g[None, :, None], beta[None, :, None])[0, :, 0]

        o, pull = jax.vjp(run, *args)
        return (o,) + pull(do)

    def both(args, do):
        f32 = jnp.float32
        got, pull = jax.vjp(scan_fn or kda_scan, *args)
        got = (got,) + pull(do)
        # [1, S, H, ...] -> [H, S, ...]
        heads = [jnp.moveaxis(a[0].astype(f32), 1, 0) for a in (*args, do)]
        want = jax.lax.map(one_head, tuple(heads))

        def worst(a, b):                     # a [1, S, H, ...], b [H, S, ...]
            a = jnp.moveaxis(a[0].astype(f32), 1, 0).reshape(b.shape[0], -1)
            b = b.reshape(b.shape[0], -1)
            return jnp.max(jnp.linalg.norm(a - b, axis=-1)
                           / jnp.linalg.norm(b, axis=-1))

        return {n: worst(a, b) for n, a, b in zip(KDA_LEAVES, got, want)}

    return both


def moe_comparison(cfg: Any) -> Callable:
    """``(system_params, reference_params, seed) -> {"moe": rms, "rows":
    tokens compared}``, to be jitted: the first expert layer's routed
    part by itself (the module's header)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kimi_linear_f32
    from torchft_tpu.models import joyai

    name = f"layers_{cfg.n_dense_layers}"

    def routed_only(layer):
        moe = dict(layer["moe"])
        moe["shared"] = dict(moe["shared"], down_proj={
            "kernel": jnp.zeros_like(moe["shared"]["down_proj"]["kernel"])})
        return dict(layer, moe=moe)

    def both(p, p_ref, seed):
        f32 = jnp.float32
        x = jax.random.normal(jax.random.key(seed), (1, MOE_ROWS, cfg.d_model),
                              f32).astype(cfg.dtype)
        y, rec = joyai._moe_sublayer(cfg, routed_only(p[name]), x)
        got = (y.astype(f32) - x.astype(f32)).reshape(MOE_ROWS, -1)
        taken = jnp.any(jax.nn.one_hot(
            rec["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        with jax.default_matmul_precision("highest"):
            layer = jax.tree_util.tree_map(lambda a: a.astype(f32),
                                           routed_only(p_ref[name]))
            n = kimi_linear_f32._rms(x.astype(f32), layer["ln_2"]["scale"],
                                     cfg.rms_eps).reshape(MOE_ROWS, -1)
            want, _ = kimi_linear_f32._experts(
                n, layer["moe"], top_k=cfg.top_k,
                first_expert=cfg.first_expert,
                routed_scale=cfg.routed_scale, use=taken)
        held = jnp.any(taken[:, cfg.first_expert:
                             cfg.first_expert + cfg.n_experts_held], axis=-1)
        error = jnp.linalg.norm(got - want, axis=-1) / jnp.maximum(
            jnp.linalg.norm(want, axis=-1), 1e-30)
        rows = jnp.sum(held)
        return {"moe": jnp.sqrt(jnp.sum(jnp.where(held, error ** 2, 0.0))
                                / jnp.maximum(rows, 1)), "rows": rows}

    return both


def judge_moe(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`moe_comparison`'s error against ``MOE_REL_L2_RMS_MAX``."""
    return {"ok": bool(float(seen["moe"]) <= MOE_REL_L2_RMS_MAX
                       and int(seen["rows"]) > 0),
            "moe_rel_l2_rms": _short(seen["moe"]),
            "moe_rows": int(seen["rows"])}


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge_kda(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`kda_comparison`'s errors against ``KDA_REL_L2_MAX``."""
    over = [n for n in KDA_LEAVES if not float(seen[n]) <= KDA_REL_L2_MAX[n]]
    return {"ok": not over, "kda_over": over,
            "kda_rel_l2": {n: _short(seen[n]) for n in KDA_LEAVES}}


def judge(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`per_token_errors` against the limits at the head of this
    file."""
    import numpy as np

    rms = float(np.sqrt(np.mean(seen["error"] ** 2)))
    worst = float(seen["error"].max())
    differs = float(seen["disagreement"])
    loss, loss_ref = float(seen["loss"]), float(seen["reference_loss"])
    diff = abs(loss - loss_ref)
    return {
        "ok": bool(rms <= HIDDEN_REL_L2_RMS_MAX
                   and worst <= HIDDEN_REL_L2_MAX
                   and differs <= TOP_K_DISAGREEMENT_MAX
                   and diff <= REFERENCE_LOSS_ATOL),
        "hidden_rel_l2_rms": _short(rms), "rms_limit": HIDDEN_REL_L2_RMS_MAX,
        "hidden_rel_l2_max": _short(worst), "max_limit": HIDDEN_REL_L2_MAX,
        "tokens": int(seen["error"].size),
        "top8_disagreement": _short(differs),
        "disagreement_limit": TOP_K_DISAGREEMENT_MAX,
        "system_loss": round(loss, 5), "reference_loss": round(loss_ref, 5),
        "abs_diff": _short(diff), "atol": REFERENCE_LOSS_ATOL,
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
    then the delta rule alone, forward and backward, against the
    recurrence, and the first expert layer's routed part alone."""
    import jax
    import numpy as np

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x7265, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    params = seed_balance_bias(params, seed)
    whole = judge(per_token_errors(model.cfg, params, params, tokens, targets))
    with jax.default_device(device):
        scan = judge_kda(jax.device_get(jax.jit(kda_comparison())(
            *kda_inputs(model.cfg, seed, KDA_SEQ))))
        moe = judge_moe(jax.device_get(jax.jit(moe_comparison(model.cfg))(
            params, params, np.uint32(seed & 0xFFFFFFFF))))
    return {**whole, **scan, **moe,
            "ok": whole["ok"] and scan["ok"] and moe["ok"]}
