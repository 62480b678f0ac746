"""Adapter for the Qwen3-Next family
(``torchft_tpu/models/qwen3_next.py``): the six functions of
``families/smallthinker.py`` — ``build``, ``init_state``,
``make_train_step``, ``make_grad_step``, ``flops_per_token``,
``check_reference`` — and nothing of any one configuration. The step
programs are the one step maker's (``models/transformer.py``) with this
family's loss; the optimizer is the configuration's AdamW behind a linear
warm-up (an optax schedule: its count is optimizer state) with the
balance-bias rule on the bias leaves (``optim.with_balance_bias``, told
which experts are held so that the optimizer wrapper's sink carries
``moe_held_share``). ``check_reference`` is
``judge(per_token_errors(...))``, ``judge_gdn(gdn_comparison(...))``,
``judge_flash(flash_comparison(...))`` and
``judge_moe(moe_comparison(...))``; each pair is apart so that a test
or ``tests/qwen3next_faults.py`` can run a faulty system against the sound
reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, l2 norms, decays, step sizes, router, gate logits,
# rotation tables and the delta rule's state) against the f32 reference on
# the same share (experts 0-31, rows 0-19071 of table and head), the same
# weights and REFERENCE_SEQUENCES seeded sequences of the timed length, one
# at a time (each beside the training state), TOKEN BY TOKEN on the
# final-norm hidden state: per token ||h - h_ref||_2 / ||h_ref||_2, then
# its root mean square and its largest over all tokens; |loss - loss_ref|.
#
# What initialises to a constant is seeded on both sides INSIDE the
# check's program (:func:`seed_check_weights`): the balance biases (normal,
# CHECK_BIAS_STD: JoyAI's), every norm's weight — the zero-centred ones
# (zero at initialisation: ``w`` for ``1 + w`` would show nowhere) and the
# delta rule's plain ``w_V`` — its initial value + CHECK_NORM_STD x normal.
#
# A flipped top-10 set (a near-tie that rounds the other way in bf16) is
# treated as ``families/smallthinker.py`` treats it: the reference is
# computed ON THE SYSTEM'S top-10 sets (``qwen3_next_f32.terms(
# selection=...)``: the weights are still the reference's own logits'
# softmax over that set), every token is compared, and the reference's OWN
# choice on that stream is counted beside it (``top10_disagreement``,
# bounded by itself).
#
# Readings on the v5e at the cell's widths, depth and share (my chip runs,
# PR 63; ``benchmark/tests/qwen3next_faults.py --sound 8 --faulty 2``: 8
# sound seeds, half of them beyond 2^31, 2 other seeds each fault; the
# table drawn at 0.1, four k tiles a forward step):
#   sound (8 seeds and the cell's own 7 runs on seeds unseen while the
#   limits were set) rms 0.01334 - 0.01361, worst 0.0178 - 0.0216,
#                    disagreement 0.1320 - 0.1356, |loss diff| 2.4e-5 - 1.5e-4
#   the rotation over 128 lanes   rms 0.0197 - 0.0198, worst 0.074 - 0.104,
#                    disagreement 0.150 - 0.152              -> all three
#   the attention gate a head     0.0240 - 0.0246 / 0.215 - 0.219 / 0.161 -
#                    0.165; left out 0.056 - 0.058 / 0.41 / 0.229 - 0.232
#   the shared expert's gate left out 0.284 - 0.286; beta = 2 sigma 0.349 -
#   0.375; the head norm's gate before the norm 0.566 - 0.574; norms with w
#   for 1 + w 0.999; value head h on key head h % 16 1.07 (disagreement 0.74
#   - 1.0, |loss diff| up to 0.42)                            -> all three
#   NOT HELD BY THESE, each held by its own comparison below: the delta
#   rule's state rounded to bf16 at chunk boundaries (rms 0.0131 - 0.0132,
#   inside the sound range: the scan's), the attention operands in 8 bits
#   (0.0135 - 0.0136: one attention layer of four; the flash call's), the
#   router's logits in bf16 (0.0134 - 0.0135, disagreement 0.139 - 0.141,
#   1.03 - 1.05 x the largest sound reading: the sublayer's).
# Every listed fault is on the wrong side of at least one limit on both
# seeds tried. The sound rms is a mean over 16 384 tokens and moves 2 %
# over 15 readings: 0.0165 is 1.21 x the largest sound reading and 0.84 x the
# smallest faulty one (0.0197, the rotation over half the head, which the
# largest error of a token holds by a factor of 1.6 besides). The largest
# error of a token: 0.045 is 2.1 x the largest sound reading and 0.61 x
# the least a fault that it holds reads (0.074); it judges a single token's
# blow-up, which the rms cannot see. The disagreement (a top-10 of 512
# softmax logits has a near-tie at the tenth place in one token-layer of
# seven or eight at this stream's bf16 noise: a logit of standard deviation
# 0.9 moves by 0.01 - 0.02): 0.143 is 1.055 x the largest of 15 sound readings (they
# spread 0.001) and 0.955 x the rotation fault's least; it is bounded by itself and no fault
# is held by it alone. The loss: the accepted cells' limit, 13 x the largest
# of 8 sound readings.
HIDDEN_REL_L2_RMS_MAX = 0.0165
HIDDEN_REL_L2_MAX = 0.045
TOP_K_DISAGREEMENT_MAX = 0.143
REFERENCE_LOSS_ATOL = 2e-3
REFERENCE_SEQUENCES = 2
CHECK_BIAS_STD = 0.05
CHECK_NORM_STD = 0.1

# THE DELTA RULE BY ITSELF, forward and backward (the whole-model
# comparison holds no gradient, so nothing above runs ``gdn_bwd``):
# ``ops/kda.py::gdn_scan`` — the kernels the step runs — as the model
# calls it, ``[rows, S, 32, 128]`` with q and k drawn at the 16 key heads
# and copied to the value heads, bf16 operands, against
# ``qwen3_next_f32.gdn_step`` position by position in f32 on the same
# rounded inputs and ``jax.vjp`` of it, LEAF BY LEAF: ``o`` and the
# gradients ``dq, dk, dv, dg, dbeta`` under one seeded cotangent, each as
# the WORST (sequence, head)'s ||got - want||_2 / ||want||_2. ``β`` over
# (0, 1), ``g`` over the initialisation's range (:func:`gdn_inputs`).
GDN_LEAVES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# positions of the recurrence whose states its backward holds at a time,
# and heads the comparison takes at a time
GDN_CHECK_BLOCK = 64
GDN_CHECK_HEADS = 8
#
# Readings on the v5e at [4, 8192, 32, 128] (my chip runs, PR 63):
#                     o        dq       dk       dv       dg       dbeta
#   sound (8 seeds +  .001663- .001663- .001665- .001663- .000070- .000040-
#   the cell's 7)     .001689  .001696  .001721  .001691  .000679  .000400
#   state bf16 at     .00238-  .00321-  .00329-  .00263-  .00350-  .00279-
#   chunk boundaries  .00254   .00333   .00346   .00277   .00353   .00296
# ``o, dq, dk, dv`` read the one bf16 rounding of each result and ``dg,
# dβ``, which leave in f32, what the kernels' three-pass matmuls are worth
# (``families/olmo_hybrid.py``'s finding, PR 56; at one lane tile a head
# the state's fault reads 1.4 - 2 x the sound rounding where that cell's
# reads 1.05 x). 0.0020 is 1.16 x the largest sound reading and 0.84 x the
# fault's smallest (``o``); ``dg`` 0.0011 and ``dβ`` 0.0008 are 1.6 and 2.0 x
# the largest sound readings and 0.31 and 0.29 x the fault's.
GDN_REL_L2_MAX = {"o": 0.0020, "dq": 0.0020, "dk": 0.0020, "dv": 0.0020,
                  "dg": 0.0011, "dbeta": 0.0008}

# THE FLASH CALL BY ITSELF at 256-wide heads, forward and backward:
# ``ops/flash.py::flash_attention`` at the cell's ``[rows, S, 16 | 2,
# 256]``, K and V at their own 2 heads, bf16 operands, against
# ``qwen3_next_f32.masked_attention`` (the ``[S, S]`` softmax a head at a
# time, f32) on the same rounded inputs and ``jax.vjp`` of it, LEAF BY
# LEAF: ``o`` and ``dq, dk, dv`` under one seeded cotangent, each as the
# WORST HEAD's relative L2 error (a key/value head's gradient is the sum
# over the 8 query heads it serves, on both sides).
#
# Readings on the v5e (my chip runs, PR 63), the worst head:
#                     o          dq         dk         dv
#   sound (8 seeds +  .002118-   .002512-   .002435-   .002322-
#   the cell's 7)     .002147    .002655    .002483    .002355
#   operands in 8 bits  .0455      .138       .068       .054 - .055
# The sound readings are the one bf16 rounding of each result (a 256-wide
# head averages more of it away than a 128-wide one: ``families/laguna.py``
# reads 0.0027 - 0.0034) and move under 6 % over 15 readings: each limit is
# 1.47 - 1.5 x the largest sound reading and under 1 / 14 of the faulty one.
FLASH_LEAVES = ("o", "dq", "dk", "dv")
FLASH_REL_L2_MAX = {"o": 0.0032, "dq": 0.0039, "dk": 0.0037, "dv": 0.0035}


# THE SPARSE SUBLAYER BY ITSELF, on ONE stream: the whole model's top-10
# disagreement reads the stream's bf16 noise (a logit of standard
# deviation 0.9 moves by 0.02 where the stream moves by 2 %, ten times what
# rounding the logit to bf16 moves it), so a router in bf16 reads inside
# the sound range there. Here ``models/qwen3_next.py::moe_sublayer`` — the
# norm, the router, the selection, the held experts, the shared expert
# behind its gate, as the step runs them — and
# ``qwen3_next_f32._experts`` are handed the SAME seeded stream (unit rms,
# rounded to the compute dtype once, one sequence of the timed length) and
# layer 0's own weights with the check's seeded bias and norm weight:
# ``flips``, the share of tokens whose top-10 sets differ (two f32
# evaluations of one logit: near-ties alone), and ``rel_l2``, the worst
# token's ||y - y_ref||_2 / ||y_ref||_2 of the sublayer's contribution,
# the reference computed on the system's sets.
#
# Readings on the v5e at 8 192 tokens, 512 logits, 32 held experts (my
# chip runs, PR 63): sound (8 seeds and the cell's 7 runs) flips 0 -
# 0.000122 (none or one token of 8 192), rel_l2 0.00720 - 0.00793; the router's logits in bf16 flips
# 0.0348 on both seeds, rel_l2 0.0090 - 0.0091; the shared expert's gate
# left out flips 0, rel_l2 29 - 52. 0.002 is the geometric mean of the
# sound and the faulty flips (16 x and 1 / 17); 0.02 is 2.5 x the largest
# sound rel_l2 and holds the gate's fault by three orders.
MOE_FLIPS_MAX = 0.002
MOE_REL_L2_MAX = 0.02


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's Qwen3NextConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # qwen3_next_flops.train_flops_per_token's


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import qwen3_next_flops
    from torchft_tpu.models.qwen3_next import Qwen3NextConfig, is_balance_bias
    from torchft_tpu.optim import with_balance_bias

    cannot = {
        k: config[k] for k, v in (
            ("hidden_act", "silu"), ("tie_word_embeddings", False),
            ("norm_topk_prob", True), ("decoder_sparse_step", 1),
            ("mlp_only_layers", []), ("rope_scaling", None),
            ("use_sliding_window", False),
        ) if config[k] != v
    }
    if cannot:
        raise ValueError(f"models/qwen3_next.py does not compute {cannot}")
    job, opt, share = config["job"], config["optimizer"], config["share"]
    cfg = Qwen3NextConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=tuple(qwen3_next_flops.layer_types(config)),
        init_depth=config["published"]["num_hidden_layers"],
        n_key_heads=config["linear_num_key_heads"],
        n_value_heads=config["linear_num_value_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        partial_rotary=float(config["partial_rotary_factor"]),
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        n_routed_experts=share["router_width"],
        first_expert=share["first_expert"],
        n_experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        rms_eps=float(config["rms_norm_eps"]),
        init_std=float(config["initializer_range"]),
        embed_std=float(config.get("embedding_initializer_range",
                                   config["initializer_range"])),
        remat=bool(job["remat"]), xent_chunks=int(job["xent_chunks"]),
    )
    peak, warm = float(opt["learning_rate"]), int(opt["warmup_steps"])
    tx = with_balance_bias(
        optax.adamw(
            # step c (from 0) runs at peak x (c + 1) / warm, then at peak
            optax.linear_schedule(peak / warm, peak, warm - 1),
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"],
            # matrices only; the taps [4, 8192], the norms, A_log and
            # dt_bias take none
            mask=lambda params: jax.tree_util.tree_map_with_path(
                lambda path, x: x.ndim >= 2
                and getattr(path[-2], "key", None) != "conv", params)),
        float(opt["balance_bias_rate"]), is_balance_bias,
        held=(cfg.first_expert, cfg.n_experts_held),
    )
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=qwen3_next_flops.train_flops_per_token(
            **qwen3_next_flops.config_dims(config))["total"],
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

    from torchft_tpu.models.qwen3_next import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        _low_bits(seed))


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.qwen3_next import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.qwen3_next import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/qwen3_next_f32.terms`` from
    the program's config."""
    return dict(
        layer_types=cfg.layer_types, n_key=cfg.n_key_heads,
        n_value=cfg.n_value_heads, key_dim=cfg.key_dim,
        value_dim=cfg.value_dim, n_head=cfg.n_heads, n_kv=cfg.n_kv_heads,
        head_dim=cfg.head_dim, theta=cfg.rope_theta, lanes=cfg.rotary_lanes,
        top_k=cfg.top_k, first_expert=cfg.first_expert, eps=cfg.rms_eps,
    )


def seed_check_weights(params: Any, seed: Any) -> Any:
    """``params`` with every balance bias drawn ``CHECK_BIAS_STD x
    normal`` and every norm weight (a leaf named ``scale``: the
    zero-centred ones and the delta rule's plain one) moved by
    ``CHECK_NORM_STD x normal`` from its value; every other leaf is the
    same array, not a copy. ``seed`` an int or, inside a program, its low
    32 bits as a uint32."""
    import jax

    from torchft_tpu.models.qwen3_next import BALANCE_BIAS

    key = jax.random.key(_low_bits(seed))
    drawn = [0]     # the leaves come in the tree's own order: a stable index

    def leaf(path, x):
        name = getattr(path[-1], "key", None)
        drawn[0] += 1
        k = jax.random.fold_in(key, drawn[0])
        if name == BALANCE_BIAS:
            return CHECK_BIAS_STD * jax.random.normal(k, x.shape, x.dtype)
        if name == "scale":
            return x + CHECK_NORM_STD * jax.random.normal(k, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None) -> Callable:
    """``(system_params, reference_params, tokens, targets, check_seed)
    -> small arrays``, to be jitted: ``models/qwen3_next.py`` as it trains
    against ``reference/qwen3_next_f32.py`` in ONE program, so that
    neither side's hidden states outlive it (``families/olmoe.py``). The
    cell passes the same weights twice; a fault passes faulty ones first,
    another ``system_cfg`` or another ``attn_fn``. Both sides' constant
    leaves are seeded from ``check_seed`` (a uint32) INSIDE the program
    (:func:`seed_check_weights`: the seeded copies are the program's
    temporaries, no array beside the training state). What comes back:
    ``error`` [N], every token's ||h - h_ref||_2 / ||h_ref||_2 on the
    final-norm hidden state, the reference computed ON THE SYSTEM'S top-k
    sets; ``disagreement``, the share of (token, layer) pairs in which
    the reference's own set, on that stream, is another; both losses; per
    layer ``rows_held``, ``held_share`` and ``load_max_over_mean`` of the
    system's routing; and the gauge of the first linear layer's step and
    decay on the system's side (the least and largest ``β`` and
    ``exp(g)``)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next_f32
    from torchft_tpu.models import qwen3_next
    from torchft_tpu.models.common import embed, rms_norm

    def both(p, p_ref, tok, tgt, check_seed):
        run = system_cfg or cfg
        p, p_ref = (seed_check_weights(z, check_seed) for z in (p, p_ref))
        got = qwen3_next.loss_terms(run, p, tok, tgt, attn_fn)
        taken = jnp.any(jax.nn.one_hot(
            got["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        want = qwen3_next_f32.terms(p_ref, tok, tgt, selection=taken,
                                    **reference_dims(cfg))
        h = got["hidden"].astype(jnp.float32).reshape(-1, cfg.d_model)
        h_ref = want["hidden"].reshape(-1, cfg.d_model)
        out = {
            "error": (jnp.linalg.norm(h - h_ref, axis=-1)
                      / jnp.linalg.norm(h_ref, axis=-1)),
            "disagreement": jnp.mean(
                jnp.any(taken != want["chosen"], axis=-1)),
            "loss": got["loss"], "reference_loss": want["loss"],
            "rows_held": got["rows_held"], "held_share": got["held_share"],
            "load_max_over_mean": got["load_max_over_mean"],
        }
        if run.layer_types[0] == qwen3_next.LINEAR:
            first = p["layers_0"]
            g, beta = qwen3_next.decay_and_step(
                run, first["gdn"], rms_norm(
                    embed(run, p, tok),
                    qwen3_next.unit_plus(first["norm_1"]["scale"]),
                    run.rms_eps))
            decay = jnp.exp(g)
            out["gauge"] = jnp.stack([jnp.min(beta), jnp.max(beta),
                                      jnp.min(decay), jnp.max(decay)])
        return out

    return both


def per_token_errors(cfg: Any, system_params: Any, reference_params: Any,
                     tokens: Any, targets: Any, check_seed: int,
                     fn: Optional[Callable] = None,
                     **faults: Any) -> Dict[str, Any]:
    """:func:`comparison`, jitted (or ``fn``, already jitted) and run
    once a sequence — each beside the training state —, the sequences'
    errors joined and their losses, routing figures and gauges
    averaged."""
    import jax
    import numpy as np

    fn = fn or jax.jit(comparison(cfg, **faults))
    bits = _low_bits(check_seed)
    seen = [jax.device_get(fn(system_params, reference_params,
                              tokens[i:i + 1], targets[i:i + 1], bits))
            for i in range(tokens.shape[0])]
    out = {k: np.mean([s[k] for s in seen], axis=0) for k in seen[0]
           if k != "error"}
    out["error"] = np.concatenate([s["error"] for s in seen])
    return out


def gdn_inputs(cfg: Any, seed: Any, rows: int, seq_len: int):
    """``((q, k, v, g, beta), do)`` of ``rows`` sequences as the model
    hands them to the scan: ``q̂, k̂`` the silu of a standard normal at the
    KEY heads, l2-normed and scaled, in the compute dtype, then copied to
    the value heads (``models/qwen3_next.py::_value_groups``); ``v`` the
    silu of a standard normal; ``g = −A·softplus(dt_bias + z)`` with ``A``
    and ``dt_bias`` as ``_gdn_params`` draws them, one a value head; ``β =
    σ(z)`` over (0, 1); the cotangent standard normal. ``seed`` as
    :func:`seed_check_weights` takes it."""
    import math

    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.qwen3_next import _value_groups

    Hk, Hv, K, V = (cfg.n_key_heads, cfg.n_value_heads, cfg.key_dim,
                    cfg.value_dim)
    k = jax.random.split(jax.random.key(_low_bits(seed)), 8)
    f32, dt = jnp.float32, cfg.dtype

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def drawn(key, heads, width):
        return jax.nn.silu(jax.random.normal(
            key, (rows, seq_len, heads, width), f32))

    a = jnp.maximum(jax.random.uniform(k[3], (Hv,), f32, 0.0, 16.0), 1e-4)
    step = jnp.exp(jax.random.uniform(
        k[4], (Hv,), f32, math.log(1e-3), math.log(1e-1)))
    g = -a * jax.nn.softplus(
        step + jnp.log(-jnp.expm1(-step))
        + jax.random.normal(k[5], (rows, seq_len, Hv), f32))
    return (
        _value_groups((l2(drawn(k[0], Hk, K)) * K ** -0.5).astype(dt), Hv),
        _value_groups(l2(drawn(k[1], Hk, K)).astype(dt), Hv),
        drawn(k[2], Hv, V).astype(dt), g,
        jax.nn.sigmoid(jax.random.normal(k[6], (rows, seq_len, Hv), f32)),
    ), jax.random.normal(k[7], (rows, seq_len, Hv, V), f32).astype(dt)


def recurrence_in_blocks(q: Any, k: Any, v: Any, g: Any, beta: Any,
                         block: int = GDN_CHECK_BLOCK,
                         at_edge: Optional[Callable] = None) -> Any:
    """``qwen3_next_f32.gdn_step`` one position after the other from a
    zero state, f32, laid out so that its ``jax.vjp`` fits beside the
    training state: a scan over blocks of ``block`` positions (the
    largest divisor of the length that ``block`` holds), each a
    CHECKPOINTED scan over its positions
    (``families/olmo_hybrid.py::recurrence_in_blocks``' layout; the
    numbers are the plain scan's). ``at_edge`` is applied to the state
    where a block ends (the faults file's rounding at chunk
    boundaries)."""
    import math

    import jax
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next_f32

    f32 = jnp.float32
    B, S, H, K = q.shape
    block = math.gcd(S, block)

    def blocks(z):      # [B, S, ...] -> [S / block, block, B, ...]
        z = jnp.moveaxis(z.astype(f32), 1, 0)
        return z.reshape((S // block, block) + z.shape[1:])

    @jax.checkpoint
    def one_block(state, xs):
        state, o = jax.lax.scan(qwen3_next_f32.gdn_step, state, xs)
        return (at_edge(state) if at_edge else state), o

    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(one_block, jnp.zeros((B, H, K, v.shape[3]), f32),
                            tuple(blocks(z) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((S,) + o.shape[2:]), 0, 1)


def gdn_comparison(scan_fn: Optional[Callable] = None) -> Callable:
    """``(args, do) -> {leaf: the worst (sequence, head)'s relative L2
    error}`` over ``GDN_LEAVES``, to be jitted: ``scan_fn`` (the program's
    ``gdn_scan``; a fault passes another) and its ``jax.vjp`` against the
    reference's recurrence and its own (:func:`recurrence_in_blocks`), on
    the same inputs — ``GDN_CHECK_HEADS`` heads at a time, each group cut
    out of the operands where they lie and compared at once, so that no
    f32 copy of a whole operand stands beside the training state."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.kda import gdn_scan

    def both(args, do):
        f32 = jnp.float32
        got, pull = jax.vjp(scan_fn or gdn_scan, *args)
        got = (got,) + pull(do)
        B, _, H, _ = do.shape
        group = next(n for n in range(GDN_CHECK_HEADS, 0, -1) if H % n == 0)

        def one_group(first):
            mine, theirs = ([jax.lax.dynamic_slice_in_dim(
                z, first, group, axis=2).astype(f32) for z in side]
                for side in ((*args, do), got))
            want, pull = jax.vjp(recurrence_in_blocks, *mine[:5])
            want = (want,) + pull(mine[5])

            def a_head(z):               # [B, S, group, ...] -> [B, group]
                return jnp.sqrt(jnp.sum(jnp.square(z).reshape(
                    B, z.shape[1], group, -1), axis=(1, 3)))

            return [a_head(a - b) / a_head(b) for a, b in zip(theirs, want)]

        errors = jax.lax.map(one_group, jnp.arange(0, H, group))
        return {n: jnp.max(e) for n, e in zip(GDN_LEAVES, errors)}

    return both


def flash_inputs(cfg: Any, seed: Any, rows: int, seq_len: int):
    """``((q, k, v), do)`` of ``rows`` sequences — ``q`` and ``do``
    ``[rows, S, H, D]``, ``k`` and ``v`` ``[rows, S, KV, D]`` — drawn
    standard normal in the compute dtype from ``seed`` (a uint32, traced
    or not): a head-normed q or k has unit rms."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.fold_in(
        jax.random.key(_low_bits(seed)), 256), 4)

    def draw(k, heads):
        return jax.random.normal(
            k, (rows, seq_len, heads, cfg.head_dim), jnp.float32
        ).astype(cfg.dtype)

    return ((draw(ks[0], cfg.n_heads), draw(ks[1], cfg.n_kv_heads),
             draw(ks[2], cfg.n_kv_heads)), draw(ks[3], cfg.n_heads))


def flash_comparison(cfg: Any, rows: int, seq_len: int,
                     attn_fn: Optional[Callable] = None) -> Callable:
    """``seed -> {leaf: the worst head's relative L2 error}`` over
    ``FLASH_LEAVES``, to be jitted (``families/laguna.py::
    flash_comparison``'s layout): on :func:`flash_inputs` of ``seed``
    (drawn INSIDE the program), ``attn_fn(q, k, v)`` (the program's
    ``causal_attention``: the flash kernels on a TPU; a fault passes
    another) and its ``jax.vjp`` against the reference's masked softmax
    and its own on the same rounded inputs, one key/value head of one
    sequence at a time and under it one of the query heads it serves at a
    time; only each head's two sums of squares leave the loop."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next_f32
    from torchft_tpu.ops.attention import causal_attention

    def sq(x):
        return jnp.sum(jnp.square(x))

    def both(seed):
        f32 = jnp.float32
        qkv, do = flash_inputs(cfg, seed, rows, seq_len)
        got, pull = jax.vjp(attn_fn or causal_attention, *qkv)
        B, S, _, D = got.shape
        KV = qkv[1].shape[2]

        def of_group(z, i):
            # [B, S, KV x G, D] -> [G, S, D] of sequence i // KV's
            # key/value head i % KV, sliced where it lies: no copy of z
            G = z.shape[2] // KV
            one = jax.lax.dynamic_slice(
                z, (i // KV, 0, (i % KV) * G, 0), (1, S, G, D))
            return one[0].transpose(1, 0, 2).astype(f32)

        tensors = (*qkv, do, got, *pull(do))

        def group(i):
            q, k, v, g, o, dq, dk, dv = (of_group(z, i) for z in tensors)

            def head(sums, x):
                qh, gh, oh, dqh = x
                with jax.default_matmul_precision("highest"):
                    want, pull_ref = jax.vjp(
                        qwen3_next_f32.masked_attention, qh, k[0], v[0])
                    wq, wk, wv = pull_ref(gh)
                return (sums[0] + wk, sums[1] + wv), jnp.stack(
                    [sq(oh - want), sq(want), sq(dqh - wq), sq(wq)])

            zero = jnp.zeros_like(k[0])
            (wk, wv), heads = jax.lax.scan(head, (zero, zero), (q, g, o, dq))
            return heads, jnp.stack(
                [sq(dk[0] - wk), sq(wk), sq(dv[0] - wv), sq(wv)])

        heads, kv_heads = jax.lax.map(group, jnp.arange(B * KV))

        def worst(sums, at):
            return jnp.max(jnp.sqrt(sums[..., at] / sums[..., at + 1]))

        return {"o": worst(heads, 0), "dq": worst(heads, 2),
                "dk": worst(kv_heads, 0), "dv": worst(kv_heads, 2)}

    return both


def moe_comparison(cfg: Any) -> Callable:
    """``(params, seed) -> {"flips", "rel_l2"}``, to be jitted: layer 0's
    sparse sublayer as the step runs it against the reference's on one
    seeded stream drawn INSIDE the program (the header above); the check's
    seeded bias and norm weights on both sides."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next_f32
    from torchft_tpu.models.qwen3_next import moe_sublayer

    def both(params, seed, seq_len):
        f32 = jnp.float32
        layer = seed_check_weights({"layers_0": params["layers_0"]},
                                   seed)["layers_0"]
        h = jax.random.normal(
            jax.random.fold_in(jax.random.key(_low_bits(seed)), 512),
            (1, seq_len, cfg.d_model), f32).astype(cfg.dtype)
        out, rec = moe_sublayer(cfg, layer, h)
        got = (out.astype(f32) - h.astype(f32)).reshape(seq_len, cfg.d_model)
        taken = jnp.any(jax.nn.one_hot(
            rec["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        with jax.default_matmul_precision("highest"):
            ref = jax.tree_util.tree_map(lambda a: a.astype(f32), layer)
            m2 = qwen3_next_f32.norm(h.astype(f32), ref["norm_2"]["scale"],
                                     cfg.rms_eps).reshape(seq_len, -1)
            want, chosen = qwen3_next_f32._experts(
                m2, ref["moe"], top_k=cfg.top_k,
                first_expert=cfg.first_expert, use=taken)
        return {
            "flips": jnp.mean(jnp.any(taken != chosen, axis=-1)),
            "rel_l2": jnp.max(jnp.linalg.norm(got - want, axis=-1)
                              / jnp.linalg.norm(want, axis=-1)),
        }

    return both


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge_gdn(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`gdn_comparison`'s errors against ``GDN_REL_L2_MAX``."""
    over = [n for n in GDN_LEAVES if not float(seen[n]) <= GDN_REL_L2_MAX[n]]
    return {"ok": not over, "gdn_over": over,
            "gdn_rel_l2": [_short(seen[n]) for n in GDN_LEAVES]}


def judge_flash(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`flash_comparison`'s errors against ``FLASH_REL_L2_MAX``."""
    over = [n for n in FLASH_LEAVES
            if not float(seen[n]) <= FLASH_REL_L2_MAX[n]]
    return {"ok": not over, "flash_over": over,
            "flash_rel_l2": [_short(seen[n]) for n in FLASH_LEAVES]}


def judge_moe(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`moe_comparison`'s two numbers against their limits."""
    flips, worst = float(seen["flips"]), float(seen["rel_l2"])
    return {"ok": bool(flips <= MOE_FLIPS_MAX and worst <= MOE_REL_L2_MAX),
            "moe": [_short(flips), MOE_FLIPS_MAX, _short(worst),
                    MOE_REL_L2_MAX]}


def judge(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`per_token_errors` against the limits at the head of this
    file; the gauge (``beta_decay``) is printed and judged by nothing."""
    import numpy as np

    rms = float(np.sqrt(np.mean(seen["error"] ** 2)))
    worst = float(seen["error"].max())
    differs = float(seen["disagreement"])
    loss, loss_ref = float(seen["loss"]), float(seen["reference_loss"])
    diff = abs(loss - loss_ref)
    out = {
        "ok": bool(rms <= HIDDEN_REL_L2_RMS_MAX
                   and worst <= HIDDEN_REL_L2_MAX
                   and differs <= TOP_K_DISAGREEMENT_MAX
                   and diff <= REFERENCE_LOSS_ATOL),
        # 600 characters of a check are printed (``run.py``): short names
        "rms": _short(rms), "rms_max": HIDDEN_REL_L2_RMS_MAX,
        "worst": _short(worst), "worst_max": HIDDEN_REL_L2_MAX,
        "tokens": int(seen["error"].size),
        "top10_disagreement": _short(differs),
        "top10_max": TOP_K_DISAGREEMENT_MAX,
        "loss": round(loss, 5), "loss_ref": round(loss_ref, 5),
        "abs_diff": _short(diff), "atol": REFERENCE_LOSS_ATOL,
        "held_share": [round(float(x), 3) for x in seen["held_share"]],
        "load_max_over_mean": [round(float(x), 1)
                               for x in seen["load_max_over_mean"]],
    }
    if "gauge" in seen:
        # the least and the largest beta and exp(g) of the first layer
        out["beta_decay"] = [float(f"{float(x):.3g}") for x in seen["gauge"]]
    return out


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the constant
    leaves seeded on both sides) and ``REFERENCE_SEQUENCES`` seeded
    sequences, at the configuration's widths, depth and share; then the
    delta rule alone and the flash call alone, forward and backward, at
    the cell's rows and the timed length, and layer 0's sparse sublayer
    alone on one seeded stream."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x7133, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    out = judge(per_token_errors(model.cfg, params, params, tokens, targets,
                                 seed))
    # both kernels' inputs are drawn inside their programs, the seeded
    # weights inside the other: the check puts no array beside the
    # training state but its two sequences of ids, and the programs'
    # temporaries are under the step's, so ``peak_hbm_gib`` is the
    # training loop's
    scan = jax.jit(lambda bits: gdn_comparison()(
        *gdn_inputs(model.cfg, bits, model.rows, model.seq_len)))
    flash = jax.jit(flash_comparison(model.cfg, model.rows, model.seq_len))
    moe = jax.jit(moe_comparison(model.cfg), static_argnums=2)
    bits = _low_bits(seed)
    with jax.default_device(device):
        for alone in (judge_gdn(jax.device_get(scan(bits))),
                      judge_flash(jax.device_get(flash(bits))),
                      judge_moe(jax.device_get(
                          moe(params, bits, model.seq_len)))):
            out.update(alone, ok=out["ok"] and alone["ok"])
    return out
