"""Adapter for the Laguna family (``torchft_tpu/models/laguna.py``): the
six functions of ``families/smallthinker.py`` — ``build``, ``init_state``,
``make_train_step``, ``make_grad_step``, ``flops_per_token``,
``check_reference`` — and nothing of any one configuration. The step
programs are the one step maker's (``models/transformer.py``) with this
family's loss; the optimizer is the configuration's AdamW behind a linear
warm-up (an optax schedule: its count is optimizer state) with the
balance-bias rule on the bias leaves (``optim.with_balance_bias``, told
which experts are held so that the optimizer wrapper's sink carries
``moe_held_share``). ``check_reference`` is
``judge(per_token_errors(...))`` and ``judge_flash(flash_comparison(...))``
for each of the model's two flash calls; each pair is apart so that a test
or ``tests/laguna_faults.py`` can run a faulty system against the sound
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
# norms, softmax, router, gate logits and rotation tables) against the f32
# reference on the same share (experts 0-31, rows 0-12543 of table and
# head), the same weights and two sequences of 8 192, TOKEN BY TOKEN on
# the final-norm hidden state: per token ||h - h_ref||_2 / ||h_ref||_2,
# then its root mean square and its largest over all 16 384 tokens. The
# balance bias is zero at initialisation, so the check seeds it (normal,
# JoyAI's ``CHECK_BIAS_STD`` 0.05) on both sides.
#
# A flipped top-8 set (a near-tie that rounds the other way in bf16) is
# treated as ``families/smallthinker.py`` treats it, for that file's
# reason: attention remembers every position, so a flipped token's jump
# reaches tokens that ARE compared. The reference is computed ON THE
# SYSTEM'S top-8 sets (``laguna_f32.terms(selection=...)``: the weights
# are still the reference's own scores renormalised over that set), every
# token is compared, and the reference's OWN choice on that stream is
# counted beside it (``top8_disagreement``, bounded by itself).
#
# Readings on the v5e at the cell's widths, depth and share (my chip
# runs, PR 59; ``benchmark/tests/laguna_faults.py --sound 8 --faulty 2``:
# 8 sound seeds, half of them beyond 2^31, and the cell's own 8 runs on
# seeds unseen while the code was written; 2 other seeds each fault):
#   sound (16 seeds) rms 0.009444 - 0.009499, max 0.01387 - 0.01515,
#                    disagreement 0.0735 - 0.0774, |loss diff| 0 - 2.0e-4
#   the router's scores rounded to bf16   rms 0.00946 - 0.00949 (as sound:
#                    the reference follows the system's sets), max 0.0140 -
#                    0.0141, disagreement 0.1110 - 0.1125  -> disagreement
#   a window of 511 / 513 keys   rms 0.01059 - 0.01063, max 0.0155 -
#                    0.0160, disagreement 0.0825 - 0.0845 -> rms, and the
#                    banded call's own (0.039 - 0.051 in every leaf)
#   the attention operands rounded to 8 bits (e4m3)   rms 0.0301 - 0.0302,
#                    max 0.083 - 0.088, disagreement 0.223 - 0.224
#                    -> all three, and both calls' own (0.047 - 0.140)
#   softmax scores in the router   rms 0.0485 - 0.0488, max 0.28 - 0.29,
#                    disagreement 0.983 - 0.985            -> all three
#   the 2.5 dropped 0.084 - 0.091; the sliding layers turned at theta 5e5
#   0.0948 - 0.0950; the weight on the expert's input 0.104 - 0.113;
#   attention_factor dropped 0.276 - 0.277; the YaRN ramp dropped 0.364 -
#   0.365; the shared expert scaled by 2.5 0.517 - 0.518; the gate a
#   softmax over heads 0.532 - 0.533; the full layers turned over the whole
#   head 0.644 - 0.646; the kinds' groupings swapped 0.651 - 0.653 (both
#   calls' own 1.33 - 1.56); the gate dropped 0.755 - 0.762 (max 0.16 -
#   1.40, disagreement 0.31 - 0.998)                         -> all three
# Every listed fault is on the wrong side of at least one limit on every
# seed tried. The sound rms is a mean over 16 384 tokens and barely moves
# from seed to seed (0.6 % over 16): 0.0100 is 1.053 x the largest sound
# reading and 0.944 x the smallest faulty one (0.01059: a window one key
# off, which the banded call's own comparison holds by a factor of 8
# besides). The largest error of a token (0.0139 - 0.0152 over 16 seeds):
# 0.03 is twice the largest sound reading and 0.36 x the least a fault
# that it holds reads (0.083, the 8-bit operands); it judges a single
# token's blow-up, which the rms cannot see. The disagreement (0.0735 -
# 0.0774 over 16 seeds: a top-8 of 256 sigmoid scores has near-ties at the
# eighth place in one token-layer of thirteen): 0.09 is 1.16 x the largest
# sound reading and 0.81 x the least reading of the fault it alone holds
# (0.111: the router's scores in bf16). The loss: the accepted JoyAI
# cell's limit, 10 x the largest of 16 sound readings (2.0e-4); no fault
# is held by it alone.
HIDDEN_REL_L2_RMS_MAX = 0.0100
HIDDEN_REL_L2_MAX = 0.03
TOP_K_DISAGREEMENT_MAX = 0.09
REFERENCE_LOSS_ATOL = 2e-3
REFERENCE_SEQUENCES = 2

# THE TWO FLASH CALLS BY THEMSELVES, forward and backward (the whole-model
# comparison holds no gradient, so nothing above runs ``flash_dq`` /
# ``flash_dkv``): ``ops/flash.py::flash_attention`` — the kernels the step
# runs — at the cell's ``[rows, S, 64 | 8, 128]`` under the 512-key band
# and ``[rows, S, 48 | 8, 128]`` causal, K and V at their own 8 heads,
# bf16 operands as the model hands them, against
# ``laguna_f32.masked_attention`` (the ``[S, S]`` softmax a head at a
# time, f32) on the same rounded inputs and ``jax.vjp`` of it, LEAF BY
# LEAF: ``o`` and the gradients ``dq, dk, dv`` under one seeded cotangent,
# each as the WORST HEAD's ||got - want||_2 / ||want||_2 (a key/value
# head's gradient is the sum over the query heads it serves, on both
# sides).
FLASH_LEAVES = ("o", "dq", "dk", "dv")
FLASH_CALLS = ("swa", "full")
#
# Readings on the v5e (my chip runs, PR 59; ``laguna_faults.py``: 8 sound
# seeds and the cell's own 8 runs; 2 seeds a stand-in), the worst head:
#                     o          dq         dk         dv
#   swa  sound        .002733-   .002944-   .003355-   .002849-
#                     .002756    .002991    .003373    .002877
#   full sound        .002653-   .003034-   .003381-   .002814-
#                     .002762    .003266    .003424    .002884
#   a window of 511 / 513 keys (swa)   .0425-.0490  .0499-.0512
#                                      .0411-.0429  .0394-.0402
#   the operands in 8 bits: swa .0465-.0769, full .0465-.1401
#   the kinds' groupings swapped: 1.33 - 1.56 in every leaf of both
# The sound readings are the one bf16 rounding of each result and move
# under 8 % over 16 seeds: each limit is 1.38 - 1.56 x the largest sound
# reading and under 1 / 8 of the smallest faulty one.
FLASH_REL_L2_MAX = {
    "swa": {"o": 0.004, "dq": 0.0045, "dk": 0.005, "dv": 0.0045},
    "full": {"o": 0.004, "dq": 0.0045, "dk": 0.005, "dv": 0.0045},
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's LagunaConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # laguna_flops.train_flops_per_token's


def _rotation(params: Dict[str, Any]) -> Any:
    """One entry of ``rope_parameters`` as the program's ``Rotation``."""
    from torchft_tpu.models.laguna import Rotation

    kind = params["rope_type"]
    if kind not in ("default", "yarn"):
        raise ValueError(f"models/laguna.py does not compute rope_type {kind}")
    base = dict(theta=float(params["rope_theta"]),
                partial=float(params["partial_rotary_factor"]))
    if kind == "default":
        return Rotation(**base)
    return Rotation(
        **base, yarn_factor=float(params["factor"]),
        original_positions=int(params["original_max_position_embeddings"]),
        beta_fast=float(params["beta_fast"]),
        beta_slow=float(params["beta_slow"]),
        attention_factor=float(params["attention_factor"]))


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import laguna_flops
    from torchft_tpu.models.laguna import LagunaConfig, is_balance_bias
    from torchft_tpu.optim import with_balance_bias

    kinds, mlps = config["layer_types"], config["mlp_layer_types"]
    heads = tuple(config["num_attention_heads_per_layer"])
    cannot = {
        k: config[k] for k, v in (
            ("attention_bias", False), ("gating", True),
            ("tie_word_embeddings", False),
            ("moe_apply_router_weight_on_input", False),
            ("num_hidden_layers", len(kinds)),
        ) if config[k] != v
    }
    if set(kinds) - {"full_attention", "sliding_attention"}:
        cannot["layer_types"] = kinds
    if set(mlps) - {"dense", "sparse"} or len(mlps) != len(kinds):
        cannot["mlp_layer_types"] = mlps
    if len(heads) != len(kinds):
        cannot["num_attention_heads_per_layer"] = heads
    if cannot:
        raise ValueError(f"models/laguna.py does not compute {cannot}")
    job, opt, share = config["job"], config["optimizer"], config["share"]
    ropes = config["rope_parameters"]
    cfg = LagunaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        windowed=tuple(int(t == "sliding_attention") for t in kinds),
        heads=heads, sparse=tuple(int(t == "sparse") for t in mlps),
        init_depth=config["published"]["num_hidden_layers"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window"],
        rope_full=_rotation(ropes["full_attention"]),
        rope_swa=_rotation(ropes["sliding_attention"]),
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        n_routed_experts=share["router_width"],
        first_expert=share["first_expert"],
        n_experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        routed_scale=float(config["moe_routed_scaling_factor"]),
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
            # matrices only; norms take none
            mask=lambda params: jax.tree_util.tree_map(
                lambda x: x.ndim >= 2, params)),
        float(opt["balance_bias_rate"]), is_balance_bias,
        held=(cfg.first_expert, cfg.n_experts_held),
    )
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=laguna_flops.train_flops_per_token(
            **laguna_flops.config_dims(config))["total"],
    )


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models.laguna import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    # --seed may pass 2**31: the key takes its low 32 bits, unsigned
    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        np.uint32(seed & 0xFFFFFFFF)
    )


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.laguna import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.laguna import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def _rope_dims(rot: Any) -> Dict[str, Any]:
    return dict(theta=rot.theta, partial=rot.partial, factor=rot.yarn_factor,
                original=rot.original_positions, beta_fast=rot.beta_fast,
                beta_slow=rot.beta_slow,
                attention_factor=rot.attention_factor)


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/laguna_f32.terms`` from the
    program's config (a layer's head count and whether it is sparse are
    read off the parameters there)."""
    return dict(
        windowed=cfg.windowed, window=cfg.window, n_kv=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_full=_rope_dims(cfg.rope_full),
        rope_swa=_rope_dims(cfg.rope_swa), top_k=cfg.top_k,
        first_expert=cfg.first_expert, routed_scale=cfg.routed_scale,
        eps=cfg.rms_eps,
    )


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None) -> Callable:
    """``(system_params, reference_params, tokens, targets) -> small
    arrays``, to be jitted: ``models/laguna.py`` as it trains against
    ``reference/laguna_f32.py`` in ONE program, so that neither side's
    hidden states outlive it (``families/olmoe.py``). The cell passes the
    same weights twice; a fault passes faulty ones first, another
    ``system_cfg`` or another ``attn_fn``. What comes back: ``error`` [N],
    every token's ||h - h_ref||_2 / ||h_ref||_2 on the final-norm hidden
    state, the reference computed ON THE SYSTEM'S top-k sets;
    ``disagreement``, the share of (token, sparse layer) pairs in which
    the reference's own set, on that stream, is another; both losses; per
    sparse layer ``rows_held``, ``held_share`` and ``load_max_over_mean``
    of the system's routing; and ``gate_range``, the least and the
    largest gate a head of layer 0 on this batch."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import laguna_f32
    from torchft_tpu.models.common import rms_norm
    from torchft_tpu.models.laguna import head_gate, loss_terms

    def both(p, p_ref, tok, tgt):
        got = loss_terms(system_cfg or cfg, p, tok, tgt, attn_fn)
        taken = jnp.any(jax.nn.one_hot(
            got["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        want = laguna_f32.terms(p_ref, tok, tgt, selection=taken,
                                **reference_dims(cfg))
        h = got["hidden"].astype(jnp.float32).reshape(-1, cfg.d_model)
        h_ref = want["hidden"].reshape(-1, cfg.d_model)
        first = p["layers_0"]
        gate = head_gate(
            rms_norm(p["wte"]["embedding"][tok].astype(jnp.float32),
                     first["norm_1"]["scale"], cfg.rms_eps),
            first["attn"]["gate"]["kernel"])
        return {
            "error": (jnp.linalg.norm(h - h_ref, axis=-1)
                      / jnp.linalg.norm(h_ref, axis=-1)),
            "disagreement": jnp.mean(
                jnp.any(taken != want["chosen"], axis=-1)),
            "loss": got["loss"], "reference_loss": want["loss"],
            "rows_held": got["rows_held"], "held_share": got["held_share"],
            "load_max_over_mean": got["load_max_over_mean"],
            "gate_range": jnp.stack([jnp.min(gate), jnp.max(gate)]),
        }

    return both


def per_token_errors(cfg: Any, system_params: Any, reference_params: Any,
                     tokens: Any, targets: Any, **faults: Any
                     ) -> Dict[str, Any]:
    """:func:`comparison`, jitted and run once."""
    import jax

    return jax.device_get(jax.jit(comparison(cfg, **faults))(
        system_params, reference_params, tokens, targets))


def flash_shape(cfg: Any, call: str) -> Dict[str, Any]:
    """``heads`` and ``window`` of one of the model's two flash calls:
    ``"swa"`` a sliding layer's, ``"full"`` a full layer's."""
    windowed = call == "swa"
    heads = {cfg.heads[i] for i, w in enumerate(cfg.windowed)
             if bool(w) == windowed}
    assert len(heads) == 1, (call, heads)
    return {"heads": heads.pop(), "window": cfg.window if windowed else None}


def flash_inputs(cfg: Any, call: str, seed: Any, rows: int, seq_len: int):
    """``((q, k, v), do)`` of ``rows`` sequences at the call's heads —
    ``q`` and ``do`` ``[rows, S, H, D]``, ``k`` and ``v`` ``[rows, S, KV,
    D]`` — drawn standard normal in the compute dtype from ``seed`` (a
    uint32, traced or not): ``n·W_q`` at init 0.02 over 2048 inputs has a
    standard deviation of 0.9."""
    import jax
    import jax.numpy as jnp

    H = flash_shape(cfg, call)["heads"]
    ks = jax.random.split(jax.random.fold_in(
        jax.random.key(seed), FLASH_CALLS.index(call)), 4)

    def draw(k, heads):
        return jax.random.normal(
            k, (rows, seq_len, heads, cfg.head_dim), jnp.float32
        ).astype(cfg.dtype)

    return ((draw(ks[0], H), draw(ks[1], cfg.n_kv_heads),
             draw(ks[2], cfg.n_kv_heads)), draw(ks[3], H))


def flash_comparison(cfg: Any, call: str, rows: int, seq_len: int,
                     attn_fn: Optional[Callable] = None) -> Callable:
    """``seed -> {leaf: the worst head's relative L2 error}`` over
    ``FLASH_LEAVES``, to be jitted: on :func:`flash_inputs` of ``seed``
    (drawn INSIDE the program, so that they are its temporaries and not
    arrays that stand in the device's peak beside the training state),
    ``attn_fn(q, k, v, window=)`` (the program's ``causal_attention``: the
    flash kernels on a TPU; a fault passes another) under the call's
    window and its ``jax.vjp`` against the reference's masked softmax and
    its own on the same rounded inputs, one key/value head of one sequence
    at a time and under it one of the query heads it serves at a time;
    only each head's two sums of squares leave the loop."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import laguna_f32
    from torchft_tpu.ops.attention import causal_attention

    window = flash_shape(cfg, call)["window"]

    def sq(x):
        return jnp.sum(jnp.square(x))

    def both(seed):
        f32 = jnp.float32
        qkv, do = flash_inputs(cfg, call, seed, rows, seq_len)
        got, pull = jax.vjp(
            lambda q, k, v: (attn_fn or causal_attention)(
                q, k, v, window=window), *qkv)
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
                        lambda q, k, v: laguna_f32.masked_attention(
                            q, k, v, window), qh, k[0], v[0])
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


def flash_errors(cfg: Any, call: str, seed: int, rows: int, seq_len: int,
                 attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """:func:`flash_comparison`, jitted and run once on ``seed`` (which
    may pass 2**31: its low 32 bits, unsigned)."""
    import jax
    import numpy as np

    return jax.device_get(jax.jit(flash_comparison(
        cfg, call, rows, seq_len, attn_fn))(np.uint32(seed & 0xFFFFFFFF)))


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge_flash(call: str, seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`flash_comparison`'s errors against ``FLASH_REL_L2_MAX``."""
    limits = FLASH_REL_L2_MAX[call]
    over = [n for n in FLASH_LEAVES if not float(seen[n]) <= limits[n]]
    return {"ok": not over, f"{call}_over": over,
            f"{call}_rel_l2": [_short(seen[n]) for n in FLASH_LEAVES]}


def yarn_gauges(cfg: Any) -> Dict[str, int]:
    """``lo``, ``hi`` of the full layers' YaRN ramp and how many of its
    frequencies are interpolated (not the plain ``theta^(-2i / lanes)``)."""
    import numpy as np

    from torchft_tpu.models.laguna import rotation_freqs, yarn_ramp

    rot = cfg.rope_full
    if rot.yarn_factor is None:
        return {}
    lo, hi = yarn_ramp(rot, int(cfg.head_dim * rot.partial))
    plain = rotation_freqs(dataclasses.replace(rot, yarn_factor=None),
                           cfg.head_dim)
    moved = int(np.sum(rotation_freqs(rot, cfg.head_dim) != plain))
    return {"yarn_lo": lo, "yarn_hi": hi, "yarn_moved": moved}


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
        # 600 characters of a check are printed (``run.py``): short names
        "rms": _short(rms), "rms_max": HIDDEN_REL_L2_RMS_MAX,
        "worst": _short(worst), "worst_max": HIDDEN_REL_L2_MAX,
        "tokens": int(seen["error"].size),
        "top8_disagreement": _short(differs),
        "top8_max": TOP_K_DISAGREEMENT_MAX,
        "loss": round(loss, 5), "loss_ref": round(loss_ref, 5),
        "abs_diff": _short(diff), "atol": REFERENCE_LOSS_ATOL,
        "held_share": [round(float(x), 3) for x in seen["held_share"]],
        "load_max_over_mean": [round(float(x), 1)
                               for x in seen["load_max_over_mean"]],
        "gate_range": [round(float(x), 3) for x in seen["gate_range"]],
    }


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the balance
    biases seeded non-zero on both sides) and ``REFERENCE_SEQUENCES``
    seeded sequences, at the configuration's widths, depth and share;
    then each of the two flash calls alone, forward and backward, at the
    cell's rows, against the masked softmax."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x7265, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    params = seed_balance_bias(params, seed)
    out = judge(per_token_errors(model.cfg, params, params, tokens, targets))
    out.update(yarn_gauges(model.cfg))
    with jax.default_device(device):
        for call in FLASH_CALLS:
            alone = judge_flash(call, flash_errors(
                model.cfg, call, seed, model.rows, model.seq_len))
            out.update(alone, ok=out["ok"] and alone["ok"])
    return out
