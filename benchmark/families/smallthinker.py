"""Adapter for the SmallThinker family
(``torchft_tpu/models/smallthinker.py``): the six functions of
``families/lfm2.py`` — ``build``, ``init_state``, ``make_train_step``,
``make_grad_step``, ``flops_per_token``, ``check_reference`` — and nothing
of any one configuration. The step programs are the one step maker's
(``models/transformer.py``) with this family's loss; the optimizer is the
configuration's AdamW behind a linear warm-up (an optax schedule: its
count is optimizer state) with the balance-bias rule on the bias leaves
(``optim.with_balance_bias``, told which experts are held so that the
optimizer wrapper's sink carries ``moe_held_share``). ``check_reference``
is ``judge(per_token_errors(...))`` and ``judge_swa(swa_comparison(...))``;
each pair is apart so that a test or ``tests/smallthinker_faults.py`` can
run a faulty system against the sound reference under the cell's own
limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# the balance bias is JoyAI's leaf under JoyAI's predicate
# (``models/common.py``), so the check seeds it with that family's
# function and spread
from benchmark.families.joyai import seed_balance_bias

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, router and the experts' activation) against the f32
# reference on the same share (experts 0-15, rows 0-37983 of table and
# head), the same weights and two sequences of 16 384, TOKEN BY TOKEN on
# the final-norm hidden state: per token ||h - h_ref||_2 / ||h_ref||_2,
# then its root mean square and its largest over all 32 768 tokens. The
# balance bias is zero at initialisation, so the check seeds it (normal,
# JoyAI's ``CHECK_BIAS_STD`` 0.05) on both sides.
#
# A flipped top-6 set (a near-tie that rounds the other way in bf16) is
# treated as ``families/lfm2.py`` treats it, for that file's reason:
# attention remembers every position (the windowed layers 4 096 of them),
# so a flipped token's jump reaches tokens that ARE compared. The
# reference is computed ON THE SYSTEM'S top-6 sets
# (``smallthinker_f32.terms(selection=...)``: the weights are still the
# softmax of the reference's own logits over that set), every token is
# compared, and the reference's OWN choice on that stream is counted
# beside it (``top6_disagreement``, bounded by itself).
#
# Readings on the v5e at the cell's widths, depth and share (my chip
# runs, PR 50; ``benchmark/tests/smallthinker_faults.py --sound 20
# --faulty 2 --unlisted``: 20 sound seeds, half of them beyond 2^31, and
# the cell's own runs; 2 other seeds each fault):
#   sound            rms 0.005033 - 0.005113, max 0.007009 - 0.007585,
#                    disagreement 0.01817 - 0.01956, |loss diff| 9.5e-7 -
#                    8.2e-5
#   the bias weighting, not only selecting   rms 0.00888 - 0.01011, max
#                    0.0239 - 0.0262, disagreement 0.0285 - 0.0306 -> all three
#   one held expert dropped   rms 0.0316 - 0.0317, max 0.30 - 0.39,
#                    disagreement 0.0518 - 0.0532            -> all three
#   the router reading n2 (the experts' input) for n1   rms 0.0318 -
#                    0.0326, max 0.176 - 0.188, disagreement 0.50 - 0.51
#                                                        -> all three
#   the window one tile short (3584 keys)   rms 0.0436, max 0.095 - 0.101,
#                    disagreement 0.0597 - 0.0599 -> all three, and the
#                    windowed call's own (0.21 - 0.23 in every leaf)
#   fp8 (e4m3) in the held experts alone (rounded on the host)
#                    rms 0.0482 - 0.0488, max 0.101 - 0.102, disagreement
#                    0.134                               -> all three
#   RoPE on the full layer too 0.0551 - 0.0553; silu for relu 0.0673 -
#   0.0682; RoPE dropped 0.0908 - 0.0917; theta 1e4 0.0979 - 0.0986;
#   interleaved pairs 0.0992 - 0.0997; not renormalised 0.144 - 0.146; the
#   window halved 0.172 - 0.173; dropped 0.210 - 0.215; key/value head
#   i % 4 for i // 7 0.494 - 0.498 (max 0.10 - 1.28, disagreement 0.14 -
#   0.40)                                                -> all three
#   NOT HELD (``smallthinker_faults.UNLISTED``): the router's logits
#   rounded to bf16 read rms 0.00507 - 0.00509 (as sound: the reference
#   follows the system's sets) and disagreement 0.0231 - 0.0239, 1.18 x
#   the largest sound reading: too close for a limit with room on both
#   sides.
# Every listed fault is on the wrong side of ALL THREE hidden-state
# limits on every seed tried. The sound rms barely moves from seed to
# seed (1.6 % over 20 seeds), so its limit can stand close: 0.0065 is
# 1.27 x the largest sound reading and 0.73 x the smallest faulty one
# (0.00888, the bias weighting). The largest error of a token has no tail
# here (1.4 - 1.5 x the rms on every seed): 0.012 is 1.58 x the largest
# sound reading and half the least a listed fault reads (0.0239). The
# disagreement (0.0182 - 0.0196 over 20 seeds): 0.024 is 1.23 x the
# largest sound reading and 0.84 x the least a listed fault reads
# (0.0285). The loss: the accepted JoyAI cell's limit, 24 x the largest
# of 20 sound readings (8.2e-5); no fault is held by it alone (a routed
# share's faults move the loss by 4e-5 - 1.5e-3 at initialisation).
HIDDEN_REL_L2_RMS_MAX = 0.0065
HIDDEN_REL_L2_MAX = 0.012
TOP_K_DISAGREEMENT_MAX = 0.024
REFERENCE_LOSS_ATOL = 2e-3
REFERENCE_SEQUENCES = 2

# THE WINDOWED FLASH CALL BY ITSELF, forward and backward (the whole-model
# comparison holds no gradient, so nothing above runs ``flash_dq`` /
# ``flash_dkv``, and a window one tile short moves few tokens' hidden
# state): ``ops/flash.py::flash_attention(window=W)`` — the kernels the
# step runs, at the cell's heads (28 of 128) and window, one seeded
# sequence of the cell's length, bf16 operands as the model hands them —
# against ``smallthinker_f32.masked_attention`` (the ``[S, S]`` softmax
# under ``(t >= s) & (t - s < W)``, a head at a time, f32) on the same
# rounded inputs and ``jax.vjp`` of it, LEAF BY LEAF: ``o`` and the
# gradients ``dq, dk, dv`` under one seeded cotangent, each as the WORST
# HEAD's ||got - want||_2 / ||want||_2.
SWA_LEAVES = ("o", "dq", "dk", "dv")
#
# Readings on the v5e (my chip runs, PR 50; ``smallthinker_faults.py``: 20
# sound seeds and the cell's own runs; 2 seeds a stand-in), the worst of
# 28 heads:
#                     o          dq         dk         dv
#   sound             .002652-   .002957-   .003405-   .002838-
#                     .002734    .003041    .003491    .002946
#   the window one tile short (3584)   .214   .231-.234   .233-.234   .213-.214
#   the window dropped .41 - .45; halved .58 - .64 in every leaf
# The sound readings are the one bf16 rounding of each result and move
# 3 % over 20 seeds: each limit is 1.43 - 1.52 x the largest sound
# reading and under 1 / 46 of the smallest faulty one.
SWA_REL_L2_MAX = {"o": 0.004, "dq": 0.0045, "dk": 0.005, "dv": 0.0045}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's SmallThinkerConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # smallthinker_flops.train_flops_per_token's


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import smallthinker_flops
    from torchft_tpu.models.smallthinker import (
        SmallThinkerConfig,
        is_balance_bias,
    )
    from torchft_tpu.optim import with_balance_bias

    windowed = tuple(config["sliding_window_layout"])
    rotated = tuple(config["rope_layout"])
    cannot = {
        k: config[k] for k, v in (
            ("moe_primary_router_apply_softmax", True),
            ("norm_topk_prob", True), ("rope_scaling", None),
            ("tie_word_embeddings", False),
            ("num_hidden_layers", len(windowed)),
        ) if config[k] != v
    }
    if len(rotated) != len(windowed) or (set(windowed) | set(rotated)) - {0, 1}:
        cannot["rope_layout"] = rotated
    if cannot:
        raise ValueError(f"models/smallthinker.py does not compute {cannot}")
    job, opt, share = config["job"], config["optimizer"], config["share"]
    cfg = SmallThinkerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        windowed=windowed, rotated=rotated,
        init_depth=config["published"]["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window_size"],
        rope_theta=float(config["rope_theta"]),
        d_expert=config["moe_ffn_hidden_size"],
        n_routed_experts=share["router_width"],
        first_expert=share["first_expert"],
        n_experts_held=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
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
            # matrices only; norms take none
            mask=lambda params: jax.tree_util.tree_map(
                lambda x: x.ndim >= 2, params)),
        float(opt["balance_bias_rate"]), is_balance_bias,
        held=(cfg.first_expert, cfg.n_experts_held),
    )
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=smallthinker_flops.train_flops_per_token(
            **smallthinker_flops.config_dims(config))["total"],
    )


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models.smallthinker import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    # --seed may pass 2**31: the key takes its low 32 bits, unsigned
    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        np.uint32(seed & 0xFFFFFFFF)
    )


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.smallthinker import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.smallthinker import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/smallthinker_f32.terms`` from
    the program's config."""
    return dict(
        windowed=cfg.windowed, rotated=cfg.rotated, window=cfg.window,
        n_head=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        theta=cfg.rope_theta, top_k=cfg.top_k,
        first_expert=cfg.first_expert, routed_scale=cfg.routed_scale,
        eps=cfg.rms_eps,
    )


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None) -> Callable:
    """``(system_params, reference_params, tokens, targets) -> small
    arrays``, to be jitted: ``models/smallthinker.py`` as it trains
    against ``reference/smallthinker_f32.py`` in ONE program, so that
    neither side's hidden states outlive it (``families/olmoe.py``). The
    cell passes the same weights twice; a fault passes faulty ones first,
    another ``system_cfg`` or another ``attn_fn``. What comes back:
    ``error`` [N], every token's ||h - h_ref||_2 / ||h_ref||_2 on the
    final-norm hidden state, the reference computed ON THE SYSTEM'S top-k
    sets; ``disagreement``, the share of (token, layer) pairs in which
    the reference's own set, on that stream, is another; both losses;
    and per layer ``rows_held``, ``held_share`` and
    ``load_max_over_mean`` of the system's routing."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import smallthinker_f32
    from torchft_tpu.models.smallthinker import loss_terms

    def both(p, p_ref, tok, tgt):
        got = loss_terms(system_cfg or cfg, p, tok, tgt, attn_fn)
        taken = jnp.any(jax.nn.one_hot(
            got["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        want = smallthinker_f32.terms(p_ref, tok, tgt, selection=taken,
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


def swa_inputs(cfg: Any, seed: int, seq_len: int):
    """``((q, k, v), do)`` of one sequence at ``cfg``'s heads, ``[1, S, H,
    D]`` each, drawn standard normal in the compute dtype: ``n·W_q`` at
    init 0.02 over 2560 inputs has a standard deviation of 1.0. One draw
    a QUERY head for k and v too: the kernels take as many key/value
    heads as query heads, and the repeat is the model's own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    shape = (1, seq_len, cfg.n_heads, cfg.head_dim)
    k = jax.random.split(jax.random.key(np.uint32(seed & 0xFFFFFFFF)), 4)
    q, kk, v, do = (jax.random.normal(k[i], shape, jnp.float32
                                      ).astype(cfg.dtype) for i in range(4))
    return (q, kk, v), do


def swa_comparison(cfg: Any, attn_fn: Optional[Callable] = None) -> Callable:
    """``(qkv, do) -> {leaf: the worst head's relative L2 error}`` over
    ``SWA_LEAVES``, to be jitted: ``attn_fn(q, k, v, window=)`` (the
    program's ``causal_attention``: the flash kernels on a TPU; a fault
    passes another) under ``cfg.window`` and its ``jax.vjp`` against the
    reference's band-masked softmax and its own, a head at a time, on the
    same rounded inputs."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import smallthinker_f32
    from torchft_tpu.ops.attention import causal_attention

    def both(qkv, do):
        f32 = jnp.float32
        got, pull = jax.vjp(
            lambda q, k, v: (attn_fn or causal_attention)(
                q, k, v, window=cfg.window), *qkv)
        grads = pull(do)

        def head(args):
            q, k, v, g = args
            with jax.default_matmul_precision("highest"):
                want, pull_ref = jax.vjp(
                    lambda q, k, v: smallthinker_f32.masked_attention(
                        q, k, v, cfg.window), q, k, v)
                return (want, *pull_ref(g))

        # [1, S, H, D] -> [H, S, D], one head's [S, S] at a time
        by_head = [z[0].transpose(1, 0, 2) for z in (*qkv, do)]
        wants = jax.lax.map(head, tuple(z.astype(f32) for z in by_head))

        def error(a, b):
            a = a[0].transpose(1, 0, 2).astype(f32)
            return jnp.max(jnp.sqrt(
                jnp.sum(jnp.square(a - b), axis=(1, 2))
                / jnp.sum(jnp.square(b), axis=(1, 2))))

        return {name: error(a, b) for name, a, b in zip(
            SWA_LEAVES, (got, *grads), wants)}

    return both


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge_swa(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`swa_comparison`'s errors against ``SWA_REL_L2_MAX``."""
    over = [n for n in SWA_LEAVES if not float(seen[n]) <= SWA_REL_L2_MAX[n]]
    return {"ok": not over, "swa_over": over,
            "swa_rel_l2": {n: _short(seen[n]) for n in SWA_LEAVES}}


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
        "top6_disagreement": _short(differs),
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
    then the windowed flash call alone, forward and backward, against the
    band-masked softmax."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x7265, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    params = seed_balance_bias(params, seed)
    whole = judge(per_token_errors(model.cfg, params, params, tokens, targets))
    with jax.default_device(device):
        swa = judge_swa(jax.device_get(jax.jit(swa_comparison(model.cfg))(
            *swa_inputs(model.cfg, seed, model.seq_len))))
    return {**whole, **swa, "ok": whole["ok"] and swa["ok"]}
