"""Adapter for the Nemotron-H family (``torchft_tpu/models/nemotron_h.py``):
the six functions of ``families/joyai.py`` — ``build``, ``init_state``,
``make_train_step``, ``make_grad_step``, ``flops_per_token``,
``check_reference`` — and nothing of any one configuration. The step
programs are the one step maker's (``models/transformer.py``) with this
family's loss; the optimizer is the configuration's AdamW behind a linear
warm-up (an optax schedule: its count is optimizer state) with the
balance-bias rule on the bias leaves (``optim.with_balance_bias``).
``check_reference`` is ``judge(per_token_errors(...))``; the two are
apart so that a test or ``tests/nemotron_faults.py`` can run a faulty
system against the sound reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# the balance bias is JoyAI's leaf under JoyAI's predicate
# (``models/nemotron_h.py`` takes both from ``models/joyai.py``), so the
# check seeds it with that family's function and spread
from benchmark.families.joyai import seed_balance_bias

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, router, decays and scan state) against the f32 reference
# — the recurrence position by position — on the same share (experts 0-7,
# the sliced tables), the same weights and two sequences of 8192, TOKEN BY
# TOKEN on the final-norm hidden state: per token ||h - h_ref||_2 /
# ||h_ref||_2, then its root mean square and its largest over all 16 384
# tokens. The balance bias is zero at initialisation, so the check seeds
# it (normal, JoyAI's ``CHECK_BIAS_STD`` 0.05) on both sides.
#
# What this family does with a flipped top-6 set (a near-tie that rounds
# the other way in bf16) is not what OLMoE's and JoyAI's checks do, and
# the chip showed why (my chip run, PR 33, seed 2147489001, the pattern
# cut after k layers, flipped tokens left out as those checks leave
# them): after ``M`` the largest error of a token is 0.0071 and after
# ``ME`` 0.0093, each 1.2 x the rms; after ``MEM`` it is 0.0725 at an rms
# of 0.0109, and the worst tokens stand in runs right behind a flipped
# one (4446 - 4448, 2043 - 2044): the scan's memory carries a flipped
# token's jump into the tokens that follow it, which are compared. So
# the reference is computed ON THE SYSTEM'S top-6 sets
# (``nemotron_h_f32.terms(selection=...)``: the weights are still the
# reference's own scores), every token is compared, and the reference's
# OWN choice on that stream is counted beside it (``top6_disagreement``,
# bounded by itself): a selection that is wrong for more tokens than
# rounding flips fails by that count, one that is wrong for fewer would
# have had those tokens left out anyway.
#
# Readings on the v5e at the cell's widths, depth and share (my chip
# runs, PR 33; ``benchmark/tests/nemotron_faults.py``: 22 sound seeds,
# half of them beyond 2^31, and the cell's own runs; 2 - 3 other seeds
# each fault):
#   sound            rms 0.01344 - 0.01368, max 0.0161 - 0.0175,
#                    disagreement 0.0614 - 0.0664, |loss diff| 1.9e-6 - 2.7e-4
#   rotary embedding applied    rms 0.0254, max 0.103             -> rms
#   key/value heads swapped     rms 0.0307 - 0.0310               -> rms
#   one held expert dropped     rms 0.046 - 0.073, max 0.34       -> rms
#   fp8 (e4m3) in the held routed experts alone (rounded on the host)
#                    rms 0.051 - 0.059, max 0.115 - 0.120        -> rms
#   state not carried over a chunk boundary   rms 0.097 - 0.114   -> rms
#   2.5 left out 0.144 - 0.166; one norm over 4096 0.280 - 0.298; group
#   h % 8 0.324 - 0.358; gate after norm 0.446 - 0.453; relu for relu^2
#   0.636 - 0.640; not renormalised 0.650 - 0.711; conv bias left out
#   0.714 - 0.717; silu left out 0.882 - 0.901; D.x left out 1.080 -
#   1.084; taps reversed 1.172 - 1.179                            -> rms
#   balance bias ignored   rms as sound (the reference follows the
#                    system's sets), disagreement 0.947 - 0.949  -> disagreement
#   the scan's state rounded to bf16 EVERY POSITION (a loop in the
#   kernels' place)  rms 0.01365 / 0.01494 / 0.01767, max 0.017 - 0.033
#   its decays rounded to bf16   rms 0.01361 / 0.01536 / 0.02066
# Every fault but the last two is on the wrong side of one of THESE limits
# on every seed tried. THE LAST TWO ARE NOT: on one seed of three each
# reads inside the sound range (the loop that stands in for the kernels,
# rounding nothing, reads 0.0132 - 0.0133: the rounding adds 3 % there and
# 55 % on another seed, by how many slowly decaying heads the seed drew).
# No limit on the final hidden state separates them from sound; the
# scan's own comparison below does, on every seed.
# The sound rms barely moves from seed to seed (a range of 1.8 % over 22
# seeds), so its limit can stand close: 0.0145 is 6 % above the largest
# sound reading and well under the smallest faulty one that it must catch
# on every seed (0.0254). The largest error of a token has no tail here
# (1.2 - 1.3 x the rms on every seed): 0.03 is 1.7 x the largest sound
# reading and under a third of the least a listed fault reads. The
# disagreement: 0.09 is 1.36 x the largest sound reading, a tenth of what
# the fault it alone catches reads. The loss: 3.7 x the largest sound one.
HIDDEN_REL_L2_RMS_MAX = 0.0145
HIDDEN_REL_L2_MAX = 0.03
TOP_K_DISAGREEMENT_MAX = 0.09
# |system loss - reference loss| (the cross entropy over the slice)
REFERENCE_LOSS_ATOL = 1e-3
REFERENCE_SEQUENCES = 2

# THE SCAN BY ITSELF, forward and backward (PR 33's review: no limit on the
# final hidden state separates a scan computed below f32 from a sound one,
# and nothing above holds ``ssd_bwd``): ``ops/ssd.py::ssd_scan`` — the
# kernels the step runs, at the cell's widths (64 heads of 64, 8 groups, a
# state of 128) and chunk, one seeded sequence of SCAN_SEQ positions (8
# chunks), bf16 operands as the model hands them — against
# ``nemotron_h_f32.recurrence`` (position by position, f32, ``highest``)
# on the same rounded inputs and ``jax.vjp`` of it, LEAF BY LEAF: ``y`` and
# the gradients of ``x, Δ, A, B, C, D`` under one seeded cotangent, each as
# ||got - want||_2 / ||want||_2 — over the whole leaf for ``dA, dB, dC,
# dD``, and for ``y, dx, dΔ`` A HEAD AT A TIME, the worst head's: a scan
# computed below f32 is wrong in its slowly decaying heads (Δ·|A| of a few
# thousandths: a memory of hundreds of positions) and nearly right in the
# other fifty, which the whole leaf's norm averages away (whole-leaf
# ``y``: sound 0.00216 - 0.00243, state in bf16 0.00195 - 0.00265). Inputs
# as the model's initialisation gives them (:func:`scan_inputs`).
#
# Readings on the v5e (my chip runs, PR 33; ``nemotron_faults.py --scan``:
# 24 sound seeds, half beyond 2^31; 20 other seeds a stand-in, each a loop
# over positions in the kernels' place):
#                     y          dx         dΔ         dA         dB, dC     dD
#   sound             .00288-    .00237-    .00183-    .0003-     .00284-    < 3e-7
#   (+ the cell's 9)  .00316     .00257     .00215     .0038      .00292
#   state rounded to  .0062-     .0063-     .0098-     .0038-     .0037-     0
#   bf16 a position   .0211      .0184      .0220      .0231      .0051
#   decays rounded    .0039-     .0041-     .0076-     .0035-     .0030-     0
#   to bf16           .0398      .0393      .0872      .0511      .0069
#   the loop rounding nothing: y, dΔ, dA, dD 0; dx .0017, dB, dC .0023 (the
#   bf16 rounding of those outputs: most of what sound reads there)
#   a first ``ssd_bwd`` (dcum's column sums from another MXU path; PERF.md
#   section 6): dA 0.12 (Q 128) and 0.25 (Q 256), all CPU tests passing
# dΔ IS THE LIMIT THAT HOLDS THE PRECISION: both lower-precision stand-ins
# read 3.5 x the largest sound reading or more on every one of 20 seeds
# (the decays reach dΔ through every position they span); 0.004 is 1.86 x
# the largest sound reading and 0.53 x the smallest faulty one. dx 0.0033
# lies between 0.00257 and 0.0041 and fails both on every seed too; y
# 0.0045 fails the rounded state on every seed and the rounded decays on
# most. dA's sound reading swings tenfold with the seed (a 64-vector, a
# few heads carry its norm): 0.02 is 5.2 x the largest of 53 sound
# readings and a sixth of the fault it is there for. dB, dC 0.0033 (1.13
# x sound, which moves 3 % over 24 seeds) and dD 1e-5 guard the kernels'
# arithmetic; no stand-in reads between.
# WHAT THIS CANNOT SEE: a chunked scan whose carried state is bf16 at the
# 8 chunk boundaries alone (the loop with that one rounding) reads BELOW
# the sound kernels in every leaf (y .0004 - .0011): the MXU's one bf16
# pass over f32 operands, inside the sound kernels by design, rounds more
# than that.
SCAN_SEQ = 2048
SCAN_LEAVES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
SCAN_BY_HEAD = ("y", "dx", "ddt")
SCAN_REL_L2_MAX = {"y": 0.0045, "dx": 0.0033, "ddt": 0.004, "dA": 0.02,
                   "dB": 0.0033, "dC": 0.0033, "dD": 1e-5}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's NemotronHConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # ssd_flops.train_flops_per_token's total


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import ssd_flops
    from torchft_tpu.models.nemotron_h import (
        MIXERS,
        NemotronHConfig,
        is_balance_bias,
    )
    from torchft_tpu.optim import with_balance_bias

    pattern = config["hybrid_override_pattern"]
    cannot = {
        k: config[k] for k, v in (
            ("n_group", 1), ("topk_group", 1), ("n_shared_experts", 1),
            ("norm_topk_prob", True), ("mlp_hidden_act", "relu2"),
            ("mamba_hidden_act", "silu"), ("mamba_proj_bias", False),
            ("use_bias", False), ("mlp_bias", False),
            ("attention_bias", False), ("use_conv_bias", True),
            ("sliding_window", None), ("tie_word_embeddings", False),
            ("residual_in_fp32", False), ("rescale_prenorm_residual", True),
            ("num_hidden_layers", len(pattern)),
            ("norm_eps", config["layer_norm_epsilon"]),
        ) if config[k] != v
    }
    if set(pattern) - set(MIXERS):
        cannot["hybrid_override_pattern"] = pattern
    if cannot:
        raise ValueError(f"models/nemotron_h.py does not compute {cannot}")
    job, opt, share = config["job"], config["optimizer"], config["share"]
    cfg = NemotronHConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        pattern=pattern,
        init_depth=config["published"]["num_hidden_layers"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"], ssm_groups=config["n_groups"],
        ssm_state=config["ssm_state_size"], conv_kernel=config["conv_kernel"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        n_routed_experts=share["router_width"],
        first_expert=share["first_expert"],
        n_experts_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        routed_scale=float(config["routed_scaling_factor"]),
        rms_eps=float(config["norm_eps"]),
        init_std=float(config["initializer_range"]),
        time_step_min=float(config["time_step_min"]),
        time_step_max=float(config["time_step_max"]),
        time_step_floor=float(config["time_step_floor"]),
        remat=bool(job["remat"]), xent_chunks=int(job["xent_chunks"]),
    )
    peak, warm = float(opt["learning_rate"]), int(opt["warmup_steps"])
    tx = with_balance_bias(
        optax.adamw(
            # step c (from 0) runs at peak x (c + 1) / warm, then at peak
            optax.linear_schedule(peak / warm, peak, warm - 1),
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"],
            # matrices only: A_log, D, dt_bias (the released Mamba-2
            # code's _no_weight_decay), norms and the convolution's bias
            # take none
            mask=lambda params: jax.tree_util.tree_map(
                lambda x: x.ndim >= 2, params)),
        float(opt["balance_bias_rate"]), is_balance_bias,
        held=(cfg.first_expert, cfg.n_experts_held),
    )
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=ssd_flops.train_flops_per_token(
            **ssd_flops.config_dims(config))["total"],
    )


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models.nemotron_h import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    # --seed may pass 2**31: the key takes its low 32 bits, unsigned
    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        np.uint32(seed & 0xFFFFFFFF)
    )


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.nemotron_h import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.nemotron_h import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/nemotron_h_f32.terms`` from
    the program's config."""
    return dict(
        pattern=cfg.pattern, ssm_heads=cfg.ssm_heads,
        ssm_head_dim=cfg.ssm_head_dim, ssm_groups=cfg.ssm_groups,
        ssm_state=cfg.ssm_state, n_head=cfg.n_heads, n_kv=cfg.n_kv_heads,
        head_dim=cfg.head_dim, top_k=cfg.top_k,
        first_expert=cfg.first_expert, routed_scale=cfg.routed_scale,
        eps=cfg.rms_eps,
    )


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None) -> Callable:
    """``(system_params, reference_params, tokens, targets) -> small
    arrays``, to be jitted: ``models/nemotron_h.py`` as it trains against
    ``reference/nemotron_h_f32.py`` in ONE program, so that neither
    side's hidden states outlive it (``families/olmoe.py``). The cell
    passes the same weights twice; a fault passes faulty ones first,
    another ``system_cfg`` or another ``attn_fn``. What comes back:
    ``error`` [N], every token's ||h - h_ref||_2 / ||h_ref||_2 on the
    final-norm hidden state, the reference computed ON THE SYSTEM'S
    top-k sets; ``disagreement``, the share of (token, layer) pairs in
    which the reference's own set, on that stream, is another; both
    losses; and per expert layer
    ``rows_held``, ``held_share`` and ``load_max_over_mean`` of the
    system's routing."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h_f32
    from torchft_tpu.models.nemotron_h import loss_terms

    def both(p, p_ref, tok, tgt):
        got = loss_terms(system_cfg or cfg, p, tok, tgt, attn_fn)
        taken = jnp.any(jax.nn.one_hot(
            got["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        want = nemotron_h_f32.terms(p_ref, tok, tgt, selection=taken,
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


def scan_inputs(cfg: Any, seed: int, seq_len: int = SCAN_SEQ):
    """``((x, Δ, A, B, C, D), dy)`` of one sequence at ``cfg``'s widths,
    drawn as the model's initialisation and a unit-rms stream give them:
    ``A = -U[1, 16]``; ``Δ = softplus(n + dt_bias)`` with ``softplus(
    dt_bias)`` log-uniform over the config's ``time_step_*`` and ``n``
    standard normal (``h·W_in`` at init 0.02 over 2 688 inputs has a
    standard deviation of 1.04); ``x, B, C`` and the cotangent standard
    normal in the compute dtype; ``D`` normal around one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    k = jax.random.split(jax.random.key(np.uint32(seed & 0xFFFFFFFF)), 8)
    f32, dt = jnp.float32, cfg.dtype
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        k[0], (H,), f32, jnp.log(cfg.time_step_min),
        jnp.log(cfg.time_step_max))), cfg.time_step_floor)
    delta = jax.nn.softplus(
        jax.random.normal(k[1], (1, seq_len, H), f32)
        + step + jnp.log(-jnp.expm1(-step)))
    return (
        jax.random.normal(k[2], (1, seq_len, H, P), f32).astype(dt), delta,
        -jax.random.uniform(k[3], (H,), f32, 1.0, 16.0),
        jax.random.normal(k[4], (1, seq_len, G, N), f32).astype(dt),
        jax.random.normal(k[5], (1, seq_len, G, N), f32).astype(dt),
        1.0 + jax.random.normal(k[6], (H,), f32),
    ), jax.random.normal(k[7], (1, seq_len, H, P), f32).astype(dt)


def scan_comparison(scan_fn: Optional[Callable] = None) -> Callable:
    """``(args, dy) -> {leaf: relative L2 error}`` over ``SCAN_LEAVES``
    (the worst head's for the leaves of ``SCAN_BY_HEAD``), to be jitted:
    ``scan_fn`` (the program's ``ssd_scan``; a fault passes another) and
    its ``jax.vjp`` against the reference's recurrence and its own, on
    the same inputs."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h_f32
    from torchft_tpu.ops.ssd import ssd_scan

    def both(args, dy):
        f32 = jnp.float32
        got, pull = jax.vjp(scan_fn or ssd_scan, *args)
        want, pull_ref = jax.vjp(nemotron_h_f32.recurrence,
                                 *(a.astype(f32) for a in args))

        def error(name, a, b):
            # y, dx [1, S, H, P] and dΔ [1, S, H]: a head at a time
            over = tuple(i for i in range(a.ndim) if i != 2) if (
                name in SCAN_BY_HEAD) else None
            gap = jnp.sum(jnp.square(a.astype(f32) - b), axis=over)
            return jnp.max(jnp.sqrt(gap / jnp.sum(jnp.square(b), axis=over)))

        return {name: error(name, a, b) for name, a, b in zip(
            SCAN_LEAVES, (got,) + pull(dy),
            (want,) + pull_ref(dy.astype(f32)))}

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
        "held_share": [round(float(x), 4) for x in seen["held_share"]],
        "load_max_over_mean": [round(float(x), 2)
                               for x in seen["load_max_over_mean"]],
    }


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the balance
    biases seeded non-zero on both sides) and ``REFERENCE_SEQUENCES``
    seeded sequences, at the configuration's widths, depth and share;
    then the scan alone, forward and backward, against the recurrence."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x7265, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    params = seed_balance_bias(params, seed)
    whole = judge(per_token_errors(model.cfg, params, params, tokens, targets))
    with jax.default_device(device):
        scan = judge_scan(jax.device_get(jax.jit(scan_comparison())(
            *scan_inputs(model.cfg, seed, SCAN_SEQ))))
    return {**whole, **scan, "ok": whole["ok"] and scan["ok"]}
