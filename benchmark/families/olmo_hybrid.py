"""Adapter for the Olmo Hybrid family
(``torchft_tpu/models/olmo_hybrid.py``): the six functions of
``families/lfm2.py`` — ``build``, ``init_state``, ``make_train_step``,
``make_grad_step``, ``flops_per_token``, ``check_reference`` — and
nothing of any one configuration. The step programs are the one step
maker's (``models/transformer.py``) with this family's loss; the
optimizer is the configuration's AdamW behind a linear warm-up (an optax
schedule: its count is optimizer state); a dense model has no balance
bias. ``check_reference`` is ``judge(per_token_errors(...))`` and
``judge_gdn(gdn_comparison(...))``; each pair is apart so that a test or
``tests/olmo_hybrid_faults.py`` can run a faulty system against the
sound reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, l2 norms, decays, step sizes and the delta rule's state)
# against the f32 reference on the same share (rows 0-12543 of table and
# head), the same weights and REFERENCE_SEQUENCES seeded sequences of the
# timed length, one at a time (each beside 10.4 GiB of training state),
# TOKEN BY TOKEN on the final-norm hidden state: per token ||h - h_ref||_2
# / ||h_ref||_2, then its root mean square and its largest over all
# tokens; and |loss - loss_ref|.
#
# At initialisation every norm weight is one and every head of W_q and W_k
# has the same scale, so a QK-norm taken a head instead of over the whole
# projection would differ by the heads' sampling noise alone, and a norm's
# weight left out would show nowhere. The check therefore seeds, on both
# sides (:func:`seed_check_weights`): every norm weight 1 + CHECK_NORM_STD
# x normal, and in each full-attention layer the columns of W_q and W_k a
# head by a factor log-uniform in [1 / CHECK_HEAD_SPREAD,
# CHECK_HEAD_SPREAD].
#
# Readings on the v5e at the cell's widths, depth and share, two sequences
# of 8192 (my chip runs, PR 56; ``benchmark/tests/olmo_hybrid_faults.py``
# and the cell's own runs), as rms / largest of the per-token error:
#   sound (30 readings: 16 seeds of the faults file, half of them beyond
#   2^31, and 14 runs of the cell)
#                    rms 0.0247 - 0.0281, largest 0.075 - 0.148,
#                    |loss diff| 1.0e-5 - 5.2e-4
#   the QK-norm a head        rms 0.082 - 0.147, largest 0.29 - 0.36
#   rotated full attention    0.148 - 0.219 / 0.39 - 0.46
#   the mixer's operands in fp8 (e4m3)  0.178 - 0.193 / 0.35 - 0.43
#   beta not doubled 0.55 - 0.74; q without its 96^-1/2 0.69 - 0.80; the
#   decay a channel from a wrong broadcast 1.13 - 1.17; the decay dropped
#   1.19 - 1.23; the gate a sigmoid 1.30 - 1.31; k not normalised: not a
#   number (the rule no longer contracts: the state overflows)
#   NOT HELD BY THESE (the scan's own comparison below holds it): the delta
#   rule's state rounded to bf16 at chunk boundaries 0.0239 - 0.0260 /
#   0.077 - 0.132, inside the sound range — in four layers of bf16
#   activations a state's rounding every 128 positions drowns.
# Every listed fault is on the wrong side of one of THESE limits, or of the
# scan's below, on every seed tried (4 - 6 a fault). The per-token error
# has a tail (the largest is 3 - 5.5 x the rms: the OLMo block adds eight
# unit-norm sublayer outputs, and where they cancel a token's stream is
# short and the same absolute error is a larger share of it), so the rms
# is the limit that judges and the largest stands wide: 0.045 is 1.6 x the
# largest sound rms and 0.55 x the smallest faulty one (0.082, the QK-norm a
# head); 0.25 is 1.7 x the largest sound reading of 30 and 0.85 x the
# smallest a listed fault reads (0.29), and holds no fault the rms does not
# hold. The loss: the accepted cells' limit, 3.8 x the largest of 30 sound
# readings.
HIDDEN_REL_L2_RMS_MAX = 0.045
HIDDEN_REL_L2_MAX = 0.25
# |system loss - reference loss| (the cross entropy over the slice): the
# accepted JoyAI, LFM2 and Kimi cells' limit
REFERENCE_LOSS_ATOL = 2e-3
REFERENCE_SEQUENCES = 2
CHECK_NORM_STD = 0.1
CHECK_HEAD_SPREAD = 2.0
# rows of scores the reference's attention holds at a time
REFERENCE_ROW_BLOCK = 1024

# THE DELTA RULE BY ITSELF, forward and backward (the whole-model
# comparison holds no gradient, so nothing above runs ``gdn_bwd``):
# ``ops/kda.py::gdn_scan`` — the kernels the step runs, at the cell's
# widths (30 heads of 96 key and 192 value channels) and ONE seeded
# sequence of the timed length (64 chunks at 8192: the state crosses 63
# edges), bf16 operands as the model hands them — against
# ``olmo_hybrid_f32.gdn_recurrence`` (position by position, f32) on the
# same rounded inputs and ``jax.vjp`` of it, LEAF BY LEAF: ``o`` and the
# gradients ``dq, dk, dv, dg, dbeta`` under one seeded cotangent, each as
# the WORST HEAD'S ||got - want||_2 / ||want||_2. Inputs as the model's
# initialisation and a unit-rms stream give them (:func:`gdn_inputs`):
# ``β`` over (0, 2), ``g`` over the initialisation's range.
GDN_LEAVES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# positions of the recurrence whose states its backward holds at a time,
# and heads the comparison takes at a time
GDN_CHECK_BLOCK = 64
GDN_CHECK_HEADS = 5
#
# Readings on the v5e at [1, 8192, 30, 96 | 192] (my chip runs, PR 56;
# ``olmo_hybrid_faults.py``, ``gdn_micro.py``):
#                     o        dq       dk       dv       dg       dbeta
#   sound (16 seeds   .001661- .001662- .001663- .001661- .000066- .000047-
#   + the cell's 14)  .001677  .001678  .001701  .001675  .000440  .000335
#   state bf16 at     .001765- .001820- .001870- .001789- .001047- .000774-
#   chunk boundaries  .002178  .002250  .002571  .002216  .002129  .001824
#   operands in fp8   .044 - .060 in o, .050 - .080 in every gradient
#   the decay dropped, the wrong broadcast: 1.0 - 17 in every leaf
# ``o, dq, dk, dv`` read the ONE bf16 rounding of each result (0.00166:
# they move 1 % over 30 readings) and ``dg, dβ``, which leave in f32, what
# the kernels' arithmetic is worth: the scan's matmuls take THREE bf16
# passes (``ops/kda.py::_gdot``; since the review round the kernels read
# the heads unpadded, four a step, and give every leaf to the bit: 15
# more sound seeds and the cell's runs read inside the ranges above). With
# ONE pass, as the channel-wise kernels take, the same leaves read 0.0051 -
# 0.0099 (``gdn_micro.py``; 10 sound seeds), ABOVE the state's fault in every
# leaf — a recurrence whose state is rounded at the chunk boundaries was then
# more exact than the kernels, and no limit could tell them apart; that is
# why the passes are three, at 4.2 % of the cell's rate (PERF.md section 6).
# The limits: ``dg`` 0.0007 is 1.6 x the largest sound reading and 0.67 x the
# smallest of the state's fault, ``dβ`` 0.00052 is 1.55 x and 0.67 x: those
# two hold the state's fault on every seed (6). ``o, dq, dk, dv`` are bounded
# by the results' rounding on both sides (the fault's smallest ``o``,
# 0.001765, is 5 % over the largest sound one): 0.0019 is 12 - 13 % above the
# largest sound reading, holds the state's fault on 5 seeds of 6 and every
# other fault by 23 x or more.
GDN_REL_L2_MAX = {"o": 0.0019, "dq": 0.0019, "dk": 0.0019, "dv": 0.0019,
                  "dg": 0.0007, "dbeta": 0.00052}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's OlmoHybridConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # olmo_hybrid_flops.train_flops_per_token's total


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import olmo_hybrid_flops
    from torchft_tpu.models.olmo_hybrid import OlmoHybridConfig

    kinds = tuple(config["layer_types"])
    cannot = {
        k: config[k] for k, v in (
            ("hidden_act", "silu"), ("attention_bias", False),
            ("tie_word_embeddings", False),
            ("num_hidden_layers", len(kinds)),
            ("num_key_value_heads", config["num_attention_heads"]),
            ("linear_num_key_heads", config["num_attention_heads"]),
            ("linear_num_value_heads", config["linear_num_key_heads"]),
        ) if config[k] != v
    }
    if config["hidden_size"] % config["num_attention_heads"]:
        cannot["num_attention_heads"] = config["num_attention_heads"]
    if cannot:
        raise ValueError(f"models/olmo_hybrid.py does not compute {cannot}")
    job, opt = config["job"], config["optimizer"]
    cfg = OlmoHybridConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=kinds, n_heads=config["num_attention_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        rope_theta=config["rope_parameters"]["rope_theta"],
        d_ff=config["intermediate_size"],
        rms_eps=float(config["rms_norm_eps"]),
        init_std=float(config["initializer_range"]),
        remat=bool(job["remat"]),
        xent_chunks=int(job["xent_chunks"]),
    )
    peak, warm = float(opt["learning_rate"]), int(opt["warmup_steps"])
    tx = optax.adamw(
        # step c (from 0) runs at peak x (c + 1) / warm, then at peak
        optax.linear_schedule(peak / warm, peak, warm - 1),
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"],
        # matrices only; the taps [4, 11520], the norms, A_log and dt_bias
        # take none
        mask=lambda params: jax.tree_util.tree_map_with_path(
            lambda path, x: x.ndim >= 2
            and getattr(path[-2], "key", None) != "conv", params))
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=olmo_hybrid_flops.train_flops_per_token(
            **olmo_hybrid_flops.config_dims(config))["total"],
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

    from torchft_tpu.models.olmo_hybrid import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        _low_bits(seed))


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.olmo_hybrid import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.olmo_hybrid import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/olmo_hybrid_f32.terms`` from
    the program's config."""
    return dict(layer_types=cfg.layer_types, n_head=cfg.n_heads,
                key_dim=cfg.key_dim, value_dim=cfg.value_dim,
                eps=cfg.rms_eps)


def seed_check_weights(cfg: Any, params: Any, seed: Any) -> Any:
    """``params`` with every norm weight drawn ``1 + CHECK_NORM_STD x
    normal`` and, in each full-attention layer, the columns of ``W_q``
    and ``W_k`` a head scaled by a factor log-uniform in ``[1 /
    CHECK_HEAD_SPREAD, CHECK_HEAD_SPREAD]`` (the module's header); every
    other leaf is the same array, not a copy. ``seed`` an int or, inside
    a program, its low 32 bits as a uint32."""
    import math

    import jax
    import jax.numpy as jnp

    key = jax.random.key(_low_bits(seed))
    drawn = [0]     # the leaves come in the tree's own order: a stable index
    span = math.log(CHECK_HEAD_SPREAD)

    def leaf(path, x):
        names = [getattr(p, "key", None) for p in path]
        drawn[0] += 1
        k = jax.random.fold_in(key, drawn[0])
        if names[-1] == "scale":
            new = 1.0 + CHECK_NORM_STD * jax.random.normal(k, x.shape, x.dtype)
        elif names[-3:-1] in (["attn", "q_proj"], ["attn", "k_proj"]):
            heads = jnp.exp(jax.random.uniform(
                k, (cfg.n_heads,), x.dtype, -span, span))
            new = x * jnp.repeat(heads, x.shape[1] // cfg.n_heads)
        else:
            return x
        return new

    return jax.tree_util.tree_map_with_path(leaf, params)


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None,
               row_block: Optional[int] = REFERENCE_ROW_BLOCK) -> Callable:
    """``(system_params, reference_params, tokens, targets, check_seed)
    -> small arrays``, to be jitted: ``models/olmo_hybrid.py`` as it trains
    against ``reference/olmo_hybrid_f32.py`` in ONE program, so that
    neither side's hidden states outlive it (``families/olmoe.py``). The
    cell passes the same weights twice; a fault passes faulty ones
    first, another ``system_cfg`` or another ``attn_fn``. Both sides'
    weights are seeded from ``check_seed`` (a uint32) INSIDE the program
    (:func:`seed_check_weights`: the seeded copies are the program's
    temporaries, no array beside the training state). What comes back: ``error`` [N], every token's ||h - h_ref||_2 / ||h_ref||_2 on
    the final-norm hidden state; both losses; and the gauge of the first
    linear layer's step and decay on the system's side: ``beta_over_1``
    (the share of (position, head) pairs with β > 1: the
    negative-eigenvalue branch) and the least and largest ``exp(g)``."""
    import jax.numpy as jnp

    from benchmark.reference import olmo_hybrid_f32
    from torchft_tpu.models.common import embed
    from torchft_tpu.models.olmo_hybrid import (
        LINEAR,
        decay_and_step,
        loss_terms,
    )

    def both(p, p_ref, tok, tgt, check_seed):
        run = system_cfg or cfg
        p, p_ref = (seed_check_weights(cfg, z, check_seed)
                    for z in (p, p_ref))
        got = loss_terms(run, p, tok, tgt, attn_fn)
        rows = min(row_block or tok.shape[1], tok.shape[1])
        want = olmo_hybrid_f32.terms(p_ref, tok, tgt, row_block=rows,
                                     **reference_dims(cfg))
        h = got["hidden"].astype(jnp.float32).reshape(-1, cfg.d_model)
        h_ref = want["hidden"].reshape(-1, cfg.d_model)
        out = {
            "error": (jnp.linalg.norm(h - h_ref, axis=-1)
                      / jnp.linalg.norm(h_ref, axis=-1)),
            "loss": got["loss"], "reference_loss": want["loss"],
        }
        if run.layer_types[0] == LINEAR:
            g, beta = decay_and_step(run, p["layers_0"]["gdn"],
                                     embed(run, p, tok))
            decay = jnp.exp(g)
            out.update(beta_over_1=jnp.mean(beta > 1.0),
                       decay_min=jnp.min(decay), decay_max=jnp.max(decay))
        return out

    return both


def per_token_errors(cfg: Any, system_params: Any, reference_params: Any,
                     tokens: Any, targets: Any, check_seed: int,
                     fn: Optional[Callable] = None,
                     **faults: Any) -> Dict[str, Any]:
    """:func:`comparison`, jitted (or ``fn``, already jitted) and run
    once a sequence — each beside the training state —, the sequences'
    errors joined and their losses and gauges averaged."""
    import jax
    import numpy as np

    fn = fn or jax.jit(comparison(cfg, **faults))
    bits = _low_bits(check_seed)
    seen = [jax.device_get(fn(system_params, reference_params,
                              tokens[i:i + 1], targets[i:i + 1], bits))
            for i in range(tokens.shape[0])]
    out = {k: np.mean([s[k] for s in seen], axis=0) for k in seen[0]
           if k not in ("error", "decay_min", "decay_max")}
    out["error"] = np.concatenate([s["error"] for s in seen])
    if "decay_min" in seen[0]:
        out["decay_min"] = min(float(s["decay_min"]) for s in seen)
        out["decay_max"] = max(float(s["decay_max"]) for s in seen)
    return out


def gdn_inputs(cfg: Any, seed: Any, seq_len: int):
    """``((q, k, v, g, beta), do)`` of one sequence at ``cfg``'s widths,
    drawn as the model's initialisation and a unit-rms stream give them:
    ``q̃, k̃, v`` the silu of a standard normal, l2-normed and scaled as
    the mixer does, in the compute dtype; ``g = −A·softplus(dt_bias +
    z)`` with ``A`` and ``dt_bias`` as ``models/olmo_hybrid.py::
    _gdn_params`` draws them, one a head (``z`` standard normal: ``n·W_a``
    at init 0.02 over 3840 unit inputs has a standard deviation of
    1.24); ``β = 2σ(z)`` over (0, 2); the cotangent standard normal.
    ``seed`` as :func:`seed_check_weights` takes it."""
    import math

    import jax
    import jax.numpy as jnp

    H, K, V = cfg.n_heads, cfg.key_dim, cfg.value_dim
    k = jax.random.split(jax.random.key(_low_bits(seed)), 8)
    f32, dt = jnp.float32, cfg.dtype

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def drawn(key, width):
        return jax.nn.silu(jax.random.normal(key, (1, seq_len, H, width), f32))

    a = jnp.maximum(jax.random.uniform(k[3], (H,), f32, 0.0, 16.0), 1e-4)
    step = jnp.exp(jax.random.uniform(
        k[4], (H,), f32, math.log(1e-3), math.log(1e-1)))
    g = -a * jax.nn.softplus(step + jnp.log(-jnp.expm1(-step))
                             + jax.random.normal(k[5], (1, seq_len, H), f32))
    return (
        (l2(drawn(k[0], K)) * K ** -0.5).astype(dt),
        l2(drawn(k[1], K)).astype(dt), drawn(k[2], V).astype(dt), g,
        2.0 * jax.nn.sigmoid(jax.random.normal(k[6], (1, seq_len, H), f32)),
    ), jax.random.normal(k[7], (1, seq_len, H, V), f32).astype(dt)


def recurrence_in_blocks(q: Any, k: Any, v: Any, g: Any, beta: Any,
                         block: int = GDN_CHECK_BLOCK,
                         at_edge: Optional[Callable] = None) -> Any:
    """``olmo_hybrid_f32.gdn_recurrence`` — the reference's
    ``gdn_step`` one position after the other from a zero state, f32 —
    laid out so that its ``jax.vjp`` fits beside the training state: a
    scan over blocks of ``block`` positions (the largest divisor of the
    length that ``block`` holds), each a CHECKPOINTED scan over its
    positions. The backward then keeps the blocks' entering states and
    one block's positions (0.1 GiB at 64 positions of five heads), where
    the plain scan's keeps every position's state: 0.6 GB a head and
    kept array at 8192. The numbers are the plain scan's. ``at_edge`` is
    applied to the state where a block ends (the faults file's rounding
    at chunk boundaries)."""
    import math

    import jax
    import jax.numpy as jnp

    from benchmark.reference import olmo_hybrid_f32

    f32 = jnp.float32
    B, S, H, K = q.shape
    block = math.gcd(S, block)

    def blocks(z):      # [B, S, ...] -> [S / block, block, B, ...]
        z = jnp.moveaxis(z.astype(f32), 1, 0)
        return z.reshape((S // block, block) + z.shape[1:])

    @jax.checkpoint
    def one_block(state, xs):
        state, o = jax.lax.scan(olmo_hybrid_f32.gdn_step, state, xs)
        return (at_edge(state) if at_edge else state), o

    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(one_block, jnp.zeros((B, H, K, v.shape[3]), f32),
                            tuple(blocks(z) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((S,) + o.shape[2:]), 0, 1)


def gdn_comparison(scan_fn: Optional[Callable] = None) -> Callable:
    """``(args, do) -> {leaf: the worst head's relative L2 error}`` over
    ``GDN_LEAVES``, to be jitted: ``scan_fn`` (the program's
    ``gdn_scan``; a fault passes another) and its ``jax.vjp`` against the
    reference's recurrence and its own (:func:`recurrence_in_blocks`), on
    the same inputs — ``GDN_CHECK_HEADS`` heads at a time, each group
    cut out of the operands where they lie and compared at once, so that
    no f32 copy of a whole operand stands beside the training state."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.kda import gdn_scan

    def both(args, do):
        f32 = jnp.float32
        got, pull = jax.vjp(scan_fn or gdn_scan, *args)
        got = (got,) + pull(do)
        H = do.shape[2]
        group = next(n for n in range(GDN_CHECK_HEADS, 0, -1) if H % n == 0)

        def one_group(first):
            mine, theirs = ([jax.lax.dynamic_slice_in_dim(
                z, first, group, axis=2).astype(f32) for z in side]
                for side in ((*args, do), got))
            want, pull = jax.vjp(recurrence_in_blocks, *mine[:5])
            want = (want,) + pull(mine[5])

            def a_head(z):                   # [1, S, group, ...] -> [group]
                return jnp.sqrt(jnp.sum(jnp.square(z).reshape(
                    z.shape[1], group, -1), axis=(0, 2)))

            return [a_head(a - b) / a_head(b) for a, b in zip(theirs, want)]

        errors = jax.lax.map(one_group, jnp.arange(0, H, group))
        return {n: jnp.max(e) for n, e in zip(GDN_LEAVES, errors)}

    return both


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge_gdn(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`gdn_comparison`'s errors against ``GDN_REL_L2_MAX``."""
    over = [n for n in GDN_LEAVES if not float(seen[n]) <= GDN_REL_L2_MAX[n]]
    return {"ok": not over, "gdn_over": over,
            "gdn_rel_l2": {n: _short(seen[n]) for n in GDN_LEAVES}}


def judge(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`per_token_errors` against the limits at the head of this
    file; the gauge (``beta_over_1``, ``decay_range``) is printed and
    judged by nothing."""
    import numpy as np

    rms = float(np.sqrt(np.mean(seen["error"] ** 2)))
    worst = float(seen["error"].max())
    loss, loss_ref = float(seen["loss"]), float(seen["reference_loss"])
    diff = abs(loss - loss_ref)
    out = {
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
    if "beta_over_1" in seen:
        out["beta_over_1"] = _short(seen["beta_over_1"])
        out["decay_range"] = [_short(seen["decay_min"]),
                              _short(seen["decay_max"])]
    return out


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the norms'
    weights and the attention heads' scales seeded on both sides) and
    ``REFERENCE_SEQUENCES`` seeded sequences, at the configuration's
    widths, depth and share; then the delta rule alone, forward and
    backward, against the recurrence at the timed length."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x6f68, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    whole = judge(per_token_errors(model.cfg, params, params, tokens,
                                   targets, seed))
    # the scan's inputs are drawn inside its program, the seeded weights
    # inside the other: the check puts no array beside the training state
    # but its two sequences of ids, and both programs' temporaries are
    # under the step's, so ``peak_hbm_gib`` is the training loop's
    alone = jax.jit(lambda bits: gdn_comparison()(
        *gdn_inputs(model.cfg, bits, model.seq_len)))
    with jax.default_device(device):
        scan = judge_gdn(jax.device_get(alone(_low_bits(seed))))
    return {**whole, **scan, "ok": whole["ok"] and scan["ok"]}
