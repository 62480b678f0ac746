"""Adapter for the Ouro family (``torchft_tpu/models/ouro.py``): the six
functions of ``families/olmo_hybrid.py`` — ``build``, ``init_state``,
``make_train_step``, ``make_grad_step``, ``flops_per_token``,
``check_reference`` — and ``flash_calls``, and nothing of any one
configuration. The step programs are the one step maker's
(``models/transformer.py``) with this family's loss; the optimizer is the
configuration's AdamW behind a linear warm-up (an optax schedule: its count
is optimizer state) under ``optim.with_step_stats``, which keeps the exit
distribution's three statistics of a step in the optimizer state for the
wrapper's gauges. ``check_reference`` is ``judge(per_token_errors(...))``;
the pair is apart so that a test or ``tests/ouro_faults.py`` can run a
faulty system against the sound reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# ``correct`` for this family: the system's ``loss_terms`` — the program
# family the timed step differentiates: bf16 stream and operands, f32
# accumulation, norms, softmax statistics, lse, gate and mix; the scan over
# the passes, the flash kernels, the weighted sweep of ``ops/xent.py`` over the T·N
# stacked rows — against ``reference/ouro_f32.py`` (f32, ``highest``, four
# unrolled passes, a dense head a block of rows) on the same weights and
# REFERENCE_SEQUENCES seeded sequences of the timed length, ONE AT A TIME
# (each beside 7.35 GB of training state), at the cell's widths, depth and
# T. What is compared, and why each:
#   loss        |objective − reference's|: the one number the mix's β term
#               and the weighing reach as the step differentiates them
#   pass_loss   the largest over the T passes of |mean_i ℓ_t,i − ref's|: a
#               pass's head on a pass's stream
#   nll         |ℓ_t,i − ref's| over all T·N, root mean square and largest:
#               token by token, so that a fault that moves tokens both ways
#               and leaves the means alone is seen
#   p           the largest |p_t,i − ref's|: the gate, the survival product
#               and which stream the gate reads
#
# At initialisation every norm weight is one and nothing tells a norm left
# out from one that is there, and the gate's bias is zero. The check
# therefore seeds, on both sides (:func:`seed_check_weights`): every norm
# weight 1 + CHECK_NORM_STD x normal, and ``b_g`` moved by CHECK_GATE_BIAS +
# CHECK_GATE_BIAS_STD x normal, which keeps every pass between 5 % and 60 %
# of the mean mass (``mass`` on the check's line, judged by nothing: 0.28 /
# 0.18 / 0.08 / 0.45 on the sweep's seed; at a zero bias the first pass
# holds half). NOTHING ELSE IS SHARPENED. ISSUE 73 asked for ``W_q``, ``W_k``
# x 5 and ``w_g`` x 3 (a flat softmax hides a rotation; a near-uniform ``p``
# hides the mix) and the chip said no (my chip runs, PR 73, one sequence of
# 8192 on seed 777, sound / θ 1e4 / fp8 stream as a token's loss's rms):
#   W_q, W_k x 1     0.0176 / 0.543 / 0.088   (p: 0.011 / 0.52 / 0.062)
#   W_q, W_k x 1.5   0.0273 / 0.762 / 0.114
#   W_q, W_k x 2     0.495  / 1.02  / 0.620   — the SOUND system off by half
#                    a nat: at q·k/√128 over 2 048-wide unit inputs the
#                    logits' standard deviation is 0.8 x gain², so x 2 is 3.2
#                    over 8 192 keys, a softmax of a few winners that bf16
#                    rounding re-elects, thirty-two layer-steps in a row
#                    (x 5 would be 20). At x 1 the softmax is NOT flat (0.8:
#                    the model's 1/√128, not a 1/64) and θ 1e4 already reads
#                    thirty times the sound system;
#   w_g x 3          p 0.032 / 0.93 / 0.20 and a third pass of 2 % of the
#                    mass (the gate saturates: a token leaves at once or
#                    never); x 1: p 0.011 / 0.52 / 0.062, the third pass 8 %.
# The TIMED weights are the initialisation's.
#
# READINGS on the v5e at the cell's widths, depth, passes and length, two
# sequences of 8192 a seed (my chip runs, PR 73; ``benchmark/tests/
# ouro_faults.py --sound 6 --faulty 2 --seed 9000``: 6 sound seeds, half of
# them beyond 2^31, and one more of one sequence; 2 other seeds each fault),
# as loss / a pass's loss / a token's loss rms and worst / p's worst:
#   sound            2.0e-5 - 2.7e-4 / 5e-5 - 4.5e-4 / 0.0171 - 0.0189 and
#                    0.097 - 0.136 / 0.0114 - 0.0163
#   the stream between layer-steps rounded to fp8 (e4m3: the nearest
#   precision below the configuration's)
#                    1.5e-4 - 5.8e-4 / 6e-4 - 1.4e-3 / 0.0880 - 0.0886 and
#                    0.587 - 0.654 / 0.067 - 0.078      -> rms, worst, p
#   three passes for four   loss 0.0099 - 0.0166, p 0.73 - 0.75 (the third
#                    pass takes the remainder)          -> loss, p
#   the un-normed stream re-entering   rms 0.861 - 0.871, a pass's 0.0076
#   the MLP's outgoing norm left out   rms 0.457 - 0.458, p 0.25 - 0.29
#   the gate on the un-normed stream   loss 0.024 - 0.034, p 0.55 - 0.57, a
#                    token's loss as sound               -> loss, p
#   p_T = λ_T S_{T-1} (no remainder)   loss 2.2 - 2.8, p 0.59 - 0.70
#   β's term dropped 0.0596 - 0.0619, its sign turned 0.119 - 0.124, the
#   four losses averaged unweighted 0.0591 - 0.0646: every other number as
#   sound                                                -> the loss alone
#   θ 1e4 for 1e6    rms 0.524 - 0.527, p 0.59 - 0.63
# Every fault is on the wrong side of at least one of THESE limits on every
# seed tried (``ouro_faults.py`` prints "0 on the wrong side"). ISSUE 73
# asked for limits at about five times the sound distance; the precision
# below reads 4.1 - 4.7 times it in the three per-token numbers, so those
# three stand at the geometric middle of their two readings instead, twice
# the largest sound reading and half the smallest fp8 one: a token's loss
# rms 0.04 (2.1 x 0.0189, 0.45 x 0.0880; the sound rms moves 10 % over seven
# seeds: every token's error is the sum of sixty-four sublayers' roundings),
# its worst 0.28 (2.06 x 0.136, 0.48 x 0.587; a maximum over 65 536 numbers
# with a light tail), p's worst 0.033 (2.0 x 0.0163, 0.49 x 0.067). The
# loss: the accepted cells' limit (JoyAI, LFM2, Kimi, Olmo Hybrid, Granite),
# 7.4 x the largest sound reading and 1/30 of the least reading of a fault
# it alone holds (0.0591). A pass's loss 1.5e-3: 3.3 x the largest sound
# reading, half the least a structural fault reads (2.9e-3: θ, the MLP's
# norm); fp8 is not its to hold.
LIMITS = {
    "loss_abs_diff": 2e-3,
    "pass_loss_abs_diff": 1.5e-3,
    "nll_abs_rms": 0.04,
    "nll_abs_max": 0.28,
    "p_abs_max": 0.033,
}
REFERENCE_SEQUENCES = 2
CHECK_NORM_STD = 0.1
CHECK_GATE_BIAS = -1.0
CHECK_GATE_BIAS_STD = 0.25
# rows of scores / of logits the reference holds at a time
REFERENCE_ROW_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's OuroConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # ouro_flops.train_flops_per_token's total


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import ouro_flops
    from torchft_tpu.models import ouro
    from torchft_tpu.optim import with_step_stats

    kinds = config["layer_types"]
    cannot = {
        k: config[k] for k, v in (
            ("hidden_act", "silu"), ("tie_word_embeddings", False),
            ("use_sliding_window", False), ("rope_scaling", None),
            ("num_hidden_layers", len(kinds)),
            ("num_key_value_heads", config["num_attention_heads"]),
            ("layer_types", ["full_attention"] * len(kinds)),
        ) if config[k] != v
    }
    if cannot:
        raise ValueError(f"models/ouro.py does not compute {cannot}")
    job, opt = config["job"], config["optimizer"]
    cfg = ouro.OuroConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], ut_steps=config["total_ut_steps"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        exit_entropy_weight=float(
            config["objective"]["exit_entropy_weight"]),
        init_std=float(config["initializer_range"]),
        remat=bool(job["remat"]), xent_chunks=int(job["xent_chunks"]),
    )
    peak, warm = float(opt["learning_rate"]), int(opt["warmup_steps"])
    tx = with_step_stats(optax.adamw(
        # step c (from 0) runs at peak x (c + 1) / warm, then at peak
        optax.linear_schedule(peak / warm, peak, warm - 1),
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"],
        # matrices (w_g [d, 1] among them) and tables only; the norms and
        # the gate's bias take none
        mask=lambda params: jax.tree_util.tree_map(
            lambda x: x.ndim >= 2, params)),
        ouro.is_exit_stats, ouro.publish_exit_gauges)
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=ouro_flops.train_flops_per_token(
            **ouro_flops.config_dims(config))["total"],
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

    from torchft_tpu.models.ouro import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        _low_bits(seed))


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.ouro import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.ouro import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def flash_calls(config: Dict[str, Any]) -> Any:
    """The flash calls a step makes, for ``readers/flash_rooflines.py``
    (found by the configuration's ``family``: ``flash_flops.calls_of``)."""
    from benchmark import ouro_flops

    return ouro_flops.flash_calls(config)


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/ouro_f32.terms`` from the
    program's config."""
    return dict(n_layers=cfg.n_layers, ut_steps=cfg.ut_steps,
                n_head=cfg.n_heads, head_dim=cfg.head_dim,
                theta=cfg.rope_theta, eps=cfg.rms_eps,
                beta=cfg.exit_entropy_weight)


def seed_check_weights(params: Any, seed: Any) -> Any:
    """``params`` with every norm weight drawn ``1 + CHECK_NORM_STD x
    normal`` and ``b_g`` moved by ``CHECK_GATE_BIAS + CHECK_GATE_BIAS_STD x
    normal`` (the module's header); every other leaf is the same array, not
    a copy. ``seed`` an int or, inside a program, its low 32 bits as a
    uint32."""
    import jax

    key = jax.random.key(_low_bits(seed))
    drawn = [0]     # the leaves come in the tree's own order: a stable index

    def leaf(path, x):
        names = [getattr(k, "key", None) for k in path]
        drawn[0] += 1
        noise = jax.random.normal(
            jax.random.fold_in(key, drawn[0]), x.shape, x.dtype)
        if names[-1] == "scale":
            return 1.0 + CHECK_NORM_STD * noise
        if names[-2:] == ["exit_gate", "bias"]:
            return x + CHECK_GATE_BIAS + CHECK_GATE_BIAS_STD * noise
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None,
               row_block: Optional[int] = REFERENCE_ROW_BLOCK) -> Callable:
    """``(system_params, reference_params, tokens, targets, check_seed)
    -> small arrays``, to be jitted: ``models/ouro.py`` as it trains
    against ``reference/ouro_f32.py`` in ONE program, so that neither
    side's streams outlive it (``families/olmoe.py``). The cell passes the
    same weights twice; a fault passes faulty ones first, another
    ``system_cfg`` or another ``attn_fn``. Both sides' weights are seeded
    from ``check_seed`` (a uint32) INSIDE the program
    (:func:`seed_check_weights`: the seeded copies are the program's
    temporaries, no array beside the training state). What comes back:
    both sides' ``loss``, ``nll`` [T, N] and ``p`` [T, N]."""
    from benchmark.reference import ouro_f32
    from torchft_tpu.models.ouro import loss_terms

    def both(p, p_ref, tok, tgt, check_seed):
        p, p_ref = (seed_check_weights(z, check_seed) for z in (p, p_ref))
        got = loss_terms(system_cfg or cfg, p, tok, tgt, attn_fn)
        want = ouro_f32.terms(p_ref, tok, tgt, row_block=row_block,
                              **reference_dims(cfg))
        return {"loss": got["loss"], "nll": got["nll"], "p": got["p"],
                "reference_loss": want["loss"], "reference_nll": want["nll"],
                "reference_p": want["p"]}

    return both


def per_token_errors(cfg: Any, system_params: Any, reference_params: Any,
                     tokens: Any, targets: Any, check_seed: int,
                     fn: Optional[Callable] = None,
                     **faults: Any) -> Dict[str, Any]:
    """:func:`comparison`, jitted (or ``fn``, already jitted) and run once a
    sequence — each beside the training state —, the sequences' per-token
    arrays joined along the tokens and their losses averaged."""
    import jax
    import numpy as np

    fn = fn or jax.jit(comparison(cfg, **faults))
    bits = _low_bits(check_seed)
    seen = [jax.device_get(fn(system_params, reference_params,
                              tokens[i:i + 1], targets[i:i + 1], bits))
            for i in range(tokens.shape[0])]
    out = {k: np.concatenate([s[k] for s in seen], axis=1)
           for k in ("nll", "p", "reference_nll", "reference_p")}
    out.update({k: float(np.mean([s[k] for s in seen]))
                for k in ("loss", "reference_loss")})
    return out


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`per_token_errors` against ``LIMITS``: every number beside its
    limit, ``over`` the names of those beyond theirs, and ``mass``, the
    reference's mean exit probability a pass (judged by nothing)."""
    import numpy as np

    # a system of fewer passes is held over those it has (and by the loss)
    passes = min(seen["nll"].shape[0], seen["reference_nll"].shape[0])
    nll, p, nll_ref, p_ref = (seen[k][:passes] for k in (
        "nll", "p", "reference_nll", "reference_p"))
    gap = np.abs(nll - nll_ref)
    read = {
        "loss_abs_diff": abs(seen["loss"] - seen["reference_loss"]),
        "pass_loss_abs_diff": np.abs(
            nll.mean(axis=1) - nll_ref.mean(axis=1)).max(),
        "nll_abs_rms": np.sqrt(np.mean(gap ** 2)),
        "nll_abs_max": gap.max(),
        "p_abs_max": np.abs(p - p_ref).max(),
    }
    over = [k for k, v in read.items() if not float(v) <= LIMITS[k]]
    return {
        "ok": not over, "over": over,
        **{k: _short(v) for k, v in read.items()}, "limits": dict(LIMITS),
        "system_loss": round(seen["loss"], 5),
        "reference_loss": round(seen["reference_loss"], 5),
        "tokens": int(nll.shape[1]), "passes": passes,
        "mass": [round(float(m), 3)
                 for m in seen["reference_p"].mean(axis=1)],
    }


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the norms'
    weights and the gate's bias seeded on both sides) and
    ``REFERENCE_SEQUENCES`` seeded sequences, at the configuration's
    widths, depth, passes and length."""
    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x6f75, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    return judge(per_token_errors(model.cfg, params, params, tokens, targets,
                                  seed))
