"""Adapter for the Phi-4-mini-flash family
(``torchft_tpu/models/phi4flash.py``): the six functions of
``families/nemotron_h.py`` — ``build``, ``init_state``,
``make_train_step``, ``make_grad_step``, ``flops_per_token``,
``check_reference`` — and nothing of any one configuration. The step
programs are the one step maker's (``models/transformer.py``) with this
family's loss; the optimizer is the configuration's AdamW behind a linear
warm-up (an optax schedule: its count is optimizer state).
``check_reference`` is ``judge(per_token_errors(...))`` and
``judge_scan(scan_comparison(...))``; each pair is apart so that a test or
``tests/phi4flash_faults.py`` can run a faulty system against the sound
reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, lambda, softplus, decays and scan state) against the f32
# reference on the same share (rows 0-25087 of the one table), the same
# weights and two sequences of 8192, TOKEN BY TOKEN on the final-norm
# hidden state: per token ||h - h_ref||_2 / ||h_ref||_2, then its root mean
# square and its largest over all 16 384 tokens; and |loss - loss_ref|.
# Every LayerNorm and projection bias is zero at initialisation, so the
# check seeds them (normal, ``CHECK_BIAS_STD``) on both sides: a bias left
# out would otherwise show nowhere.
#
# Readings on the v5e (my chip runs, PR 47; ``benchmark/tests/
# phi4flash_faults.py``: 20 sound seeds and the cell's own runs, 2 seeds a
# fault), as rms / largest of the per-token error:
#   sound             rms 0.0189 - 0.0218, largest 0.0235 - 0.0272,
#                     |loss diff| 7.6e-6 - 3.0e-4
#   window 511 keys   rms 0.0293 - 0.0302, largest 0.137 - 0.163
#   window 513 keys   rms 0.0294 - 0.0302, largest 0.150 - 0.163
#   the scan's decays in bf16      0.0305 - 0.0862 / 0.131 - 0.52
#   the cross layer on layer 1's k, v   0.105 / 0.117
#   the GMU on the gated y 0.132 - 0.143; m without D x 0.138 - 0.140;
#   lambda_init of the cut's index 0.147 - 0.158; lambda left out 0.174 -
#   0.264; a LayerNorm's bias left out 0.367 - 0.368; no window 0.455 -
#   0.478; (1 - lambda_init) left out 0.545 - 0.556; the 128-wide norm left
#   out 0.675 - 0.678; the value halves swapped 0.917 - 0.927; the taps
#   reversed 1.294 - 1.297; softplus left out: not a number
#   NOT HELD BY THESE TWO (the scan's own comparison holds the first): the
#   scan's state in bf16 0.0191 - 0.0220 / 0.0238 - 0.0613; and
#   ``phi4flash_faults.UNLISTED``: bf16 parameters 0.0197 - 0.0200 /
#   0.0242 - 0.0258, inside the sound range.
# Every listed fault is on the wrong side of one of THESE limits, or of the
# scan's below, on every seed tried. The sound rms moves more from seed to
# seed here than in the sibling cells (15 % over 21 seeds, where LFM2's
# moves 1 %), so the limit cannot stand close enough to hold bf16
# parameters: 0.025 is 1.15 x the largest sound reading and 0.85 x the
# smallest faulty one the rms must catch alone (0.0293, a window one key
# off - which the largest error holds five times over: a token at the
# window's edge is 0.14 - 0.16 off). The largest error of a token: 0.045 is
# 1.65 x the largest sound reading and a third of the least a listed fault
# reads. The loss: the accepted LFM2 and JoyAI cells' limit, 6.6 x the
# largest of 21 sound readings.
HIDDEN_REL_L2_RMS_MAX = 0.025
HIDDEN_REL_L2_MAX = 0.045
REFERENCE_LOSS_ATOL = 2e-3
REFERENCE_SEQUENCES = 2
CHECK_BIAS_STD = 0.05
# rows of scores the reference's attention holds at a time
REFERENCE_ROW_BLOCK = 512

# THE SCAN BY ITSELF, forward and backward (the whole-model comparison
# holds no gradient, so nothing above runs ``s6_bwd``): ``ops/s6.py::
# s6_scan`` — the kernels the step runs, at the cell's widths (5120
# channels, 16 states), one seeded sequence of SCAN_SEQ positions (32
# chunks: the state and its cotangent cross 31 boundaries), bf16 operands
# as the model hands them — against ``phi4flash_f32.selective_scan`` (the
# recurrence, position by position, f32) on the same rounded inputs and
# ``jax.vjp`` of it, LEAF BY LEAF: ``y`` and the gradients ``dx, dΔ, dA,
# dB, dC, dD`` under one seeded cotangent, each as ||got - want||_2 /
# ||want||_2 — for the wide leaves (``SCAN_BY_BLOCK``: 5120 channels) the
# WORST block of 512 channels, the kernels' own grid step. Inputs as the
# model's initialisation and a unit-rms stream give them
# (:func:`scan_inputs`).
SCAN_SEQ = 2048
SCAN_LEAVES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
SCAN_BY_BLOCK = ("y", "dx", "ddt", "dA")
SCAN_BLOCK = 512
#
# Readings on the v5e (my chip runs, PR 47; 21 sound seeds, 2 a stand-in):
#                    y        dx       dΔ       dA       dB       dC      dD
#   sound           .001659- .001660- 0        8.6e-7-  .001645- .001631- 2.2e-7-
#                   .001664  .001665           9.7e-7   .001680  .001680  2.3e-7
#   state in bf16   .00136-  .00132-  .0134-   .0101    .00438-  .00455-  0
#                   .00138   .00134   .0135             .00440   .00463
#   decays in bf16  .00209-  .00209-  .0515-   .0332-   .00635-  .00623-  0
#                   .00216   .00214   .0549    .0349    .00639   .00646
# ``y``, ``dx``, ``dB``, ``dC`` read the one bf16 rounding of each result
# and move 2 % over the seeds (the stand-ins' ``y`` and ``dx`` read LESS:
# inside one program the TPU compiler elides their final cast, so they are
# never rounded): 0.0025 is 1.5 x the largest sound reading, and for ``dB``
# / ``dC`` 0.0028 is 1.67 x it and 0.64 x the smallest faulty one. ``dΔ``
# reads exactly 0 on every seed: on the chip the kernels ARE the
# recurrence, the same float32 operations in the same order a position
# (the CPU interpreter reads 1.4e-7), so 1e-4 is 1 / 130 of the smallest
# faulty reading. ``dA`` and ``dD`` are f32 sums over the positions taken
# in another order: 1e-4 is 100 x the largest sound ``dA`` and 1 / 100 of
# the faulty one; ``dD`` has no faulty reading (the stand-ins compute it as
# the reference does) and 1e-5 is 43 x the sound one.
SCAN_REL_L2_MAX = {"y": 0.0025, "dx": 0.0025, "ddt": 1e-4, "dA": 1e-4,
                   "dB": 0.0028, "dC": 0.0028, "dD": 1e-5}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's Phi4FlashConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # phi4flash_flops.train_flops_per_token's total


def decayed(params: Any) -> Any:
    """The weight-decay mask: matrices, but not ``A_log`` (a table of
    decay rates, which Mamba's own recipe exempts)."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.ndim >= 2
        and getattr(path[-1], "key", None) != "A_log", params)


def build(config: Dict[str, Any]) -> Model:
    import optax

    from benchmark import phi4flash_flops
    from torchft_tpu.models.phi4flash import Phi4FlashConfig

    ids, ssm = tuple(config["layer_ids"]), config["mamba"]
    cannot = {
        k: config[k] for k, v in (
            ("hidden_act", "silu"), ("tie_word_embeddings", True),
            ("mlp_bias", False), ("lm_head_bias", False), ("embd_pdrop", 0),
            ("resid_pdrop", 0), ("num_hidden_layers", len(ids)),
        ) if config[k] != v
    }
    if config["hidden_size"] % config["num_attention_heads"]:
        cannot["num_attention_heads"] = config["num_attention_heads"]
    if not ssm["conv_bias"]:
        cannot["mamba.conv_bias"] = False
    if cannot:
        raise ValueError(f"models/phi4flash.py does not compute {cannot}")
    job, opt, share = config["job"], config["optimizer"], config["vocab_share"]
    cfg = Phi4FlashConfig(
        vocab_size=config["vocab_size"], vocab_ways=share["vocab_ways"],
        first_vocab_row=share["first_vocab_row"],
        d_model=config["hidden_size"],
        n_published_layers=config["published"]["num_hidden_layers"],
        layer_ids=ids, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        window=config["sliding_window"], d_ff=config["intermediate_size"],
        d_inner=ssm["expand"] * config["hidden_size"],
        d_state=ssm["d_state"], dt_rank=ssm["dt_rank"],
        conv_kernel=ssm["d_conv"], dt_min=float(ssm["dt_min"]),
        dt_max=float(ssm["dt_max"]), dt_floor=float(ssm["dt_init_floor"]),
        ln_eps=float(config["layer_norm_eps"]),
        init_std=float(config["initializer_range"]),
        lambda_std=float(config["lambda_std"]),
        remat=bool(job["remat"]), xent_chunks=int(job["xent_chunks"]),
    )
    peak, warm = float(opt["learning_rate"]), int(opt["warmup_steps"])
    tx = optax.adamw(
        # step c (from 0) runs at peak x (c + 1) / warm, then at peak
        optax.linear_schedule(peak / warm, peak, warm - 1),
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], mask=decayed)
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=phi4flash_flops.train_flops_per_token(
            **phi4flash_flops.config_dims(config))["total"],
    )


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models.phi4flash import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    # --seed may pass 2**31: the key takes its low 32 bits, unsigned
    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        np.uint32(seed & 0xFFFFFFFF)
    )


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.phi4flash import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.phi4flash import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/phi4flash_f32.terms`` from the
    program's config."""
    return dict(
        layer_ids=cfg.layer_ids, n_layers=cfg.n_published_layers,
        n_head=cfg.n_heads, n_kv=cfg.n_kv_heads, window=cfg.window,
        state=cfg.d_state, rank=cfg.dt_rank, eps=cfg.ln_eps,
    )


def seed_biases(params: Any, seed: int) -> Any:
    """``params`` with every bias that is zero at initialisation (the
    LayerNorms', ``Wqkv``'s, ``Wq``'s, the output projections') drawn
    normal with ``CHECK_BIAS_STD`` from ``seed``; every other leaf
    (``b_dt`` and the convolution's bias among them) is the same array,
    not a copy."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    drawn = [0]     # the leaves come in the tree's own order: a stable index

    def leaf(path, x):
        names = [getattr(p, "key", None) for p in path]
        if names[-1] != "bias" or names[-2] in ("dt_proj", "conv"):
            return x
        drawn[0] += 1
        return jax.device_put(
            CHECK_BIAS_STD * jax.random.normal(
                jax.random.fold_in(key, drawn[0]), x.shape, x.dtype),
            x.sharding)

    return jax.tree_util.tree_map_with_path(leaf, params)


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Callable] = None,
               row_block: Optional[int] = REFERENCE_ROW_BLOCK) -> Callable:
    """``(system_params, reference_params, tokens, targets) -> small
    arrays``, to be jitted: ``models/phi4flash.py`` as it trains against
    ``reference/phi4flash_f32.py`` in ONE program, so that neither side's
    hidden states outlive it (``families/olmoe.py``). The cell passes the
    same weights twice; a fault passes faulty ones first, another
    ``system_cfg`` or another ``attn_fn``. What comes back: ``error``
    [N], every token's ||h - h_ref||_2 / ||h_ref||_2 on the final-norm
    hidden state, and both losses."""
    import jax.numpy as jnp

    from benchmark.reference import phi4flash_f32
    from torchft_tpu.models.phi4flash import loss_terms

    def both(p, p_ref, tok, tgt):
        got = loss_terms(system_cfg or cfg, p, tok, tgt, attn_fn)
        want = phi4flash_f32.terms(p_ref, tok, tgt, row_block=row_block,
                                   **reference_dims(cfg))
        h = got["hidden"].astype(jnp.float32).reshape(-1, cfg.d_model)
        h_ref = want["hidden"].reshape(-1, cfg.d_model)
        return {
            "error": (jnp.linalg.norm(h - h_ref, axis=-1)
                      / jnp.linalg.norm(h_ref, axis=-1)),
            "loss": got["loss"], "reference_loss": want["loss"],
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
    ``A = −(1 … N)`` a channel; ``Δ = softplus(n + b_dt)`` with
    ``softplus(b_dt)`` log-uniform over the config's ``dt_*`` and ``n``
    standard normal; ``x, B, C`` and the cotangent standard normal in the
    compute dtype; ``D`` normal around one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    C, N = cfg.d_inner, cfg.d_state
    k = jax.random.split(jax.random.key(np.uint32(seed & 0xFFFFFFFF)), 7)
    f32, dt = jnp.float32, cfg.dtype
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        k[0], (C,), f32, jnp.log(cfg.dt_min), jnp.log(cfg.dt_max))),
        cfg.dt_floor)
    delta = jax.nn.softplus(
        jax.random.normal(k[1], (1, seq_len, C), f32)
        + step + jnp.log(-jnp.expm1(-step)))
    return (
        jax.random.normal(k[2], (1, seq_len, C), f32).astype(dt), delta,
        -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=f32), (C, N)),
        jax.random.normal(k[3], (1, seq_len, N), f32).astype(dt),
        jax.random.normal(k[4], (1, seq_len, N), f32).astype(dt),
        1.0 + jax.random.normal(k[5], (C,), f32),
    ), jax.random.normal(k[6], (1, seq_len, C), f32).astype(dt)


def scan_comparison(scan_fn: Optional[Callable] = None) -> Callable:
    """``(args, dy) -> {leaf: relative L2 error}`` over ``SCAN_LEAVES``
    (the worst block of ``SCAN_BLOCK`` channels for the leaves of
    ``SCAN_BY_BLOCK``), to be jitted: ``scan_fn`` (the program's
    ``s6_scan``; a fault passes another) and its ``jax.vjp`` against the
    reference's recurrence and its own, on the same inputs."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import phi4flash_f32
    from torchft_tpu.ops.s6 import s6_scan

    def both(args, dy):
        f32 = jnp.float32
        got, pull = jax.vjp(scan_fn or s6_scan, *args)
        want, pull_ref = jax.vjp(phi4flash_f32.selective_scan,
                                 *(a.astype(f32) for a in args))

        def error(name, a, b):
            a = a.astype(f32)
            if name in SCAN_BY_BLOCK:
                # y, dx, dΔ [1, S, C] and dA [C, N]: the channels in front,
                # a block of them a row
                if name != "dA":
                    a, b = (jnp.moveaxis(z, -1, 0) for z in (a, b))
                block = min(SCAN_BLOCK, a.shape[0])
                a, b = (z.reshape(z.shape[0] // block, -1) for z in (a, b))
            else:
                a, b = a.reshape(1, -1), b.reshape(1, -1)
            return jnp.max(jnp.sqrt(jnp.sum(jnp.square(a - b), axis=1)
                                    / jnp.sum(jnp.square(b), axis=1)))

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
    loss, loss_ref = float(seen["loss"]), float(seen["reference_loss"])
    diff = abs(loss - loss_ref)
    return {
        "ok": bool(rms <= HIDDEN_REL_L2_RMS_MAX
                   and worst <= HIDDEN_REL_L2_MAX
                   and diff <= REFERENCE_LOSS_ATOL),
        "hidden_rel_l2_rms": _short(rms), "rms_limit": HIDDEN_REL_L2_RMS_MAX,
        "hidden_rel_l2_max": _short(worst), "max_limit": HIDDEN_REL_L2_MAX,
        "tokens": int(seen["error"].size),
        "system_loss": round(loss, 5), "reference_loss": round(loss_ref, 5),
        "abs_diff": _short(diff), "atol": REFERENCE_LOSS_ATOL,
    }


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the zero
    biases seeded non-zero on both sides) and ``REFERENCE_SEQUENCES``
    seeded sequences, at the configuration's
    widths, depth and share; then the scan alone, forward and backward,
    against the recurrence."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x7068, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    params = seed_biases(params, seed)
    whole = judge(per_token_errors(model.cfg, params, params, tokens, targets))
    with jax.default_device(device):
        scan = judge_scan(jax.device_get(jax.jit(scan_comparison())(
            *scan_inputs(model.cfg, seed, SCAN_SEQ))))
    return {**whole, **scan, "ok": whole["ok"] and scan["ok"]}
