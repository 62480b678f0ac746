"""Adapter for the Keye family (``torchft_tpu/models/keye.py``): the six
functions of ``families/smallthinker.py`` — ``build``, ``init_state``,
``make_train_step``, ``make_grad_step``, ``flops_per_token``,
``check_reference`` — and nothing of any one configuration. The step
programs are the one step maker's (``models/transformer.py``) with this
family's loss; the optimizer is the configuration's AdamW behind a linear
warm-up with the balance-bias rule on the bias leaves
(``optim.with_balance_bias``). ``check_reference`` is
``judge(per_token_errors(...))`` a sequence and
``judge_kernels(kernel_comparison(...))``; each pair is apart so that a
test or ``tests/keye_faults.py`` can run a faulty system against the
sound reference under the cell's own limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

from benchmark.families.joyai import seed_balance_bias

# ``correct`` for this family: the system (bf16 compute; f32 accumulation,
# norms, softmax, router, index scores, threshold, p-bar, L_I) against the
# f32 reference on the same share, the same weights (balance biases AND
# the indexer's LayerNorm bias seeded away from zero on both sides) and
# ``REFERENCE_SEQUENCES`` sequences of the cell's length, ONE PROGRAM A
# SEQUENCE, token by token on the final-norm hidden state: per token ||h -
# h_ref||_2 / ||h_ref||_2, then its root mean square and its largest.
#
# The reference is computed ON THE SYSTEM'S CHOICES — its top-8 sets of
# experts (``selection=``) and its sets of keys (``keys=``) —, for
# ``families/lfm2.py``'s reason: attention remembers, so a near-tie that
# rounds the other way in bf16 reaches tokens that ARE compared. The
# reference's OWN choices on that stream are counted beside it:
# ``top8_disagreement`` (the share of (token, layer) pairs whose expert set
# differs) and ``key_set_overlap`` (over (token, layer) pairs with t >=
# topk, the mean share of the system's keys that the reference's own
# top-k also holds). Two streams that differ by bf16 rounding swap keys at
# the rank-2048 boundary, and only there: the overlap is high and not 1.
# Every query's set has EXACTLY min(t + 1, topk) members and none with s >
# t: counted in the program (``bad_sets``, ``late_keys``), and any other
# count fails the run.
#
# Both L_CE and both L_I are compared, each with its own atol.
#
# Readings on the v5e at the cell's widths, depth (4) and share (my chip
# runs, PR 66: the cell's own seven runs on seven seeds, two of them
# beyond 2^31, and ``benchmark/tests/keye_faults.py --sound 1 --faulty 1``,
# whose sequences carry an image span's three streams):
#   sound            rms 0.004799 - 0.004914, max 0.006957 - 0.007445,
#                    top8_disagreement 0.03011 - 0.03238, key_set_overlap
#                    0.9956 on every seed (0.9959 once), bad_sets 0,
#                    late_keys 0, |L_CE diff| 7.6e-6 - 5.6e-5, |L_I diff|
#                    1.9e-5 - 5.1e-5 at L_I 0.281 - 0.286
#   topk 2047        bad_sets 57 348 (every query that chooses, 4 layers)
#   no relu          key_set_overlap 0.739, |L_I diff| 0.257
#   w unscaled       |L_I diff| 134 (the softmax over S_t is 32 x sharper)
#   p-bar from head 0 alone   |L_I diff| 1.85
#   a 4 096-key window in place of S_t   bad_sets 57 344, overlap 0.297
#   the height and width streams swapped   rms 0.01426, max 0.0415,
#                    top8_disagreement 0.0827 (overlap 0.9913, |L_I diff|
#                    1.2e-4: inside their limits)
#   index scores rounded to bf16 (the precision below the stated f32)
#                    the kernels' comparison: lse_i 6.3e-4, dqi 0.0195, dki
#                    0.0139, dw 0.021, kl 5.9e-5, overlap under 0.999
# A fault of the INDEXER leaves the hidden state's readings where they
# were (0.00485 / 0.00708: the reference follows the system's sets) and
# shows in the sets' sizes, the overlap or L_I; a fault of the core shows
# in the hidden state. The limits: rms 0.0065 is 1.32 x the largest sound
# reading and 0.46 x the least faulty one held by it (0.01426); max 0.011
# is 1.48 x and 0.27 x (0.0415); the disagreement 0.042 is 1.30 x and 0.51
# x (0.0827); the overlap 0.99 stands 0.0056 under a reading that does not
# move (two bf16 streams swap 0.44 % of the keys at the rank-2048
# boundary, every seed) and 0.25 above the nearest fault (0.739); L_I's
# 1e-3 is 20 x the largest sound reading and 1 / 257 of the least faulty
# one (0.257); L_CE's is the accepted cells' 2e-3, 36 x the largest sound
# reading (no fault is held by it alone). NOT SHOWN BY A FORWARD PASS, and
# held on the CPU's gradients instead (tests/test_keye.py): the indexer's
# input not detached, L_I left out, the backward on a re-chosen set.
HIDDEN_REL_L2_RMS_MAX = 0.0065
HIDDEN_REL_L2_MAX = 0.011
TOP_K_DISAGREEMENT_MAX = 0.042
KEY_SET_OVERLAP_MIN = 0.99
REFERENCE_LOSS_ATOL = 2e-3
INDEX_KL_ATOL = 1e-3
REFERENCE_SEQUENCES = 2

# THE NEW KERNELS BY THEMSELVES, forward and backward, at the cell's rows
# and length, inputs drawn inside the program (bf16 operands as the model
# hands them), against the reference computed in blocks of rows
# (``keye_f32``'s own functions, f32 at ``highest``):
#   select   ``dsa.select``: ``lse_i`` against the reference's
#            log-sum-exp of ITS scores over the system's set (the index
#            scores), the share of the system's keys in the reference's
#            own top-k (the threshold: the same rounded operands give the
#            same products, so only the f32 sums' order differs),
#            exact set sizes;
#   attend   ``dsa.attend`` on a GIVEN selection: ``o`` and, under one
#            seeded cotangent, ``dq``, ``dk``, ``dv``, each as the WORST
#            head's ||got - want||_2 / ||want||_2;
#   kl       ``dsa.index_kl``: its value and its gradients onto ``qi``,
#            ``ki``, ``w`` (relative L2 a leaf).
# Sound on the v5e (my chip runs, PR 66; eight seeds): lse_i 2.2e-8 -
# 2.3e-8, o .002729 - .002760, dq .002964 - .003114, dk .003355 -
# .003395, dv .002832 - .002870, kl 0 - 3.7e-7, dqi .002269 - .002277,
# dki .002007 - .002018, dw 5.23e-5 - 5.28e-5, overlap 1.0: the one bf16
# rounding of each result (dw, kl and lse_i leave in f32). Each limit is
# 1.3 - 1.5 x the largest sound reading where the result is rounded to
# bf16 and 4 - 40 x where it is not; bf16 index scores read 6 - 400 x a
# limit in five leaves.
KERNEL_LEAVES = ("lse_i", "o", "dq", "dk", "dv", "kl", "dqi", "dki", "dw")
KERNEL_REL_L2_MAX = {"lse_i": 1e-6, "o": 0.004, "dq": 0.0045, "dk": 0.005,
                     "dv": 0.0045, "kl": 1e-5, "dqi": 0.0035, "dki": 0.003,
                     "dw": 2e-4}
KERNEL_OVERLAP_MIN = 0.999


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's KeyeConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    flops_per_token: float  # keye_flops.train_flops_per_token's


def build(config: Dict[str, Any]) -> Model:
    import jax
    import optax

    from benchmark import keye_flops
    from torchft_tpu.models.keye import KeyeConfig, is_balance_bias
    from torchft_tpu.optim import with_balance_bias

    sa = config["sa_config"]
    cannot = {
        k: config[k] for k, v in (
            ("attention_bias", False), ("decoder_sparse_step", 1),
            ("mlp_only_layers", []), ("norm_topk_prob", True),
            ("hidden_act", "silu"), ("tie_word_embeddings", False),
            ("use_sliding_window", False),
            ("num_local_experts", config["num_experts"]),
        ) if config[k] != v
    }
    if sa["indexer_num_kv_heads"] != 1:
        cannot["indexer_num_kv_heads"] = sa["indexer_num_kv_heads"]
    if config["rope_scaling"].get("rope_type", "default") != "default":
        cannot["rope_scaling"] = config["rope_scaling"]
    if cannot:
        raise ValueError(f"models/keye.py does not compute {cannot}")
    job, opt, share = config["job"], config["optimizer"], config["share"]
    cfg = KeyeConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        init_depth=config["published"]["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        index_rope_dim=sa["indexer_head_dim"] // 2, index_topk=sa["topk"],
        index_kl_weight=float(config["index_kl_weight"]),
        d_expert=config["moe_intermediate_size"],
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
            optax.linear_schedule(peak / warm, peak, warm - 1),
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"],
            # matrices only; norms and the LayerNorm's bias take none
            mask=lambda params: jax.tree_util.tree_map(
                lambda x: x.ndim >= 2, params)),
        float(opt["balance_bias_rate"]), is_balance_bias,
        held=(cfg.first_expert, cfg.n_experts_held),
    )
    return Model(
        cfg=cfg, tx=tx, seq_len=int(job["seq_len"]),
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        flops_per_token=keye_flops.train_flops_per_token(
            **keye_flops.config_dims(config))["total"],
    )


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models.keye import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        np.uint32(seed & 0xFFFFFFFF)
    )


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make
    from torchft_tpu.models.keye import loss_fn

    return make(model.cfg, model.tx, donate=True, loss=loss_fn)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make
    from torchft_tpu.models.keye import loss_fn

    return make(model.cfg, loss=loss_fn)


def flops_per_token(model: Model) -> float:
    return model.flops_per_token


def reference_dims(cfg: Any) -> Dict[str, Any]:
    """The keyword arguments of ``reference/keye_f32.terms`` from the
    program's config."""
    return dict(
        n_layers=cfg.n_layers, n_head=cfg.n_heads, n_kv=cfg.n_kv_heads,
        head_dim=cfg.head_dim, theta=cfg.rope_theta,
        sections=cfg.mrope_section, index_heads=cfg.index_heads,
        index_rope=cfg.index_rope_dim, topk=cfg.index_topk,
        kl_weight=cfg.index_kl_weight, top_k=cfg.top_k,
        first_expert=cfg.first_expert, routed_scale=cfg.routed_scale,
        eps=cfg.rms_eps, ln_eps=cfg.ln_eps,
    )


def seed_check_params(params: Any, seed: int) -> Any:
    """The balance biases (JoyAI's function and spread) and the indexer's
    LayerNorm bias (normal 0.05) away from the zeros they are initialised
    at, so that a system that dropped either would read otherwise."""
    import jax
    import numpy as np

    params = seed_balance_bias(params, seed)
    key = jax.random.key(np.uint32((seed + 0x6b6c) & 0xFFFFFFFF))

    def leaf(path, x):
        keys = [getattr(p, "key", None) for p in path]
        if keys[-2:] != ["k_norm", "bias"]:
            return x
        return 0.05 * jax.random.normal(
            jax.random.fold_in(key, len(jax.tree_util.keystr(path))),
            x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, params)


def causal_words(seq_len: int):
    """The packed set of EVERY key at or before each row (``ops/dsa.py``'s
    layout), ``[S, S / 32]`` int32, built from the words' positions."""
    import jax
    import jax.numpy as jnp

    width = seq_len // 32
    t = jnp.arange(seq_len, dtype=jnp.int32)[:, None]
    c = jnp.arange(width, dtype=jnp.int32)[None, :]
    n = jnp.maximum(t - c, 0) // width          # bits 0 .. n are causal
    full = (jnp.uint32(2) << n.astype(jnp.uint32)) - jnp.uint32(1)
    return jax.lax.bitcast_convert_type(
        jnp.where(t >= c, full, jnp.uint32(0)), jnp.int32)


def set_counts(sel: Any, topk: int):
    """``(bad_sets, late_keys, selected_share [L])`` of packed sets ``[L,
    B, S, S / 32]``: rows whose set has another size than ``min(t + 1,
    topk)``, keys chosen after their query, chosen pairs over causal
    pairs a layer."""
    import jax
    import jax.numpy as jnp

    seq_len = sel.shape[2]
    size = jnp.sum(jax.lax.population_count(sel), axis=-1)
    want = jnp.minimum(jnp.arange(seq_len) + 1, topk)
    late = jnp.sum(jax.lax.population_count(sel & ~causal_words(seq_len)))
    return (jnp.sum(size != want), late,
            jnp.sum(size, axis=(1, 2)) / (
                sel.shape[1] * seq_len * (seq_len + 1) / 2))


def comparison(cfg: Any, system_cfg: Optional[Any] = None,
               attn_fn: Optional[Any] = None,
               positions: Optional[Any] = None,
               system_positions: Optional[Any] = None) -> Callable:
    """``(system_params, reference_params, tokens, targets) -> small
    arrays``, to be jitted: ``models/keye.py`` as it trains against
    ``reference/keye_f32.py`` in ONE program, so that neither side's
    hidden states outlive it. The cell passes the same weights twice; a
    fault passes faulty ones first, another ``system_cfg``, another
    ``attn_fn`` (the three calls of ``ops/dsa.py``); ``positions [3, S]``
    are both sides' streams (text's where none is given), and
    ``system_positions`` the system's alone where a fault hands it
    others."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import keye_f32
    from torchft_tpu.models.keye import loss_terms

    def both(p, p_ref, tok, tgt):
        got = loss_terms(
            system_cfg or cfg, p, tok, tgt, attn_fn,
            positions if system_positions is None else system_positions)
        taken = jnp.any(jax.nn.one_hot(
            got["experts"], cfg.n_routed_experts, dtype=bool), axis=-2)
        sel = got["sel"]
        want = keye_f32.terms(p_ref, tok, tgt, selection=taken, keys=sel,
                              positions=positions, **reference_dims(cfg))
        h = got["hidden"].astype(jnp.float32).reshape(-1, cfg.d_model)
        h_ref = want["hidden"].reshape(-1, cfg.d_model)
        bad, late, share = set_counts(sel, cfg.index_topk)
        chooses = jnp.arange(sel.shape[2]) >= cfg.index_topk
        shared = jnp.sum(jax.lax.population_count(sel & want["own_keys"]),
                         axis=-1) / jnp.maximum(
            jnp.sum(jax.lax.population_count(sel), axis=-1), 1)
        return {
            "error": (jnp.linalg.norm(h - h_ref, axis=-1)
                      / jnp.linalg.norm(h_ref, axis=-1)),
            "disagreement": jnp.mean(
                jnp.any(taken != want["chosen"], axis=-1)),
            "overlap": jnp.sum(jnp.where(chooses, shared, 0.0)) / jnp.maximum(
                jnp.sum(chooses) * sel.shape[0] * sel.shape[1], 1),
            "bad_sets": bad, "late_keys": late, "selected_share": share,
            "ce": got["ce"], "reference_ce": want["ce"],
            "index_kl": got["index_kl"],
            "reference_index_kl": want["index_kl"],
            "held_share": got["held_share"],
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


def _short(x: Any) -> float:
    """Four significant digits: ``run.py`` prints 600 characters a check."""
    return float(f"{float(x):.4g}")


def judge(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`per_token_errors` against the limits at the head of this
    file."""
    import numpy as np

    rms = float(np.sqrt(np.mean(seen["error"] ** 2)))
    worst = float(seen["error"].max())
    differs, overlap = float(seen["disagreement"]), float(seen["overlap"])
    bad, late = int(seen["bad_sets"]), int(seen["late_keys"])
    ce_diff = abs(float(seen["ce"]) - float(seen["reference_ce"]))
    kl_diff = abs(float(seen["index_kl"])
                  - float(seen["reference_index_kl"]))
    return {
        "ok": bool(rms <= HIDDEN_REL_L2_RMS_MAX
                   and worst <= HIDDEN_REL_L2_MAX
                   and differs <= TOP_K_DISAGREEMENT_MAX
                   and overlap >= KEY_SET_OVERLAP_MIN
                   and bad == 0 and late == 0
                   and ce_diff <= REFERENCE_LOSS_ATOL
                   and kl_diff <= INDEX_KL_ATOL),
        "hidden_rel_l2_rms": _short(rms), "rms_limit": HIDDEN_REL_L2_RMS_MAX,
        "hidden_rel_l2_max": _short(worst), "max_limit": HIDDEN_REL_L2_MAX,
        "top8_disagreement": _short(differs),
        "disagreement_limit": TOP_K_DISAGREEMENT_MAX,
        "key_set_overlap": _short(overlap),
        "overlap_limit": KEY_SET_OVERLAP_MIN,
        "bad_sets": bad, "late_keys": late,
        "ce_abs_diff": _short(ce_diff), "ce_atol": REFERENCE_LOSS_ATOL,
        "index_kl": _short(seen["index_kl"]),
        "kl_abs_diff": _short(kl_diff), "kl_atol": INDEX_KL_ATOL,
        "dsa_selected_share": _short(np.mean(seen["selected_share"])),
        "held_share": [round(float(x), 3) for x in seen["held_share"]],
    }


def merge(verdicts) -> Dict[str, Any]:
    """One verdict of several sequences': the worst of each reading."""
    out = dict(verdicts[0])
    for v in verdicts[1:]:
        for k, x in v.items():
            if k == "ok":
                out[k] = out[k] and x
            elif k == "key_set_overlap":
                out[k] = min(out[k], x)
            elif k.endswith(("_limit", "_atol")) or isinstance(x, list):
                continue
            elif k in ("dsa_selected_share", "index_kl"):
                out[k] = _short((out[k] + x) / 2)
            else:
                out[k] = max(out[k], x)
    return out


# -- the kernels alone -------------------------------------------------------


def kernel_inputs(cfg: Any, seed: int, rows: int, seq_len: int):
    """``q, k, v, do`` (standard normal: ``n·W`` behind a head's norm has
    unit size), ``qi``, ``ki`` (unit size: behind the LayerNorm), ``w``
    (f32, the size ``n·W_w / 32`` has at init 0.02 over 2048 inputs x 5
    so that the scores spread) in the layouts ``ops/dsa.py`` takes, bf16
    as the model hands them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    k = jax.random.split(jax.random.key(np.uint32(seed & 0xFFFFFFFF)), 7)
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    HI, DI = cfg.index_heads, cfg.index_head_dim

    def draw(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(cfg.dtype)

    return dict(
        q=draw(k[0], rows, H, seq_len, D), k=draw(k[1], rows, KV, seq_len, D),
        v=draw(k[2], rows, KV, seq_len, D),
        do=draw(k[3], rows, H, seq_len, D),
        qi=draw(k[4], rows, HI, seq_len, DI), ki=draw(k[5], rows, seq_len, DI),
        w=0.15 * jax.random.normal(k[6], (rows, seq_len, HI), jnp.float32))


def kernel_comparison(cfg: Any, ops: Optional[Any] = None) -> Callable:
    """``inputs -> {leaf: relative L2 error}`` over ``KERNEL_LEAVES`` with
    ``overlap`` and ``bad_sets``, to be jitted: the three calls of
    ``ops`` (``ops/dsa.py``: the kernels on a TPU; a fault passes others)
    and their gradients against ``keye_f32``'s functions, a block of rows
    at a time with the block's own ``jax.vjp``, on the same rounded
    inputs."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import keye_f32
    from torchft_tpu.ops import dsa

    ops = dsa if ops is None else ops
    f32 = jnp.float32

    def both(x):
        q, k, v, do, qi, ki, w = (x[n] for n in
                                  ("q", "k", "v", "do", "qi", "ki", "w"))
        B, H, S, D = q.shape
        sel, lse_i = ops.select(qi, ki, w, cfg.index_topk)
        (o, lse), pull = jax.vjp(
            lambda q, k, v: ops.attend(q, k, v, sel), q, k, v)
        dq, dk, dv = pull((do, jnp.zeros_like(lse)))
        kl, (dqi, dki, dw) = jax.value_and_grad(
            lambda qi, ki, w: ops.index_kl(q, k, lse, qi, ki, w, sel, lse_i),
            argnums=(0, 1, 2))(qi, ki, w)
        block = min(keye_f32.ROW_BLOCK, S)

        def sequence(args):
            q, k, v, do, qi, ki, w, sel = args
            q, k, v, do, qi, ki = (a.astype(f32) for a in
                                   (q, k, v, do, qi, ki))

            def rows(carry, i):
                at = i * block
                cut = lambda a, axis=0: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    a, at, block, axis)
                keep = keye_f32.unpack_keys(cut(sel))
                t_pos = at + jnp.arange(block)

                def attend(qb, k, v):
                    return keye_f32.sparse_attention(qb, k, v, keep)

                (ob, pbar), pull = jax.vjp(attend, cut(q, 1), k, v)
                dqb, dkb, dvb = pull((cut(do, 1), jnp.zeros_like(pbar)))

                def scores(qib, ki, wb):
                    return keye_f32.index_scores(qib, ki, wb)

                def kl_of(qib, ki, wb):
                    return jnp.sum(keye_f32.index_kl_rows(
                        pbar, scores(qib, ki, wb), keep))

                qib, wb = cut(qi, 1).transpose(1, 0, 2), cut(w)
                klb, (dqib, dkib, dwb) = jax.value_and_grad(
                    kl_of, argnums=(0, 1, 2))(qib, ki, wb)
                own_scores = scores(qib, ki, wb)
                own = keye_f32.top_keys(own_scores, t_pos, cfg.index_topk)
                lse_own = jax.nn.logsumexp(
                    jnp.where(keep, own_scores, -jnp.inf), axis=-1)
                in_both = jnp.sum(keep & own, axis=-1) / jnp.maximum(
                    jnp.sum(keep, axis=-1), 1)
                dk_sum, dv_sum, dki_sum, kl_sum = carry
                return ((dk_sum + dkb, dv_sum + dvb, dki_sum + dkib,
                         kl_sum + klb),
                        (ob, dqb, dqib.transpose(1, 0, 2), dwb, lse_own,
                         in_both))

            with jax.default_matmul_precision("highest"):
                (dk_r, dv_r, dki_r, kl_r), per_block = jax.lax.scan(
                    rows, (jnp.zeros_like(k), jnp.zeros_like(v),
                           jnp.zeros_like(ki), jnp.zeros((), f32)),
                    jnp.arange(S // block))
            ob, dqb, dqib, dwb, lse_own, in_both = per_block

            def heads_first(a):      # [blocks, heads, rows, D]
                return a.transpose(1, 0, 2, 3).reshape(a.shape[1], S, -1)

            return (heads_first(ob), heads_first(dqb), dk_r, dv_r, kl_r,
                    heads_first(dqib), dki_r, dwb.reshape(S, -1),
                    lse_own.reshape(S), in_both.reshape(S))

        want = jax.lax.map(sequence, (q, k, v, do, qi, ki, w, sel))
        o_r, dq_r, dk_r, dv_r, kl_r, dqi_r, dki_r, dw_r, lse_r, in_both = want

        def rel(a, b, axes):
            a, b = a.astype(f32), b.astype(f32)
            return jnp.max(jnp.sqrt(jnp.sum(jnp.square(a - b), axis=axes)
                                    / jnp.sum(jnp.square(b), axis=axes)))

        head, whole = (2, 3), None
        chooses = jnp.arange(S) >= cfg.index_topk
        bad, late, _ = set_counts(sel[None], cfg.index_topk)
        return {
            "lse_i": rel(lse_i, lse_r, whole), "o": rel(o, o_r, head),
            "dq": rel(dq, dq_r, head), "dk": rel(dk, dk_r, head),
            "dv": rel(dv, dv_r, head),
            "kl": jnp.abs(kl - jnp.sum(kl_r)) / jnp.sum(kl_r),
            "dqi": rel(dqi, dqi_r, whole), "dki": rel(dki, dki_r, whole),
            "dw": rel(dw, dw_r, whole),
            "overlap": jnp.sum(jnp.where(chooses, in_both, 0.0))
            / jnp.maximum(jnp.sum(chooses) * B, 1),
            "bad_sets": bad + late,
        }

    return both


def judge_kernels(seen: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`kernel_comparison`'s readings against ``KERNEL_REL_L2_MAX``
    and ``KERNEL_OVERLAP_MIN``."""
    over = [n for n in KERNEL_LEAVES
            if not float(seen[n]) <= KERNEL_REL_L2_MAX[n]]
    if not float(seen["overlap"]) >= KERNEL_OVERLAP_MIN:
        over.append("overlap")
    if int(seen["bad_sets"]):
        over.append("bad_sets")
    return {"ok": not over, "kernels_over": over,
            "kernel_rel_l2": {n: _short(seen[n]) for n in KERNEL_LEAVES},
            "kernel_overlap": _short(seen["overlap"])}


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system against the reference on the same weights (the balance
    biases and the indexer's LayerNorm bias seeded non-zero on both
    sides) and ``REFERENCE_SEQUENCES`` seeded sequences, one program a
    sequence, at the configuration's widths, depth and share; then the
    new kernels alone, forward and backward, at the cell's rows and
    length."""
    import jax

    from benchmark.traffic_gen import BatchSource

    tokens, targets = BatchSource(
        seed, 0x6b79, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    params = seed_check_params(params, seed)
    one = jax.jit(comparison(model.cfg))
    whole = merge([
        judge(jax.device_get(one(params, params, tokens[i:i + 1],
                                 targets[i:i + 1])))
        for i in range(REFERENCE_SEQUENCES)])
    with jax.default_device(device):
        kernels = judge_kernels(jax.device_get(
            jax.jit(kernel_comparison(model.cfg))(
                kernel_inputs(model.cfg, seed, model.rows, model.seq_len))))
    return {**whole, **kernels, "ok": whole["ok"] and kernels["ok"]}
