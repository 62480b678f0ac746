"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``; the
benchmark's configuration is Kwai-Keye/Keye-VL-2.0-30B-A3B) as ONE CHIP'S
SHARE of an expert-parallel layer: a pre-norm stack whose attention
CHOOSES ITS KEYS FROM THE DATA. Every layer, with ``N(x; g) = x ·
rsqrt(mean(x²) + eps) · g`` in f32:

    n  = N(x; g1)
    q, k, v = n·W_q, n·W_k, n·W_v        H query on KV key/value heads of D
    q_h, k_g = N_D(q_h; g_q), N_D(k_g; g_k)   one D-wide weight each
    q, k turned (``rotate_half`` over the whole head) by THREE position
        streams: pair (i, i + D/2) by p_c(i)[t] · theta^(-2i / D), c(i) the
        ``mrope_section`` the pair falls in (:func:`mrope_tables`)
    n̄  = stop_gradient(n)                the indexer reads the stream, and
                                          no gradient of its goes back
    qI = n̄·W_qI  (HI heads of DI),  kI = LayerNorm(n̄·W_kI)  (ONE head),
    w  = n̄·W_w · (HI · DI)^(-1/2);  the first ``index_rope_dim`` lanes of
        every qI head and of kI turned by p_0 at theta^(-2i / rope_dim)
    I[t, s] = Σ_j w[t, j] · relu(qI[t, j] · kI[s]),  s <= t        (f32)
    S_t = the min(t + 1, index_topk) largest of I[t, :t + 1], ties to the
        lower s                                     (``ops/dsa.py::select``)
    o[t, h] = Σ_{s in S_t} softmax_{S_t}(q[t, h] · k[s, h // group] / √D) ·
        v[s, h // group];  h = x + o·W_o                      (``attend``)
    L_I += mean_t KL(p̄[t] || softmax_{S_t}(I[t])),  p̄ = mean_h P[t, h],
        detached                                            (``index_kl``)
    y  = h + Σ_{e held} w_e · SwiGLU_e(N(h; g2))   the top ``top_k`` of
        logits + balance bias choose, weights the softmax over the chosen
        logits (``common.routed_sublayer(score="softmax")``), no shared
        expert

a final ``N``, an untied head, no bias. The loss is ``L_CE +
index_kl_weight · L_I``: the cross entropy's gradient reaches no
parameter of the indexer (the set is not differentiable and ``n̄`` is
detached), ``L_I``'s reaches ``W_qI``, ``W_kI``, the LayerNorm and ``W_w``
alone. ``loss_terms`` returns both; the loop reports ``ce`` as every
family's.

WHAT CROSSES A LAYER'S CHECKPOINT. Under ``remat`` the layer runs again
in the backward pass, on a stream the forward pass's fusion may have
rounded otherwise: a set chosen again could differ at the rank-
``index_topk`` boundary, and the backward would differentiate another
function than the forward computed. The packed sets and their
log-sum-exp (``[B, S, S / 32]`` int32 and ``[B, S]`` f32 a layer) are
tagged ``KEY_CHOICE`` where ``ops/dsa.py`` makes them and kept by
``common.checkpoint_layer``, as
the router's choice is (``ROUTER_CHOICE``): the selection runs once a
step. So are the attention's output and its rows' log-sum-exp over those
sets (``[B, H, S, D]`` in the compute type and ``[B, H, S]`` f32: 268 + 4
MB a layer at 2 x 16 384): the kernel computes every causal tile to
attend to a quarter of the pairs, and at that price a second forward a
step costs more than keeping its result. And the gradient of the layer's
``L_I`` term. ``index_kl``'s forward rule computes ``dqi [B, HI, S, DI]``
(compute type), ``dki [B, S, DI]`` and ``dw [B, S, HI]`` (f32) in the call
that computes its value (``ops/dsa.py::_kl_fwd``: ``dsa_kl`` runs once a
layer and step, in the forward pass), but those are 67.1 + 4.2 + 2.1 = 73
MB a layer there and kept they raise the peak by 0.24 GiB at depth 4
(PERF.md, PR 67). So :func:`_index_term` pulls them back through the
indexer's projections in the forward pass as well, where the stream still
is, and what crosses is shaped like the indexer's parameters (``q_proj``
8.4 MB, ``k_proj`` 0.5, ``weights_proj`` 0.13, the LayerNorm's two: 9 MB
a layer, f32); the backward pass scales them by the term's cotangent, and
the layer run again makes neither the indexer's inputs nor the term.

The three position streams (temporal, height, width) are ``[3, S]``;
text has all three equal to ``t``, which is what ``forward_hidden``
feeds where the caller gives none (there is no vision tower here to make
an image span; ``tests/test_keye.py`` holds the table on unequal
streams).

Conventions of ``models/smallthinker.py``: float32 parameters, bf16
compute, float32 norms / router / index scores, stable paths
``layers_<i>/{norm_1,norm_2}``, ``layers_<i>/attn/...``,
``layers_<i>/indexer/...``, ``layers_<i>/moe/...``, and the step
programs of ``transformer.make_train_step`` / ``make_grad_step``
(``loss=keye.loss_fn``).

Device-trace scopes: ``embed``; ``attn`` with ``gqa_proj`` (the norm, q /
k / v, the head norms, ``W_o``, and inside it ``rope``), ``dsa_index``
(the indexer's projections, LayerNorm and rotation), ``dsa_select`` (the
scores, the threshold and the packing: one kernel, the scores never
leave it), ``dsa_core`` (attention over the chosen keys), ``dsa_kl``
(``p̄``, ``L_I`` and its gradients: one call, in the forward pass); ``mlp``
with ``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``;
``lm_head_xent``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from torchft_tpu.models.common import (
    BALANCE_BIAS,
    checkpoint_layer,
    embed,
    is_balance_bias,
    rms_norm,
    routed_sublayer,
    routing_record,
    share_loss_terms,
)
from torchft_tpu.models.transformer import ce_from_hidden
from torchft_tpu.ops import dsa
from torchft_tpu.ops.ssm_pointwise import rotary

__all__ = ["KeyeConfig", "KEYE_CONFIGS", "BALANCE_BIAS", "is_balance_bias",
           "init_params", "mrope_tables", "forward_hidden", "loss_terms",
           "loss_fn"]


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """Defaults: Kwai-Keye/Keye-VL-2.0-30B-A3B's language model as
    published, every expert held."""
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    init_depth: int = 48          # the PUBLISHED depth: residual outputs
                                  # are initialised / sqrt(init_depth)
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)   # pairs a stream
    index_heads: int = 16
    index_head_dim: int = 64
    index_rope_dim: int = 32      # lanes of an indexer head that turn
    index_topk: int = 2048
    index_kl_weight: float = 1.0
    ln_eps: float = 1e-6          # the indexer's LayerNorm
    d_expert: int = 768
    n_routed_experts: int = 128   # the router's width
    first_expert: int = 0         # the share held here:
    n_experts_held: int = 128     # experts first .. first + held
    top_k: int = 8
    routed_scale: float = 1.0
    rms_eps: float = 1e-6
    init_std: float = 0.02
    embed_std: Optional[float] = None   # the table's; None: init_std
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert self.n_heads % self.n_kv_heads == 0
        assert 2 * sum(self.mrope_section) == self.head_dim
        assert 0 < self.index_rope_dim <= self.index_head_dim
        assert self.index_rope_dim % 2 == 0 and self.index_topk >= 1
        assert 1 <= self.top_k <= self.n_routed_experts
        assert 0 <= self.first_expert and 1 <= self.n_experts_held
        assert self.first_expert + self.n_experts_held <= self.n_routed_experts


KEYE_CONFIGS: Dict[str, KeyeConfig] = {
    # the tests' size: 8 query heads a key/value head, three streams of
    # unequal width, an indexer of 3 heads that keeps 12 of up to 64 keys
    # (most queries choose), a share of 4 of 8 experts
    "keye_tiny": KeyeConfig(
        vocab_size=512, d_model=48, n_layers=2, init_depth=8, n_heads=16,
        n_kv_heads=2, head_dim=16, mrope_section=(2, 3, 3), index_heads=3,
        index_head_dim=8, index_rope_dim=4, index_topk=12, d_expert=24,
        n_routed_experts=8, first_expert=0, n_experts_held=4, top_k=2,
        init_std=0.125,
    ),
}


def init_params(cfg: KeyeConfig, key) -> Dict:
    """Matrices normal with ``init_std``, those onto the residual stream
    (``o_proj``, ``down_proj``) / sqrt(``init_depth``); the table normal
    with ``embed_std`` where a configuration gives it one of its own
    (``models/smallthinker.py`` says why); norm weights one,
    the LayerNorm's bias and the balance bias zero; the table and the head
    two leaves."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 2)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def out(k, *shape):
        return normal(k, *shape) / math.sqrt(cfg.init_depth)

    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    hi, di = cfg.index_heads, cfg.index_head_dim
    held, f = cfg.n_experts_held, cfg.d_expert
    params: Dict[str, Any] = {
        "wte": {"embedding": jax.random.normal(
            keys[0], (cfg.vocab_size, d), pd) * (
                cfg.init_std if cfg.embed_std is None else cfg.embed_std)},
        "ln_f": {"scale": jnp.ones((d,), pd)},
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 11)
        params[f"layers_{i}"] = {
            "norm_1": {"scale": jnp.ones((d,), pd)},
            "norm_2": {"scale": jnp.ones((d,), pd)},
            "attn": {
                "q_proj": {"kernel": normal(k[0], d, q)},
                "k_proj": {"kernel": normal(k[1], d, kv)},
                "v_proj": {"kernel": normal(k[2], d, kv)},
                "o_proj": {"kernel": out(k[3], q, d)},
                "q_norm": {"scale": jnp.ones((cfg.head_dim,), pd)},
                "k_norm": {"scale": jnp.ones((cfg.head_dim,), pd)},
            },
            "indexer": {
                "q_proj": {"kernel": normal(k[4], d, hi * di)},
                "k_proj": {"kernel": normal(k[5], d, di)},
                "k_norm": {"scale": jnp.ones((di,), pd),
                           "bias": jnp.zeros((di,), pd)},
                "weights_proj": {"kernel": normal(k[6], d, hi)},
            },
            "moe": {
                "gate_proj": {"kernel": normal(k[7], held, d, f)},
                "up_proj": {"kernel": normal(k[8], held, d, f)},
                "down_proj": {"kernel": out(k[9], held, f, d)},
                "router": {"kernel": normal(k[10], d, cfg.n_routed_experts)},
                BALANCE_BIAS: jnp.zeros((cfg.n_routed_experts,), pd),
            },
        }
    return params


def mrope_tables(cfg: KeyeConfig, positions):
    """``(cos, sin)`` for ``ops/ssm_pointwise.py::rotary`` over the whole
    head (``half = head_dim / 2``) from ``positions [3, S]`` (temporal,
    height, width): pair ``i`` turns by ``positions[c(i), t] · theta^(-2i /
    head_dim)``, ``c(i)`` the section of ``mrope_section`` that holds
    ``i``. Laid out a lane as ``rotary_tables`` lays its own: lanes ``i``
    and ``half + i`` hold the cosine, lane ``i`` minus and lane ``half +
    i`` plus the sine. f32, built once a step."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    stream = jnp.repeat(jnp.arange(len(cfg.mrope_section)),
                        jnp.asarray(cfg.mrope_section),
                        total_repeat_length=half)
    angles = positions.astype(jnp.float32)[stream].T * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _index_tables(cfg: KeyeConfig, positions):
    """``(cos, sin) [S, index_rope_dim / 2]`` of the indexer's rotation:
    the temporal stream at ``theta^(-2i / index_rope_dim)``."""
    half = cfg.index_rope_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[0].astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _turn(x, cos, sin):
    """``rotate_half`` over the FIRST ``2r`` lanes of ``x [B, S, .., D]``
    (``cos``, ``sin`` ``[S, r]``), the others pass: the indexer's heads
    are 64 wide, which the rotation kernel's lanes do not take. f32
    arithmetic, one rounding."""
    r = cos.shape[-1]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (r,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = (x[..., :r].astype(jnp.float32),
              x[..., r:2 * r].astype(jnp.float32))
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x2 * cos + x1 * sin).astype(x.dtype), x[..., 2 * r:]], axis=-1)


def _layer_norm(x, scale, bias, eps: float):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


# the indexer reads the stream through this, by name (a seam: a test puts
# the identity here to show what the detachment holds)
_detach = jax.lax.stop_gradient


def indexer_inputs(cfg: KeyeConfig, ix: Dict, n, index_table):
    """``(qi [B, HI, S, DI], ki [B, S, DI], w [B, S, HI] f32)`` of the
    DETACHED normed stream ``n [B, S, d]``: what ``ops/dsa.py`` scores. A
    seam by name (``benchmark/tests/keye_faults.py``)."""
    dt = cfg.dtype
    B, S, _ = n.shape
    n = _detach(n)
    qi = (n @ ix["q_proj"]["kernel"].astype(dt)).reshape(
        B, S, cfg.index_heads, cfg.index_head_dim)
    ki = _layer_norm(n @ ix["k_proj"]["kernel"].astype(dt),
                     ix["k_norm"]["scale"], ix["k_norm"]["bias"], cfg.ln_eps)
    w = (n @ ix["weights_proj"]["kernel"].astype(dt)).astype(jnp.float32) * (
        cfg.index_heads * cfg.index_head_dim) ** -0.5
    return (_turn(qi, *index_table).transpose(0, 2, 1, 3),
            _turn(ki, *index_table), w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _index_term(cfg: KeyeConfig, ops, ix: Dict, n, index_table, q, k, lse,
                sel, lse_i):
    """A layer's ``L_I`` (the sum over its rows) of the indexer's
    parameters ``ix`` and the normed stream ``n``: :func:`indexer_inputs`
    (the values ``select`` was given: one computation to the compiler),
    then ``ops.index_kl``. ONE differentiable unit, so that what its
    backward reads is shaped like the parameters (module docstring)."""
    with jax.named_scope("dsa_index"):
        qi, ki, w = indexer_inputs(cfg, ix, n, index_table)
    with jax.named_scope("dsa_kl"):
        return ops.index_kl(q, k, lse, qi, ki, w, sel, lse_i)


def _index_term_fwd(cfg, ops, ix, n, index_table, q, k, lse, sel, lse_i):
    # asked for only where the term is differentiated: ``index_kl``'s
    # gradients come with its value (``ops/dsa.py::_kl_fwd``) and are
    # pulled back through the projections HERE, where the stream still is;
    # the backward rule scales. Every cotangent ``jax.vjp`` gives goes
    # back, the stream's too: ``_detach`` decides what it holds. The
    # parameters' are named (under ``checkpoint_layer`` the forward pass
    # keeps them); the stream's is not: detached it is zeros, which depend
    # on nothing, and a layer run again makes them again
    with jax.named_scope("dsa_index"):
        (qi, ki, w), pull = jax.vjp(
            lambda ix, n: indexer_inputs(cfg, ix, n, index_table), ix, n)
    with jax.named_scope("dsa_kl"):
        kl, grads = jax.value_and_grad(
            lambda *index: ops.index_kl(q, k, lse, *index, sel, lse_i),
            argnums=(0, 1, 2))(qi, ki, w)
    with jax.named_scope("dsa_index"):
        d_ix, d_n = pull(grads)
    return kl, (jax.tree_util.tree_map(
        lambda a: checkpoint_name(a, dsa.KEY_CHOICE), d_ix), d_n)


def _index_term_bwd(cfg, ops, grads, g):
    d_ix, d_n = jax.tree_util.tree_map(lambda a: (g * a).astype(a.dtype),
                                       grads)
    return d_ix, d_n, None, None, None, None, None, None


_index_term.defvjp(_index_term_fwd, _index_term_bwd)


@jax.named_scope("attn")
def _attn_mixer(cfg: KeyeConfig, layer: Dict, x, tables,
                ops) -> Tuple[Any, Dict]:
    """``(x + attention over the chosen keys, {kl, sel})``: the second
    holds the layer's ``L_I`` and its packed sets. ``ops``: the three
    calls of ``ops/dsa.py``."""
    a, dt = layer["attn"], cfg.dtype
    B, S, _ = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    table, index_table = tables
    with jax.named_scope("gqa_proj"):
        n = rms_norm(x, layer["norm_1"]["scale"], cfg.rms_eps)

        def heads(proj, count, norm=None):
            z = n @ a[proj]["kernel"].astype(dt)
            if norm is None:
                return z
            return rms_norm(z.reshape(B, S, count, D), a[norm]["scale"],
                            cfg.rms_eps).reshape(B, S, count * D)

        q, k = heads("q_proj", H, "q_norm"), heads("k_proj", KV, "k_norm")
        v = heads("v_proj", KV).reshape(B, S, KV, D).transpose(0, 2, 1, 3)
        with jax.named_scope("rope"):
            # heads first: the order ``ops/dsa.py`` takes
            q, k = (rotary(z, *table, D // 2) for z in (q, k))
    with jax.named_scope("dsa_index"):
        qi, ki, w = indexer_inputs(cfg, layer["indexer"], n, index_table)
    with jax.named_scope("dsa_select"):
        sel, lse_i = ops.select(qi, ki, w, cfg.index_topk)
    with jax.named_scope("dsa_core"):
        o, lse = ops.attend(q, k, v, sel)
    kl = _index_term(cfg, ops, layer["indexer"], n, index_table, q, k, lse,
                     sel, lse_i) / (B * S)
    with jax.named_scope("gqa_proj"):
        y = jnp.einsum("bhsd,hdm->bsm", o,
                       a["o_proj"]["kernel"].astype(dt).reshape(H, D, -1))
    return x + y, {"kl": kl, "sel": sel}


def _layer(cfg: KeyeConfig, layer: Dict, x, tables,
           ops) -> Tuple[Any, Dict]:
    """One layer: ``(x, record)``; the record is the router's with the
    indexer's ``kl`` and the packed ``sel`` beside it."""
    h, chose = _attn_mixer(cfg, layer, x, tables, ops)
    y, rec = routed_sublayer(cfg, h, layer["norm_2"]["scale"], layer["moe"],
                             score="softmax")
    return y, dict(rec, **chose)


def forward_hidden(cfg: KeyeConfig, params: Dict, tokens,
                   attn_fn: Optional[Any] = None,
                   positions: Optional[Any] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d], record).
    ``attn_fn``, in the place the step maker hands every family's
    attention through, is whatever has the three calls ``select``,
    ``attend`` and ``index_kl`` (``ops/dsa.py`` where none is given: the
    kernels on a TPU). ``positions [3, S]``: the temporal, height and width streams; text's
    (all three ``t``) where none is given. The record holds
    ``common.routing_record``'s ``experts``, ``loads`` and ``carrier`` and,
    a layer, ``kl [L]`` (``L_I``'s terms) and ``sel [L, B, S, S / 32]``
    (the packed sets)."""
    S = tokens.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (3, S))
    tables = (mrope_tables(cfg, positions), _index_tables(cfg, positions))
    x = embed(cfg, params, tokens)
    records = []
    for i in range(cfg.n_layers):
        run = functools.partial(_layer, cfg, tables=tables,
                                ops=dsa if attn_fn is None else attn_fn)
        if cfg.remat:
            run = checkpoint_layer(run)
        x, rec = run(params[f"layers_{i}"], x)
        records.append(rec)
    out = routing_record(records)
    out["kl"] = jnp.stack([r["kl"] for r in records])
    out["sel"] = jnp.stack([r["sel"] for r in records])
    return rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps), out


def loss_terms(cfg: KeyeConfig, params, tokens, targets,
               attn_fn: Optional[Any] = None,
               positions: Optional[Any] = None) -> Dict[str, Any]:
    """``common.share_loss_terms`` of this model's forward pass with the
    indexer's term: ``loss`` = ``ce`` + ``index_kl_weight`` · ``index_kl``
    (the sum of the layers' ``kl``); a layer, ``selected_share``: chosen
    pairs over causal pairs."""
    h, rec = forward_hidden(cfg, params, tokens, attn_fn, positions)
    B, S = tokens.shape
    out = share_loss_terms(
        cfg, h, rec, ce_from_hidden(h, params["lm_head"]["kernel"], targets,
                                    cfg.xent_chunks),
        more_loss=lambda r: cfg.index_kl_weight * jnp.sum(r["kl"]))
    out["index_kl"] = jnp.sum(out["kl"])
    out["selected_share"] = jnp.sum(
        jax.lax.population_count(out["sel"]).astype(jnp.float32),
        axis=(1, 2, 3)) / (B * S * (S + 1) / 2)
    return out


def loss_fn(cfg: KeyeConfig, params, tokens, targets,
            attn_fn: Optional[Any] = None, positions: Optional[Any] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn,
                      positions)["loss"]
