"""Laguna decoder (``model_type`` ``laguna``; the benchmark's
configuration is poolside/Laguna-XS.2, 33.4B-A3B) as ONE CHIP'S SHARE of
an expert-parallel layer. **What a layer's attention is changes with its
kind** (``layer_types[l]``): the number of query heads (``heads[l]`` =
``num_attention_heads_per_layer[l]``: 64 under the window, 48 under full
attention, so ``W_q``, ``W_o`` and the gate differ in shape from layer to
layer, and a key/value head serves 8 or 6 consecutive query heads), the
mask (the last ``window`` keys, or every earlier key) and the rotation:

    n1 = RMSNorm(x; g1)
    q, k, v = n1·W_q [H_l × D], n1·W_k [KV × D], n1·W_v [KV × D]
    sliding: q, k <- rotate_half over the whole head, theta 1e4
    full:    q, k <- rotate_half over the FIRST half of the head at YaRN's
             frequencies (theta 5e5 interpolated by ``factor`` behind a
             linear ramp), cos and sin both times ``attention_factor``;
             the other lanes pass (``common.rotary``)
    a  = causal softmax(q·kᵀ / sqrt(D))·v, query head i on key/value head
         i // (H_l / KV); a sliding position sees ``window`` keys, itself
         among them
    γ  = sigmoid(n1·W_γ) [H_l]        a gate a head a position, float32
    h  = x + (γ ⊙ a)·W_o
    n2 = RMSNorm(h; g2)
    dense (``sparse[l]`` 0):  y = h + SwiGLU(n2), ``d_ff`` wide
    sparse:  s = sigmoid(n2·W_r) in float32 over all routed experts; the
             top k of s + b choose (b the balance bias: selects, never
             weights); w = routed_scale · s / Σ_chosen s;
             y = h + Σ_{e chosen, held} w_e · SwiGLU_e(n2) + SwiGLU_s(n2)
             (the shared expert, weight 1)

A final RMSNorm, an untied ``lm_head``; no bias anywhere, no norm on q or
k. The head count of the flash call follows the layer (``ops/flash.py``
reads key/value head ``i // group``; nothing is copied).

The layer is told which routed experts it holds (``first_expert``,
``n_experts_held``), routes over all ``n_routed_experts`` and computes its
own experts' part; the balance bias, its place in the gradient tree, the
sparse sublayer (``common.routed_sublayer(score="sigmoid", shared=…)``)
and the dense one (``common.dense_sublayer``) are ``models/common.py``'s.

Conventions of ``models/lfm2.py``: float32 parameters, bf16 compute,
float32 norms, router, gate logits and rotation tables, an explicit
parameter tree with stable paths ``layers_<i>/{norm_1,norm_2}``,
``layers_<i>/attn/...`` and ``layers_<i>/mlp/...`` (dense) or
``layers_<i>/moe/...`` (sparse), per-layer ``checkpoint_layer`` behind
``remat``, a plain Python loop over layers whose shapes differ, and the
step programs of ``transformer.make_train_step`` / ``make_grad_step``
(``loss=laguna.loss_fn``).

``checkpoint_layer`` (``models/common.py``) is ``jax.checkpoint`` that
keeps what a layer's router decided — the experts, their weights, the
chosen scores, the loads —, so the backward pass does not run the router
again (``common.routed_sublayer`` says why the weights are among them).

The rotation and the gate's multiply are Pallas kernels
(``ops/ssm_pointwise.py::rotary`` from tables built once a step,
``gate_heads``): bf16 read once and written once, f32 inside, the jnp
forms' values; the flash call's side of both is heads first, so no layout
copy stands between them and the call. ``attn_fn`` still takes and gives
``[B, S, H, D]``, and ``head_gate`` still makes the gate.

Device-trace scopes: ``embed``; ``attn`` with ``gqa_proj`` (the norm, q /
k / v, and inside it ``rope`` or ``rope_yarn`` around the rotation and
``attn_gate`` around the gate's matmul, sigmoid and multiply; ``W_o``)
and ``gqa_core`` with ``swa_core`` or ``full_core`` around the one flash
call (the accepted readers' names); ``mlp`` alone in a dense layer and
with ``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``,
``moe_shared`` in a sparse one; ``lm_head_xent``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models.common import (
    BALANCE_BIAS,
    checkpoint_layer,
    dense_sublayer,
    embed,
    is_balance_bias,
    rms_norm,
    routed_sublayer,
    routing_record,
    share_loss_terms,
    swiglu,
)
from torchft_tpu.models.transformer import ce_from_hidden
from torchft_tpu.ops.attention import causal_attention
from torchft_tpu.ops.ssm_pointwise import gate_heads, rotary, rotary_tables

__all__ = ["LagunaConfig", "Rotation", "LAGUNA_CONFIGS", "BALANCE_BIAS",
           "is_balance_bias", "yarn_ramp", "rotation_freqs",
           "rotation_tables", "init_params",
           "forward_hidden", "loss_terms", "loss_fn"]


@dataclasses.dataclass(frozen=True)
class Rotation:
    """One kind of layer's ``rope_parameters``: ``rope_type`` default
    where ``yarn_factor`` is None."""
    theta: float
    partial: float = 1.0                  # share of the head's lanes turned
    yarn_factor: Optional[float] = None
    original_positions: int = 4096        # YaRN's trained context
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0         # on cos and sin


def yarn_ramp(rot: Rotation, lanes: int) -> Tuple[int, int]:
    """``(lo, hi)``, the ends of YaRN's linear ramp over the ``lanes / 2``
    frequencies: ``floor`` / ``ceil`` of ``c(beta) = lanes · ln(original /
    (2 pi beta)) / (2 ln theta)`` at ``beta_fast`` / ``beta_slow``, inside
    ``[0, lanes / 2 - 1]``."""
    def c(beta: float) -> float:
        return (lanes * math.log(rot.original_positions / (2 * math.pi * beta))
                / (2 * math.log(rot.theta)))

    last = lanes // 2 - 1
    return (min(max(math.floor(c(rot.beta_fast)), 0), last),
            min(max(math.ceil(c(rot.beta_slow)), 0), last))


def rotation_freqs(rot: Rotation, head_dim: int) -> np.ndarray:
    """The ``lanes / 2`` float32 frequencies of a rotation over the first
    ``lanes = partial · head_dim`` lanes of a head: ``f_i = theta^(-2i /
    lanes)``, and under YaRN (Peng et al., arXiv:2309.00071, as the
    ``transformers`` library computes it) ``f_i / factor`` where the ramp
    ``r_i = clip((i - lo) / (hi - lo), 0, 1)`` is 1, ``f_i`` where it is 0
    and their mix between."""
    lanes = int(head_dim * rot.partial)
    assert lanes % 2 == 0 and 0 < lanes <= head_dim, lanes
    i = np.arange(lanes // 2, dtype=np.float64)
    f = rot.theta ** (-2.0 * i / lanes)
    if rot.yarn_factor is not None:
        lo, hi = yarn_ramp(rot, lanes)
        r = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
        f = f / rot.yarn_factor * r + f * (1.0 - r)
    return f.astype(np.float32)


_YARN_XS2 = Rotation(theta=5e5, partial=0.5, yarn_factor=64.0,
                     original_positions=4096, beta_fast=64.0, beta_slow=1.0,
                     attention_factor=0.1 * math.log(64.0) + 1.0)
_PERIOD = (0, 1, 1, 1)     # full, sliding, sliding, sliding


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Defaults: poolside/Laguna-XS.2 as published, every expert held."""
    vocab_size: int = 100352
    d_model: int = 2048
    windowed: Tuple[int, ...] = _PERIOD * 10     # layer_types: 1 sliding
    heads: Tuple[int, ...] = (48, 64, 64, 64) * 10
    sparse: Tuple[int, ...] = (0,) + (1,) * 39   # mlp_layer_types
    init_depth: int = 40          # the PUBLISHED depth: residual outputs
                                  # are initialised / sqrt(init_depth)
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512             # keys a sliding position sees, with itself
    rope_full: Rotation = _YARN_XS2
    rope_swa: Rotation = Rotation(theta=1e4)
    d_ff: int = 8192              # the dense layer's MLP
    d_expert: int = 512           # one routed expert's width
    d_shared: int = 512           # the shared expert's
    n_routed_experts: int = 256   # the router's width
    first_expert: int = 0         # the share held here:
    n_experts_held: int = 256     # experts first .. first + held
    top_k: int = 8
    routed_scale: float = 2.5
    rms_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        n = len(self.windowed)
        assert n and len(self.heads) == len(self.sparse) == n
        assert set(self.windowed) | set(self.sparse) <= {0, 1}
        assert all(h % self.n_kv_heads == 0 for h in self.heads)
        assert self.window >= 1 and 1 <= self.top_k <= self.n_routed_experts
        assert 0 <= self.first_expert and 1 <= self.n_experts_held
        assert self.first_expert + self.n_experts_held <= self.n_routed_experts

    @property
    def n_layers(self) -> int:
        return len(self.windowed)


LAGUNA_CONFIGS: Dict[str, LagunaConfig] = {
    # the tests' size: both kinds of layer twice, two head counts in
    # groups of 2 and 3, a dense first layer, a window shorter than the
    # sequence and no multiple of a tile edge, a YaRN ramp that has pure,
    # mixed and interpolated frequencies at 8 of them (lo 1, hi 6), a
    # share of 4 of 8 experts
    "laguna_tiny": LagunaConfig(
        vocab_size=512, d_model=48, windowed=(0, 1, 1, 0, 1),
        heads=(4, 6, 6, 4, 6), sparse=(0, 1, 1, 1, 1), init_depth=8,
        n_kv_heads=2, head_dim=32, window=20,
        rope_full=Rotation(theta=1e4, partial=0.5, yarn_factor=8.0,
                           original_positions=32, beta_fast=4.0,
                           beta_slow=0.25, attention_factor=1.2),
        rope_swa=Rotation(theta=100.0), d_ff=96, d_expert=24, d_shared=16,
        n_routed_experts=8, first_expert=0, n_experts_held=4, top_k=2,
        init_std=0.125,
    ),
}


def init_params(cfg: LagunaConfig, key) -> Dict:
    """Matrices normal with ``init_std``, those onto the residual stream
    (``o_proj``, every ``down_proj``) / sqrt(``init_depth``); the table
    like every matrix; norm weights one; the balance bias zero; the table and the head
    two leaves. ``q_proj``, ``o_proj`` and ``gate`` take their shape from
    the layer's head count."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 2)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def out(k, *shape):
        return normal(k, *shape) / math.sqrt(cfg.init_depth)

    def mlp(k, width, *held):
        k = jax.random.split(k, 3)
        return {"gate_proj": {"kernel": normal(k[0], *held, d, width)},
                "up_proj": {"kernel": normal(k[1], *held, d, width)},
                "down_proj": {"kernel": out(k[2], *held, width, d)}}

    kv = cfg.n_kv_heads * cfg.head_dim
    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": {"scale": jnp.ones((d,), pd)},
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
    }
    for i, (H, is_sparse) in enumerate(zip(cfg.heads, cfg.sparse)):
        k = jax.random.split(keys[2 + i], 8)
        q = H * cfg.head_dim
        layer = {
            "norm_1": {"scale": jnp.ones((d,), pd)},
            "norm_2": {"scale": jnp.ones((d,), pd)},
            "attn": {
                "q_proj": {"kernel": normal(k[0], d, q)},
                "k_proj": {"kernel": normal(k[1], d, kv)},
                "v_proj": {"kernel": normal(k[2], d, kv)},
                "gate": {"kernel": normal(k[3], d, H)},
                "o_proj": {"kernel": out(k[4], q, d)},
            },
        }
        if is_sparse:
            layer["moe"] = dict(
                mlp(k[5], cfg.d_expert, cfg.n_experts_held),
                shared=mlp(k[6], cfg.d_shared),
                router={"kernel": normal(k[7], d, cfg.n_routed_experts)},
                **{BALANCE_BIAS: jnp.zeros((cfg.n_routed_experts,), pd)})
        else:
            layer["mlp"] = mlp(k[5], cfg.d_ff)
        params[f"layers_{i}"] = layer
    return params


def _rotation(cfg: LagunaConfig, windowed: bool) -> Rotation:
    return cfg.rope_swa if windowed else cfg.rope_full


def _rope_scope(rot: Rotation):
    return jax.named_scope("rope" if rot.yarn_factor is None else "rope_yarn")


def rotation_tables(cfg: LagunaConfig, seq_len: int) -> Dict[bool, Tuple]:
    """``{windowed: (cos, sin)}``, the ``[seq_len, head_dim]`` float32
    tables of the kinds of layer the model has
    (``ops/ssm_pointwise.py::rotary_tables``: ``common.rotary``'s
    expressions, a lane of the head): built once a step, handed to every
    layer of the kind."""
    tables = {}
    for windowed in sorted({bool(w) for w in cfg.windowed}):
        rot = _rotation(cfg, windowed)
        with jax.named_scope("attn"), jax.named_scope("gqa_proj"), \
                _rope_scope(rot):
            tables[windowed] = rotary_tables(
                jnp.asarray(rotation_freqs(rot, cfg.head_dim)), seq_len,
                cfg.head_dim, rot.attention_factor)
    return tables


def _rotate(cfg: LagunaConfig, windowed: bool, q, k, table):
    """``q [B, S, H·D]`` and ``k [B, S, KV·D]`` of one layer through its
    kind's rotation -> ``[B, S, H, D]`` and ``[B, S, KV, D]``: the
    transposed views of the kernel's heads-first results, which the flash
    call's own transposition folds away."""
    rot = _rotation(cfg, windowed)
    half = int(cfg.head_dim * rot.partial) // 2
    with _rope_scope(rot):
        return tuple(rotary(x, *table, half).transpose(0, 2, 1, 3)
                     for x in (q, k))


def head_gate(n32, kernel):
    """``sigmoid(n1·W_γ)`` ``[B, S, H]`` in float32: one gate a head a
    position, read from the normed stream before it is rounded."""
    return jax.nn.sigmoid(jnp.dot(
        n32, kernel.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))


@jax.named_scope("attn")
def _attn_mixer(cfg: LagunaConfig, windowed: bool, layer: Dict, x, table,
                *, attn_fn):
    """``x + (γ ⊙ attention(n1))·W_o`` at the layer's own head count;
    ``table`` its kind's of :func:`rotation_tables`. The rotation and the
    gate's multiply are ``ops/ssm_pointwise.py``'s kernels on either side
    of ``attn_fn``, which takes and gives ``[B, S, H, D]``."""
    a, dt = layer["attn"], cfg.dtype
    B, S, _ = x.shape
    KV, D = cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("gqa_proj"):
        n32 = rms_norm(x.astype(jnp.float32), layer["norm_1"]["scale"],
                       cfg.rms_eps)
        n = n32.astype(dt)
        q, k = _rotate(cfg, windowed, n @ a["q_proj"]["kernel"].astype(dt),
                       n @ a["k_proj"]["kernel"].astype(dt), table)
        v = (n @ a["v_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
    with jax.named_scope("gqa_core"):
        with jax.named_scope("swa_core" if windowed else "full_core"):
            o = attn_fn(q, k, v, window=cfg.window if windowed else None)
    with jax.named_scope("gqa_proj"):
        with jax.named_scope("attn_gate"):
            o = gate_heads(o.transpose(0, 2, 1, 3),
                           head_gate(n32, a["gate"]["kernel"]))
        return x + o @ a["o_proj"]["kernel"].astype(dt)


def _layer(cfg: LagunaConfig, windowed: bool, is_sparse: bool, layer: Dict,
           x, table, *, attn_fn) -> Tuple[Any, Optional[Dict]]:
    """One layer: ``(x, record)``, the record ``None`` of a dense one."""
    h = _attn_mixer(cfg, windowed, layer, x, table, attn_fn=attn_fn)
    if not is_sparse:
        return dense_sublayer(cfg, h, layer["norm_2"]["scale"],
                              layer["mlp"]), None
    m = layer["moe"]
    return routed_sublayer(
        cfg, h, layer["norm_2"]["scale"], m, score="sigmoid",
        shared=lambda n2: swiglu(n2, m["shared"], cfg.dtype))


def forward_hidden(cfg: LagunaConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d], record).
    ``attn_fn(q, k, v, window=None)`` is the local causal attention
    (``ops/attention.py::causal_attention`` by default: the flash kernels
    on a TPU), handed ``q`` at the layer's head count. The record holds
    ``experts`` [L_sparse, N, top_k] and ``loads`` [L_sparse, routed] of
    the sparse layers in order, and ``carrier`` (zero; see
    ``common.loads_as_gradient``)."""
    if attn_fn is None:
        attn_fn = causal_attention
    x = embed(cfg, params, tokens)
    tables = rotation_tables(cfg, tokens.shape[1])
    records = []
    for i, (windowed, is_sparse) in enumerate(zip(cfg.windowed, cfg.sparse)):
        run = functools.partial(_layer, cfg, bool(windowed), bool(is_sparse),
                                attn_fn=attn_fn)
        if cfg.remat:
            run = checkpoint_layer(run)
        x, rec = run(params[f"layers_{i}"], x, tables[bool(windowed)])
        if rec is not None:
            records.append(rec)
    return (rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps),
            routing_record(records))


def loss_terms(cfg: LagunaConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``common.share_loss_terms`` of this model's forward pass, the cross
    entropy through the untied head."""
    h, rec = forward_hidden(cfg, params, tokens, attn_fn)
    return share_loss_terms(
        cfg, h, rec, ce_from_hidden(h, params["lm_head"]["kernel"], targets,
                                    cfg.xent_chunks))


def loss_fn(cfg: LagunaConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
