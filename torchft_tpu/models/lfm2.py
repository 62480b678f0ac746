"""LFM2-MoE decoder (Hugging Face ``model_type`` ``lfm2_moe``; the
benchmark's configuration is LiquidAI/LFM2-8B-A1B) as ONE CHIP'S SHARE of
an expert-parallel layer. Every layer is a sequence mixer and an MLP,
each behind its own RMSNorm:

    h = x + mixer(RMSNorm(x))          y = h + mlp(RMSNorm(h))

and TWO things of the config say which: ``layer_types[i]`` the mixer
(``"conv"`` or ``"full_attention"``), ``i < n_dense_layers`` the MLP
(dense, else experts). A final RMSNorm, then the head — **the embedding
table itself**: ``logits = h·Eᵀ``, one leaf used twice (tied), so its
gradient is the sum of both uses, and ``ddp``, the optimizer's moments
and a heal carry one array. ``d`` = ``d_model``; no bias anywhere.

``conv`` — the gated short convolution. With ``n = RMSNorm(x)``: ``[B ;
C ; X] = n·W_in`` (``d -> 3d``); ``u = B ⊙ X``; ``v_t = Σ_{j<K} w_j ⊙
u_{t-(K-1)+j}`` (depthwise, causal, ``K`` = ``conv_kernel`` taps ``w [K,
d]``, zeros before the sequence's start, no bias, no activation); ``out =
(C ⊙ v)·W_out``. ``C ⊙ conv(B ⊙ X)`` is ``ops/ssm_pointwise.py::
gated_conv``: one kernel forward (``sconv_fwd``) and one backward
(``sconv_bwd``), reading ``W_in``'s output once.

``full_attention`` — ``q = n·W_q`` -> ``n_heads`` × ``head_dim``, ``k, v
= n·W_k, n·W_v`` -> ``n_kv_heads`` × ``head_dim``; an RMSNorm with a
learned ``head_dim``-wide weight on every q head and every k head; RoPE
``rope_theta`` over the whole head (``rotate_half``: ``models/llama.py::
_rope``); causal softmax of ``q·k / sqrt(head_dim)``, ``·v``, each
key/value head serving ``n_heads / n_kv_heads`` consecutive query heads
(the flash call takes k and v at their own head count and reads head ``i
// group`` for query head ``i``: ``ops/flash.py``); ``·W_o``.

Dense MLP: ``W_down·(silu(W_gate n) ⊙ W_up n)``, ``d_ff`` wide.

Expert MLP: ``s = sigmoid(n·W_r)`` in float32 over all routed experts;
``sel`` = the ``top_k`` largest of ``s + b``; ``g_e = routed_scale · s_e
/ (Σ_sel s + 1e-6)`` — ``b`` selects and never weights, no gradient
reaches it; ``y = Σ_{e in sel and held} g_e · SwiGLU_e(n)``, ``d_expert``
wide; no shared expert, no auxiliary loss. The layer is told which routed
experts it holds (``first_expert``, ``n_experts_held``), routes over all
``n_routed_experts`` and computes its own experts' part
(``ops/moe.py::moe_mlp``). The balance bias ``b`` and the way its loads
reach ``optim.with_balance_bias`` in the gradient tree are
``models/common.py``'s.

Conventions of ``models/joyai.py`` and ``models/nemotron_h.py``: float32
parameters, bf16 compute, float32 norms / router / taps, an explicit
parameter tree with stable paths ``layers_<i>/{norm_1,norm_2}`` and
``layers_<i>/{conv|attn}/...``, ``layers_<i>/{mlp|moe}/...``, per-layer
``checkpoint_layer`` behind ``remat``, and the step programs of
``transformer.make_train_step`` / ``make_grad_step``
(``loss=lfm2.loss_fn``).

``checkpoint_layer`` (``models/common.py``) is ``jax.checkpoint`` that
keeps what a layer's router decided — the experts, their weights, the
chosen scores, the loads —, so the backward pass does not run the router
again (``common.routed_sublayer`` says why the weights are among them).

Device-trace scopes: ``embed``; both mixers under ``attn``, told apart
inside — ``sconv_in`` (norm, ``W_in``), ``sconv_core`` (the kernels),
``sconv_out``; ``gqa_proj`` (norm, q / k / v, QK-norm, RoPE,
``W_o``), ``gqa_core`` (the flash call) — Nemotron-H's names: the same
measurement; ``mlp`` with the dense SwiGLU straight under it and the
experts' ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``; ``lm_head_xent``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

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
)
from torchft_tpu.models.llama import _rope
from torchft_tpu.models.transformer import (
    _local_causal_attention,
    ce_from_hidden,
)
from torchft_tpu.ops.ssm_pointwise import gated_conv

__all__ = ["Lfm2Config", "LFM2_CONFIGS", "BALANCE_BIAS", "is_balance_bias",
           "init_params", "forward_hidden", "loss_terms", "loss_fn"]

MIXERS = {"conv": "conv", "full_attention": "attn"}
_C, _A = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """Defaults: LiquidAI/LFM2-8B-A1B as published, every expert held."""
    vocab_size: int = 65536
    d_model: int = 2048
    layer_types: Tuple[str, ...] = (
        _C, _C, _A, _C, _C, _C, _A, _C, _C, _C, _A, _C,
        _C, _C, _A, _C, _C, _C, _A, _C, _C, _A, _C, _C)
    n_dense_layers: int = 2       # layers 0 .. n-1 have the dense MLP
    init_depth: int = 24          # the PUBLISHED depth: residual outputs
                                  # are initialised / sqrt(init_depth)
    conv_kernel: int = 3
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    d_ff: int = 7168              # the dense MLP's width
    d_expert: int = 1792          # one routed expert's
    n_routed_experts: int = 32    # the router's width
    first_expert: int = 0         # the share held here:
    n_experts_held: int = 32      # experts first .. first + held
    top_k: int = 4
    routed_scale: float = 1.0
    renorm_eps: float = 1e-6      # beside the chosen scores' sum
    rms_eps: float = 1e-5
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert self.layer_types and set(self.layer_types) <= set(MIXERS)
        assert 0 <= self.n_dense_layers <= len(self.layer_types)
        assert self.n_heads % self.n_kv_heads == 0
        assert 1 <= self.top_k <= self.n_routed_experts
        assert 0 <= self.first_expert
        assert 1 <= self.n_experts_held
        assert self.first_expert + self.n_experts_held <= self.n_routed_experts

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


LFM2_CONFIGS: Dict[str, Lfm2Config] = {
    # the tests' size: both mixers and both MLPs, a share of 4 of 8
    # experts, two query heads a key/value head
    "lfm2_tiny": Lfm2Config(
        vocab_size=512, d_model=64, layer_types=(_C, _A, _C, _A),
        n_dense_layers=1, init_depth=4, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=96, d_expert=32, n_routed_experts=8,
        first_expert=0, n_experts_held=4, top_k=2, init_std=0.125,
    ),
}


def _swiglu_params(k, normal, out, d: int, f: int, *lead: int) -> Dict:
    """``d -> f -> d``, ``lead`` (an expert axis) in front."""
    return {
        "gate_proj": {"kernel": normal(k[0], *lead, d, f)},
        "up_proj": {"kernel": normal(k[1], *lead, d, f)},
        "down_proj": {"kernel": out(k[2], *lead, f, d)},
    }


def init_params(cfg: Lfm2Config, key) -> Dict:
    """Matrices normal with ``init_std``, those onto the residual stream
    (``out_proj``, ``o_proj``, ``down_proj``) / sqrt(``init_depth``);
    norm weights one; the taps as a depthwise ``Conv1d``'s default,
    U(-1/sqrt(K), 1/sqrt(K)); the balance bias zero. ONE table: there is
    no ``lm_head`` leaf."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 1)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def out(k, *shape):
        return normal(k, *shape) / math.sqrt(cfg.init_depth)

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": {"scale": jnp.ones((d,), pd)},
    }
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[1 + i], 8)
        layer: Dict[str, Any] = {
            "norm_1": {"scale": jnp.ones((d,), pd)},
            "norm_2": {"scale": jnp.ones((d,), pd)},
        }
        if kind == _C:
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            layer["conv"] = {
                "in_proj": {"kernel": normal(k[0], d, 3 * d)},
                "conv": {"kernel": jax.random.uniform(
                    k[1], (cfg.conv_kernel, d), pd, -bound, bound)},
                "out_proj": {"kernel": out(k[2], d, d)},
            }
        else:
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            layer["attn"] = {
                "q_proj": {"kernel": normal(k[0], d, q)},
                "k_proj": {"kernel": normal(k[1], d, kv)},
                "v_proj": {"kernel": normal(k[2], d, kv)},
                "o_proj": {"kernel": out(k[3], q, d)},
                "q_norm": {"scale": jnp.ones((cfg.head_dim,), pd)},
                "k_norm": {"scale": jnp.ones((cfg.head_dim,), pd)},
            }
        if i < cfg.n_dense_layers:
            layer["mlp"] = _swiglu_params(k[4:], normal, out, d, cfg.d_ff)
        else:
            layer["moe"] = dict(
                _swiglu_params(k[4:], normal, out, d, cfg.d_expert,
                               cfg.n_experts_held),
                router={"kernel": normal(k[7], d, cfg.n_routed_experts)},
                **{BALANCE_BIAS: jnp.zeros((cfg.n_routed_experts,), pd)})
        params[f"layers_{i}"] = layer
    return params


def _gated_conv(m: Dict, bcx, dt):
    """``C ⊙ conv(B ⊙ X)``: a seam over ``ops/ssm_pointwise.py``'s
    kernels, kept under this name because ``benchmark/tests/
    lfm2_faults.py`` puts its stand-ins in its place."""
    return gated_conv(bcx, m["conv"]["kernel"]).astype(dt)


@jax.named_scope("attn")
def _conv_mixer(cfg: Lfm2Config, layer: Dict, x):
    m, dt = layer["conv"], cfg.dtype
    with jax.named_scope("sconv_in"):
        n = rms_norm(x, layer["norm_1"]["scale"], cfg.rms_eps)
        bcx = n @ m["in_proj"]["kernel"].astype(dt)
    with jax.named_scope("sconv_core"):
        y = _gated_conv(m, bcx, dt)
    with jax.named_scope("sconv_out"):
        return x + y @ m["out_proj"]["kernel"].astype(dt)


@jax.named_scope("attn")
def _attn_mixer(cfg: Lfm2Config, layer: Dict, x, *, attn_fn):
    a, dt, eps = layer["attn"], cfg.dtype, cfg.rms_eps
    B, S, _ = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("gqa_proj"):
        n = rms_norm(x, layer["norm_1"]["scale"], eps)
        q = (n @ a["q_proj"]["kernel"].astype(dt)).reshape(B, S, H, D)
        k = (n @ a["k_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
        v = (n @ a["v_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
        # a head at a time: the norm's weight is head_dim wide
        q = _rope(rms_norm(q, a["q_norm"]["scale"], eps), cfg.rope_theta)
        k = _rope(rms_norm(k, a["k_norm"]["scale"], eps), cfg.rope_theta)
    with jax.named_scope("gqa_core"):
        o = attn_fn(q, k, v)
    with jax.named_scope("gqa_proj"):
        return x + o.reshape(B, S, H * D) @ a["o_proj"]["kernel"].astype(dt)


def _dense_mlp(cfg: Lfm2Config, layer: Dict, x):
    return dense_sublayer(cfg, x, layer["norm_2"]["scale"], layer["mlp"])


def _moe_mlp(cfg: Lfm2Config, layer: Dict, x) -> Tuple[Any, Dict]:
    """``common.routed_sublayer`` with this model's norm and the
    renormalisation's published epsilon; no shared expert."""
    return routed_sublayer(cfg, x, layer["norm_2"]["scale"], layer["moe"],
                           renorm_eps=cfg.renorm_eps)


def _layer(cfg: Lfm2Config, kind: str, dense: bool, layer: Dict, x, *,
           attn_fn):
    """One layer: ``(x, record or None)``."""
    if kind == _C:
        x = _conv_mixer(cfg, layer, x)
    else:
        x = _attn_mixer(cfg, layer, x, attn_fn=attn_fn)
    if dense:
        return _dense_mlp(cfg, layer, x), None
    return _moe_mlp(cfg, layer, x)


def forward_hidden(cfg: Lfm2Config, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d], record). The
    record holds ``experts`` [L_e, N, top_k] and ``loads`` [L_e, routed]
    of every expert layer in order, and ``carrier`` (zero; see
    ``common.loads_as_gradient``)."""
    if attn_fn is None:
        attn_fn = _local_causal_attention
    x = embed(cfg, params, tokens)
    records = []
    for i, kind in enumerate(cfg.layer_types):
        run = functools.partial(_layer, cfg, kind, i < cfg.n_dense_layers,
                                attn_fn=attn_fn)
        if cfg.remat:
            run = checkpoint_layer(run)
        x, rec = run(params[f"layers_{i}"], x)
        if rec is not None:
            records.append(rec)
    out = routing_record(records)
    return rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps), out


def loss_terms(cfg: Lfm2Config, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``common.share_loss_terms`` of this model's forward pass, the cross
    entropy through the tied head."""
    h, rec = forward_hidden(cfg, params, tokens, attn_fn)
    with jax.named_scope("lm_head_xent"):
        # the head is the table: [V, d] read as [d, V]
        head = params["wte"]["embedding"].T
    return share_loss_terms(
        cfg, h, rec, ce_from_hidden(h, head, targets, cfg.xent_chunks))


def loss_fn(cfg: Lfm2Config, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
