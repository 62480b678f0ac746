"""OLMoE decoder (Muennighoff et al., arXiv:2409.02060; Hugging Face
``model_type`` ``olmoe``): RMSNorm before each sublayer AND on the whole
q and k projections, RoPE, multi-head causal attention, and a sparse
SwiGLU MLP of ``n_experts`` experts of which each token takes its
``top_k`` by softmax router probability — not renormalised, no shared
expert, no capacity: every assignment is computed (``ops/moe.py``).
Untied token table and head, no biases.

Same conventions as ``models/transformer.py``: float32 parameters, bf16
compute, float32 norms / softmax / router, an explicit parameter pytree
with stable path names, per-layer ``jax.checkpoint`` behind ``remat``,
and the step programs of ``transformer.make_train_step`` /
``make_grad_step`` (``loss=olmoe.loss_fn``). RoPE is
``models/llama.py::_rope``, which IS the Hugging Face ``rotate_half``
convention (first half of a head against its second half, frequencies
``theta ** (-2i / D)``), not the interleaved-pairs one.

The training loss is cross entropy + ``lb_coef`` x load-balancing loss +
``z_coef`` x router z-loss, each auxiliary term summed over layers:
``n_experts * sum_e f_e * P_e`` with ``f_e`` the share of the layer's
``top_k * N`` assignments that went to expert ``e`` (a count: no
gradient) and ``P_e`` the mean router probability; and
``mean(logsumexp(router logits) ** 2)``.

Device-trace scopes: ``embed``, ``attn`` (norms, projections, QK-norm,
RoPE, the flash call), ``lm_head_xent``, and the whole sparse sublayer
under ``mlp`` with inner scopes ``moe_router`` (norm, router matmul,
softmax, top-k, the auxiliary terms), ``moe_dispatch``, ``moe_experts``,
``moe_combine``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.common import embed, rms_norm
from torchft_tpu.models.llama import _rope
from torchft_tpu.models.transformer import (
    _local_causal_attention,
    ce_from_hidden,
)
from torchft_tpu.ops import moe

__all__ = ["OlmoeConfig", "OLMOE_CONFIGS", "init_params", "forward_hidden",
           "loss_terms", "loss_fn"]


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    """Defaults: allenai/OLMoE-1B-7B-0125-Instruct as published."""
    vocab_size: int = 50304
    d_model: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_experts: int = 64
    top_k: int = 8
    d_expert: int = 1024          # SwiGLU width of one expert
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    lb_coef: float = 0.01         # load-balancing loss (the paper's)
    z_coef: float = 0.001         # router z-loss (the paper's)
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert self.d_model % self.n_heads == 0
        assert (self.d_model // self.n_heads) % 2 == 0   # RoPE halves
        assert 1 <= self.top_k <= self.n_experts

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


OLMOE_CONFIGS: Dict[str, OlmoeConfig] = {
    # the tests' size; 1/sqrt(64) keeps the sublayers' outputs of order 1
    "olmoe_tiny": OlmoeConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_experts=8,
        top_k=2, d_expert=32, max_seq_len=64, init_std=0.125,
    ),
}


def init_params(cfg: OlmoeConfig, key) -> Dict:
    """Every matrix normal with ``init_std``, every norm weight one."""
    pd, d, f, e = cfg.param_dtype, cfg.d_model, cfg.d_expert, cfg.n_experts
    keys = jax.random.split(key, cfg.n_layers + 2)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def ones():
        return {"scale": jnp.ones((d,), pd)}

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": ones(),
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
    }
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[2 + i], 8)
        params[f"layers_{i}"] = {
            "ln_1": ones(),
            "attn": {
                "q_proj": {"kernel": normal(lk[0], d, d)},
                "k_proj": {"kernel": normal(lk[1], d, d)},
                "v_proj": {"kernel": normal(lk[2], d, d)},
                "o_proj": {"kernel": normal(lk[3], d, d)},
                "q_norm": ones(),
                "k_norm": ones(),
            },
            "ln_2": ones(),
            "moe": {
                "router": {"kernel": normal(lk[4], d, e)},
                "gate_proj": {"kernel": normal(lk[5], e, d, f)},
                "up_proj": {"kernel": normal(lk[6], e, d, f)},
                "down_proj": {"kernel": normal(lk[7], e, f, d)},
            },
        }
    return params


def _qk_norm(x, scale, eps: float):
    """RMSNorm over the whole ``[.., d_model]`` projection, before the
    head split (not per head)."""
    return rms_norm(x, scale, eps)


@jax.named_scope("attn")
def _attn_sublayer(cfg: OlmoeConfig, layer: Dict, x, *, attn_fn):
    dt, eps = cfg.dtype, cfg.rms_eps
    a = layer["attn"]
    B, S, d = x.shape
    h = rms_norm(x, layer["ln_1"]["scale"], eps)
    q = _qk_norm(h @ a["q_proj"]["kernel"].astype(dt), a["q_norm"]["scale"],
                 eps)
    k = _qk_norm(h @ a["k_proj"]["kernel"].astype(dt), a["k_norm"]["scale"],
                 eps)
    v = h @ a["v_proj"]["kernel"].astype(dt)
    heads = (B, S, cfg.n_heads, cfg.head_dim)
    q = _rope(q.reshape(heads), cfg.rope_theta)
    k = _rope(k.reshape(heads), cfg.rope_theta)
    out = attn_fn(q, k, v.reshape(heads)).reshape(B, S, d)
    return x + out @ a["o_proj"]["kernel"].astype(dt)


@jax.named_scope("mlp")
def _moe_sublayer(cfg: OlmoeConfig, layer: Dict, x) -> Tuple[Any, Any]:
    """``(x + y, (load-balancing term, z term, experts [N, top_k]))``."""
    m = layer["moe"]
    B, S, d = x.shape
    with jax.named_scope("moe_router"):
        h32 = rms_norm(x.astype(jnp.float32), layer["ln_2"]["scale"],
                       cfg.rms_eps).reshape(B * S, d)
        # the router reads the normed stream before it is rounded to the
        # compute dtype, in true float32: a near-tie between the k-th and
        # the next expert then flips only on what is upstream of it
        logits = jnp.dot(h32, m["router"]["kernel"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = moe.top_k_routing(probs, cfg.top_k)
        share = jnp.zeros((cfg.n_experts,), jnp.float32).at[
            experts.reshape(-1)].add(1.0 / experts.size)
        lb = cfg.n_experts * jnp.sum(
            jax.lax.stop_gradient(share) * jnp.mean(probs, axis=0))
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    y = moe.moe_mlp(
        h32.astype(cfg.dtype), weights, experts, m["gate_proj"]["kernel"],
        m["up_proj"]["kernel"], m["down_proj"]["kernel"],
    )
    return x + y.reshape(B, S, d), (lb, z, experts)


def _block(cfg: OlmoeConfig, layer: Dict, x, *, attn_fn):
    x = _attn_sublayer(cfg, layer, x, attn_fn=attn_fn)
    return _moe_sublayer(cfg, layer, x)


def forward_hidden(cfg: OlmoeConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d], router
    record: ``load_balance`` and ``router_z`` summed over layers, and
    ``experts`` [L, B*S, top_k])."""
    if attn_fn is None:
        attn_fn = _local_causal_attention
    x = embed(cfg, params, tokens)
    block = functools.partial(_block, cfg, attn_fn=attn_fn)
    if cfg.remat:
        block = jax.checkpoint(block)
    lb = z = jnp.zeros((), jnp.float32)
    chosen = []
    for i in range(cfg.n_layers):
        x, (lb_i, z_i, experts) = block(params[f"layers_{i}"], x)
        lb, z = lb + lb_i, z + z_i
        chosen.append(experts)
    h = rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps)
    return h, {"load_balance": lb, "router_z": z,
               "experts": jnp.stack(chosen)}


def loss_terms(cfg: OlmoeConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``loss`` (what is trained on) and what it is made of: ``ce``,
    ``load_balance``, ``router_z``; the routing ``experts`` and the
    final-norm ``hidden`` states, for whoever compares them per token."""
    h, router = forward_hidden(cfg, params, tokens, attn_fn)
    ce = ce_from_hidden(h, params["lm_head"]["kernel"], targets,
                        cfg.xent_chunks)
    loss = (ce + cfg.lb_coef * router["load_balance"]
            + cfg.z_coef * router["router_z"])
    return dict(router, ce=ce, loss=loss, hidden=h)


def loss_fn(cfg: OlmoeConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
