"""Llama-family decoder: RMSNorm + RoPE + SwiGLU + grouped-query attention.

A second transformer architecture family (the GPT family lives in
transformer.py; the reference framework ships no models at all — its
example trains a CIFAR CNN, ref train_ddp.py:33-152). Parameter paths use
the q_proj/k_proj/v_proj/o_proj/gate_proj/up_proj/down_proj naming that
``parallel.sharding.tp_rules_gpt`` already matches, so the same
Megatron-style TP rules shard this family unchanged.

GQA: ``n_kv_heads <= n_heads``; ``attn_fn(q, k, v)`` is handed K and V at
their own head count, ``[B, S, n_kv_heads, D]``, and query head ``i`` reads
key/value head ``i // (n_heads / n_kv_heads)``: ``ops/attention.py::
causal_attention`` (the default) and ``ops/flash.py`` take them so and
copy nothing; a kernel of equal head counts only wants ``common.
repeat_kv`` in its ``attn_fn`` (``examples/train_llama_ring.py``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from torchft_tpu.models.common import rms_norm, rotary

__all__ = [
    "LlamaConfig",
    "LLAMA_CONFIGS",
    "llama_init_params",
    "llama_forward",
    "llama_loss_fn",
]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 4          # GQA: kv heads < query heads
    d_ff: int = 1408             # ~8/3 * d_model, SwiGLU sizing
    max_seq_len: int = 512
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # >0: llama_loss_fn fuses the vocab projection via ops/xent.py's
    # sweep over row tiles (never materializes [B,S,V] logits); 0=dense
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert self.d_model % self.n_heads == 0, (
            f"d_model {self.d_model} not divisible by n_heads "
            f"{self.n_heads}"
        )
        assert self.n_heads % self.n_kv_heads == 0, (
            f"n_heads {self.n_heads} not divisible by n_kv_heads "
            f"{self.n_kv_heads} (GQA repeat factor must be integral)"
        )
        assert (self.d_model // self.n_heads) % 2 == 0, (
            f"head_dim {self.d_model // self.n_heads} must be even "
            "(RoPE rotates dimension pairs)"
        )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


LLAMA_CONFIGS: Dict[str, LlamaConfig] = {
    "llama_tiny": LlamaConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=176, max_seq_len=128, remat=False,
    ),
    "llama_120m": LlamaConfig(
        vocab_size=32768, d_model=768, n_layers=12, n_heads=12,
        n_kv_heads=4, d_ff=2048, max_seq_len=1024,
    ),
}


def llama_init_params(cfg: LlamaConfig, key) -> Dict:
    pd = cfg.param_dtype
    d, hd = cfg.d_model, cfg.head_dim
    kv_d = cfg.n_kv_heads * hd
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, din, dout, scale=None):
        scale = scale if scale is not None else (2.0 / (din + dout)) ** 0.5
        return jax.random.normal(k, (din, dout), pd) * scale

    params: Dict = {
        "tok_embed": {
            "embedding": jax.random.normal(
                keys[0], (cfg.vocab_size, d), pd
            ) * 0.02
        },
        "lm_head": {"kernel": dense(keys[1], d, cfg.vocab_size)},
        "final_norm": {"scale": jnp.ones((d,), pd)},
        "layers": [],
    }
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i + 2], 7)
        params["layers"].append({
            "attn_norm": {"scale": jnp.ones((d,), pd)},
            "attn": {
                "q_proj": {"kernel": dense(lk[0], d, d)},
                "k_proj": {"kernel": dense(lk[1], d, kv_d)},
                "v_proj": {"kernel": dense(lk[2], d, kv_d)},
                "o_proj": {"kernel": dense(lk[3], d, d)},
            },
            "mlp_norm": {"scale": jnp.ones((d,), pd)},
            "mlp": {
                "gate_proj": {"kernel": dense(lk[4], d, cfg.d_ff)},
                "up_proj": {"kernel": dense(lk[5], d, cfg.d_ff)},
                "down_proj": {"kernel": dense(lk[6], cfg.d_ff, d)},
            },
        })
    return params


def _rope(x, theta: float):
    """Rotary embedding over [B, S, H, D] (D even): ``common.rotary`` over
    the whole head at the frequencies ``theta^(-i / (D/2))``."""
    half = x.shape[-1] // 2
    freqs = theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    return rotary(x, freqs)


def _default_attention(q, k, v):
    from torchft_tpu.ops.attention import causal_attention

    return causal_attention(q, k, v)


def _block(cfg: LlamaConfig, layer: Dict, x, *, attn_fn):
    dt = cfg.dtype
    B, S, _ = x.shape
    hd = cfg.head_dim

    h = rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_eps)
    q = (h @ layer["attn"]["q_proj"]["kernel"].astype(dt)).reshape(
        B, S, cfg.n_heads, hd
    )
    k = (h @ layer["attn"]["k_proj"]["kernel"].astype(dt)).reshape(
        B, S, cfg.n_kv_heads, hd
    )
    v = (h @ layer["attn"]["v_proj"]["kernel"].astype(dt)).reshape(
        B, S, cfg.n_kv_heads, hd
    )
    q = _rope(q, cfg.rope_theta)
    k = _rope(k, cfg.rope_theta)
    # GQA: k and v go at their own head count (module docstring)
    a = attn_fn(q, k, v).reshape(B, S, cfg.d_model)
    x = x + a @ layer["attn"]["o_proj"]["kernel"].astype(dt)

    h = rms_norm(x, layer["mlp_norm"]["scale"], cfg.rms_eps)
    gate = h @ layer["mlp"]["gate_proj"]["kernel"].astype(dt)
    up = h @ layer["mlp"]["up_proj"]["kernel"].astype(dt)
    x = x + (
        jax.nn.silu(gate) * up
    ) @ layer["mlp"]["down_proj"]["kernel"].astype(dt)
    return x


def llama_forward_hidden(cfg: LlamaConfig, params, tokens,
                         attn_fn: Optional[Callable] = None):
    """tokens -> final-RMSNorm hidden states [B,S,d_model]."""
    if attn_fn is None:
        attn_fn = _default_attention
    dt = cfg.dtype
    x = params["tok_embed"]["embedding"].astype(dt)[tokens]
    block = functools.partial(_block, cfg, attn_fn=attn_fn)
    if cfg.remat:
        block = jax.checkpoint(block)
    for layer in params["layers"]:
        x = block(layer, x)
    return rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)


def llama_forward(cfg: LlamaConfig, params, tokens,
                  attn_fn: Optional[Callable] = None):
    x = llama_forward_hidden(cfg, params, tokens, attn_fn)
    # final projection in f32 (parity with transformer.py): logits feed
    # log_softmax, and bf16 rounding there would contaminate the loss
    return x.astype(jnp.float32) @ params["lm_head"]["kernel"].astype(
        jnp.float32
    )


def llama_loss_fn(cfg: LlamaConfig, params, tokens, targets,
                  attn_fn: Optional[Callable] = None):
    if cfg.xent_chunks > 0:
        from torchft_tpu.ops.xent import hidden_cross_entropy

        h = llama_forward_hidden(cfg, params, tokens, attn_fn)
        return hidden_cross_entropy(
            h, params["lm_head"]["kernel"], targets, cfg.xent_chunks
        )
    logits = llama_forward(cfg, params, tokens, attn_fn)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)
