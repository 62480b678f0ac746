"""JoyAI-LLM-Flash decoder (Hugging Face ``model_type`` ``joyai_llm_flash``,
48B-A2.7B; its config's keys are the DeepSeek-V3 family's, arXiv:2412.19437)
as ONE CHIP'S SHARE of an expert-parallel layer: the layer is told which
routed experts it holds (``first_expert``, ``n_experts_held``), routes
over all ``n_routed_experts`` and computes its own experts' part of the
result. Attention, router, balance bias and shared expert are whole on
every chip. ``d`` = ``d_model``, no biases, untied table and head.

MLA sublayer (the training form: nothing absorbed, no latent cache).
``h = RMSNorm(x)``. ``c_q = RMSNorm(h·W_qa)`` [q_lora_rank];
``q = c_q·W_qb`` -> heads of ``[q_nope ; q_rope]`` (128 + 64).
``[c_kv ; k_r] = h·W_kva`` (kv_lora_rank + 64); ``c_kv = RMSNorm(c_kv)``;
per head ``[k_nope 128 ; v 128] = c_kv·W_kvb``. ``q_rope`` and ``k_r``
take RoPE over INTERLEAVED pairs ``(x_2i, x_2i+1)`` with angle
``s · theta^(-2i/64)`` (``rope_interleave``; ``models/llama.py::_rope``
is ``rotate_half``), no length factor; ``k = [k_nope ; k_r]`` with a
token's one ``k_r`` used by every head. Causal softmax of
``q·k / sqrt(192)``, ``o = P·v`` (heads of 128) ``·W_o``; ``x + o``.

Dense layer (the first ``n_dense_layers``): MLA, then
``x + (silu(h·W_g) * (h·W_u))·W_d`` on ``h = RMSNorm(x)``, width ``d_ff``.

Expert layer: MLA, then on ``h = RMSNorm(x)``:
``s = sigmoid(h·W_r)`` in float32 over all routed experts; ``sel`` = the
``top_k`` largest of ``s + b`` (no group limit); ``g_e = routed_scale ·
s_e / (sum_sel s + 1e-20)`` — ``b`` selects and never weights;
``y = sum_{e in sel and held} g_e·SwiGLU_e(h) + SwiGLU_shared(h)``.

The balance bias ``b`` (``topk_method: noaux_tc``) is a leaf of the
parameters (``.../moe/balance_bias``) that no gradient of the loss moves.
After each step ``b_e += rate · sign(mean(load) - load_e)`` with
``load_e`` the step's count of assignments to ``e``. The loads reach the
rule IN THE GRADIENT TREE AT ``b``'S PLACE (``common.loads_as_gradient``: a
term that adds 0 to the loss and whose cotangent for ``b`` is the loads), so
whatever averages gradients over replica groups averages the loads, and
the rule is part of the optax transformation
(``optim.with_balance_bias`` over ``is_balance_bias``), applied behind the same commit gate.

MTP module (``n_mtp`` = 1; DeepSeek-V3 section 2.2):
``h'_i = W_eh·[RMSNorm(Emb(t_{i+1})) ; RMSNorm(x^L_i)]`` with ``x^L`` the
main model's last residual stream (before the final norm), one expert
layer on ``h'``, its own final RMSNorm, the SHARED token table and head;
``L = CE(main -> t_{i+1}) + mtp_coef · CE(MTP -> t_{i+2})``. With this
repo's batches ``Emb(t_{i+1})`` is ``Emb(targets)`` and ``t_{i+2}`` is
``targets`` rolled left by one; every position is kept.

Conventions of ``models/olmoe.py``: float32 parameters, bf16 compute,
float32 norms / router / softmax, an explicit parameter tree with stable
paths, per-layer ``checkpoint_layer`` behind ``remat``, and the step
programs of ``transformer.make_train_step`` / ``make_grad_step``
(``loss=joyai.loss_fn``).

``checkpoint_layer`` (``models/common.py``) is ``jax.checkpoint`` that
keeps what a layer's router decided — the experts, their weights, the
chosen scores, the loads —, so the backward pass does not run the router
again (``common.routed_sublayer`` says why the weights are among them).

Device-trace scopes: ``embed``; ``attn`` with inner ``mla_q`` (down,
norm, up, RoPE), ``mla_kv`` (down, norm, up, RoPE, laying ``k`` out),
``mla_core`` (the flash calls), ``mla_out``; ``mlp`` with inner
``moe_router``, ``moe_shared``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``; ``mtp`` around the whole module (its ``attn`` / ``mlp``
/ ``embed`` / ``lm_head_xent`` stay inside it); ``lm_head_xent`` twice.
"""

from __future__ import annotations

import dataclasses
import functools
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
    share_loss_terms,
    swiglu,
)
from torchft_tpu.models.transformer import (
    _local_causal_attention,
    ce_from_hidden,
)

__all__ = ["JoyaiConfig", "JOYAI_CONFIGS", "BALANCE_BIAS", "is_balance_bias",
           "init_params", "layer_params", "mla_sublayer", "forward_hidden",
           "loss_terms", "loss_fn"]


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    """Defaults: jdopensource/JoyAI-LLM-Flash as published, every expert
    held."""
    vocab_size: int = 129280
    d_model: int = 2048
    n_layers: int = 40            # dense + expert layers, without the MTP
    n_dense_layers: int = 1       # first_k_dense_replace
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 7168              # the dense layers' SwiGLU width
    d_expert: int = 768           # one routed or shared expert's width
    n_routed_experts: int = 256   # the router's width
    first_expert: int = 0         # the share held here:
    n_experts_held: int = 256     # experts first .. first + held
    top_k: int = 8
    routed_scale: float = 2.5
    n_mtp: int = 1                # 0 or 1 multi-token-prediction module
    mtp_coef: float = 0.3
    rope_theta: float = 32000000.0
    rms_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert self.qk_rope_dim % 2 == 0                  # RoPE pairs
        assert 0 <= self.n_dense_layers <= self.n_layers
        assert self.n_mtp in (0, 1)
        assert 1 <= self.top_k <= self.n_routed_experts
        assert 0 <= self.first_expert
        assert 1 <= self.n_experts_held
        assert self.first_expert + self.n_experts_held <= self.n_routed_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


JOYAI_CONFIGS: Dict[str, JoyaiConfig] = {
    # the tests' size: every mechanism, a share of 4 of 8 experts
    "joyai_tiny": JoyaiConfig(
        vocab_size=512, d_model=64, n_layers=2, n_dense_layers=1, n_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, d_ff=128, d_expert=32, n_routed_experts=8,
        first_expert=0, n_experts_held=4, top_k=2, init_std=0.125,
    ),
}


def layer_params(cfg, key, normal, ones, dense: bool) -> Dict:
    """One layer (``models/kimi_linear.py``'s too, with ITS config): MLA,
    then the dense SwiGLU (``mlp``) or the router, its balance bias, the
    held routed experts and the shared expert (``moe``)."""
    d, f = cfg.d_model, cfg.d_expert
    e, h = cfg.n_experts_held, cfg.n_heads
    k = jax.random.split(key, 12)
    layer = {
        "ln_1": ones(d),
        "attn": {
            "q_a_proj": {"kernel": normal(k[0], d, cfg.q_lora_rank)},
            "q_a_norm": ones(cfg.q_lora_rank),
            "q_b_proj": {"kernel": normal(
                k[1], cfg.q_lora_rank, h * cfg.qk_head_dim)},
            "kv_a_proj": {"kernel": normal(
                k[2], d, cfg.kv_lora_rank + cfg.qk_rope_dim)},
            "kv_a_norm": ones(cfg.kv_lora_rank),
            "kv_b_proj": {"kernel": normal(
                k[3], cfg.kv_lora_rank,
                h * (cfg.qk_nope_dim + cfg.v_head_dim))},
            "o_proj": {"kernel": normal(k[4], h * cfg.v_head_dim, d)},
        },
        "ln_2": ones(d),
    }
    if dense:
        mk = jax.random.split(jax.random.fold_in(key, 1), 3)
        layer["mlp"] = {
            "gate_proj": {"kernel": normal(mk[0], d, cfg.d_ff)},
            "up_proj": {"kernel": normal(mk[1], d, cfg.d_ff)},
            "down_proj": {"kernel": normal(mk[2], cfg.d_ff, d)},
        }
        return layer
    layer["moe"] = {
        "router": {"kernel": normal(k[5], d, cfg.n_routed_experts)},
        BALANCE_BIAS: jnp.zeros((cfg.n_routed_experts,), cfg.param_dtype),
        "gate_proj": {"kernel": normal(k[6], e, d, f)},
        "up_proj": {"kernel": normal(k[7], e, d, f)},
        "down_proj": {"kernel": normal(k[8], e, f, d)},
        "shared": {
            "gate_proj": {"kernel": normal(k[9], d, f)},
            "up_proj": {"kernel": normal(k[10], d, f)},
            "down_proj": {"kernel": normal(k[11], f, d)},
        },
    }
    return layer


def init_params(cfg: JoyaiConfig, key) -> Dict:
    """Every matrix normal with ``init_std``, every norm weight one, the
    balance bias zero."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 4)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def ones(n):
        return {"scale": jnp.ones((n,), pd)}

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": ones(d),
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
    }
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_params(
            cfg, keys[4 + i], normal, ones, dense=i < cfg.n_dense_layers)
    if cfg.n_mtp:
        params["mtp"] = {
            "enorm": ones(d), "hnorm": ones(d),
            "eh_proj": {"kernel": normal(keys[2], 2 * d, d)},
            "block": layer_params(cfg, keys[3], normal, ones, dense=False),
            "ln_f": ones(d),
        }
    return params


def _rope_pairs(x, theta: float):
    """RoPE over interleaved pairs of the last dim of ``[B, S, H, D]``:
    ``(x_2i, x_2i+1)`` turned by ``s · theta^(-2i/D)``, in float32."""
    b, s, h, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(b, s, h, d // 2, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack(
        [x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1
    ).reshape(b, s, h, d).astype(x.dtype)


@jax.named_scope("attn")
def mla_sublayer(cfg, layer: Dict, x, *, attn_fn):
    dt, eps, a = cfg.dtype, cfg.rms_eps, layer["attn"]
    B, S, _ = x.shape
    H, nope, rope = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    h = rms_norm(x, layer["ln_1"]["scale"], eps)
    # ``models/kimi_linear.py`` calls this with ITS config: no q latent
    # (``q_lora_rank`` 0: one ``q_proj``) and no rotation (``rope_theta``
    # None: the shared key channels stay in the score as they are)
    rotate = cfg.rope_theta is not None
    with jax.named_scope("mla_q"):
        if cfg.q_lora_rank:
            c_q = rms_norm(h @ a["q_a_proj"]["kernel"].astype(dt),
                            a["q_a_norm"]["scale"], eps)
            q = c_q @ a["q_b_proj"]["kernel"].astype(dt)
        else:
            q = h @ a["q_proj"]["kernel"].astype(dt)
        q = q.reshape(B, S, H, nope + rope)
        if rotate:
            q = jnp.concatenate(
                [q[..., :nope], _rope_pairs(q[..., nope:], cfg.rope_theta)],
                axis=-1)
    with jax.named_scope("mla_kv"):
        kv_a = h @ a["kv_a_proj"]["kernel"].astype(dt)
        c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank],
                        a["kv_a_norm"]["scale"], eps)
        k_r = kv_a[..., None, cfg.kv_lora_rank:]
        if rotate:
            k_r = _rope_pairs(k_r, cfg.rope_theta)
        kv = (c_kv @ a["kv_b_proj"]["kernel"].astype(dt)).reshape(
            B, S, H, nope + cfg.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (B, S, H, rope))], axis=-1)
        v = kv[..., nope:]
    with jax.named_scope("mla_core"):
        o = attn_fn(q, k, v)                      # [B, S, H, v_head_dim]
    with jax.named_scope("mla_out"):
        return x + o.reshape(B, S, H * cfg.v_head_dim) @ a["o_proj"][
            "kernel"].astype(dt)


def _moe_sublayer(cfg: JoyaiConfig, layer: Dict, x) -> Tuple[Any, Dict]:
    """``common.routed_sublayer`` with this model's norm and its SwiGLU
    shared expert."""
    m = layer["moe"]
    return routed_sublayer(
        cfg, x, layer["ln_2"]["scale"], m,
        shared=lambda h: swiglu(h, m["shared"], cfg.dtype))


def _dense_block(cfg: JoyaiConfig, layer: Dict, x, *, attn_fn):
    return dense_sublayer(
        cfg, mla_sublayer(cfg, layer, x, attn_fn=attn_fn),
        layer["ln_2"]["scale"], layer["mlp"])


def _expert_block(cfg: JoyaiConfig, layer: Dict, x, *, attn_fn):
    return _moe_sublayer(cfg, layer, mla_sublayer(
        cfg, layer, x, attn_fn=attn_fn))


@jax.named_scope("embed")
def _mtp_input(cfg: JoyaiConfig, params: Dict, x_last, next_tokens):
    """``W_eh·[RMSNorm(Emb(t_{i+1})) ; RMSNorm(x^L_i)]``: the embedding
    first, as the released DeepSeek-V3 code has it."""
    p, eps = params["mtp"], cfg.rms_eps
    e = rms_norm(embed(cfg, params, next_tokens), p["enorm"]["scale"], eps)
    h = rms_norm(x_last, p["hnorm"]["scale"], eps)
    return jnp.concatenate([e, h], axis=-1) @ p["eh_proj"]["kernel"].astype(
        cfg.dtype)


def forward_hidden(cfg: JoyaiConfig, params: Dict, tokens, next_tokens=None,
                   attn_fn: Optional[Callable] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d] of the main
    model, record). The record holds ``experts`` [L_e, N, top_k] and
    ``loads`` [L_e, routed] of every expert layer (the MTP module's last),
    ``carrier`` (zero; see ``common.loads_as_gradient``) and, where the MTP
    module runs (``next_tokens`` given), its final-norm ``mtp_hidden``."""
    if attn_fn is None:
        attn_fn = _local_causal_attention
    dense = functools.partial(_dense_block, cfg, attn_fn=attn_fn)
    expert = functools.partial(_expert_block, cfg, attn_fn=attn_fn)
    if cfg.remat:
        dense, expert = checkpoint_layer(dense), checkpoint_layer(expert)
    x = embed(cfg, params, tokens)
    records = []
    for i in range(cfg.n_layers):
        if i < cfg.n_dense_layers:
            x = dense(params[f"layers_{i}"], x)
        else:
            x, rec = expert(params[f"layers_{i}"], x)
            records.append(rec)
    out: Dict[str, Any] = {}
    if cfg.n_mtp and next_tokens is not None:
        with jax.named_scope("mtp"):
            y, rec = expert(params["mtp"]["block"],
                            _mtp_input(cfg, params, x, next_tokens))
            records.append(rec)
            out["mtp_hidden"] = rms_norm(
                y, params["mtp"]["ln_f"]["scale"], cfg.rms_eps)
    out.update(
        experts=jnp.stack([r["experts"] for r in records]),
        loads=jnp.stack([r["loads"] for r in records]),
        carrier=sum(r["carrier"] for r in records),
    )
    return rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps), out


def loss_terms(cfg: JoyaiConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``common.share_loss_terms`` (without ``held_share``) of this
    model's forward pass, with ``mtp_coef`` times the MTP module's cross
    entropy ``mtp_ce`` in the loss; ``mtp_hidden`` beside ``hidden``."""
    h, rec = forward_hidden(cfg, params, tokens, targets, attn_fn)
    head = params["lm_head"]["kernel"]

    def mtp_term(rec):
        with jax.named_scope("mtp"):
            rec["mtp_ce"] = ce_from_hidden(
                rec["mtp_hidden"], head, jnp.roll(targets, -1, axis=1),
                cfg.xent_chunks)
        return cfg.mtp_coef * rec["mtp_ce"]

    return share_loss_terms(
        cfg, h, rec, ce_from_hidden(h, head, targets, cfg.xent_chunks),
        more_loss=mtp_term if cfg.n_mtp else None, held_share=False)


def loss_fn(cfg: JoyaiConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
