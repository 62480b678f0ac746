"""Olmo Hybrid decoder (Hugging Face ``model_type`` ``olmo_hybrid``; the
benchmark's configuration is allenai/Olmo-Hybrid-7B) as ONE CHIP'S SHARE
of a vocabulary-parallel stage: the rows of table and head held here,
every mixer and MLP whole. A dense model; every layer is a sequence
mixer and a SwiGLU MLP with the OLMo family's norms (OLMo 2,
arXiv:2501.00656) — the sublayer's OUTPUT is normed, its input is the
stream as it is:

    h = x + RMSNorm(mixer(x))          y = h + RMSNorm(mlp(h))

and the config's ``layer_types`` says which mixer, layer by layer:
``linear_attention`` the gated delta rule with ONE decay a head (Gated
DeltaNet, arXiv:2412.06464), ``full_attention`` softmax attention. A
final RMSNorm, an untied head. ``d`` = ``d_model``; no bias anywhere.
(The config has no key for the place of the norms: a pre-norm linear
layer, ``x + mixer(RMSNorm(x))``, is the other reading, costs the same
passes over ``[N, d]`` and is not computed here.)

Linear-attention mixer (``H`` heads of ``K = key_dim`` key and ``V =
value_dim`` value channels; ``n`` the mixer's input). ``[q̃ ; k̃ ; ṽ] =
silu(conv(n·W_qkv))``: ONE projection ``d -> 2HK + HV`` and ONE causal
depthwise convolution of ``conv_kernel`` taps over its output, no bias
(``ops/ssm_pointwise.py::conv_silu``, a zero bias passed); per head ``q
= q̃ / ‖q̃‖₂ · K^{-1/2}``, ``k = k̃ / ‖k̃‖₂`` (:func:`_l2_normed`); the
step ``β = 2·σ(n·W_b)`` (``allow_neg_eigval``: β in (0, 2), so ``I − β
k kᵀ`` has the eigenvalue ``1 − β`` in (−1, 1); else ``σ`` alone) and
the log-decay ``g = −exp(A_log_h) · softplus(n·W_a + dt_bias_h)``, each
ONE number a head a position, f32 from the projection's accumulator on;

    S_t = exp(g_t) (I − β_t k_t k_tᵀ) S_{t-1} + β_t k_t v_tᵀ,
    o_t = S_tᵀ q_t                       (``ops/kda.py::gdn_scan``)

then ``y = W_o·[RMSNorm_head(o; w ∈ R^V) ⊙ silu(n·W_g)]``: the norm over
a head's ``V`` channels with one learned weight, the gate AFTER it and a
SiLU (:func:`_gated_head_norm`; Kimi Linear's is a sigmoid on a low-rank
gate) — ONE kernel, ``ops/ssm_pointwise.py::kda_ogate`` with this
file's seam: two heads of 192 are three lane tiles. The l2 norms are
XLA's: ``q̃``'s and ``k̃``'s 2 880 channels are 22.5 lane tiles, so no
block of whole heads and whole tiles divides them. (A 128-wide head is
ONE lane tile: ``models/qwen3_next.py`` runs this seam and these norms so,
32 value heads of 128 over 16 key heads.)

Full-attention mixer: ``H`` heads of ``d / H``, as many key/value heads;
``q = RMSNorm(n·W_q; w_q)``, ``k = RMSNorm(n·W_k; w_k)`` over the WHOLE
projection, one ``d``-wide weight each, before the heads are split
(OLMo 2's QK-norm, :func:`_qk_normed`); NOT ROTATED (``rope_theta``
None: the config's ``rope_parameters`` is ``{"rope_theta": null}`` and
the recurrent layers carry position; a number rotates as
``models/llama.py::_rope``); causal softmax at ``head_dim^{-1/2}``
through the flash kernels.

Conventions of ``models/kimi_linear.py``: float32 parameters, bf16
compute, float32 norms / softmax statistics / l2 norms / ``g``, its
cumulative sums, ``β`` and the delta rule's state; an explicit parameter
tree with stable paths ``layers_<i>/{post_attn_norm, post_mlp_norm}``,
``layers_<i>/{gdn|attn}``, ``layers_<i>/mlp``; per-layer
``jax.checkpoint`` behind ``remat``; the step programs of
``transformer.make_train_step`` / ``make_grad_step``
(``loss=olmo_hybrid.loss_fn``).

Device-trace scopes: ``embed``; both mixers under ``attn``, told apart
inside — ``gdn_in`` (the five projections), ``gdn_conv`` (the
convolution's kernels ``ssm_conv_fwd`` / ``ssm_conv_bwd``, the l2 norms,
the step and the decay), ``gdn_core`` (the scan's kernels ``gdn_fwd`` /
``gdn_bwd``), ``gdn_gate`` (the head norm and the gate), ``gdn_out``
(``W_o``, the output's norm, the residual); ``gqa_proj`` (q / k / v, the
QK-norm, ``W_o``, the output's norm) and ``gqa_core`` with ``full_core``
inside it around the flash call (Nemotron-H's and Phi-4-mini-flash's
names); ``mlp``; ``lm_head_xent``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.common import embed, rms_norm, swiglu
from torchft_tpu.models.llama import _rope
from torchft_tpu.models.transformer import (
    _local_causal_attention,
    ce_from_hidden,
)
from torchft_tpu.ops.kda import gdn_scan
from torchft_tpu.ops.ssm_pointwise import conv_silu, kda_ogate

__all__ = ["OlmoHybridConfig", "OLMO_HYBRID_CONFIGS", "LINEAR", "FULL",
           "init_params", "forward_hidden", "loss_terms", "loss_fn",
           "decay_and_step"]

L2_EPS = 1e-6     # beside a head's squared norm, under the root
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Defaults: allenai/Olmo-Hybrid-7B as published, the whole
    vocabulary held."""
    vocab_size: int = 100352
    d_model: int = 3840
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
    n_heads: int = 30             # of both mixers (and key/value heads)
    key_dim: int = 96             # a linear head's key channels
    value_dim: int = 192          # a linear head's value channels
    conv_kernel: int = 4
    allow_neg_eigval: bool = True     # β = 2σ(·)
    rope_theta: Optional[float] = None
    d_ff: int = 11008
    rms_eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert set(self.layer_types) <= {LINEAR, FULL}, self.layer_types
        assert self.d_model % self.n_heads == 0

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


OLMO_HYBRID_CONFIGS: Dict[str, OlmoHybridConfig] = {
    # the tests' size: one whole period L L L F, key and value widths
    # that differ and are no lane tile, a head count no four divides
    "olmo_hybrid_tiny": OlmoHybridConfig(
        vocab_size=256, d_model=48, layer_types=(LINEAR, LINEAR, LINEAR, FULL),
        n_heads=6, key_dim=12, value_dim=24, d_ff=96, init_std=0.125,
    ),
}


def _gdn_params(cfg: OlmoHybridConfig, key, normal) -> Dict:
    """The taps as a depthwise ``Conv1d``'s default, U(-1/sqrt(T),
    1/sqrt(T)); ``A_log = log U(0, 16)`` a head, clipped away from 0;
    ``dt_bias`` the inverse softplus of a step drawn log-uniform in
    [1e-3, 1e-1], a head; the head norm's weight one; the matrices
    normal (the Gated DeltaNet layer's defaults)."""
    pd, d, H = cfg.param_dtype, cfg.d_model, cfg.n_heads
    hk, hv = H * cfg.key_dim, H * cfg.value_dim
    k = jax.random.split(key, 8)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    dt = jnp.exp(jax.random.uniform(
        k[6], (H,), pd, math.log(1e-3), math.log(1e-1)))
    return {
        "qkv_proj": {"kernel": normal(k[0], d, 2 * hk + hv)},
        "conv": {"kernel": jax.random.uniform(
            k[1], (cfg.conv_kernel, 2 * hk + hv), pd, -bound, bound)},
        "a_proj": {"kernel": normal(k[2], d, H)},
        "b_proj": {"kernel": normal(k[3], d, H)},
        "g_proj": {"kernel": normal(k[4], d, hv)},
        "A_log": jnp.log(jnp.maximum(
            jax.random.uniform(k[5], (H,), pd, 0.0, 16.0), 1e-4)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": {"scale": jnp.ones((cfg.value_dim,), pd)},
        "o_proj": {"kernel": normal(k[7], hv, d)},
    }


def init_params(cfg: OlmoHybridConfig, key) -> Dict:
    """Every matrix normal with ``init_std``, every norm weight one; the
    delta rule's own leaves as :func:`_gdn_params` says."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 2)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def ones(n):
        return {"scale": jnp.ones((n,), pd)}

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": ones(d),
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
    }
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[2 + i], 8)
        layer: Dict[str, Any] = {
            "post_attn_norm": ones(d), "post_mlp_norm": ones(d),
            "mlp": {"gate_proj": {"kernel": normal(k[0], d, cfg.d_ff)},
                    "up_proj": {"kernel": normal(k[1], d, cfg.d_ff)},
                    "down_proj": {"kernel": normal(k[2], cfg.d_ff, d)}},
        }
        if kind == LINEAR:
            layer["gdn"] = _gdn_params(cfg, k[3], normal)
        else:
            layer["attn"] = {
                **{f"{n}_proj": {"kernel": normal(k[4 + j], d, d)}
                   for j, n in enumerate(("q", "k", "v", "o"))},
                "q_norm": ones(d), "k_norm": ones(d)}
        params[f"layers_{i}"] = layer
    return params


def _gdn_scan(q, k, v, g, beta):
    """The delta rule: a seam over ``ops/kda.py``'s kernels, kept under
    this name because ``benchmark/tests/olmo_hybrid_faults.py`` puts its
    stand-ins in its place."""
    return gdn_scan(q, k, v, g, beta)


def _gated_head_norm(o, scale, gate, eps: float):
    """``RMSNorm_head(o) ⊙ silu(gate)``: the norm FIRST, over a head's
    channels (the last axis; one ``V``-wide weight), then the SiLU gate;
    in f32, rounded once (a seam: see :func:`_gdn_scan`)."""
    f32 = jnp.float32
    return (rms_norm(o.astype(f32), scale, eps)
            * jax.nn.silu(gate.astype(f32))).astype(o.dtype)


def _l2_normed(x):
    """``x / ‖x‖₂`` over the last axis, a head's channels, in f32 (a
    seam: see :func:`_gdn_scan`)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def _qk_normed(z, scale, eps: float, n_heads: int):
    """OLMo 2's QK-norm: RMSNorm over the WHOLE projection ``z [B, S,
    d]``, one ``d``-wide weight, THEN the heads are split (a seam: see
    :func:`_gdn_scan`)."""
    B, S, d = z.shape
    return rms_norm(z, scale, eps).reshape(B, S, n_heads, d // n_heads)


def decay_and_step(cfg: OlmoHybridConfig, m: Dict, n):
    """``(g, β)`` of a linear mixer on its input ``n``, each ``[B, S,
    H]`` f32: the two ``d -> H`` projections keep their f32 accumulator
    (a log-decay rounded to bf16 would move by 2^-9 of its size)."""
    f32 = jnp.float32
    a = jnp.dot(n, m["a_proj"]["kernel"].astype(n.dtype),
                preferred_element_type=f32)
    b = jnp.dot(n, m["b_proj"]["kernel"].astype(n.dtype),
                preferred_element_type=f32)
    g = -jnp.exp(m["A_log"].astype(f32)) * jax.nn.softplus(
        a + m["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(b)
    return g, 2.0 * beta if cfg.allow_neg_eigval else beta


def _gdn_mixer(cfg: OlmoHybridConfig, m: Dict, n):
    dt = cfg.dtype
    B, S, _ = n.shape
    H, K, V = cfg.n_heads, cfg.key_dim, cfg.value_dim
    with jax.named_scope("gdn_in"):
        qkv = n @ m["qkv_proj"]["kernel"].astype(dt)
        gate = n @ m["g_proj"]["kernel"].astype(dt)
        g, beta = decay_and_step(cfg, m, n)
    with jax.named_scope("gdn_conv"):
        taps = m["conv"]["kernel"]
        qkv = conv_silu(qkv, taps, jnp.zeros(taps.shape[1:], taps.dtype))
        q = (_l2_normed(qkv[..., :H * K].reshape(B, S, H, K))
             * K ** -0.5).astype(dt)
        k = _l2_normed(
            qkv[..., H * K:2 * H * K].reshape(B, S, H, K)).astype(dt)
        v = qkv[..., 2 * H * K:].reshape(B, S, H, V)
    with jax.named_scope("gdn_core"):
        o = _gdn_scan(q, k, v, g, beta)                  # [B, S, H, V]
    with jax.named_scope("gdn_gate"):
        y = kda_ogate(o.reshape(B, S, H * V), gate, m["o_norm"]["scale"],
                      cfg.rms_eps, _gated_head_norm)
    with jax.named_scope("gdn_out"):
        return y @ m["o_proj"]["kernel"].astype(dt)


@jax.named_scope("attn")
def _gdn_sublayer(cfg: OlmoHybridConfig, layer: Dict, x):
    y = _gdn_mixer(cfg, layer["gdn"], x)
    with jax.named_scope("gdn_out"):
        return x + rms_norm(y, layer["post_attn_norm"]["scale"], cfg.rms_eps)


@jax.named_scope("attn")
def _attn_sublayer(cfg: OlmoHybridConfig, layer: Dict, x, *, attn_fn):
    a, dt, eps = layer["attn"], cfg.dtype, cfg.rms_eps
    B, S, _ = x.shape
    H, D = cfg.n_heads, cfg.head_dim
    with jax.named_scope("gqa_proj"):
        q = _qk_normed(x @ a["q_proj"]["kernel"].astype(dt),
                       a["q_norm"]["scale"], eps, H)
        k = _qk_normed(x @ a["k_proj"]["kernel"].astype(dt),
                       a["k_norm"]["scale"], eps, H)
        v = (x @ a["v_proj"]["kernel"].astype(dt)).reshape(B, S, H, D)
        if cfg.rope_theta is not None:
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    with jax.named_scope("gqa_core"):
        with jax.named_scope("full_core"):
            o = attn_fn(q, k, v)
    with jax.named_scope("gqa_proj"):
        y = o.reshape(B, S, H * D) @ a["o_proj"]["kernel"].astype(dt)
        return x + rms_norm(y, layer["post_attn_norm"]["scale"], eps)


@jax.named_scope("mlp")
def _mlp_sublayer(cfg: OlmoHybridConfig, layer: Dict, x):
    return x + rms_norm(swiglu(x, layer["mlp"], cfg.dtype),
                        layer["post_mlp_norm"]["scale"], cfg.rms_eps)


def _layer(cfg: OlmoHybridConfig, kind: str, layer: Dict, x, *, attn_fn):
    if kind == LINEAR:
        x = _gdn_sublayer(cfg, layer, x)
    else:
        x = _attn_sublayer(cfg, layer, x, attn_fn=attn_fn)
    return _mlp_sublayer(cfg, layer, x)


def forward_hidden(cfg: OlmoHybridConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None):
    """tokens [B, S] -> final-norm hidden states [B, S, d]."""
    if attn_fn is None:
        attn_fn = _local_causal_attention
    x = embed(cfg, params, tokens)
    for i, kind in enumerate(cfg.layer_types):
        run = functools.partial(_layer, cfg, kind, attn_fn=attn_fn)
        if cfg.remat:
            run = jax.checkpoint(run)
        x = run(params[f"layers_{i}"], x)
    return rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps)


def loss_terms(cfg: OlmoHybridConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``hidden`` (the final-norm states) and ``loss``, the mean
    next-token cross entropy over the rows of ``lm_head`` held here."""
    h = forward_hidden(cfg, params, tokens, attn_fn)
    loss = ce_from_hidden(h, params["lm_head"]["kernel"], targets,
                          cfg.xent_chunks)
    return {"hidden": h, "loss": loss}


def loss_fn(cfg: OlmoHybridConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
