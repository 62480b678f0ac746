"""Kimi Linear decoder (Hugging Face ``model_type`` ``kimi_linear``; the
benchmark's configuration is moonshotai/Kimi-Linear-48B-A3B-Instruct,
arXiv:2510.26692) as ONE CHIP'S SHARE of an expert-parallel layer. Every
layer is a sequence mixer and an MLP, each behind its own RMSNorm:

    h = x + mixer(RMSNorm(x))          y = h + mlp(RMSNorm(h))

and two lists of the config say which mixer, by the layer's PUBLISHED
number (from 1): ``kda_layers`` the channel-wise gated delta rule,
``full_attn_layers`` latent attention; ``i < n_dense_layers`` (from 0)
the MLP (dense, else experts). A final RMSNorm, an untied head. ``d`` =
``d_model``; no bias anywhere.

KDA mixer (``H`` heads of ``D = kda_head_dim`` key and value channels;
``n = RMSNorm(x)``). ``[q̃ ; k̃ ; v] = silu(conv(n·W_qkv))``: ONE
projection ``d -> 3HD`` and ONE causal depthwise convolution of
``conv_kernel`` taps over its output, no bias
(``ops/ssm_pointwise.py::conv_silu``, a zero bias passed); per head ``q
= q̃ / ‖q̃‖₂ · D^{-1/2}``, ``k = k̃ / ‖k̃‖₂``; the log-decay of every key
channel ``g = −exp(A_log_h) · softplus(n·W_f↓·W_f↑ + dt_bias)`` (``d ->
kda_rank -> HD``; ``A_log`` a scalar a head, ``dt_bias`` a channel; f32)
— the three in ONE kernel (``ops/ssm_pointwise.py::kda_qkg``: ``q̃`` and
``k̃`` read as thirds of the convolution's one array, the decay's
projection in bf16, ``q`` and ``k`` written in bf16 and ``g`` in f32,
``[B, S, H·D]`` as the scan's kernels read them) — and the step ``β =
σ(n·W_β)`` (a scalar a head; f32; XLA's: ``[B, S, H]``);

    S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t-1} + β_t k_t v_tᵀ,
    o_t = S_tᵀ q_t                       (``ops/kda.py::kda_scan``)

then ``y = W_o·[RMSNorm_head(o) ⊙ σ(n·W_g↓·W_g↑)]``: the norm over a
head's ``D`` channels with one learned ``D``-wide weight, the gate AFTER
it and a sigmoid (Nemotron-H's ``gated_norm`` gates first, with silu),
ONE kernel too (``ops/ssm_pointwise.py::kda_ogate``). What a head's
normalisation and its gated norm ARE stays here: :func:`_l2_normed` and
:func:`_gated_head_norm` are jnp code over the last axis, and the two
kernels call them on the ``[rows, D]`` f32 blocks they load (``q``'s
before ``k``'s, head by head) and take their ``jax.vjp`` in the backward
kernels, so whatever stands in those names' place
(``benchmark/tests/kimi_faults.py``) is what the step computes.

MLA mixer: ``models/joyai.py::mla_sublayer`` itself, called with this
config — ``q_lora_rank`` 0 (no q latent: one ``q_proj`` to ``H × (nope +
rope)``) and ``rope_theta`` None (``mla_use_nope``: nothing is rotated;
the ``rope``-wide key channels a token shares between its heads stay in
the score); ``c_kv`` (``kv_lora_rank``, RMSNorm) up to ``nope`` key +
``v_head_dim`` value channels a head; causal softmax at ``(nope +
rope)^{-1/2}`` through the flash kernels.

MLPs: ``models/common.py``'s ``dense_sublayer`` and ``routed_sublayer``,
bound as ``models/joyai.py`` binds them — the dense SwiGLU ``d_ff`` wide; the sparse sublayer with
sigmoid scores over all ``n_routed_experts``, the balance bias
(``models/common.py``), the ``top_k`` largest of ``s + b``, renormalised,
``× routed_scale``, SwiGLU experts ``d_expert`` wide of which this share
holds ``first_expert .. first_expert + n_experts_held``
(``ops/moe.py::moe_mlp``), and one shared expert.

Conventions of ``models/joyai.py``: float32 parameters, bf16 compute,
float32 norms / router / decays / step sizes (the delta-rule mixer's
projections stay bf16 up to its kernels, which upcast on load, compute
in f32 and round ``q``, ``k`` and ``y`` once; of the stream's size ``[B,
S, H·D]`` only ``g`` and its cotangent are f32 in HBM), an explicit parameter tree
with stable paths ``layers_<i>/{ln_1, ln_2}``, ``layers_<i>/{kda|attn}``,
``layers_<i>/{mlp|moe}``, per-layer ``checkpoint_layer`` behind ``remat``,
and the step programs of ``transformer.make_train_step`` /
``make_grad_step`` (``loss=kimi_linear.loss_fn``).

``checkpoint_layer`` (``models/common.py``) is ``jax.checkpoint`` that
keeps what a layer's router decided — the experts, their weights, the
chosen scores, the loads —, so the backward pass does not run the router
again (``common.routed_sublayer`` says why the weights are among them).

Device-trace scopes: ``embed``; both mixers under ``attn``, told apart
inside — ``kda_in`` (norm, the five projections), ``kda_conv`` (the
convolution's kernels ``ssm_conv_fwd`` / ``ssm_conv_bwd``, the l2 norms'
and the decay's ``kda_qkg_fwd`` / ``kda_qkg_bwd``, the slice of ``v``
and the step's sigmoid), ``kda_core`` (the scan's kernels ``kda_fwd`` /
``kda_bwd``), ``kda_gate`` (the head norm's and the gate's
``kda_ogate_fwd`` / ``kda_ogate_bwd``), ``kda_out``; JoyAI's ``mla_q``,
``mla_kv``, ``mla_core``, ``mla_out``; ``mlp`` with the dense SwiGLU
straight under it and JoyAI's ``moe_router``, ``moe_shared``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``; ``lm_head_xent``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models import joyai
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
from torchft_tpu.models.transformer import (
    _local_causal_attention,
    ce_from_hidden,
)
from torchft_tpu.ops.kda import kda_scan
from torchft_tpu.ops.ssm_pointwise import conv_silu, kda_ogate, kda_qkg

__all__ = ["KimiLinearConfig", "KIMI_LINEAR_CONFIGS", "BALANCE_BIAS",
           "is_balance_bias", "init_params", "forward_hidden", "loss_terms",
           "loss_fn"]

L2_EPS = 1e-6     # beside a head's squared norm, under the root


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Defaults: moonshotai/Kimi-Linear-48B-A3B-Instruct as published,
    every expert held. The attention fields carry ``JoyaiConfig``'s
    names: ``models/joyai.py``'s sublayers read them."""
    vocab_size: int = 163840
    d_model: int = 2304
    # the mixers, by the layers' published numbers (from 1)
    kda_layers: Tuple[int, ...] = (
        1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23,
        25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    n_dense_layers: int = 1       # first_k_dense_replace
    n_heads: int = 32             # of both mixers
    kda_head_dim: int = 128       # key and value channels of a KDA head
    kda_rank: int = 128           # the decay's and the gate's low rank
    conv_kernel: int = 4
    q_lora_rank: int = 0          # no q latent
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64         # not rotated (mla_use_nope)
    v_head_dim: int = 128
    rope_theta: Optional[float] = None
    d_ff: int = 9216              # the dense layers' SwiGLU width
    d_expert: int = 1024          # one routed or shared expert's width
    n_routed_experts: int = 256   # the router's width
    first_expert: int = 0         # the share held here:
    n_experts_held: int = 256     # experts first .. first + held
    top_k: int = 8
    routed_scale: float = 2.446
    rms_eps: float = 1e-5
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        numbered = sorted(self.kda_layers + self.full_attn_layers)
        assert numbered == list(range(1, len(numbered) + 1)), numbered
        assert 0 <= self.n_dense_layers <= len(numbered)
        assert not self.q_lora_rank        # init_params makes one q_proj
        assert 1 <= self.top_k <= self.n_routed_experts
        assert 0 <= self.first_expert
        assert 1 <= self.n_experts_held
        assert self.first_expert + self.n_experts_held <= self.n_routed_experts

    @property
    def n_layers(self) -> int:
        return len(self.kda_layers) + len(self.full_attn_layers)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    def is_kda(self, i: int) -> bool:
        """Whether layer ``i`` (from 0) mixes by the delta rule."""
        return i + 1 in self.kda_layers


KIMI_LINEAR_CONFIGS: Dict[str, KimiLinearConfig] = {
    # the tests' size: both mixers and both MLPs (K K M K, the first
    # dense), a share of 4 of 8 experts
    "kimi_linear_tiny": KimiLinearConfig(
        vocab_size=512, d_model=64, kda_layers=(1, 2, 4),
        full_attn_layers=(3,), n_dense_layers=1, n_heads=4, kda_head_dim=16,
        kda_rank=16, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, d_ff=128, d_expert=32, n_routed_experts=8,
        first_expert=0, n_experts_held=4, top_k=2, init_std=0.125,
    ),
}


def _kda_params(cfg: KimiLinearConfig, key, normal) -> Dict:
    """The taps as a depthwise ``Conv1d``'s default, U(-1/sqrt(K),
    1/sqrt(K)); ``A_log = log U(1, 16)`` a head; ``dt_bias`` the inverse
    softplus of a step drawn log-uniform in [1e-3, 1e-1], a channel; the
    head norm's weight one; the matrices normal."""
    pd, d = cfg.param_dtype, cfg.d_model
    hd, r = cfg.n_heads * cfg.kda_head_dim, cfg.kda_rank
    k = jax.random.split(key, 10)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    dt = jnp.exp(jax.random.uniform(
        k[8], (hd,), pd, math.log(1e-3), math.log(1e-1)))
    return {
        "qkv_proj": {"kernel": normal(k[0], d, 3 * hd)},
        "conv": {"kernel": jax.random.uniform(
            k[1], (cfg.conv_kernel, 3 * hd), pd, -bound, bound)},
        "f_a_proj": {"kernel": normal(k[2], d, r)},
        "f_b_proj": {"kernel": normal(k[3], r, hd)},
        "b_proj": {"kernel": normal(k[4], d, cfg.n_heads)},
        "g_a_proj": {"kernel": normal(k[5], d, r)},
        "g_b_proj": {"kernel": normal(k[6], r, hd)},
        "A_log": jnp.log(jax.random.uniform(
            k[7], (cfg.n_heads,), pd, 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": {"scale": jnp.ones((cfg.kda_head_dim,), pd)},
        "o_proj": {"kernel": normal(k[9], hd, d)},
    }


def init_params(cfg: KimiLinearConfig, key) -> Dict:
    """Every matrix normal with ``init_std``, every norm weight one, the
    balance bias zero; the delta rule's own leaves as
    :func:`_kda_params` says. The MLA leaves carry JoyAI's names, with
    ``q_proj`` in the place of its three q-latent leaves."""
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
    for i in range(cfg.n_layers):
        # JoyAI's layer for ``ln_1``, ``ln_2`` and the MLP's leaves; its
        # attention's leaves kept only where this layer has them
        layer = joyai.layer_params(cfg, keys[2 + i], normal, ones,
                                    dense=i < cfg.n_dense_layers)
        a = layer.pop("attn")
        mk = jax.random.fold_in(keys[2 + i], 2)
        if cfg.is_kda(i):
            layer["kda"] = _kda_params(cfg, mk, normal)
        else:
            layer["attn"] = {
                "q_proj": {"kernel": normal(
                    mk, d, cfg.n_heads * cfg.qk_head_dim)},
                **{n: a[n] for n in ("kv_a_proj", "kv_a_norm", "kv_b_proj",
                                     "o_proj")}}
        params[f"layers_{i}"] = layer
    return params


def _kda_scan(q, k, v, g, beta):
    """The delta rule: a seam over ``ops/kda.py``'s kernels, kept under
    this name because ``benchmark/tests/kimi_faults.py`` puts its
    stand-ins in its place."""
    return kda_scan(q, k, v, g, beta)


def _gated_head_norm(o, scale, gate, eps: float):
    """``RMSNorm_head(o) ⊙ σ(gate)``: the norm FIRST, over a head's
    channels (the last axis; one ``D``-wide weight), then the sigmoid
    gate; in f32, rounded once. The body of ``kda_ogate``'s kernels (a
    seam: see :func:`_kda_scan`)."""
    f32 = jnp.float32
    return (rms_norm(o.astype(f32), scale, eps)
            * jax.nn.sigmoid(gate.astype(f32))).astype(o.dtype)


def _l2_normed(x):
    """``x / ‖x‖₂`` over the last axis, a head's channels, in f32. The
    body of ``kda_qkg``'s kernels, called for ``q̃``, then for ``k̃`` (a
    seam: see :func:`_kda_scan`)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


@jax.named_scope("attn")
def _kda_sublayer(cfg: KimiLinearConfig, layer: Dict, x):
    m, dt, f32 = layer["kda"], cfg.dtype, jnp.float32
    B, S, _ = x.shape
    H, D = cfg.n_heads, cfg.kda_head_dim
    with jax.named_scope("kda_in"):
        n = rms_norm(x, layer["ln_1"]["scale"], cfg.rms_eps)
        qkv = n @ m["qkv_proj"]["kernel"].astype(dt)
        f = (n @ m["f_a_proj"]["kernel"].astype(dt)) @ m["f_b_proj"][
            "kernel"].astype(dt)
        gate = (n @ m["g_a_proj"]["kernel"].astype(dt)) @ m["g_b_proj"][
            "kernel"].astype(dt)
        b = n @ m["b_proj"]["kernel"].astype(dt)
    with jax.named_scope("kda_conv"):
        taps = m["conv"]["kernel"]
        qkv = conv_silu(qkv, taps, jnp.zeros(taps.shape[1:], taps.dtype))
        q, k, v, g = (a.reshape(B, S, H, D) for a in kda_qkg(
            qkv, f, m["dt_bias"], m["A_log"], _l2_normed))
        beta = jax.nn.sigmoid(b.astype(f32))
    with jax.named_scope("kda_core"):
        o = _kda_scan(q, k, v, g, beta)                  # [B, S, H, D]
    with jax.named_scope("kda_gate"):
        y = kda_ogate(o.reshape(B, S, H * D), gate, m["o_norm"]["scale"],
                      cfg.rms_eps, _gated_head_norm)
    with jax.named_scope("kda_out"):
        return x + y @ m["o_proj"]["kernel"].astype(dt)


def _moe_sublayer(cfg: KimiLinearConfig, layer: Dict, x) -> Tuple[Any, Dict]:
    """``common.routed_sublayer`` as ``models/joyai.py`` binds it: the
    norm ``ln_2``, SwiGLU experts, a SwiGLU shared expert."""
    m = layer["moe"]
    return routed_sublayer(
        cfg, x, layer["ln_2"]["scale"], m,
        shared=lambda h: swiglu(h, m["shared"], cfg.dtype))


def _layer(cfg: KimiLinearConfig, kda: bool, dense: bool, layer: Dict, x, *,
           attn_fn):
    """One layer: ``(x, record or None)``."""
    if kda:
        x = _kda_sublayer(cfg, layer, x)
    else:
        x = joyai.mla_sublayer(cfg, layer, x, attn_fn=attn_fn)
    if dense:
        return dense_sublayer(
            cfg, x, layer["ln_2"]["scale"], layer["mlp"]), None
    return _moe_sublayer(cfg, layer, x)


def forward_hidden(cfg: KimiLinearConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d], record). The
    record holds ``experts`` [L_e, N, top_k] and ``loads`` [L_e, routed]
    of every expert layer in order, and ``carrier`` (zero; see
    ``common.loads_as_gradient``)."""
    if attn_fn is None:
        attn_fn = _local_causal_attention
    x = embed(cfg, params, tokens)
    records = []
    for i in range(cfg.n_layers):
        run = functools.partial(_layer, cfg, cfg.is_kda(i),
                                i < cfg.n_dense_layers, attn_fn=attn_fn)
        if cfg.remat:
            run = checkpoint_layer(run)
        x, rec = run(params[f"layers_{i}"], x)
        if rec is not None:
            records.append(rec)
    out = routing_record(records)
    return rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps), out


def loss_terms(cfg: KimiLinearConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``common.share_loss_terms`` of this model's forward pass, the cross
    entropy through ``lm_head``."""
    h, rec = forward_hidden(cfg, params, tokens, attn_fn)
    return share_loss_terms(cfg, h, rec, ce_from_hidden(
        h, params["lm_head"]["kernel"], targets, cfg.xent_chunks))


def loss_fn(cfg: KimiLinearConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
