"""Granite 4.0-H decoder (Hugging Face ``model_type`` ``granitemoehybrid``
with ``num_local_experts`` 0; the benchmark's configuration is
ibm-granite/granite-4.0-h-micro) as ONE CHIP'S SHARE of a
vocabulary-parallel stage: the rows of the ONE tied table held here,
every mixer and MLP whole. A dense model. Every layer is a sequence mixer
THEN a SwiGLU MLP, each behind its own RMSNorm and each branch scaled by
``residual_multiplier`` ``r`` before it joins the stream:

    x <- x + r · mixer(RMSNorm(x; w_in))     x <- x + r · mlp(RMSNorm(x; w_post))

and the config's ``layer_types`` says which mixer, layer by layer:
``mamba`` a Mamba-2 mixer (Bamba's), ``attention`` grouped-query softmax
attention with NO position embedding (``position_embedding_type``
``nope``: the recurrent layers carry position). Around the stack the
three other muP multipliers: ``x_0 = embedding_multiplier · E[tokens]``,
the softmax scale is ``attention_multiplier`` (NOT ``head_dim^-1/2``),
and ``logits = RMSNorm(x_L; w_f) Eᵀ / logits_scaling`` over the tied
table ``E``. ``d`` = ``d_model``; no bias but the convolution's.

``mamba`` (``H`` heads of ``P`` channels, inner width ``I = H·P``; ``G``
groups, state ``N``, ``K`` taps). With ``n`` the normed stream: ``[z I ;
xBC I + 2GN ; δ H] = n·W_in``; ``xBC_t <- silu(b + Σ_{j<K} w_j ⊙
xBC_{t-(K-1)+j})``, depth-wise, zeros before the sequence's start; ``xBC
-> x [H × P] ; B [G × N] ; C [G × N]`` — at the published ``G = 1`` ONE
``B`` and ONE ``C`` serve all ``H`` heads; ``Δ = softplus(δ + dt_bias)``
(no clamp), ``A = −exp(A_log)``, a scalar a head;

    S_t = exp(Δ_t A)·S_{t-1} + Δ_t · x_t ⊗ B_t,    y_t = S_t·C_t + D·x_t

from ``S_0 = 0`` at every sequence's start (``ops/ssd.py``, whose grid
takes a group's heads in blocks of eight); ``y <- RMSNorm_grouped(y ⊙
silu(z); w_g)`` — the gate first, each of the ``G`` runs of ``I/G``
channels normalised alone (ONE run of 4 096 at the published size) —;
``m = y·W_out``.

``attention``: ``q = n·W_q`` -> ``n_heads`` × ``head_dim``, ``k, v =
n·W_k, n·W_v`` -> ``n_kv_heads`` × ``head_dim`` (query head ``i`` reads
key/value head ``i // (n_heads / n_kv_heads)`` where it lies:
``ops/flash.py``), not rotated, causal ``softmax(attention_multiplier ·
q·k) v``, ``·W_o``.

Conventions of the other families: float32 parameters, bf16 compute,
float32 norms / softmax statistics / softplus / decays / scan state / the
convolution's taps; an explicit parameter tree with stable paths
``layers_<i>/{norm, post_norm}``, ``layers_<i>/{mamba|attn}``,
``layers_<i>/mlp``; one ``jax.checkpoint`` a layer behind ``remat``; the
step programs of ``transformer.make_train_step`` / ``make_grad_step``
(``loss=granite_hybrid.loss_fn``). ``_conv_silu`` and ``_gated_norm`` are
``models/nemotron_h.py``'s seams over ``ops/ssm_pointwise.py``, looked
up here by name (``benchmark/tests/granite_faults.py`` puts its stand-ins
in their place, as in ``ssd_scan``'s).

Device-trace scopes (the readers' names, ``models/nemotron_h.py``'s):
``embed`` (the gather and its multiplier); both mixers under ``attn``,
told apart inside — ``ssm_in`` (norm, ``W_in``, the split), ``ssm_conv``,
``ssm_scan`` (softplus, decays, the kernels ``ssd_fwd`` / ``ssd_bwd``),
``ssm_gate``, ``ssm_out`` (``W_out``, the branch's multiplier, the
residual); ``gqa_proj`` (norm, q / k / v, ``W_o``, multiplier, residual),
``gqa_core`` (the flash call); ``mlp`` (norm, SwiGLU, multiplier,
residual); ``lm_head_xent`` (the logits' scaling, the tied head, the
cross entropy).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.common import rms_norm, swiglu
from torchft_tpu.models.nemotron_h import _conv_silu, _gated_norm
from torchft_tpu.models.transformer import ce_from_hidden
from torchft_tpu.ops.attention import causal_attention
from torchft_tpu.ops.ssd import ssd_scan

__all__ = ["GraniteHybridConfig", "GRANITE_HYBRID_CONFIGS", "MAMBA",
           "ATTENTION", "init_params", "forward_hidden", "loss_terms",
           "loss_fn"]

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Defaults: ibm-granite/granite-4.0-h-micro as published, the whole
    vocabulary held."""
    vocab_size: int = 100352
    d_model: int = 2048
    layer_types: Tuple[str, ...] = (
        (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    conv_kernel: int = 4
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 8192
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    init_std: float = 0.02
    time_step_min: float = 0.001  # dt_bias is initialised from these
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert set(self.layer_types) <= {MAMBA, ATTENTION}, self.layer_types
        assert self.ssm_heads % self.ssm_groups == 0
        assert self.n_heads % self.n_kv_heads == 0

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state


GRANITE_HYBRID_CONFIGS: Dict[str, GraniteHybridConfig] = {
    # the tests' size: a period in miniature (M A M M), sixteen heads on ONE
    # B and C, four query heads on two key/value heads, multipliers that
    # are no power of two
    "granite_hybrid_tiny": GraniteHybridConfig(
        vocab_size=256, d_model=64,
        layer_types=(MAMBA, ATTENTION, MAMBA, MAMBA),
        ssm_heads=16, ssm_head_dim=16, ssm_groups=1, ssm_state=16,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
        embedding_multiplier=6.0, residual_multiplier=0.3,
        attention_multiplier=0.1, logits_scaling=3.0, init_std=0.125,
    ),
}


def _mamba_params(cfg: GraniteHybridConfig, key, normal) -> Dict:
    """``A_log = log(1 .. H)`` and ``D = 1`` (the Bamba mixer's
    initialisation); ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly between ``time_step_min`` and ``time_step_max``; the
    convolution as a depth-wise ``Conv1d``'s default."""
    pd, d = cfg.param_dtype, cfg.d_model
    H, I, K = cfg.ssm_heads, cfg.ssm_inner, cfg.conv_kernel
    k = jax.random.split(key, 5)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        k[2], (H,), pd, math.log(cfg.time_step_min),
        math.log(cfg.time_step_max))), cfg.time_step_floor)
    bound = 1.0 / math.sqrt(K)
    return {
        "in_proj": {"kernel": normal(k[0], d, I + cfg.conv_dim + H)},
        "conv": {
            "kernel": jax.random.uniform(
                k[1], (K, cfg.conv_dim), pd, -bound, bound),
            "bias": jax.random.uniform(k[3], (cfg.conv_dim,), pd,
                                       -bound, bound),
        },
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus(dt_bias) == dt
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=pd)),
        "D": jnp.ones((H,), pd),
        "norm": {"scale": jnp.ones((I,), pd)},
        "out_proj": {"kernel": normal(k[4], I, d)},
    }


def init_params(cfg: GraniteHybridConfig, key) -> Dict:
    """Every matrix and the table normal with ``init_std``, every norm
    weight one; the mixer's own leaves as :func:`_mamba_params` says. ONE
    table: the head is its transpose."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 1)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def ones(n):
        return {"scale": jnp.ones((n,), pd)}

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": ones(d),
    }
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[1 + i], 8)
        layer: Dict[str, Any] = {
            "norm": ones(d), "post_norm": ones(d),
            "mlp": {"gate_proj": {"kernel": normal(k[0], d, cfg.d_ff)},
                    "up_proj": {"kernel": normal(k[1], d, cfg.d_ff)},
                    "down_proj": {"kernel": normal(k[2], cfg.d_ff, d)}},
        }
        if kind == MAMBA:
            layer["mamba"] = _mamba_params(cfg, k[3], normal)
        else:
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            layer["attn"] = {
                "q_proj": {"kernel": normal(k[4], d, q)},
                "k_proj": {"kernel": normal(k[5], d, kv)},
                "v_proj": {"kernel": normal(k[6], d, kv)},
                "o_proj": {"kernel": normal(k[7], q, d)},
            }
        params[f"layers_{i}"] = layer
    return params


@jax.named_scope("embed")
def _embed(cfg: GraniteHybridConfig, params: Dict, tokens):
    """``embedding_multiplier · E[tokens]``, multiplied in f32 and rounded
    once."""
    rows = params["wte"]["embedding"][tokens].astype(jnp.float32)
    return (rows * cfg.embedding_multiplier).astype(cfg.dtype)


def _branch(cfg: GraniteHybridConfig, x, m):
    """``x + residual_multiplier · m``, the product in f32."""
    return x + (m.astype(jnp.float32) * cfg.residual_multiplier).astype(
        x.dtype)


@jax.named_scope("attn")
def _mamba_sublayer(cfg: GraniteHybridConfig, layer: Dict, x):
    m, dt, f32 = layer["mamba"], cfg.dtype, jnp.float32
    B, S, _ = x.shape
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    I = cfg.ssm_inner
    with jax.named_scope("ssm_in"):
        n = rms_norm(x, layer["norm"]["scale"], cfg.rms_eps)
        proj = n @ m["in_proj"]["kernel"].astype(dt)
        z, xbc, dt_raw = (proj[..., :I], proj[..., I:I + cfg.conv_dim],
                          proj[..., I + cfg.conv_dim:])
    with jax.named_scope("ssm_conv"):
        xbc = _conv_silu(m, xbc, dt)
    with jax.named_scope("ssm_scan"):
        y = ssd_scan(
            xbc[..., :I].reshape(B, S, H, P),
            jax.nn.softplus(dt_raw.astype(f32) + m["dt_bias"].astype(f32)),
            -jnp.exp(m["A_log"].astype(f32)),
            xbc[..., I:I + G * N].reshape(B, S, G, N),
            xbc[..., I + G * N:].reshape(B, S, G, N),
            m["D"].astype(f32),
        )
    with jax.named_scope("ssm_gate"):
        y = _gated_norm(y, z, m["norm"]["scale"], G, cfg.rms_eps, dt)
    with jax.named_scope("ssm_out"):
        return _branch(cfg, x, y @ m["out_proj"]["kernel"].astype(dt))


@jax.named_scope("attn")
def _attn_sublayer(cfg: GraniteHybridConfig, layer: Dict, x, *, attn_fn):
    a, dt = layer["attn"], cfg.dtype
    B, S, _ = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("gqa_proj"):
        n = rms_norm(x, layer["norm"]["scale"], cfg.rms_eps)
        q = (n @ a["q_proj"]["kernel"].astype(dt)).reshape(B, S, H, D)
        k = (n @ a["k_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
        v = (n @ a["v_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
    with jax.named_scope("gqa_core"):
        o = attn_fn(q, k, v)
    with jax.named_scope("gqa_proj"):
        return _branch(cfg, x, o.reshape(B, S, H * D)
                       @ a["o_proj"]["kernel"].astype(dt))


@jax.named_scope("mlp")
def _mlp_sublayer(cfg: GraniteHybridConfig, layer: Dict, x):
    n = rms_norm(x, layer["post_norm"]["scale"], cfg.rms_eps)
    return _branch(cfg, x, swiglu(n, layer["mlp"], cfg.dtype))


def _layer(cfg: GraniteHybridConfig, kind: str, layer: Dict, x, *, attn_fn):
    if kind == MAMBA:
        x = _mamba_sublayer(cfg, layer, x)
    else:
        x = _attn_sublayer(cfg, layer, x, attn_fn=attn_fn)
    return _mlp_sublayer(cfg, layer, x)


def forward_hidden(cfg: GraniteHybridConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None):
    """tokens [B, S] -> final-norm hidden states [B, S, d]. ``attn_fn(q,
    k, v)`` left ``None`` is causal attention at ``attention_multiplier``
    (the flash kernels on a TPU)."""
    if attn_fn is None:
        attn_fn = functools.partial(causal_attention,
                                    scale=cfg.attention_multiplier)
    x = _embed(cfg, params, tokens)
    for i, kind in enumerate(cfg.layer_types):
        run = functools.partial(_layer, cfg, kind, attn_fn=attn_fn)
        if cfg.remat:
            run = jax.checkpoint(run)
        x = run(params[f"layers_{i}"], x)
    return rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps)


def loss_terms(cfg: GraniteHybridConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``hidden`` (the final-norm states) and ``loss``, the mean
    next-token cross entropy of ``hidden · Eᵀ / logits_scaling`` over the
    rows of the tied table held here."""
    h = forward_hidden(cfg, params, tokens, attn_fn)
    with jax.named_scope("lm_head_xent"):
        # the head is the table, [V, d] read as [d, V]; the scaling goes
        # on the d-wide side, in f32
        scaled = h.astype(jnp.float32) / cfg.logits_scaling
        head = params["wte"]["embedding"].T
    return {"hidden": h, "loss": ce_from_hidden(
        scaled, head, targets, cfg.xent_chunks)}


def loss_fn(cfg: GraniteHybridConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
