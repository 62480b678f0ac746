"""Nemotron-H decoder (Hugging Face ``model_type`` ``nemotron_h``,
arXiv:2504.03624; the benchmark's configuration is
NVIDIA-Nemotron-3-Nano-30B-A3B) as ONE CHIP'S SHARE of an expert-parallel
layer: a layer is ONE mixer behind one RMSNorm, ``x + mixer(RMSNorm(x))``,
and which mixer is data of the config — ``pattern``, a string over ``M``
(Mamba-2), ``E`` (experts) and ``*`` (attention). A final RMSNorm, then
the head. ``d`` = ``d_model``; no biases but the convolution's; untied
table and head; no position embedding of any kind.

``M`` — Mamba-2 mixer (``H`` heads of ``P`` channels, inner width ``I =
H·P``; ``G`` groups, state ``N``, convolution of ``K`` taps). With ``h =
RMSNorm(x)``: ``[z I ; xBC I + 2GN ; dt H] = h·W_in``.
``xBC_t <- silu(b_c + Σ_{j<K} w_j ⊙ xBC_{t-(K-1)+j})``, depthwise, zeros
before the sequence's start. ``xBC -> x [H × P] ; B [G × N] ; C [G × N]``;
head ``h`` reads group ``h // (H/G)``. ``Δ_t = softplus(dt_t + dt_bias)``
[H] (no clamp), ``A = -exp(A_log)`` [H]. Per head, ``S ∈ R^{P×N}``,
``S_0 = 0`` at every sequence's start (nothing is carried between
sequences or steps):

    S_t = exp(Δ_t A)·S_{t-1} + Δ_t · x_t ⊗ B_t,    y_t = S_t·C_t + D·x_t

(``ops/ssd.py``, the chunked form: the same result whatever the chunk).
Then ``y <- RMSNorm_grouped(y ⊙ silu(z))·w`` — the gate first, then the
norm, each of the ``G`` groups of ``I/G`` channels normalised alone —
and ``out = y·W_out``.

``*`` — attention mixer: ``q = h·W_q`` -> ``n_heads`` × ``head_dim``,
``k, v = h·W_k, h·W_v`` -> ``n_kv_heads`` × ``head_dim`` (each serves
``n_heads / n_kv_heads`` consecutive query heads), causal softmax of
``q·k / sqrt(head_dim)``, ``·v``, ``·W_o``. No rotary embedding. The
key/value heads reach the flash call as they are, ``n_kv_heads`` of
them: its index maps read head ``i // group`` for query head ``i`` and
its dkv kernel sums a group's gradients in float32 (``ops/flash.py``).

``E`` — expert mixer: ``s = sigmoid(h·W_r)`` in float32 over all routed
experts; ``sel`` = the ``top_k`` largest of ``s + b``; ``g_e =
routed_scale · s_e / (Σ_sel s + 1e-20)`` — ``b`` selects and never
weights; ``y = Σ_{e in sel and held} g_e · W_down,e · relu(W_up,e h)² +
W_down,s · relu(W_up,s h)²`` (two matrices an expert, no gate; the
shared expert ``d_shared`` wide). The layer is told which routed experts
it holds (``first_expert``, ``n_experts_held``), routes over all
``n_routed_experts`` and computes its own experts' part. The balance
bias ``b`` and the way its loads reach ``optim.with_balance_bias`` in
the gradient tree are ``models/common.py``'s (JoyAI's too).

Conventions of ``models/joyai.py``: float32 parameters, bf16 compute,
float32 norms / router / softplus / decays / convolution taps, an
explicit parameter tree with stable paths ``layers_<i>/norm`` and
``layers_<i>/{mamba|attn|moe}/...``, per-layer ``checkpoint_layer`` behind
``remat``, and the step programs of ``transformer.make_train_step`` /
``make_grad_step`` (``loss=nemotron_h.loss_fn``).

``checkpoint_layer`` (``models/common.py``) is ``jax.checkpoint`` that
keeps what a layer's router decided — the experts, their weights, the
chosen scores, the loads —, so the backward pass does not run the router
again (``common.routed_sublayer`` says why the weights are among them).

``_conv_silu`` and ``_gated_norm`` are seams over ``ops/ssm_pointwise.py``
(one fused kernel forward and one backward each, reading ``xBC`` / ``y``,
``z`` once in the compute dtype): they keep their names and signatures
because ``benchmark/tests/nemotron_faults.py`` puts its stand-ins in
their place by name; the f32 formulas they held are the oracle of
``tests/test_ssm_pointwise.py``.

Device-trace scopes: ``embed``; both sequence mixers under ``attn``,
told apart inside — ``ssm_in`` (norm, ``W_in``, the split), ``ssm_conv``
(convolution + silu: the kernels ``ssm_conv_fwd`` / ``ssm_conv_bwd``),
``ssm_scan`` (softplus, decays, the scan), ``ssm_gate`` (gate, grouped
norm: ``ssm_gate_fwd`` / ``ssm_gate_bwd``), ``ssm_out``; ``gqa_proj``,
``gqa_core`` (the flash call); ``mlp`` with inner ``moe_router``,
``moe_shared``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``;
``lm_head_xent``.
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
    embed,
    is_balance_bias,
    rms_norm,
    routed_sublayer,
    routing_record,
    share_loss_terms,
)
from torchft_tpu.models.transformer import (
    _local_causal_attention,
    ce_from_hidden,
)
from torchft_tpu.ops.ssd import ssd_scan
from torchft_tpu.ops.ssm_pointwise import conv_silu, gated_norm

__all__ = ["NemotronHConfig", "NEMOTRON_H_CONFIGS", "BALANCE_BIAS",
           "is_balance_bias", "init_params", "forward_hidden", "loss_terms",
           "loss_fn"]

MIXERS = {"M": "mamba", "*": "attn", "E": "moe"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Defaults: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as published,
    every expert held."""
    vocab_size: int = 131072
    d_model: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    init_depth: int = 52          # the PUBLISHED depth: residual outputs
                                  # are initialised / sqrt(init_depth)
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    d_expert: int = 1856          # one routed expert's width
    d_shared: int = 3712          # the shared expert's
    n_routed_experts: int = 128   # the router's width
    first_expert: int = 0         # the share held here:
    n_experts_held: int = 128     # experts first .. first + held
    top_k: int = 6
    routed_scale: float = 2.5
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
        assert self.pattern and set(self.pattern) <= set(MIXERS)
        assert self.ssm_heads % self.ssm_groups == 0
        assert self.n_heads % self.n_kv_heads == 0
        assert 1 <= self.top_k <= self.n_routed_experts
        assert 0 <= self.first_expert
        assert 1 <= self.n_experts_held
        assert self.first_expert + self.n_experts_held <= self.n_routed_experts

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state


NEMOTRON_H_CONFIGS: Dict[str, NemotronHConfig] = {
    # the tests' size: every kind of layer, a share of 4 of 8 experts,
    # two heads a group, eight query heads a key/value head's two
    "nemotron_h_tiny": NemotronHConfig(
        vocab_size=512, d_model=64, pattern="ME*E", init_depth=4,
        ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=16,
        n_heads=4, n_kv_heads=2, head_dim=16, d_expert=32, d_shared=48,
        n_routed_experts=8, first_expert=0, n_experts_held=4, top_k=2,
        init_std=0.125,
    ),
}


def _mixer_params(cfg: NemotronHConfig, kind: str, key, normal, out) -> Dict:
    """One mixer's leaves. ``normal(key, *shape)`` is a matrix into the
    layer, ``out(key, *shape)`` one back onto the residual stream."""
    pd, d = cfg.param_dtype, cfg.d_model
    k = jax.random.split(key, 8)
    if kind == "mamba":
        H, I, K = cfg.ssm_heads, cfg.ssm_inner, cfg.conv_kernel
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k[2], (H,), pd, math.log(cfg.time_step_min),
            math.log(cfg.time_step_max))), cfg.time_step_floor)
        bound = 1.0 / math.sqrt(K)     # a depthwise Conv1d's default init
        return {
            "in_proj": {"kernel": normal(k[0], d, I + cfg.conv_dim + H)},
            "conv": {
                "kernel": jax.random.uniform(
                    k[1], (K, cfg.conv_dim), pd, -bound, bound),
                "bias": jax.random.uniform(
                    k[3], (cfg.conv_dim,), pd, -bound, bound),
            },
            # softplus(dt_bias) == dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(k[4], (H,), pd, 1.0, 16.0)),
            "D": jnp.ones((H,), pd),
            "norm": {"scale": jnp.ones((I,), pd)},
            "out_proj": {"kernel": out(k[5], I, d)},
        }
    if kind == "attn":
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return {
            "q_proj": {"kernel": normal(k[0], d, q)},
            "k_proj": {"kernel": normal(k[1], d, kv)},
            "v_proj": {"kernel": normal(k[2], d, kv)},
            "o_proj": {"kernel": out(k[3], q, d)},
        }
    e, f = cfg.n_experts_held, cfg.d_expert
    return {
        "router": {"kernel": normal(k[0], d, cfg.n_routed_experts)},
        BALANCE_BIAS: jnp.zeros((cfg.n_routed_experts,), pd),
        "up_proj": {"kernel": normal(k[1], e, d, f)},
        "down_proj": {"kernel": out(k[2], e, f, d)},
        "shared": {
            "up_proj": {"kernel": normal(k[3], d, cfg.d_shared)},
            "down_proj": {"kernel": out(k[4], cfg.d_shared, d)},
        },
    }


def init_params(cfg: NemotronHConfig, key) -> Dict:
    """Matrices normal with ``init_std``, those onto the residual stream
    (``out_proj``, ``o_proj``, ``down_proj``) / sqrt(``init_depth``)
    (``rescale_prenorm_residual``); norm weights and ``D`` one; ``A_log``
    = log U[1, 16]; ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly between ``time_step_min`` and ``time_step_max``; the
    convolution as a depthwise ``Conv1d``'s default; the balance bias
    zero."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 2)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def out(k, *shape):
        return normal(k, *shape) / math.sqrt(cfg.init_depth)

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": {"scale": jnp.ones((d,), pd)},
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
    }
    for i, letter in enumerate(cfg.pattern):
        kind = MIXERS[letter]
        params[f"layers_{i}"] = {
            "norm": {"scale": jnp.ones((d,), pd)},
            kind: _mixer_params(cfg, kind, keys[2 + i], normal, out),
        }
    return params


def _conv_silu(m: Dict, xbc, dt):
    """``silu(b + Σ_j w_j ⊙ xBC_{t-(K-1)+j})``: tap ``j`` multiplies the
    position ``K-1-j`` back, zeros before the sequence's start; f32
    inside ``ops/ssm_pointwise.py``'s kernel."""
    return conv_silu(xbc, m["conv"]["kernel"], m["conv"]["bias"]).astype(dt)


def _gated_norm(y, z, scale, groups: int, eps: float, dt):
    """``RMSNorm_grouped(y ⊙ silu(z))·scale``: the gate first, then each
    of the ``groups`` runs of channels normalised alone; f32 inside
    ``ops/ssm_pointwise.py``'s kernel."""
    return gated_norm(y.reshape(z.shape), z, scale, groups, eps).astype(dt)


@jax.named_scope("attn")
def _mamba_mixer(cfg: NemotronHConfig, layer: Dict, x):
    m, dt, f32 = layer["mamba"], cfg.dtype, jnp.float32
    B, S, _ = x.shape
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    I = cfg.ssm_inner
    with jax.named_scope("ssm_in"):
        h = rms_norm(x, layer["norm"]["scale"], cfg.rms_eps)
        proj = h @ m["in_proj"]["kernel"].astype(dt)
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
        return x + y @ m["out_proj"]["kernel"].astype(dt)


@jax.named_scope("attn")
def _attn_mixer(cfg: NemotronHConfig, layer: Dict, x, *, attn_fn):
    a, dt = layer["attn"], cfg.dtype
    B, S, _ = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("gqa_proj"):
        h = rms_norm(x, layer["norm"]["scale"], cfg.rms_eps)
        q = (h @ a["q_proj"]["kernel"].astype(dt)).reshape(B, S, H, D)
        k = (h @ a["k_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
        v = (h @ a["v_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
    with jax.named_scope("gqa_core"):
        o = attn_fn(q, k, v)
    with jax.named_scope("gqa_proj"):
        return x + o.reshape(B, S, H * D) @ a["o_proj"]["kernel"].astype(dt)


def _relu2(h, m: Dict, dt):
    u = (h @ m["up_proj"]["kernel"].astype(dt)).astype(jnp.float32)
    return jnp.square(jax.nn.relu(u)).astype(dt) @ m["down_proj"][
        "kernel"].astype(dt)


def _moe_mixer(cfg: NemotronHConfig, layer: Dict, x) -> Tuple[Any, Dict]:
    """``common.routed_sublayer`` with this model's norm, its relu²
    experts (no gate matrix) and its relu² shared expert."""
    m = layer["moe"]
    return routed_sublayer(
        cfg, x, layer["norm"]["scale"], m,
        shared=lambda h: _relu2(h, m["shared"], cfg.dtype))


def forward_hidden(cfg: NemotronHConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d], record). The
    record holds ``experts`` [L_e, N, top_k] and ``loads`` [L_e, routed]
    of every expert layer in the pattern's order, and ``carrier`` (zero;
    see ``common.loads_as_gradient``)."""
    if attn_fn is None:
        attn_fn = _local_causal_attention
    mixers = {
        "M": functools.partial(_mamba_mixer, cfg),
        "*": functools.partial(_attn_mixer, cfg, attn_fn=attn_fn),
        "E": functools.partial(_moe_mixer, cfg),
    }
    if cfg.remat:
        mixers = {k: checkpoint_layer(f) for k, f in mixers.items()}
    x = embed(cfg, params, tokens)
    records = []
    for i, letter in enumerate(cfg.pattern):
        x = mixers[letter](params[f"layers_{i}"], x)
        if letter == "E":
            x, rec = x
            records.append(rec)
    out = routing_record(records)
    return rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps), out


def loss_terms(cfg: NemotronHConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``common.share_loss_terms`` of this model's forward pass, the cross
    entropy through ``lm_head``."""
    h, rec = forward_hidden(cfg, params, tokens, attn_fn)
    return share_loss_terms(cfg, h, rec, ce_from_hidden(
        h, params["lm_head"]["kernel"], targets, cfg.xent_chunks))


def loss_fn(cfg: NemotronHConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
