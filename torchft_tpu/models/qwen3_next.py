"""Qwen3-Next decoder (Hugging Face ``model_type`` ``qwen3_next``; the
benchmark's configuration is Qwen/Qwen3-Next-80B-A3B-Instruct) as ONE
CHIP'S SHARE of an expert-parallel layer. A PRE-norm block whose norm
weights are zero-centred, ``N(x; w) = x · rsqrt(mean(x²) + eps) · (1 +
w)`` with ``w`` initialised 0 (:func:`unit_plus`):

    h = x + mixer(N(x; w1))          y = h + moe(N(h; w2))

``layer_types`` says which mixer, layer by layer; every layer is sparse.
A final ``N``, an untied head, no bias anywhere. ``d`` = ``d_model``.

Linear-attention mixer: the gated delta rule (Gated DeltaNet,
arXiv:2412.06464) with ``H_v = n_value_heads`` state heads of ``K ×
V`` over ``H_k = n_key_heads`` key heads, ``r = H_v / H_k`` value heads
reading one key head's q and k. ``[q̃ ; k̃ ; ṽ ; z] = n·W_qkvz``: ONE
projection ``d -> 2 H_k K + 2 H_v V`` (one leaf; the program multiplies
its ``[q̃ ; k̃ ; ṽ]`` columns and its ``z`` columns apart, so that the
convolution's and the gate's kernels each read an array of their own and
no slice of the activations is copied), ``[b ; a] = n·W_ba`` (``d -> 2
H_v``, f32 from the accumulator on); ``[q̂ ; k̂ ; v] = silu(conv([q̃ ; k̃ ;
ṽ]))``, one causal depthwise convolution of ``conv_kernel`` taps, no bias
(``ops/ssm_pointwise.py::conv_silu``, a zero bias passed); a key head ``q
= q̂ / ‖q̂‖₂ · K^{-1/2}``, ``k = k̂ / ‖k̂‖₂`` (:func:`_l2_normed`, taken in
the layout the convolution leaves and the scan reads:
:func:`_heads_normed`); a value head ``β = σ(b_h)`` — NOT doubled: the
config has no ``allow_neg_eigval`` —, ``g = −exp(A_log_h) · softplus(a_h
+ dt_bias_h)``;
value head ``h`` reads key head ``h // r`` (:func:`_value_groups`, the
published ``repeat_interleave``, says which; ``ops/kda.py::gdn_scan``
takes q and k at the KEY heads and reads them there, so the normal path
copies nothing: :func:`_gdn_mixer`);

    S_t = exp(g_t) (I − β_t k_t k_tᵀ) S_{t-1} + β_t k_t v_tᵀ,
    o_t = S_tᵀ q_t                       (``ops/kda.py::gdn_scan``)

then ``y = W_o·[RMSNorm_head(o; w_V ∈ R^V) ⊙ silu(z)]``, ``w_V`` one PLAIN
weight (initialised 1) for all heads, the gate after the norm
(``ops/ssm_pointwise.py::kda_ogate`` with ``models/olmo_hybrid.py``'s
body: at ``V`` 128 a head is one lane tile).

Full-attention mixer (``H`` query, ``KV`` key/value heads of ``D``):
``[q̃ ; γ] = n·W_q`` (``d -> 2 H D``: a head's ``D`` query channels and
its ``D`` gate channels; one leaf, ``γ``'s columns multiplied apart so
that the gate's logits keep their f32 accumulator), ``k̃ = n·W_k``, ``v =
n·W_v``; ``q_h = N_D(q̃_h; w_q)``, ``k_j = N_D(k̃_j; w_k)``: the
zero-centred norm over a head's channels, one ``D``-wide weight each;
``rotate_half`` over the head's FIRST ``partial_rotary · D`` lanes at
``rope_theta`` (``ops/ssm_pointwise.py::rotary`` from tables built once a
step), the other lanes pass; causal softmax at ``D^{-1/2}``, query head
``h`` on key/value head ``h // (H / KV)`` where it lies; ``y = W_o·[o ⊙
σ(γ)]``, a gate an ELEMENT (:func:`attn_gate`).

Sparse sublayer on ``m = N(h; w2)`` (``common.routed_sublayer(
score="softmax", shared=…)``): logits ``m·W_r`` over all
``n_routed_experts`` in f32; the ``top_k`` of logits + balance bias
choose; weights ``softmax`` over the chosen logits (= the softmax over
all, renormalised: ``norm_topk_prob``); the experts held here
(``first_expert`` on) SwiGLU; plus ``σ(m·w_s) · SwiGLU_s(m)``, the shared
expert behind ONE sigmoid gate a token (:func:`_shared_expert`).

Conventions of ``models/kimi_linear.py``: float32 parameters, bf16
compute, float32 norms / softmax statistics / l2 norms / ``g`` / ``β`` /
router / both gates' logits / rotation tables / the delta rule's state;
an explicit parameter tree with stable paths ``layers_<i>/{norm_1,
norm_2}``, ``layers_<i>/{gdn|attn}``, ``layers_<i>/moe``; per-layer
``checkpoint_layer`` behind ``remat``; the step programs of
``transformer.make_train_step`` / ``make_grad_step``
(``loss=qwen3_next.loss_fn``).

``checkpoint_layer`` (``models/common.py``) is ``jax.checkpoint`` that
keeps what a layer's router decided — the experts, their weights, the
chosen scores, the loads —, so the backward pass does not run the router
again (``common.routed_sublayer`` says why the weights are among them).

Device-trace scopes: ``embed``; both mixers under ``attn`` — ``gdn_in``
(the input norm, the projections, ``g`` and ``β``), ``gdn_conv`` (the
convolution, the l2 norms, and inside it ``gdn_repeat`` around the copy
of q and k to the value heads, which the normal path does not make: the
scope then holds nothing), ``gdn_core``, ``gdn_gate``, ``gdn_out``
(``models/olmo_hybrid.py``'s names); ``gqa_proj`` (the input norm, the
projections, the head norms, ``W_o``, and inside it ``rope`` around the
rotation and ``attn_gate`` around the gate) and ``gqa_core`` with
``full_core`` around the flash call (``models/laguna.py``'s names);
``mlp`` with ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``, ``moe_shared`` (its gate inside it); ``lm_head_xent``.
Counters: ``TRACED["gdn_value_group_calls"]``, a traced mixer whose value
heads outnumber its key heads, and ``TRACED["gdn_value_group_copies"]``,
those of them whose q and k were copied to the value heads after all —
here (a stand-in at one of the two seams) or in ``ops/kda.py::gdn_scan``
(heads and widths no grid step of its kernels fits).
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
from torchft_tpu.ops.kda import gdn_scan
from torchft_tpu.ops.ssm_pointwise import (
    conv_silu,
    kda_ogate,
    rotary,
    rotary_tables,
)
from torchft_tpu.utils.metrics import TRACED

__all__ = ["Qwen3NextConfig", "QWEN3_NEXT_CONFIGS", "LINEAR", "FULL",
           "BALANCE_BIAS", "is_balance_bias", "unit_plus", "rotation_freqs",
           "decay_and_step", "attn_gate", "moe_sublayer", "init_params",
           "forward_hidden",
           "loss_terms", "loss_fn"]

L2_EPS = 1e-6     # beside a head's squared norm, under the root
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """Defaults: Qwen/Qwen3-Next-80B-A3B-Instruct as published, every
    expert and the whole vocabulary held."""
    vocab_size: int = 151936
    d_model: int = 2048
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 12
    init_depth: int = 48          # the PUBLISHED depth: residual outputs
                                  # are initialised / sqrt(init_depth)
    n_key_heads: int = 16         # the delta rule's q and k
    n_value_heads: int = 32       # its v, g, β and state
    key_dim: int = 128
    value_dim: int = 128
    conv_kernel: int = 4
    n_heads: int = 16             # full attention's query heads
    n_kv_heads: int = 2
    head_dim: int = 256
    rope_theta: float = 1e7
    partial_rotary: float = 0.25  # share of a head's lanes turned
    d_expert: int = 512           # one routed expert's width
    d_shared: int = 512           # the shared expert's
    n_routed_experts: int = 512   # the router's width
    first_expert: int = 0         # the share held here:
    n_experts_held: int = 512     # experts first .. first + held
    top_k: int = 10
    routed_scale: float = 1.0
    rms_eps: float = 1e-6
    init_std: float = 0.02
    embed_std: Optional[float] = None   # the table's; None: init_std
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert self.layer_types and set(self.layer_types) <= {LINEAR, FULL}
        assert self.n_value_heads % self.n_key_heads == 0
        assert self.n_heads % self.n_kv_heads == 0
        assert 0 < self.rotary_lanes <= self.head_dim
        assert self.rotary_lanes % 2 == 0
        assert 1 <= self.top_k <= self.n_routed_experts
        assert 0 <= self.first_expert and 1 <= self.n_experts_held
        assert self.first_expert + self.n_experts_held <= self.n_routed_experts

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def rotary_lanes(self) -> int:
        return int(self.head_dim * self.partial_rotary)


QWEN3_NEXT_CONFIGS: Dict[str, Qwen3NextConfig] = {
    # the tests' size: one whole period L L L F, two value heads a key
    # head, key and value widths that differ and are no lane tile, a group
    # of 3 query heads, a quarter of a head turned, a share of 4 of 8
    # experts
    "qwen3_next_tiny": Qwen3NextConfig(
        vocab_size=256, d_model=48, layer_types=(LINEAR, LINEAR, LINEAR, FULL),
        init_depth=8, n_key_heads=2, n_value_heads=4, key_dim=12,
        value_dim=24, n_heads=6, n_kv_heads=2, head_dim=16, rope_theta=100.0,
        d_expert=24, d_shared=16, n_routed_experts=8, first_expert=0,
        n_experts_held=4, top_k=3, init_std=0.125,
    ),
}


def unit_plus(w):
    """``1 + w``: the zero-centred norm's weight as ``common.rms_norm``
    takes one (a seam: ``benchmark/tests/qwen3next_faults.py`` puts the
    plain weight in its place)."""
    return 1.0 + w.astype(jnp.float32)


def rotation_freqs(cfg: Qwen3NextConfig) -> np.ndarray:
    """The ``rotary_lanes / 2`` float32 frequencies ``f_i = theta^(-2i /
    rotary_lanes)`` of the rotation over a head's first lanes."""
    lanes = cfg.rotary_lanes
    i = np.arange(lanes // 2, dtype=np.float64)
    return (cfg.rope_theta ** (-2.0 * i / lanes)).astype(np.float32)


def _gdn_params(cfg: Qwen3NextConfig, key, normal, out) -> Dict:
    """``models/olmo_hybrid.py::_gdn_params``' draws at this mixer's
    shapes: the taps U(-1/sqrt(T), 1/sqrt(T)); ``A_log = log U(0, 16)``
    and ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3,
    1e-1], one a VALUE head; the head norm's weight one. The fused
    projections' columns in the order ``[q ; k ; v ; z]`` and ``[b ; a]``,
    each part heads-major (the checkpoint interleaves them a key head: a
    layout, not mathematics)."""
    pd, d = cfg.param_dtype, cfg.d_model
    Hv = cfg.n_value_heads
    hk, hv = cfg.n_key_heads * cfg.key_dim, Hv * cfg.value_dim
    k = jax.random.split(key, 6)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    dt = jnp.exp(jax.random.uniform(
        k[4], (Hv,), pd, math.log(1e-3), math.log(1e-1)))
    return {
        "qkvz_proj": {"kernel": normal(k[0], d, 2 * hk + 2 * hv)},
        "ba_proj": {"kernel": normal(k[1], d, 2 * Hv)},
        "conv": {"kernel": jax.random.uniform(
            k[2], (cfg.conv_kernel, 2 * hk + hv), pd, -bound, bound)},
        "A_log": jnp.log(jnp.maximum(
            jax.random.uniform(k[3], (Hv,), pd, 0.0, 16.0), 1e-4)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": {"scale": jnp.ones((cfg.value_dim,), pd)},
        "o_proj": {"kernel": out(k[5], hv, d)},
    }


def init_params(cfg: Qwen3NextConfig, key) -> Dict:
    """Matrices normal with ``init_std``, those onto the residual stream
    (``o_proj``, every ``down_proj``) / sqrt(``init_depth``); the table
    with ``embed_std`` where a configuration gives it one of its own;
    every zero-centred norm weight ZERO (the block's, the heads' and the
    final one), the delta rule's head norm one; the balance bias zero; the
    table and the head two leaves."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 2)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def out(k, *shape):
        return normal(k, *shape) / math.sqrt(cfg.init_depth)

    def zeros(n):
        return {"scale": jnp.zeros((n,), pd)}

    def mlp(k, width, *held):
        k = jax.random.split(k, 3)
        return {"gate_proj": {"kernel": normal(k[0], *held, d, width)},
                "up_proj": {"kernel": normal(k[1], *held, d, width)},
                "down_proj": {"kernel": out(k[2], *held, width, d)}}

    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    params: Dict[str, Any] = {
        "wte": {"embedding": jax.random.normal(
            keys[0], (cfg.vocab_size, d), pd) * (
                cfg.init_std if cfg.embed_std is None else cfg.embed_std)},
        "ln_f": zeros(d),
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
    }
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[2 + i], 9)
        layer: Dict[str, Any] = {
            "norm_1": zeros(d), "norm_2": zeros(d),
            "moe": dict(
                mlp(k[0], cfg.d_expert, cfg.n_experts_held),
                shared=dict(mlp(k[1], cfg.d_shared),
                            gate={"kernel": normal(k[2], d, 1)}),
                router={"kernel": normal(k[3], d, cfg.n_routed_experts)},
                **{BALANCE_BIAS: jnp.zeros((cfg.n_routed_experts,), pd)}),
        }
        if kind == LINEAR:
            layer["gdn"] = _gdn_params(cfg, k[4], normal, out)
        else:
            layer["attn"] = {
                "q_proj": {"kernel": normal(k[5], d, 2 * q)},
                "k_proj": {"kernel": normal(k[6], d, kv)},
                "v_proj": {"kernel": normal(k[7], d, kv)},
                "o_proj": {"kernel": out(k[8], q, d)},
                "q_norm": zeros(cfg.head_dim), "k_norm": zeros(cfg.head_dim),
            }
        params[f"layers_{i}"] = layer
    return params


def _gdn_scan(q, k, v, g, beta):
    """The delta rule: a seam over ``ops/kda.py``'s kernels, kept under
    this name because ``benchmark/tests/qwen3next_faults.py`` puts its
    stand-ins in its place. It takes q and k at the key heads or at the
    value heads and says so (``takes_key_heads``); a stand-in, which does
    not, is handed them at the value heads (:func:`_gdn_mixer`)."""
    return gdn_scan(q, k, v, g, beta)


_gdn_scan.takes_key_heads = True


def _gated_head_norm(o, scale, gate, eps: float):
    """``RMSNorm_head(o) ⊙ silu(gate)``: the norm FIRST, over a head's
    channels (the last axis; one PLAIN ``V``-wide weight), then the SiLU
    gate; in f32, rounded once (``models/olmo_hybrid.py``'s body; a seam:
    see :func:`_gdn_scan`)."""
    f32 = jnp.float32
    return (rms_norm(o.astype(f32), scale, eps)
            * jax.nn.silu(gate.astype(f32))).astype(o.dtype)


def _l2_normed(x):
    """``x / ‖x‖₂`` over the last axis, a head's channels, in f32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


_ROWS = 8     # positions a tile of a flat f32 ``[B, S, width]`` array holds


def _heads_normed(z, heads: int, scale: Optional[float], dtype):
    """``[B, S, heads·K]`` -> ``[B, S, heads, K]`` in ``dtype``: every head
    :func:`_l2_normed` and, where given, scaled — the numbers of the plain
    form on ``z.reshape(B, S, heads, K)``, bit for bit, forward and
    backward (``tests/test_qwen3_next.py``; on the chip at ``[4, 8192, 16,
    128]``, PERF.md section 6, PR 65). The arithmetic is done on the view
    ``[B, S/8, heads, 8, K]``, which is how the flat array lies in the
    chip's memory (tiles of 8 positions by 128 lanes: a head of 128
    channels is a lane tile), and its flat result is held behind an
    ``optimization_barrier``, so that the scan's two kernels read ONE
    array in the layout they take. On ``[B, S, heads, K]`` XLA puts the
    heads on the sublanes for the reduction: the f32 array is moved there
    by a ``copy`` of 268 MB that carries no scope, q and k, in each of the
    three passes, and what comes out is moved back to the flat layout for
    the kernels by ``reshape`` instructions as large — 60 ms of the cell's
    867 ms step."""
    B, S, width = z.shape
    K = width // heads

    def normed(x):
        x = _l2_normed(x)
        return (x if scale is None else x * scale).astype(dtype)

    if S % _ROWS:
        return normed(z.reshape(B, S, heads, K))
    tiles = z.reshape(B, S // _ROWS, _ROWS, heads, K).transpose(0, 1, 3, 2, 4)
    flat = jax.lax.optimization_barrier(
        normed(tiles).transpose(0, 1, 3, 2, 4).reshape(B, S, width))
    return flat.reshape(B, S, heads, K)


def _value_groups(x, n_value_heads: int):
    """``[B, S, H_k, K]`` -> ``[B, S, H_v, K]``: value head ``h`` reads
    key head ``h // (H_v / H_k)``, by a copy (the published
    ``repeat_interleave``; a seam: see :func:`_gdn_scan`). The ONE place
    that says which key head a value head reads: :func:`_gdn_mixer` asks
    it for the map and copies only where the map is another than the
    kernels' own."""
    return jnp.repeat(x, n_value_heads // x.shape[2], axis=2)


def _reads_key_head_h_over_r(n_key_heads: int, n_value_heads: int) -> bool:
    """Whether what stands in :func:`_value_groups`' place maps value
    head ``h`` to key head ``h // r``, the map ``ops/kda.py::gdn_scan``
    reads q and k by: the function applied to the key heads' indices, at
    trace time."""
    with jax.ensure_compile_time_eval():
        heads = _value_groups(
            jnp.arange(n_key_heads).reshape(1, 1, n_key_heads, 1),
            n_value_heads)
    return np.array_equal(
        np.asarray(heads).reshape(-1),
        np.arange(n_value_heads) // (n_value_heads // n_key_heads))


def decay_and_step(cfg: Qwen3NextConfig, m: Dict, n):
    """``(g, β)`` of a linear mixer on its normed input ``n``, each ``[B,
    S, H_v]`` f32: the ``d -> 2 H_v`` projection keeps its f32 accumulator
    (a log-decay rounded to bf16 would move by 2^-9 of its size)."""
    f32 = jnp.float32
    Hv = cfg.n_value_heads
    ba = jnp.dot(n, m["ba_proj"]["kernel"].astype(n.dtype),
                 preferred_element_type=f32)
    g = -jnp.exp(m["A_log"].astype(f32)) * jax.nn.softplus(
        ba[..., Hv:] + m["dt_bias"].astype(f32))
    return g, jax.nn.sigmoid(ba[..., :Hv])


def _gdn_mixer(cfg: Qwen3NextConfig, layer: Dict, x):
    """``x + mixer(N(x; w1))``, the delta-rule mixer. With more value
    than key heads, q and k go to the scan at the KEY heads where
    :func:`_value_groups` is the map the kernels read by and what stands
    in :func:`_gdn_scan`'s place takes them so; in every other case they
    are copied through :func:`_value_groups`, whatever stands there
    (scope ``gdn_repeat``, counted)."""
    m, dt = layer["gdn"], cfg.dtype
    B, S, _ = x.shape
    Hk, Hv, K, V = (cfg.n_key_heads, cfg.n_value_heads, cfg.key_dim,
                    cfg.value_dim)
    conv_width = 2 * Hk * K + Hv * V
    with jax.named_scope("gdn_in"):
        n = rms_norm(x, unit_plus(layer["norm_1"]["scale"]), cfg.rms_eps)
        w = m["qkvz_proj"]["kernel"]
        qkv = n @ w[:, :conv_width].astype(dt)
        z = n @ w[:, conv_width:].astype(dt)
        g, beta = decay_and_step(cfg, m, n)
    with jax.named_scope("gdn_conv"):
        taps = m["conv"]["kernel"]
        qkv = conv_silu(qkv, taps, jnp.zeros(taps.shape[1:], taps.dtype))
        q = _heads_normed(qkv[..., :Hk * K], Hk, K ** -0.5, dt)
        k = _heads_normed(qkv[..., Hk * K:2 * Hk * K], Hk, None, dt)
        v = qkv[..., 2 * Hk * K:].reshape(B, S, Hv, V)
        if Hv != Hk:
            TRACED.incr("gdn_value_group_calls")
            if not (getattr(_gdn_scan, "takes_key_heads", False)
                    and _reads_key_head_h_over_r(Hk, Hv)):
                TRACED.incr("gdn_value_group_copies")
                with jax.named_scope("gdn_repeat"):
                    q, k = _value_groups(q, Hv), _value_groups(k, Hv)
    with jax.named_scope("gdn_core"):
        o = _gdn_scan(q, k, v, g, beta)                  # [B, S, H_v, V]
    with jax.named_scope("gdn_gate"):
        y = kda_ogate(o.reshape(B, S, Hv * V), z, m["o_norm"]["scale"],
                      cfg.rms_eps, _gated_head_norm)
    with jax.named_scope("gdn_out"):
        return x + y @ m["o_proj"]["kernel"].astype(dt)


def attn_gate(o, logits):
    """``o ⊙ σ(γ)``, a gate an ELEMENT: ``o [B, S, H, D]`` in the compute
    dtype and ``logits [B, S, H·D]`` f32 -> ``[B, S, H·D]``; one f32
    multiply and one rounding (a seam: see :func:`_gdn_scan`)."""
    B, S, H, D = o.shape
    return (o.reshape(B, S, H * D).astype(jnp.float32)
            * jax.nn.sigmoid(logits)).astype(o.dtype)


def _head_normed(z, w, eps: float, heads: int):
    """``[B, S, heads·D]`` -> the zero-centred norm over each head's ``D``
    channels, one ``D``-wide weight, back as ``[B, S, heads·D]``."""
    B, S, width = z.shape
    return rms_norm(z.reshape(B, S, heads, width // heads), unit_plus(w),
                    eps).reshape(B, S, width)


def _attn_mixer(cfg: Qwen3NextConfig, layer: Dict, x, table, *, attn_fn):
    """``x + W_o·[attention(N(x)) ⊙ σ(γ)]``. The rotation is
    ``ops/ssm_pointwise.py::rotary``, whose heads-first result's
    transposed view ``attn_fn`` takes (``[B, S, H, D]``), so that the
    flash call's own transposition folds it away."""
    a, dt, eps = layer["attn"], cfg.dtype, cfg.rms_eps
    B, S, _ = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    half = cfg.rotary_lanes // 2
    with jax.named_scope("gqa_proj"):
        n = rms_norm(x, unit_plus(layer["norm_1"]["scale"]), eps)
        w = a["q_proj"]["kernel"]
        q = _head_normed(n @ w[:, :H * D].astype(dt),
                         a["q_norm"]["scale"], eps, H)
        k = _head_normed(n @ a["k_proj"]["kernel"].astype(dt),
                         a["k_norm"]["scale"], eps, KV)
        v = (n @ a["v_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
        with jax.named_scope("attn_gate"):
            logits = jnp.dot(n, w[:, H * D:].astype(dt),
                             preferred_element_type=jnp.float32)
        with jax.named_scope("rope"):
            q, k = (rotary(z, *table, half).transpose(0, 2, 1, 3)
                    for z in (q, k))
    with jax.named_scope("gqa_core"):
        with jax.named_scope("full_core"):
            o = attn_fn(q, k, v)
    with jax.named_scope("gqa_proj"):
        with jax.named_scope("attn_gate"):
            o = attn_gate(o, logits)
        return x + o @ a["o_proj"]["kernel"].astype(dt)


def _shared_expert(cfg: Qwen3NextConfig, m: Dict, h):
    """``σ(h·w_s) · SwiGLU_s(h)`` on ``h [N, d]``: ONE gate a token, its
    logit f32 from the accumulator on (a seam: see :func:`_gdn_scan`)."""
    gate = jax.nn.sigmoid(jnp.dot(
        h, m["gate"]["kernel"].astype(h.dtype),
        preferred_element_type=jnp.float32))
    return (swiglu(h, m, cfg.dtype).astype(jnp.float32) * gate).astype(h.dtype)


def moe_sublayer(cfg: Qwen3NextConfig, layer: Dict, h) -> Tuple[Any, Dict]:
    """``(h + moe(N(h; w2)), record)``: ``common.routed_sublayer`` on this
    model's terms — the softmax over the chosen logits, the shared expert
    behind its gate."""
    m = layer["moe"]
    return routed_sublayer(
        cfg, h, unit_plus(layer["norm_2"]["scale"]), m, score="softmax",
        shared=lambda n2: _shared_expert(cfg, m["shared"], n2))


def _layer(cfg: Qwen3NextConfig, kind: str, layer: Dict, x, table, *,
           attn_fn) -> Tuple[Any, Dict]:
    """One layer: ``(x, record)``."""
    with jax.named_scope("attn"):
        if kind == LINEAR:
            h = _gdn_mixer(cfg, layer, x)
        else:
            h = _attn_mixer(cfg, layer, x, table, attn_fn=attn_fn)
    return moe_sublayer(cfg, layer, h)


def forward_hidden(cfg: Qwen3NextConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d], record).
    ``attn_fn(q, k, v)`` is the local causal attention
    (``ops/attention.py::causal_attention`` by default: the flash kernels
    on a TPU), handed ``k`` and ``v`` at their own head count. The record
    holds ``experts`` [L, N, top_k] and ``loads`` [L, routed] of every
    layer in order, and ``carrier`` (zero; see
    ``common.loads_as_gradient``)."""
    if attn_fn is None:
        attn_fn = causal_attention
    x = embed(cfg, params, tokens)
    table = None
    if FULL in cfg.layer_types:
        with jax.named_scope("attn"), jax.named_scope("gqa_proj"), \
                jax.named_scope("rope"):
            table = rotary_tables(jnp.asarray(rotation_freqs(cfg)),
                                  tokens.shape[1], cfg.head_dim)
    records = []
    for i, kind in enumerate(cfg.layer_types):
        run = functools.partial(_layer, cfg, kind, attn_fn=attn_fn)
        if cfg.remat:
            run = checkpoint_layer(run)
        x, rec = run(params[f"layers_{i}"], x, table)
        records.append(rec)
    return (rms_norm(x, unit_plus(params["ln_f"]["scale"]), cfg.rms_eps),
            routing_record(records))


def loss_terms(cfg: Qwen3NextConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``common.share_loss_terms`` of this model's forward pass, the cross
    entropy through the untied head."""
    h, rec = forward_hidden(cfg, params, tokens, attn_fn)
    return share_loss_terms(
        cfg, h, rec, ce_from_hidden(h, params["lm_head"]["kernel"], targets,
                                    cfg.xent_chunks))


def loss_fn(cfg: Qwen3NextConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
