"""Phi-4-mini-flash-reasoning (Hugging Face ``model_type`` ``phi4flash``;
SambaY, arXiv:2507.06607) as ONE CHIP'S SHARE of a vocabulary-parallel
stage: a decoder whose second half reads the first half's memory. Every
layer is a sequence mixer and a SwiGLU MLP, each behind its own LayerNorm
with bias:

    h = x + mixer_i(LN(x))          x' = h + W_down(silu(W_gate n) ⊙ W_up n)

No position embedding, no dropout, one table (``logits = h·Eᵀ``, tied), a
final LayerNorm. ``i`` is the PUBLISHED index of the layer (``layer_ids``
names the layers a cut keeps) and, of ``n = n_published_layers``, says
the mixer (``layer_kind``):

``mamba`` (even ``i <= n/2``) — Mamba-1 (arXiv:2312.00752): ``[x̃ ; z] =
W_in u``; ``x̃ = silu(conv(x̃))``, a causal depthwise convolution of
``conv_kernel`` taps with bias (``ops/ssm_pointwise.py::conv_silu``);
``[δ ; B ; C] = W_x x̃``; ``Δ = softplus(W_dt δ + b_dt)``; ``A =
−exp(A_log)``; the selective scan ``y = s6_scan(x̃, Δ, A, B, C, D)``
(``ops/s6.py``: a decay a channel AND a state index); out ``W_out (y ⊙
silu(z))``. **Layer ``n/2`` also hands on ``m = y``** — the scan's output
with its ``D`` term, before the gate.

``swa`` (odd ``i < n/2``) and ``full`` (``i = n/2 + 1``) — differential
attention (arXiv:2410.05258): ``[q ; k ; v] = W_qkv u + b``; the ``n_heads``
query heads are ``n_heads / 2`` PAIRS ``(q1_p, q2_p)``, the key heads
pairs ``(k1_r, k2_r)``, the value heads pairs joined to ``2·head_dim``-wide
``v_r``, pair ``p`` reading ``r = p // (n_heads / n_kv_heads)``; ``a_j =
softmax(q_j k_jᵀ / sqrt(head_dim) + mask) v_r`` for both halves — TWO flash
calls a layer, each over ``n_heads / 2`` heads at ``Dqk = head_dim``, ``Dv
= 2·head_dim`` (``ops/flash.py``'s two widths); ``λ = exp(λ_q1·λ_k1) −
exp(λ_q2·λ_k2) + λ_init`` with ``λ_init = 0.8 − 0.6 exp(−0.3 i)``; ``o_p =
(1 − λ_init)·RMSNorm(a1_p − λ a2_p)`` (one learned ``2·head_dim`` scale a
layer); out ``W_o [o_0 … ] + b_o``. The mask is causal; under ``swa``
position ``t`` sees its last ``window`` keys, itself among them
(``flash_attention(window=)``). **Layer ``n/2 + 1`` also hands on its
``k`` and ``v``.**

``gmu`` (even ``i >= n/2 + 2``) — ``W_out (silu(W_in u) ⊙ m)``.

``cross`` (odd ``i >= n/2 + 3``) — ``q = W_q u + b`` alone; the
differential attention above, causal and full, over the handed-on ``k``
and ``v``; its own ``λ`` vectors, norm and ``W_o``.

**A stack that is not a chain.** ``forward_hidden`` carries ``(x, memory)``
from layer to layer: the two source layers return what they hand on
beside ``x``, the readers take it as an argument, each layer under its
own ``jax.checkpoint`` behind ``remat``. So ``m`` (``[B, S, d_inner]`` in
the compute dtype) and ``k, v`` (``[B, S, n_kv_heads·head_dim]`` each) are
checkpoint boundaries and stay alive from their layer to the end of the
backward pass, and a gradient comes back into them from every reader.
Where those are summed: a handed-on value leaves its layer through
``_fan_out`` (its own use and the later layers'), and feeds more than one
reader through another; ``_fan_out``'s backward adds its cotangents in
float32 under the scope ``memory_grad``.

Conventions of the other families: float32 parameters, bf16 compute,
float32 norms / softmax / ``λ`` / softplus / decays / scan state, an
explicit parameter tree with stable paths ``layers_<j>/...`` (``j`` the
position in the cut), and the step programs of
``transformer.make_train_step`` / ``make_grad_step``
(``loss=phi4flash.loss_fn``).

Device-trace scopes: ``embed``; every mixer under ``attn``, told apart
inside — the Mamba mixer's ``ssm_in`` (norm, ``W_in``, ``W_x``, ``W_dt``),
``ssm_conv``, ``ssm_scan`` (softplus, ``A``, the kernels), ``ssm_gate``,
``ssm_out``: Nemotron-H's names, the same kind of work; the attention
mixers whole under ``diff_attn`` with ``diff_proj`` (norm, projections,
the heads' layout, ``W_o``), ``swa_core`` or ``full_core`` (the flash
call) and ``diff_combine`` (``λ``, the norm, the scale); ``gmu``;
``memory_grad``; ``mlp``; ``lm_head_xent``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.common import embed, rms_norm, swiglu
from torchft_tpu.models.transformer import _layer_norm, ce_from_hidden
from torchft_tpu.ops.attention import causal_attention
from torchft_tpu.ops.s6 import s6_scan
from torchft_tpu.ops.ssm_pointwise import conv_silu

__all__ = ["Phi4FlashConfig", "PHI4FLASH_CONFIGS", "layer_kind",
           "lambda_init", "init_params", "forward_hidden", "loss_terms",
           "loss_fn"]

KINDS = ("mamba", "swa", "full", "gmu", "cross")


def layer_kind(i: int, n: int) -> str:
    """The mixer of published layer ``i`` of ``n``."""
    half = n // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gmu"
    if i < half:
        return "swa"
    return "full" if i == half + 1 else "cross"


def lambda_init(i: int) -> float:
    """``λ_init`` of published layer ``i``."""
    return 0.8 - 0.6 * math.exp(-0.3 * i)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """Defaults: microsoft/Phi-4-mini-flash-reasoning as published, every
    layer and the whole table."""
    vocab_size: int = 200064      # rows of the table held here:
    vocab_ways: int = 1           # one of ``vocab_ways`` equal slices,
    first_vocab_row: int = 0      # from this row of the padded table on
    d_model: int = 2560
    n_published_layers: int = 32  # says each layer's kind, and the init
    layer_ids: Tuple[int, ...] = tuple(range(32))
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    window: int = 512
    d_ff: int = 10240
    d_inner: int = 5120           # Mamba-1 and the GMU: expand 2
    d_state: int = 16
    dt_rank: int = 160            # ceil(d_model / 16)
    conv_kernel: int = 4
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    dt_floor: float = 1e-4
    ln_eps: float = 1e-5
    init_std: float = 0.02
    lambda_std: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        ids, n = self.layer_ids, self.n_published_layers
        assert ids and list(ids) == sorted(set(ids)) and ids[-1] < n
        assert self.n_heads % self.n_kv_heads == 0
        assert self.n_heads % 2 == 0 and self.n_kv_heads % 2 == 0
        kinds = [layer_kind(i, n) for i in ids]
        # a reader needs its source in the cut
        assert "gmu" not in kinds or n // 2 in ids
        assert "cross" not in kinds or n // 2 + 1 in ids

    @property
    def n_layers(self) -> int:
        return len(self.layer_ids)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(layer_kind(i, self.n_published_layers)
                     for i in self.layer_ids)


PHI4FLASH_CONFIGS: Dict[str, Phi4FlashConfig] = {
    # the tests' size: the cell's six layers (every kind), a window
    # shorter than the tests' sequences, two query pairs a key/value pair
    "phi4flash_tiny": Phi4FlashConfig(
        vocab_size=256, d_model=64, layer_ids=(0, 1, 16, 17, 18, 19),
        n_heads=8, n_kv_heads=4, head_dim=8, window=16, d_ff=96,
        d_inner=128, d_state=8, dt_rank=4, init_std=0.125,
    ),
}


def init_params(cfg: Phi4FlashConfig, key) -> Dict:
    """Matrices normal with ``init_std``, those onto the residual stream /
    sqrt(2·``n_published_layers``); LayerNorm weights one and biases zero;
    projection biases zero; the taps and their bias as a depthwise
    ``Conv1d``'s default, U(±1/sqrt(K)); Mamba-1's released defaults:
    ``A_log = log(1 … N)`` a channel, ``D = 1``, ``W_dt`` U(±dt_rank^-1/2)
    and ``b_dt`` the inverse softplus of a ``Δ`` drawn log-uniform in
    [``dt_min``, ``dt_max``], floored at ``dt_floor``; the four ``λ``
    vectors normal with ``lambda_std``, the head norm's weight one. ONE
    table: there is no ``lm_head`` leaf."""
    pd, d, f, di = cfg.param_dtype, cfg.d_model, cfg.d_ff, cfg.d_inner
    N, R, K, D = cfg.d_state, cfg.dt_rank, cfg.conv_kernel, cfg.head_dim
    q, kv = cfg.n_heads * D, cfg.n_kv_heads * D
    keys = jax.random.split(key, cfg.n_layers + 1)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def out(k, *shape):
        return normal(k, *shape) / math.sqrt(2 * cfg.n_published_layers)

    def norm():
        return {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)}

    def diff(k):
        return dict(
            {f"lambda_{n}": jax.random.normal(k_, (D,), pd) * cfg.lambda_std
             for n, k_ in zip(("q1", "k1", "q2", "k2"),
                              jax.random.split(k, 4))},
            subln={"scale": jnp.ones((2 * D,), pd)})

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": norm(),
    }
    for j, kind in enumerate(cfg.kinds):
        k = jax.random.split(keys[1 + j], 10)
        layer: Dict[str, Any] = {
            "norm_1": norm(), "norm_2": norm(),
            "mlp": {"gate_proj": {"kernel": normal(k[0], d, f)},
                    "up_proj": {"kernel": normal(k[1], d, f)},
                    "down_proj": {"kernel": out(k[2], f, d)}},
        }
        if kind == "mamba":
            bound = 1.0 / math.sqrt(K)
            dt = jnp.maximum(jnp.exp(
                jax.random.uniform(k[7], (di,), pd)
                * (math.log(cfg.dt_max) - math.log(cfg.dt_min))
                + math.log(cfg.dt_min)), cfg.dt_floor)
            layer["ssm"] = {
                "in_proj": {"kernel": normal(k[3], d, 2 * di)},
                "conv": {"kernel": jax.random.uniform(
                    k[4], (K, di), pd, -bound, bound),
                    "bias": jax.random.uniform(
                        k[5], (di,), pd, -bound, bound)},
                "x_proj": {"kernel": normal(k[6], di, R + 2 * N)},
                "dt_proj": {"kernel": jax.random.uniform(
                    k[8], (R, di), pd, -R ** -0.5, R ** -0.5),
                    "bias": dt + jnp.log(-jnp.expm1(-dt))},
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=pd)), (di, N)),
                "D": jnp.ones((di,), pd),
                "out_proj": {"kernel": out(k[9], di, d)},
            }
        elif kind in ("swa", "full"):
            layer["attn"] = dict(
                diff(k[5]),
                qkv_proj={"kernel": normal(k[3], d, q + 2 * kv),
                          "bias": jnp.zeros((q + 2 * kv,), pd)},
                o_proj={"kernel": out(k[4], q, d),
                        "bias": jnp.zeros((d,), pd)})
        elif kind == "cross":
            layer["attn"] = dict(
                diff(k[5]),
                q_proj={"kernel": normal(k[3], d, q),
                        "bias": jnp.zeros((q,), pd)},
                o_proj={"kernel": out(k[4], q, d),
                        "bias": jnp.zeros((d,), pd)})
        else:
            layer["gmu"] = {"in_proj": {"kernel": normal(k[3], d, di)},
                            "out_proj": {"kernel": out(k[4], di, d)}}
        params[f"layers_{j}"] = layer
    return params


# ---------------------------------------------------------------- the memory
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fan_out(x, n: int):
    """``x`` for ``n`` readers. The forward pass copies nothing; the
    backward adds the readers' cotangents in float32 in ONE place, under
    the scope ``memory_grad``."""
    return (x,) * n


def _fan_out_fwd(x, n):
    return (x,) * n, None


def _fan_out_bwd(n, _, cotangents):
    with jax.named_scope("memory_grad"):
        total = sum(g.astype(jnp.float32) for g in cotangents)
        return (total.astype(cotangents[0].dtype),)


_fan_out.defvjp(_fan_out_fwd, _fan_out_bwd)


def _norm(cfg, p: Dict, x):
    return _layer_norm(x, p["scale"], p["bias"], cfg.ln_eps)


# ----------------------------------------------------------------- the mixers
def _conv_silu(m: Dict, x, dt):
    """A seam over ``ops/ssm_pointwise.py``'s kernels (``benchmark/tests/
    phi4flash_faults.py`` puts its stand-ins here, and at ``_scan``)."""
    return conv_silu(x, m["conv"]["kernel"], m["conv"]["bias"]).astype(dt)


def _scan(x, delta, a, bm, cm, d):
    return s6_scan(x, delta, a, bm, cm, d)


def _softplus(pre):
    """``Δ`` from ``W_dt δ + b_dt`` (a seam, as ``_scan``)."""
    return jax.nn.softplus(pre)


def _memory(y, xs, z, d):
    """What the source layer hands on as ``m``: the scan's output ``y``,
    with its ``D`` term, before the gate ``silu(z)`` (a seam: the faults
    file's stand-ins hand on something else)."""
    return y


@jax.named_scope("attn")
def _mamba_mixer(cfg: Phi4FlashConfig, layer: Dict, x, hand_on: bool):
    """``(x + mixer, m or None)``."""
    m, dt, f32 = layer["ssm"], cfg.dtype, jnp.float32
    di, N, R = cfg.d_inner, cfg.d_state, cfg.dt_rank
    with jax.named_scope("ssm_in"):
        u, w_in = _norm(cfg, layer["norm_1"], x), m["in_proj"]["kernel"]
        # the halves of W_in apart: no [B, S, 2·d_inner] array to cut
        xs, z = u @ w_in[:, :di].astype(dt), u @ w_in[:, di:].astype(dt)
    with jax.named_scope("ssm_conv"):
        xs = _conv_silu(m, xs, dt)
    with jax.named_scope("ssm_in"):
        dbc = xs @ m["x_proj"]["kernel"].astype(dt)
        low, bm, cm = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
        # bf16 operands, an f32 result: Δ is never rounded to bf16
        pre = jnp.dot(low, m["dt_proj"]["kernel"].astype(dt),
                      preferred_element_type=f32)
    with jax.named_scope("ssm_scan"):
        delta = _softplus(pre + m["dt_proj"]["bias"].astype(f32))
        y = _scan(xs, delta, -jnp.exp(m["A_log"].astype(f32)), bm, cm,
                  m["D"].astype(f32))
    memory = None
    if hand_on:
        y, memory = _fan_out(y, 2)
        memory = _memory(memory, xs, z, m["D"])
    with jax.named_scope("ssm_gate"):
        y = y * jax.nn.silu(z)
    with jax.named_scope("ssm_out"):
        return x + y @ m["out_proj"]["kernel"].astype(dt), memory


def _halves(cfg: Phi4FlashConfig, q, k, v):
    """``q [B, S, n_heads·head_dim]``, ``k, v [B, S, n_kv_heads·head_dim]``
    -> ``((q1, k1), (q2, k2), v)`` as the flash calls take them: ``q_j [B,
    S, n_heads/2, head_dim]``, the half ``j`` of every query pair; ``k_j
    [B, S, n_kv_heads/2, head_dim]`` the same half of the key pairs and
    ``v [B, S, n_kv_heads/2, 2·head_dim]`` the pairs' two value heads
    joined. A key/value pair serves the ``n_heads / n_kv_heads``
    consecutive query pairs that read it inside the call (``ops/
    flash.py``: query head ``i`` reads key/value head ``i // group``);
    nothing is copied."""
    B, S, _ = q.shape
    D = cfg.head_dim
    q = q.reshape(B, S, cfg.n_heads // 2, 2, D)
    k = k.reshape(B, S, cfg.n_kv_heads // 2, 2, D)
    v = v.reshape(B, S, cfg.n_kv_heads // 2, 2 * D)
    return tuple((q[:, :, :, j], k[:, :, :, j]) for j in (0, 1)) + (v,)


def _lambda(a: Dict, init: float):
    f32 = jnp.float32
    return (jnp.exp(jnp.sum(a["lambda_q1"].astype(f32)
                            * a["lambda_k1"].astype(f32)))
            - jnp.exp(jnp.sum(a["lambda_q2"].astype(f32)
                              * a["lambda_k2"].astype(f32))) + init)


def _combine(cfg: Phi4FlashConfig, a: Dict, a1, a2, init: float):
    """``(1 − λ_init)·RMSNorm(a1 − λ a2)`` a pair: ``a1, a2 [B, S,
    n_heads/2, 2·head_dim]`` -> ``[B, S, n_heads·head_dim]``."""
    B, S, pairs, W = a1.shape
    d = (a1.astype(jnp.float32)
         - _lambda(a, init) * a2.astype(jnp.float32))
    d = rms_norm(d, a["subln"]["scale"], cfg.ln_eps) * (1.0 - init)
    return d.astype(cfg.dtype).reshape(B, S, pairs * W)


@jax.named_scope("attn")
def _attn_mixer(cfg: Phi4FlashConfig, kind: str, init: float, layer: Dict,
                x, memory: Optional[Tuple], hand_on: bool, *, attn_fn):
    """``(x + mixer, (k, v) or None)``: ``swa``, ``full`` or ``cross``
    (which reads ``memory``); the source layer hands its ``k, v`` on."""
    a, dt = layer["attn"], cfg.dtype
    q_w, kv_w = (n * cfg.head_dim for n in (cfg.n_heads, cfg.n_kv_heads))
    handed = None
    with jax.named_scope("diff_attn"):
        with jax.named_scope("diff_proj"):
            u = _norm(cfg, layer["norm_1"], x)
            if kind == "cross":
                q = (u @ a["q_proj"]["kernel"].astype(dt)
                     + a["q_proj"]["bias"].astype(dt))
                k, v = memory
            else:
                qkv = (u @ a["qkv_proj"]["kernel"].astype(dt)
                       + a["qkv_proj"]["bias"].astype(dt))
                q, k, v = (qkv[..., :q_w], qkv[..., q_w:q_w + kv_w],
                           qkv[..., q_w + kv_w:])
                if hand_on:
                    (k, k_on), (v, v_on) = _fan_out(k, 2), _fan_out(v, 2)
                    handed = (k_on, v_on)
            first, second, v = _halves(cfg, q, k, v)
        with jax.named_scope("swa_core" if kind == "swa" else "full_core"):
            a1, a2 = (attn_fn(q_j, k_j, v,
                              window=cfg.window if kind == "swa" else None)
                      for q_j, k_j in (first, second))
        with jax.named_scope("diff_combine"):
            o = _combine(cfg, a, a1, a2, init)
        with jax.named_scope("diff_proj"):
            return (x + o @ a["o_proj"]["kernel"].astype(dt)
                    + a["o_proj"]["bias"].astype(dt)), handed


@jax.named_scope("attn")
def _gmu_mixer(cfg: Phi4FlashConfig, layer: Dict, x, m):
    g, dt = layer["gmu"], cfg.dtype
    with jax.named_scope("gmu"):
        u = _norm(cfg, layer["norm_1"], x) @ g["in_proj"]["kernel"].astype(dt)
        return x + (jax.nn.silu(u) * m) @ g["out_proj"]["kernel"].astype(dt)


@jax.named_scope("mlp")
def _mlp(cfg: Phi4FlashConfig, layer: Dict, x):
    return x + swiglu(_norm(cfg, layer["norm_2"], x), layer["mlp"], cfg.dtype)


def _layer(cfg: Phi4FlashConfig, i: int, layer: Dict, x, memory, *, attn_fn):
    """Published layer ``i``: ``(x', what it hands on or None)``.
    ``memory`` is what the layer reads: ``m`` for a GMU, ``(k, v)`` for a
    cross layer, nothing for the others."""
    n = cfg.n_published_layers
    kind = layer_kind(i, n)
    hand_on = None
    if kind == "mamba":
        x, hand_on = _mamba_mixer(cfg, layer, x, hand_on=i == n // 2)
    elif kind == "gmu":
        x = _gmu_mixer(cfg, layer, x, memory)
    else:
        x, hand_on = _attn_mixer(cfg, kind, lambda_init(i), layer, x, memory,
                                 i == n // 2 + 1, attn_fn=attn_fn)
    return _mlp(cfg, layer, x), hand_on


def _readers(handed, n: int):
    """What a source layer handed on, once a later reader (a pytree of
    arrays; ``n`` tuples of it)."""
    if handed is None or n == 0:
        return []
    if n == 1:
        return [handed]
    each = [_fan_out(leaf, n) for leaf in jax.tree_util.tree_leaves(handed)]
    treedef = jax.tree_util.tree_structure(handed)
    return [treedef.unflatten([leaf[r] for leaf in each]) for r in range(n)]


def forward_hidden(cfg: Phi4FlashConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None):
    """tokens [B, S] -> final-norm hidden states [B, S, d]. ``attn_fn(q,
    k, v, window=None)`` is the local causal attention (``ops/attention.py::
    causal_attention`` by default: the flash kernels on a TPU)."""
    if attn_fn is None:
        attn_fn = causal_attention
    kinds = cfg.kinds
    x = embed(cfg, params, tokens)
    waiting = {"gmu": [], "cross": []}
    for j, (i, kind) in enumerate(zip(cfg.layer_ids, kinds)):
        run = functools.partial(_layer, cfg, i, attn_fn=attn_fn)
        if cfg.remat:
            run = jax.checkpoint(run)
        memory = waiting[kind].pop() if kind in waiting else None
        x, handed = run(params[f"layers_{j}"], x, memory)
        if handed is not None:
            reads = "gmu" if kind == "mamba" else "cross"
            waiting[reads] = _readers(handed, kinds[j + 1:].count(reads))
    return _norm(cfg, params["ln_f"], x)


def loss_terms(cfg: Phi4FlashConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``loss`` — the mean next-token cross entropy over the rows of the
    table held here, through the tied head — and the final-norm
    ``hidden`` states."""
    h = forward_hidden(cfg, params, tokens, attn_fn)
    with jax.named_scope("lm_head_xent"):
        # the head is the table: [V, d] read as [d, V]
        head = params["wte"]["embedding"].T
    return {"loss": ce_from_hidden(h, head, targets, cfg.xent_chunks),
            "hidden": h}


def loss_fn(cfg: Phi4FlashConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
