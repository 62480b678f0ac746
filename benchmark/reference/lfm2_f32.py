"""Plain reference of LFM2-MoE (Hugging Face ``model_type`` ``lfm2_moe``;
LiquidAI/LFM2-8B-A1B's config) for one chip's share of an expert-parallel
layer: experts ``first_expert .. first_expert + E_held`` of each expert
layer and the rows of the one table the parameters hold. Straightforward
``jax.numpy`` in float32 with matmuls at ``highest`` precision: no
kernel, no sort, no dispatch, no chunked cross entropy, nothing imported
from the program.

Every layer is ``h = x + mixer(RMSNorm(x))``, ``y = h + mlp(RMSNorm(h))``;
``layer_types[i]`` names the mixer, ``i < n_dense`` the MLP.

``conv``: ``[B ; C ; X] = n·W_in``; ``u = B ⊙ X``; the convolution as
``K`` shifted products, ``v_t = Σ_j w_j ⊙ u_{t-(K-1)+j}`` with zeros
before the start, no bias, no activation; ``(C ⊙ v)·W_out``.

``full_attention``: ``q`` -> ``n_head`` heads, ``k, v`` -> ``n_kv``
heads; an RMSNorm with a ``head_dim``-wide weight on every q and every k
head; RoPE ``theta`` over the whole head in the ``rotate_half`` form
(``x·cos + [-x_2 ; x_1]·sin``, angle ``t · theta^(-i / (D/2))`` for both
halves' channel ``i``); query head ``i`` reads key/value head ``i //
(n_head / n_kv)``; the full ``[S, S]`` causal softmax of ``q·k /
sqrt(D)`` one head at a time; ``·W_o``.

Dense MLP: ``(silu(n·W_gate) ⊙ n·W_up)·W_down``.

Expert MLP, written as **every held expert on every token**, weighted by
an ``[N, E_held]`` matrix that is zero outside ``sel ∩ held``: ``s =
sigmoid(n·W_r)``; ``sel`` = the ``top_k`` largest of ``s + b``; ``g_e =
routed_scale · s_e / (Σ_sel s + 1e-6)``; ``y = Σ g_e · SwiGLU_e(n)``.
What the absent experts would add is left out, and that partial result
goes on.

The head is the table: ``logits = hidden·Eᵀ``; ``loss`` = the mean
next-token cross entropy over the rows held. On the CPU ``jax.grad`` of
:func:`loss` is the reference gradient (the table's is the sum of both
uses, by the chain rule alone).

Departures from the published description, each also in the
configuration file: the share (absent experts' part left out; the
vocabulary's rows held); the renormalisation's 1e-6 is the published
one (the program passes it to ``top_k_routing``, whose default is
1e-20); nothing else.

Parameter tree as ``torchft_tpu/models/lfm2.py::init_params`` makes it.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

RENORM_EPS = 1e-6


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _swiglu(h: Any, gate: Any, up: Any, down: Any) -> Any:
    a = h @ gate
    return (a * jax.nn.sigmoid(a) * (h @ up)) @ down


def short_conv(bcx: Any, taps: Any) -> Any:
    """``C ⊙ conv(B ⊙ X)``: ``bcx [B, S, 3C]`` (``[B ; C ; X]``), ``taps
    [K, C]`` -> ``[B, S, C]``; tap ``j`` reads the position ``K-1-j``
    ago, zeros before the start."""
    S = bcx.shape[1]
    K, C = taps.shape
    b, c, x = bcx[..., :C], bcx[..., C:2 * C], bcx[..., 2 * C:]
    u = b * x
    v = jnp.zeros_like(u)
    for j in range(K):
        back = K - 1 - j
        v = v + taps[j] * jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u[:, :S - back]], axis=1)
    return c * v


def _conv(n: Any, m: Dict[str, Any]) -> Any:
    return short_conv(n @ m["in_proj"]["kernel"],
                      m["conv"]["kernel"]) @ m["out_proj"]["kernel"]


def _rotary(x: Any, theta: float) -> Any:
    """``x [B, S, H, D]``: position ``t``'s channels ``i`` and ``i + D/2``
    turn by ``t · theta^(-2i / D)``."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def _attention(n: Any, a: Dict[str, Any], *, n_head: int, n_kv: int,
               head_dim: int, theta: float, eps: float) -> Any:
    B, S, _ = n.shape
    D = head_dim
    q = (n @ a["q_proj"]["kernel"]).reshape(B, S, n_head, D)
    k = (n @ a["k_proj"]["kernel"]).reshape(B, S, n_kv, D)
    v = (n @ a["v_proj"]["kernel"]).reshape(B, S, n_kv, D)
    q = _rotary(_rms(q, a["q_norm"]["scale"], eps), theta)
    k = _rotary(_rms(k, a["k_norm"]["scale"], eps), theta)
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    serves = n_head // n_kv

    def one_head(i: Any) -> Any:
        b, head = i // n_head, i % n_head
        kv = head // serves
        s = (q[b, :, head] @ k[b, :, kv].T) / jnp.sqrt(float(D))
        return jax.nn.softmax(
            jnp.where(causal, s, -jnp.inf), axis=-1) @ v[b, :, kv]

    # one [S, S] score matrix at a time: 8192 fits beside a training state
    o = jax.lax.map(one_head, jnp.arange(B * n_head))        # [B*H, S, D]
    o = o.reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return o.reshape(B, S, n_head * D) @ a["o_proj"]["kernel"]


def _experts(n: Any, m: Dict[str, Any], *, top_k: int, first_expert: int,
             routed_scale: float, use: Any = None) -> Tuple[Any, Any]:
    """``n [N, d]`` -> (y [N, d], the top-k mask [N, E_routed]). With
    ``use`` (a mask of the same shape) the layer is computed on THAT
    selection — the weights are still this function's own scores — and
    the mask returned is still this function's own choice."""
    s = jax.nn.sigmoid(n @ m["router"]["kernel"])
    biased = s + m["balance_bias"]
    n_routed = s.shape[-1]
    kth = jnp.sort(biased, axis=-1)[:, n_routed - top_k]
    chosen = biased >= kth[:, None]
    gates = jnp.where(chosen if use is None else use, s, 0.0)
    gates = routed_scale * gates / (
        jnp.sum(gates, axis=-1, keepdims=True) + RENORM_EPS)
    n_held = m["up_proj"]["kernel"].shape[0]
    held = gates[:, first_expert:first_expert + n_held]       # [N, E_held]

    def add_expert(y, args):
        gate, up, down, g = args
        return y + _swiglu(n, gate, up, down) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(n), (
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
        m["down_proj"]["kernel"], held.T))
    return y, chosen


def cross_entropy(hidden: Any, table: Any, targets: Any) -> Any:
    """The mean cross entropy of ``targets`` under the tied head:
    ``logits = hidden·tableᵀ``."""
    with jax.default_matmul_precision("highest"):
        logits = hidden @ table.astype(jnp.float32).T
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        return -jnp.mean(
            jnp.take_along_axis(logp, targets[..., None], axis=-1))


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *,
          layer_types: Sequence[str], n_dense: int, n_head: int, n_kv: int,
          head_dim: int, theta: float, top_k: int, first_expert: int,
          routed_scale: float, eps: float,
          selection: Any = None) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S];
    ``hidden`` [B, S, d], the final-norm states the head reads;
    ``chosen`` [L_e, B*S, E_routed], the top-k mask of every expert layer
    in order. ``selection`` (the same shape), where given, is the
    selection every expert layer is computed on in place of its own: the
    cell's check hands over the system's, so that a near-tie that rounds
    the other way in bf16 is COUNTED (``chosen`` is still the reference's
    own choice, on the stream that selection gave) and does not reach,
    through the convolution's and attention's memory, the tokens that
    follow it."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        table = p["wte"]["embedding"]
        x = table[tokens]
        masks = []
        for i, kind in enumerate(layer_types):
            layer = p[f"layers_{i}"]
            n = _rms(x, layer["norm_1"]["scale"], eps)
            if kind == "conv":
                x = x + _conv(n, layer["conv"])
            elif kind == "full_attention":
                x = x + _attention(n, layer["attn"], n_head=n_head,
                                   n_kv=n_kv, head_dim=head_dim, theta=theta,
                                   eps=eps)
            else:
                raise ValueError(f"no mixer {kind!r}")
            n = _rms(x, layer["norm_2"]["scale"], eps)
            if i < n_dense:
                m = layer["mlp"]
                x = x + _swiglu(n, m["gate_proj"]["kernel"],
                                m["up_proj"]["kernel"],
                                m["down_proj"]["kernel"])
            else:
                y, chosen = _experts(
                    n.reshape(B * S, -1), layer["moe"], top_k=top_k,
                    first_expert=first_expert, routed_scale=routed_scale,
                    use=None if selection is None else selection[len(masks)])
                x = x + y.reshape(x.shape)
                masks.append(chosen)
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        out = {"loss": cross_entropy(hidden, table, targets),
               "hidden": hidden}
        if masks:
            out["chosen"] = jnp.stack(masks)
        return out


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
