"""Plain reference of Granite 4.0-H (Hugging Face ``model_type``
``granitemoehybrid`` with no experts; ibm-granite/granite-4.0-h-micro's
config) for one chip's share of a vocabulary-parallel stage: the rows of
the ONE tied table the parameters hold, every mixer and MLP whole.
Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: no kernel, no chunked scan, no chunked cross entropy, nothing
imported from the program.

``x_0 = embedding_multiplier · E[tokens]``. Layer ``l``:

    n = RMSNorm(x; w_in);    x <- x + r · mixer_l(n)
    n' = RMSNorm(x; w_post); x <- x + r · W_down (silu(n' W_gate) ⊙ n' W_up)

with ``r = residual_multiplier`` and the mixer the entry of
``layer_types`` at the layer's place.

``mamba``. ``[z ; xBC ; δ] = n·W_in`` (widths ``I = H·P``, ``I + 2·G·N``,
``H``). The convolution as ``K`` shifted sums: ``xBC_t <- silu(b + Σ_j
w_j ⊙ xBC_{t-(K-1)+j})``, zeros before the start. ``xBC -> x [H, P] ; B
[G, N] ; C [G, N]``, head ``h`` reads group ``h // (H/G)`` (one group: every
head the same ``B`` and ``C``); ``Δ = softplus(δ + dt_bias)``, ``A =
-exp(A_log)``. **The recurrence itself, position by position** (a
``lax.scan`` over ``t`` with the ``[B, H, P, N]`` state):

    S_t = exp(Δ_t A)·S_{t-1} + Δ_t · x_t ⊗ B_t,    y_t = S_t·C_t + D·x_t.

``y <- RMSNorm(y ⊙ silu(z))`` over each of the ``G`` runs of ``I/G``
channels alone (the gate FIRST), times the norm's weight; ``·W_out``.

``attention``. ``q`` -> ``n_head`` heads, ``k, v`` -> ``n_kv`` heads; query
head ``i`` reads key/value head ``i // (n_head / n_kv)``; NO rotation;
the causal softmax of ``attention_multiplier · q·k`` one head and one
block of ``row_block`` query rows at a time; ``·W_o``.

``hidden = RMSNorm(x_L; w_f)``; ``logits = hidden·Eᵀ / logits_scaling``;
``loss`` = the mean next-token cross entropy over the rows of the table
held. On the CPU ``jax.grad`` of :func:`loss` is the reference gradient
(the table's is the sum of both its uses).

Parameter tree as ``torchft_tpu/models/granite_hybrid.py::init_params``
makes it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def recurrence(x: Any, delta: Any, A: Any, Bm: Any, Cm: Any, D: Any) -> Any:
    """The state-space recurrence itself, position by position: ``x [B, S,
    H, P]``, ``delta [B, S, H]``, ``A [H]``, ``Bm, Cm [B, S, G, N]`` (head
    ``h`` reads group ``h // (H/G)``), ``D [H]`` -> ``y [B, S, H, P]``,

        S_t = exp(Δ_t A)·S_{t-1} + Δ_t · x_t ⊗ B_t,    y_t = S_t·C_t + D·x_t,

    a ``lax.scan`` over ``t`` with the ``[B, G, H/G, P, N]`` state from
    zero (a group's ``B_t`` and ``C_t`` broadcast over its heads: no copy a
    head). The positions are taken in stretches of up to 64 behind a
    ``jax.checkpoint``, which changes no value: a ``jax.vjp`` of this
    function then keeps a state a stretch and not one a position."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    x5 = x.reshape(B, S, G, R, P)
    d5 = delta.reshape(B, S, G, R)
    A5 = A.reshape(G, R)

    def position(S_prev, at):
        x_t, d_t, b_t, c_t = at      # [B,G,R,P] [B,G,R] [B,G,N] [B,G,N]
        S_t = (jnp.exp(d_t * A5)[..., None, None] * S_prev
               + (d_t[..., None] * x_t)[..., :, None]
               * b_t[:, :, None, None, :])
        return S_t, jnp.sum(S_t * c_t[:, :, None, None, :], axis=-1)

    @jax.checkpoint
    def stretch(S_prev, ats):
        return jax.lax.scan(position, S_prev, ats)

    n = math.gcd(S, 64)
    _, y = jax.lax.scan(            # sums and products only: no matmul
        stretch, jnp.zeros((B, G, R, P, N), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0).reshape(S // n, n, *a.shape[:1],
                                           *a.shape[2:])
              for a in (x5, d5, Bm, Cm)))
    y = jnp.moveaxis(y.reshape(S, B, H, P), 0, 1)
    return y + D[:, None] * x


def _mamba(n: Any, m: Dict[str, Any], *, heads: int, head_dim: int,
           groups: int, state: int, eps: float) -> Any:
    B, S, _ = n.shape
    H, P, G, N = heads, head_dim, groups, state
    inner = H * P
    proj = n @ m["in_proj"]["kernel"]
    z = proj[..., :inner]
    xbc = proj[..., inner:2 * inner + 2 * G * N]
    dt = proj[..., 2 * inner + 2 * G * N:]
    taps = m["conv"]["kernel"]                               # [K, channels]
    K = taps.shape[0]
    conv = jnp.zeros_like(xbc) + m["conv"]["bias"]
    for j in range(K):
        back = K - 1 - j               # tap j reads the position `back` ago
        shifted = jnp.concatenate(
            [jnp.zeros_like(xbc[:, :back]), xbc[:, :S - back]], axis=1)
        conv = conv + taps[j] * shifted
    xbc = jax.nn.silu(conv)
    y = recurrence(
        xbc[..., :inner].reshape(B, S, H, P),
        jax.nn.softplus(dt + m["dt_bias"]), -jnp.exp(m["A_log"]),
        xbc[..., inner:inner + G * N].reshape(B, S, G, N),
        xbc[..., inner + G * N:].reshape(B, S, G, N), m["D"])
    gated = (y.reshape(B, S, inner) * jax.nn.silu(z)).reshape(
        B, S, G, inner // G)
    normed = gated / jnp.sqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return (normed.reshape(B, S, inner) * m["norm"]["scale"]) @ m[
        "out_proj"]["kernel"]


def _attention(n: Any, a: Dict[str, Any], *, n_head: int, n_kv: int,
               head_dim: int, scale: float, row_block: int) -> Any:
    B, S, _ = n.shape
    D = head_dim
    q = (n @ a["q_proj"]["kernel"]).reshape(B, S, n_head, D)
    k = (n @ a["k_proj"]["kernel"]).reshape(B, S, n_kv, D)
    v = (n @ a["v_proj"]["kernel"]).reshape(B, S, n_kv, D)
    serves = n_head // n_kv
    rows = math.gcd(S, row_block)
    keys = jnp.arange(S)

    def one_block(i: Any) -> Any:
        # one head's scores of one block of query rows: [rows, S]
        bh, blk = i // (S // rows), i % (S // rows)
        b, head = bh // n_head, bh % n_head
        first = blk * rows
        qb = jax.lax.dynamic_slice_in_dim(q[b, :, head], first, rows)
        s = scale * (qb @ k[b, :, head // serves].T)
        seen = keys[None, :] <= (first + jnp.arange(rows))[:, None]
        return jax.nn.softmax(
            jnp.where(seen, s, -jnp.inf), axis=-1) @ v[b, :, head // serves]

    o = jax.lax.map(one_block, jnp.arange(B * n_head * (S // rows)))
    o = o.reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return o.reshape(B, S, n_head * D) @ a["o_proj"]["kernel"]


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *,
          layer_types: Sequence[str], ssm_heads: int, ssm_head_dim: int,
          ssm_groups: int, ssm_state: int, n_head: int, n_kv: int,
          head_dim: int, embedding_multiplier: float,
          residual_multiplier: float, attention_multiplier: float,
          logits_scaling: float, eps: float,
          row_block: Optional[int] = None) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S] and
    ``hidden`` [B, S, d], the final-norm states the head reads.
    ``row_block``: query rows the attention's scores hold at a time (the
    whole sequence where ``None``)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        table = p["wte"]["embedding"]
        x = embedding_multiplier * table[tokens]
        for i, kind in enumerate(layer_types):
            layer = p[f"layers_{i}"]
            n = _rms(x, layer["norm"]["scale"], eps)
            if kind == "mamba":
                m = _mamba(n, layer["mamba"], heads=ssm_heads,
                           head_dim=ssm_head_dim, groups=ssm_groups,
                           state=ssm_state, eps=eps)
            elif kind == "attention":
                m = _attention(n, layer["attn"], n_head=n_head, n_kv=n_kv,
                               head_dim=head_dim, scale=attention_multiplier,
                               row_block=row_block or tokens.shape[1])
            else:
                raise ValueError(f"no mixer {kind!r}")
            x = x + residual_multiplier * m
            n = _rms(x, layer["post_norm"]["scale"], eps)
            mlp = layer["mlp"]
            x = x + residual_multiplier * (
                (jax.nn.silu(n @ mlp["gate_proj"]["kernel"])
                 * (n @ mlp["up_proj"]["kernel"]))
                @ mlp["down_proj"]["kernel"])
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        logits = (hidden @ table.T) / logits_scaling
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
        return {"loss": ce, "hidden": hidden}


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
