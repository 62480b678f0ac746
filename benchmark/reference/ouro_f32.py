"""Plain reference of the Ouro looped language model (Hugging Face
``model_type`` ``ouro``; ByteDance/Ouro-2.6B's config; arXiv:2510.25741).
Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: no kernel, no scan over the passes, no chunked or weighted
cross entropy, nothing imported from the program.

``T`` passes over ONE stack of ``L`` layers, ``x⁽⁰⁾ = E[tokens]``; in pass
``t``, ``h ← x⁽ᵗ⁻¹⁾`` and layer by layer, the same weights every pass,

    a = Attn_l(RMSNorm(h; w¹_l));    h ← h + RMSNorm(a; w²_l)
    n = RMSNorm(h; w³_l);  m = W_d (silu(n W_g) ⊙ n W_u);
                                     h ← h + RMSNorm(m; w⁴_l)

then ``x⁽ᵗ⁾ = RMSNorm(h; w_f)``, which the next pass, the head and the gate
read. ``Attn``: ``n_head`` heads of ``head_dim``, as many key/value heads;
q and k rotated in the ``rotate_half`` pairing (lane ``i`` with lane ``i +
D/2``, position ``s`` by ``s · θ^(−2i/D)``); the causal softmax of ``q·k /
√D`` one head and one block of ``row_block`` query rows at a time; ``W_o``.

After pass ``t``: ``ℓ_t,i = lse(z_i) − z_i[target_i]`` with ``z = x⁽ᵗ⁾
W_head`` (a block of ``row_block`` rows at a time) and ``λ_t,i = σ(x⁽ᵗ⁾_i ·
w_g + b_g)``. ``S_0 = 1``, ``S_t = S_{t−1}(1 − λ_t)``; ``p_t = λ_t
S_{t−1}`` for ``t < T`` and ``p_T = S_{T−1}``. The loss is ``mean_i [Σ_t
p_t,i ℓ_t,i + β Σ_t p_t,i log p_t,i]`` (``0 log 0 = 0``).

On the CPU ``jax.grad`` of :func:`loss` is the reference gradient (a
layer's is the sum over its ``T`` uses). Parameter tree as
``torchft_tpu/models/ouro.py::init_params`` makes it; the leaf
``exit_stats`` is read by nothing here.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.scipy.special import xlogy


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotated(x: Any, theta: float) -> Any:
    """``x [B, S, H, D]`` with lanes ``i`` and ``i + D/2`` turned by ``s ·
    θ^(−2i/D)`` at position ``s``."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + half * sin


def _attention(n: Any, a: Dict[str, Any], *, n_head: int, head_dim: int,
               theta: float, row_block: int) -> Any:
    B, S, _ = n.shape
    D = head_dim
    q = _rotated((n @ a["q_proj"]["kernel"]).reshape(B, S, n_head, D), theta)
    k = _rotated((n @ a["k_proj"]["kernel"]).reshape(B, S, n_head, D), theta)
    v = (n @ a["v_proj"]["kernel"]).reshape(B, S, n_head, D)
    rows = math.gcd(S, row_block)
    keys = jnp.arange(S)

    def one_block(i: Any) -> Any:
        # one head's scores of one block of query rows: [rows, S]
        bh, blk = i // (S // rows), i % (S // rows)
        b, head = bh // n_head, bh % n_head
        first = blk * rows
        qb = jax.lax.dynamic_slice_in_dim(q[b, :, head], first, rows)
        s = (qb @ k[b, :, head].T) / math.sqrt(D)
        seen = keys[None, :] <= (first + jnp.arange(rows))[:, None]
        return jax.nn.softmax(
            jnp.where(seen, s, -jnp.inf), axis=-1) @ v[b, :, head]

    o = jax.lax.map(one_block, jnp.arange(B * n_head * (S // rows)))
    o = o.reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return o.reshape(B, S, n_head * D) @ a["o_proj"]["kernel"]


def _token_losses(x: Any, head: Any, targets: Any, row_block: int) -> Any:
    """``ℓ_i`` [N] of ``x [N, d]`` through ``head [d, V]``, a block of rows
    of logits at a time."""
    rows = math.gcd(x.shape[0], row_block)

    def one_block(xt: Any) -> Any:
        xb, tb = xt
        z = xb @ head
        return jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, tb[:, None], axis=-1)[:, 0]

    return jax.lax.map(one_block, (x.reshape(-1, rows, x.shape[-1]),
                                   targets.reshape(-1, rows))).reshape(-1)


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *,
          n_layers: int, ut_steps: int, n_head: int, head_dim: int,
          theta: float, eps: float, beta: float,
          row_block: Optional[int] = None) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S], ``nll``
    [T, N] (every pass's per-token cross entropy), ``p`` [T, N] (the exit
    distribution) and ``hidden`` [B, S, d], the last pass's normed stream.
    ``row_block``: rows of scores or logits held at a time (the whole
    sequence where ``None``)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        block = row_block or tokens.shape[1]
        x = p["wte"]["embedding"][tokens]
        d = x.shape[-1]
        nll, lam = [], []
        for _t in range(ut_steps):
            h = x
            for i in range(n_layers):
                layer = p[f"layers_{i}"]
                a = _attention(
                    _rms(h, layer["attn_norm"]["scale"], eps), layer["attn"],
                    n_head=n_head, head_dim=head_dim, theta=theta,
                    row_block=block)
                h = h + _rms(a, layer["attn_out_norm"]["scale"], eps)
                n = _rms(h, layer["mlp_norm"]["scale"], eps)
                mlp = layer["mlp"]
                m = (jax.nn.silu(n @ mlp["gate_proj"]["kernel"])
                     * (n @ mlp["up_proj"]["kernel"])
                     ) @ mlp["down_proj"]["kernel"]
                h = h + _rms(m, layer["mlp_out_norm"]["scale"], eps)
            x = _rms(h, p["ln_f"]["scale"], eps)
            flat = x.reshape(-1, d)
            nll.append(_token_losses(flat, p["lm_head"]["kernel"],
                                     targets.reshape(-1), block))
            lam.append(jax.nn.sigmoid(
                flat @ p["exit_gate"]["kernel"][:, 0]
                + p["exit_gate"]["bias"][0]))
        survive, prob = jnp.ones_like(nll[0]), []
        for t in range(ut_steps - 1):
            prob.append(lam[t] * survive)
            survive = survive * (1.0 - lam[t])
        prob.append(survive)
        nll, prob = jnp.stack(nll), jnp.stack(prob)
        loss = jnp.mean(jnp.sum(prob * nll + beta * xlogy(prob, prob), axis=0))
        return {"loss": loss, "nll": nll, "p": prob, "hidden": x}


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
