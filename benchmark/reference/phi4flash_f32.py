"""Plain reference of Phi-4-mini-flash-reasoning (Hugging Face
``model_type`` ``phi4flash``; SambaY, arXiv:2507.06607) for one chip's
share of a vocabulary-parallel stage: the layers ``layer_ids`` names and
the rows of the one table the parameters hold. Straightforward
``jax.numpy`` in float32 with matmuls at ``highest`` precision: no
kernel, no checkpoint, no chunked cross entropy, nothing imported from
the program.

``LN`` is LayerNorm with scale and bias. Layer ``i`` (the PUBLISHED
index, of ``n``): ``h = x + mixer_i(LN1(x))``, ``x' = h + W_down(silu(g) ⊙
u)`` with ``g, u = W_gate LN2(h), W_up LN2(h)``. No position embedding, a
final LN, the head is the table.

Mamba-1 (even ``i <= n/2``): ``[x̃ ; z] = W_in u``; the convolution as
``K`` shifted products with bias, then silu; ``[δ ; B ; C] = W_x x̃``; ``Δ =
softplus(W_dt δ + b_dt)``; ``A = −exp(A_log)``; the recurrence POSITION BY
POSITION (``lax.scan`` over a ``[d_inner, N]`` state a sequence):
``S_t = exp(Δ_t A) ⊙ S_{t−1} + (Δ_t x̃_t) ⊗ B_t``, ``y_t = S_t C_t + D ⊙
x̃_t``; out ``W_out (y ⊙ silu(z))``. Layer ``n/2`` hands ``m = y`` on.

Differential attention (odd ``i < n/2`` under a window of ``window``
keys; ``i = n/2 + 1`` full, handing ``k, v`` on; odd ``i >= n/2 + 3``
``q`` alone over the handed-on ``k, v``): query pair ``p`` = heads ``(2p,
2p + 1)``, key pair ``r = p // (n_head / n_kv)`` likewise, ``v_r`` the
pair's two value heads joined; scores and an EXPLICIT ``[S, S]`` mask,
one pair at a time (a block of rows at a time under ``row_block``: the
same numbers, ``[rows, S]`` of scores alive); ``λ = exp(λ_q1·λ_k1) −
exp(λ_q2·λ_k2) + λ_init``, ``λ_init = 0.8 − 0.6 exp(−0.3 i)``; ``o_p = (1
− λ_init) RMSNorm(a1_p − λ a2_p)``; ``W_o [o_0 …] + b_o``.

GMU (even ``i >= n/2 + 2``): ``W_out (silu(W_in u) ⊙ m)``.

``loss`` = the mean next-token cross entropy over the rows held. On the
CPU ``jax.vjp`` of :func:`loss` is the reference gradient (the table's is
the sum of both uses, and ``m``'s, ``k``'s and ``v``'s the sums over
their readers, by the chain rule alone).

Departures from the published description, each also in the
configuration file: the share (the vocabulary's rows held; 6 of 32
layers); ``gate_up`` as its two halves ``gate_proj`` / ``up_proj`` (two
leaves of the parameter tree, the same numbers); nothing else.

Parameter tree as ``torchft_tpu/models/phi4flash.py::init_params`` makes
it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp


def _ln(x: Any, p: Dict, eps: float) -> Any:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _kind(i: int, n: int) -> str:
    half = n // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gmu"
    if i < half:
        return "swa"
    return "full" if i == half + 1 else "cross"


def causal_conv(x: Any, taps: Any, bias: Any) -> Any:
    """``bias + Σ_j taps[j] ⊙ x[t − (K−1) + j]``, zeros before the start:
    ``x [B, S, C]``, ``taps [K, C]``."""
    K, S = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return bias + sum(taps[j] * padded[:, j:j + S] for j in range(K))


def selective_scan(x: Any, delta: Any, a: Any, bm: Any, cm: Any,
                   d: Any) -> Any:
    """The Mamba-1 recurrence, one position at a time: ``x, delta [B, S,
    C]``, ``a [C, N]``, ``bm, cm [B, S, N]``, ``d [C]`` -> ``y [B, S,
    C]``."""
    def step(state, at):
        xt, dt, bt, ct = at                     # [B, C] [B, C] [B, N] [B, N]
        state = (jnp.exp(dt[..., None] * a) * state
                 + (dt * xt)[..., None] * bt[:, None, :])
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), jnp.float32),
        tuple(jnp.moveaxis(z, 1, 0) for z in (x, delta, bm, cm)))
    return jnp.moveaxis(y, 0, 1) + d * x


def _mamba(m: Dict, u: Any, state: int, rank: int):
    """``(mixer's output, y)``."""
    xz = u @ m["in_proj"]["kernel"]
    di = xz.shape[-1] // 2
    xs = jax.nn.silu(causal_conv(xz[..., :di], m["conv"]["kernel"],
                                 m["conv"]["bias"]))
    dbc = xs @ m["x_proj"]["kernel"]
    delta = jax.nn.softplus(
        dbc[..., :rank] @ m["dt_proj"]["kernel"] + m["dt_proj"]["bias"])
    y = selective_scan(xs, delta, -jnp.exp(m["A_log"]),
                       dbc[..., rank:rank + state], dbc[..., rank + state:],
                       m["D"])
    return (y * jax.nn.silu(xz[..., di:])) @ m["out_proj"]["kernel"], y


def _softmax_rows(q: Any, k: Any, v: Any, window: Optional[int],
                  row_block: Optional[int]) -> Any:
    """``softmax(q kᵀ / sqrt(D) + mask) v`` for one head: ``q, k [B, S,
    D]``, ``v [B, S, Dv]``."""
    S, D = q.shape[1], q.shape[2]
    cols = jnp.arange(S)

    def rows(q_rows, first):
        at = first + jnp.arange(q_rows.shape[1])
        seen = at[:, None] >= cols[None, :]
        if window is not None:
            seen &= at[:, None] - cols[None, :] < window
        s = jnp.einsum("bqd,bkd->bqk", q_rows, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v)

    if row_block is None or row_block >= S:
        return rows(q, 0)
    blocks = q.reshape(q.shape[0], S // row_block, row_block, D)
    out = jax.lax.map(
        lambda t: rows(t[0], t[1]),
        (jnp.moveaxis(blocks, 1, 0), jnp.arange(S // row_block) * row_block))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[0], S, v.shape[-1])


def _differential(a: Dict, q: Any, k: Any, v: Any, i: int, n_head: int,
                  n_kv: int, window: Optional[int], eps: float,
                  row_block: Optional[int]) -> Any:
    """``q [B, S, n_head·D]``, ``k, v [B, S, n_kv·D]`` -> the mixer's
    output before ``W_o``, ``[B, S, n_head·D]``."""
    B, S, _ = q.shape
    D = q.shape[-1] // n_head
    q = q.reshape(B, S, n_head // 2, 2, D)
    k = k.reshape(B, S, n_kv // 2, 2, D)
    v = v.reshape(B, S, n_kv // 2, 2 * D)
    init = 0.8 - 0.6 * math.exp(-0.3 * i)
    lam = (jnp.exp(jnp.sum(a["lambda_q1"] * a["lambda_k1"]))
           - jnp.exp(jnp.sum(a["lambda_q2"] * a["lambda_k2"])) + init)
    out = []
    for p in range(n_head // 2):
        r = p // (n_head // n_kv)
        a1, a2 = (_softmax_rows(q[:, :, p, j], k[:, :, r, j], v[:, :, r],
                                window, row_block) for j in (0, 1))
        diff = a1 - lam * a2
        rms = jnp.sqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + eps)
        out.append(diff / rms * a["subln"]["scale"] * (1.0 - init))
    return jnp.concatenate(out, axis=-1)


def hidden_states(params: Dict, tokens: Any, *, layer_ids: Sequence[int],
                  n_layers: int, n_head: int, n_kv: int, window: int,
                  state: int, rank: int, eps: float,
                  row_block: Optional[int] = None) -> Any:
    """tokens [B, S] -> final-norm hidden states [B, S, d], float32.
    ``n_layers`` is the PUBLISHED depth; ``row_block`` computes attention a
    block of rows at a time (the cell's sequences: ``[S, S]`` scores of a
    pair would be 268 MB a sequence)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)
        x = p["wte"]["embedding"][tokens]
        memory = kv = None
        for j, i in enumerate(layer_ids):
            layer, kind = p[f"layers_{j}"], _kind(i, n_layers)
            u = _ln(x, layer["norm_1"], eps)
            if kind == "mamba":
                mixed, y = _mamba(layer["ssm"], u, state, rank)
                if i == n_layers // 2:
                    memory = y
            elif kind == "gmu":
                g = layer["gmu"]
                mixed = ((jax.nn.silu(u @ g["in_proj"]["kernel"]) * memory)
                         @ g["out_proj"]["kernel"])
            else:
                a = layer["attn"]
                if kind == "cross":
                    q = u @ a["q_proj"]["kernel"] + a["q_proj"]["bias"]
                    k, v = kv
                else:
                    qkv = u @ a["qkv_proj"]["kernel"] + a["qkv_proj"]["bias"]
                    q_w = a["o_proj"]["kernel"].shape[0]
                    kv_w = (qkv.shape[-1] - q_w) // 2
                    q, k, v = (qkv[..., :q_w], qkv[..., q_w:q_w + kv_w],
                               qkv[..., q_w + kv_w:])
                    if kind == "full":
                        kv = (k, v)
                mixed = _differential(
                    a, q, k, v, i, n_head, n_kv,
                    window if kind == "swa" else None, eps, row_block,
                ) @ a["o_proj"]["kernel"] + a["o_proj"]["bias"]
            h = x + mixed
            m = layer["mlp"]
            n2 = _ln(h, layer["norm_2"], eps)
            x = h + ((jax.nn.silu(n2 @ m["gate_proj"]["kernel"])
                      * (n2 @ m["up_proj"]["kernel"]))
                     @ m["down_proj"]["kernel"])
        return _ln(x, p["ln_f"], eps)


def cross_entropy(hidden: Any, table: Any, targets: Any) -> Any:
    """Mean cross entropy of ``hidden·tableᵀ`` against ``targets``."""
    with jax.default_matmul_precision("highest"):
        logits = hidden @ table.astype(jnp.float32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, targets[..., None], axis=-1))


def terms(params: Dict, tokens: Any, targets: Any, **dims: Any
          ) -> Dict[str, Any]:
    """``hidden`` and ``loss`` of one batch."""
    hidden = hidden_states(params, tokens, **dims)
    return {"hidden": hidden,
            "loss": cross_entropy(hidden, params["wte"]["embedding"],
                                  targets)}


def loss(params: Dict, tokens: Any, targets: Any, **dims: Any) -> Any:
    return terms(params, tokens, targets, **dims)["loss"]
