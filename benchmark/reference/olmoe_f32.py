"""Plain reference of OLMoE (Muennighoff et al., arXiv:2409.02060; the
Hugging Face ``olmoe`` implementation for the conventions): RMSNorm
before each sublayer and over the whole q and k projections, RoPE in the
``rotate_half`` convention, multi-head causal attention, and a sparse
SwiGLU MLP in which each token takes its 8 of 64 experts by softmax
router probability, NOT renormalised, with no shared expert; untied
head; no biases. Straightforward ``jax.numpy`` in float32 with matmuls
at ``highest`` precision: no kernel, no sort, no dispatch, no chunked
cross entropy, nothing imported from the program.

The sparse sublayer is written as **every expert on every token**,
weighted by an ``[N, E]`` matrix that holds the router probability where
the expert is among the token's top k and zero elsewhere (a ``lax.scan``
over the experts keeps one expert's activations in memory at a time).
That is independent of the system's sort / grouped-matmul / unsort by
construction, and it cannot drop a token.

The loss is the one the system trains on: cross entropy
+ ``lb_coef`` x sum over layers of ``E * sum_e f_e * P_e`` (``f_e`` the
share of the layer's ``k * N`` assignments that went to expert ``e``,
``P_e`` the mean router probability)
+ ``z_coef`` x sum over layers of ``mean(logsumexp(router logits)**2)``.
On the CPU ``jax.grad`` of :func:`loss` is the reference gradient.

Parameter tree as ``torchft_tpu/models/olmoe.py::init_params`` makes it.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate_half(x: Any) -> Any:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x: Any, theta: float) -> Any:
    """[B, S, H, D]: ``x * cos + rotate_half(x) * sin`` with the angle of
    position ``s`` and pair ``i`` equal to ``s * theta ** (-2i / D)``,
    the same for both halves of a head."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    return x * jnp.cos(angle) + _rotate_half(x) * jnp.sin(angle)


def _moe(h: Any, m: Dict[str, Any], top_k: int):
    """``h [N, d]`` -> (y [N, d], load-balancing term, z term, top-k mask
    [N, E])."""
    logits = h @ m["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    n_experts = probs.shape[-1]
    kth = jnp.sort(probs, axis=-1)[:, n_experts - top_k]
    chosen = probs >= kth[:, None]
    gates = jnp.where(chosen, probs, 0.0)                     # [N, E]

    def add_expert(y, args):
        gate, up, down, g = args
        return y + ((jax.nn.silu(h @ gate) * (h @ up)) @ down) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
        m["down_proj"]["kernel"], gates.T,
    ))
    share = jnp.mean(chosen.astype(jnp.float32), axis=0) / top_k
    lb = n_experts * jnp.sum(
        jax.lax.stop_gradient(share) * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, lb, z, chosen


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *, n_layer: int,
          n_head: int, top_k: int, eps: float, rope_theta: float,
          lb_coef: float, z_coef: float) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S] and its
    parts ``ce``, ``load_balance``, ``router_z``; ``logits`` [B, S, V];
    ``hidden`` [B, S, d], the final-norm states the head reads; and
    ``chosen`` [L, B*S, E], the top-k mask of every layer."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        x = p["wte"]["embedding"][tokens]
        d = x.shape[-1]
        hd = d // n_head
        causal = jnp.tril(jnp.ones((S, S), dtype=bool))

        def attend(qkv):
            q, k, v = qkv                                   # [S, H, D]
            s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
            s = jnp.where(causal[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        lb = z = jnp.zeros((), jnp.float32)
        masks = []
        for i in range(n_layer):
            layer = p[f"layers_{i}"]
            a = layer["attn"]
            h = _rms(x, layer["ln_1"]["scale"], eps)
            q = _rms(h @ a["q_proj"]["kernel"], a["q_norm"]["scale"], eps)
            k = _rms(h @ a["k_proj"]["kernel"], a["k_norm"]["scale"], eps)
            v = h @ a["v_proj"]["kernel"]
            q = _rope(q.reshape(B, S, n_head, hd), rope_theta)
            k = _rope(k.reshape(B, S, n_head, hd), rope_theta)
            v = v.reshape(B, S, n_head, hd)
            o = jax.lax.map(attend, (q, k, v))   # a sequence at a time
            x = x + o.reshape(B, S, d) @ a["o_proj"]["kernel"]
            h = _rms(x, layer["ln_2"]["scale"], eps).reshape(B * S, d)
            y, lb_i, z_i, chosen = _moe(h, layer["moe"], top_k)
            x = x + y.reshape(B, S, d)
            lb, z = lb + lb_i, z + z_i
            masks.append(chosen)
        x = _rms(x, p["ln_f"]["scale"], eps)
        logits = x @ p["lm_head"]["kernel"]
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        ce = jnp.mean(nll)
        return {
            "loss": ce + lb_coef * lb + z_coef * z, "ce": ce,
            "load_balance": lb, "router_z": z, "logits": logits,
            "hidden": x, "chosen": jnp.stack(masks),
        }


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
