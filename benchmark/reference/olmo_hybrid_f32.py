"""Plain reference of Olmo Hybrid (Hugging Face ``model_type``
``olmo_hybrid``; allenai/Olmo-Hybrid-7B's config) for one chip's share of
a vocabulary-parallel stage: the rows of table and head the parameters
hold. Straightforward ``jax.numpy`` in float32 with matmuls at
``highest`` precision: no kernel, no chunk, no chunked cross entropy,
nothing imported from the program (``torchft_tpu/ops``, ``models``).

Every layer is, with the OLMo family's norms (OLMo 2, arXiv:2501.00656:
the sublayer's OUTPUT is normed),

    h = x + RMSNorm(mixer(x))          y = h + RMSNorm(mlp(h))

and ``layer_types[i]`` names the mixer: ``linear_attention`` the gated
delta rule (Gated DeltaNet, arXiv:2412.06464; the negative-eigenvalue
extension arXiv:2411.12537), ``full_attention`` softmax attention. A
final RMSNorm, an untied head, no bias anywhere.

Linear attention (``x`` the mixer's input, ``H`` heads of ``K`` key and
``V`` value channels): ``[q̃ ; k̃ ; ṽ] = silu(conv(x·W_qkv))``, the
convolution as its taps' shifted products, ``c_t = Σ_j w_j ⊙ x_{t-(T-1)+j}``
with zeros before the start, no bias; per head ``q = q̃ / sqrt(‖q̃‖² +
1e-6) · K^{-1/2}``, ``k = k̃ / sqrt(‖k̃‖² + 1e-6)``; ``β = 2·σ(x·W_b)``
(``linear_allow_neg_eigval``: β in (0, 2)); ``g = −exp(A_log_h) ·
softplus(x·W_a + dt_bias_h)``, ONE number a head a position; then **the
recurrence position by position** (:func:`gdn_recurrence`):

    S ← exp(g_t) S;   u = β_t (v_t − Sᵀ k_t);   S ← S + k_t uᵀ;   o_t = Sᵀ q_t

which is ``S_t = exp(g_t)(I − β_t k_t k_tᵀ) S_{t-1} + β_t k_t v_tᵀ``
multiplied out, ``S ∈ R^{K×V}`` from zero; ``y = W_o·[RMSNorm_head(o; w ∈
R^V) ⊙ silu(x·W_g)]``.

Full attention: ``q = RMSNorm(x·W_q; w_q)``, ``k = RMSNorm(x·W_k; w_k)``
over the WHOLE projection (OLMo 2's QK-norm: one weight as wide as the
model) before the heads are split, ``v = x·W_v``; NOTHING IS ROTATED
(``rope_parameters.rope_theta`` null) unless ``rope_theta`` is a number
(the key's other reading); the causal softmax of ``q·k /
sqrt(D)`` one head at a time, in blocks of ``row_block`` query rows where
asked; ``·W_o``.

MLP: ``(silu(h·W_gate) ⊙ h·W_up)·W_down``.

On the CPU ``jax.grad`` of :func:`loss` is the reference gradient.

Departures from the published description, each also in the
configuration file: the share (rows ``0 … V_held`` of table and head;
ids, logits and the loss over the slice); the three projections as ONE
matrix ``[q ; k ; v]`` and their three convolutions as one over its
channels (the same numbers); the sizes the config has no key for
(``assumed``). Parameter tree as
``torchft_tpu/models/olmo_hybrid.py::init_params`` makes it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _swiglu(h: Any, m: Dict[str, Any]) -> Any:
    return (jax.nn.silu(h @ m["gate_proj"]["kernel"])
            * (h @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]


def conv_silu(x: Any, taps: Any) -> Any:
    """``x [B, S, C]``, ``taps [T, C]`` -> ``silu(Σ_j taps_j ⊙ x_{t-(T-1)+j})``,
    zeros before the start."""
    T = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (T - 1, 0), (0, 0)))
    S = x.shape[1]
    return jax.nn.silu(sum(taps[j] * padded[:, j:j + S] for j in range(T)))


def gdn_step(S: Any, at: Any) -> Any:
    """One position of the recurrence: the state ``S [B, H, K, V]`` that
    enters and ``(q_t, k_t [B, H, K], v_t [B, H, V], g_t, β_t [B, H])``
    -> the state that leaves and ``o_t [B, H, V]``."""
    qt, kt, vt, gt, bt = at
    S = S * jnp.exp(gt)[..., None, None]
    u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
    S = S + kt[..., None] * u[..., None, :]
    return S, jnp.einsum("bhkv,bhk->bhv", S, qt)


def gdn_recurrence(q: Any, k: Any, v: Any, g: Any, beta: Any) -> Any:
    """``q, k [B, S, H, K]``, ``v [B, S, H, V]``, ``g, beta [B, S, H]``
    -> ``o [B, S, H, V]``: :func:`gdn_step` one position after the other,
    the state ``[B, H, K, V]`` from zero."""
    with jax.default_matmul_precision("highest"):
        B, _, H, K = q.shape
        _, o = jax.lax.scan(
            gdn_step, jnp.zeros((B, H, K, v.shape[3]), jnp.float32),
            tuple(jnp.moveaxis(z.astype(jnp.float32), 1, 0)
                  for z in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1)


def _l2(x: Any) -> Any:
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _linear(x: Any, m: Dict[str, Any], *, n_head: int, key_dim: int,
            value_dim: int, eps: float) -> Any:
    B, S, _ = x.shape
    H, K, V = n_head, key_dim, value_dim
    qkv = conv_silu(x @ m["qkv_proj"]["kernel"], m["conv"]["kernel"])
    q = qkv[..., :H * K].reshape(B, S, H, K)
    k = qkv[..., H * K:2 * H * K].reshape(B, S, H, K)
    v = qkv[..., 2 * H * K:].reshape(B, S, H, V)
    g = -jnp.exp(m["A_log"]) * jax.nn.softplus(
        x @ m["a_proj"]["kernel"] + m["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(x @ m["b_proj"]["kernel"])
    o = gdn_recurrence(_l2(q) * K ** -0.5, _l2(k), v, g, beta)
    gate = jax.nn.silu(x @ m["g_proj"]["kernel"]).reshape(B, S, H, V)
    y = _rms(o, m["o_norm"]["scale"], eps) * gate
    return y.reshape(B, S, H * V) @ m["o_proj"]["kernel"]


def _rotate(x: Any, theta: float) -> Any:
    """Rotary embedding over ``[B, S, H, D]`` in the half-split
    convention (the other reading of ``rope_parameters``)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = (jnp.concatenate([f(angle)] * 2, axis=-1)[None, :, None, :]
                for f in (jnp.cos, jnp.sin))
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + half * sin


def _full(x: Any, a: Dict[str, Any], *, n_head: int, eps: float,
          row_block: Optional[int], rope_theta: Optional[float]) -> Any:
    B, S, d = x.shape
    D = d // n_head
    q = _rms(x @ a["q_proj"]["kernel"], a["q_norm"]["scale"], eps)
    k = _rms(x @ a["k_proj"]["kernel"], a["k_norm"]["scale"], eps)
    q, k, v = (z.reshape(B, S, n_head, D)
               for z in (q, k, x @ a["v_proj"]["kernel"]))
    if rope_theta is not None:
        q, k = _rotate(q, rope_theta), _rotate(k, rope_theta)
    rows = row_block or S
    assert S % rows == 0, (S, rows)
    at = jnp.arange(S)

    def one_head(qkv):
        qh, kh, vh = qkv                                  # [S, D]

        def block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, rows)
            s = (qb @ kh.T) / jnp.sqrt(float(D))
            seen = at[None, :] <= (start + jnp.arange(rows))[:, None]
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ vh

        return jax.lax.map(block, jnp.arange(0, S, rows)).reshape(S, D)

    def heads(x4):                                        # -> [B*H, S, D]
        return x4.transpose(0, 2, 1, 3).reshape(B * n_head, S, D)

    o = jax.lax.map(one_head, (heads(q), heads(k), heads(v)))
    o = o.reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return o.reshape(B, S, d) @ a["o_proj"]["kernel"]


def cross_entropy(hidden: Any, head: Any, targets: Any) -> Any:
    """Mean next-token cross entropy over the rows ``head`` holds."""
    logits = hidden @ head
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *,
          layer_types: Sequence[str], n_head: int, key_dim: int,
          value_dim: int, eps: float, row_block: Optional[int] = None,
          rope_theta: Optional[float] = None) -> Dict[str, Any]:
    """tokens, targets [B, S] -> ``hidden`` (the final-norm hidden states
    [B, S, d]) and ``loss``, in float32."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        x = p["wte"]["embedding"][tokens]
        for i, kind in enumerate(layer_types):
            layer = p[f"layers_{i}"]
            w = layer["post_attn_norm"]["scale"]
            if kind == LINEAR:
                x = x + _rms(_linear(
                    x, layer["gdn"], n_head=n_head, key_dim=key_dim,
                    value_dim=value_dim, eps=eps), w, eps)
            else:
                assert kind == FULL, kind
                x = x + _rms(_full(
                    x, layer["attn"], n_head=n_head, eps=eps,
                    row_block=row_block, rope_theta=rope_theta), w, eps)
            x = x + _rms(_swiglu(x, layer["mlp"]),
                         layer["post_mlp_norm"]["scale"], eps)
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        return {"hidden": hidden,
                "loss": cross_entropy(hidden, p["lm_head"]["kernel"], targets)}


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
