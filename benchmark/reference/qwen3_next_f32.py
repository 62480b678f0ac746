"""Plain reference of Qwen3-Next (Hugging Face ``model_type``
``qwen3_next``; Qwen/Qwen3-Next-80B-A3B-Instruct's config) for one chip's
share of an expert-parallel layer: experts ``first_expert .. first_expert
+ E_held`` of each layer and the rows of the table and the head the
parameters hold. Straightforward ``jax.numpy`` in float32 with matmuls at
``highest`` precision: no kernel, no chunk, no sort, no dispatch, no
chunked cross entropy, nothing imported from the program.

``N(x; w) = x / sqrt(mean(x²) + eps) · (1 + w)`` (the zero-centred norm).
Layer ``l`` (``x [B, S, d]``, no bias anywhere):

    h = x + mixer_l(N(x; w1))        y = h + moe(N(h; w2))

Linear attention (``layer_types[l]`` ``linear_attention``; ``H_k`` key
heads, ``H_v`` value heads of ``K`` and ``V`` channels, ``r = H_v /
H_k``): ``[q̃ ; k̃ ; ṽ ; z] = n·W_qkvz``, ``[b ; a] = n·W_ba``; ``[q̂ ; k̂ ;
v] = silu(conv([q̃ ; k̃ ; ṽ]))``, the convolution as its taps' shifted
products, ``c_t = Σ_j w_j ⊙ x_{t-(T-1)+j}`` with zeros before the start;
a key head ``q = q̂ / sqrt(‖q̂‖² + 1e-6) · K^{-1/2}``, ``k = k̂ / sqrt(‖k̂‖²
+ 1e-6)``; a value head ``β = σ(b_h)``, ``g = −exp(A_log_h) ·
softplus(a_h + dt_bias_h)``, and it reads the q and k of key head ``h //
r`` (``repeat_interleave``); then **the recurrence position by position**
(:func:`gdn_recurrence`):

    S ← exp(g_t) S;   u = β_t (v_t − Sᵀ k_t);   S ← S + k_t uᵀ;   o_t = Sᵀ q_t

``S ∈ R^{K×V}`` from zero; ``y = W_o·[(o / sqrt(mean_V(o²) + eps)) ⊙ w_V ⊙
silu(z)]``, ``w_V`` a plain weight.

Full attention (``H`` query heads, ``KV`` key/value heads of ``D``): ``[q̃
; γ] = n·W_q``, ``k̃ = n·W_k``, ``v = n·W_v``; ``q_h = N_D(q̃_h; w_q)``,
``k_j = N_D(k̃_j; w_k)`` over a head's channels; ``rotate_half`` over the
head's first ``lanes`` lanes, pairs ``(i, i + lanes / 2)``, the angle of
position ``t`` ``t · theta^(-2i / lanes)``, the other lanes unchanged;
the causal softmax of ``q·kᵀ / sqrt(D)`` a head at a time in blocks of
rows, query head ``h`` on key/value head ``h // (H / KV)``; ``y = W_o·[o ⊙
σ(γ)]``, the gate an element.

Sparse sublayer on ``m = N(h; w2)`` ``[N, d]``: ``z = m·W_r``; ``E`` = the
``top_k`` largest of ``z + b`` (``b`` selects only); ``w = softmax(z)``
over ``E``, 0 elsewhere; ``out = Σ_{e in E, held} w_e · MLP_e(m) + σ(m·w_s)
· MLP_s(m)``, ``MLP(m) = (silu(m·W_g) ⊙ m·W_u)·W_d``. The experts are
written as **every held expert on every token**, weighted by an ``[N,
E_held]`` matrix that is zero outside ``E ∩ held``; what the absent
experts would add is left out, and that partial result goes on.

A final ``N``; ``logits = hidden·W_head`` (untied); ``loss`` = the mean
next-token cross entropy over the rows held. On the CPU ``jax.grad`` of
:func:`loss` is the reference gradient.

Departures from the published description, each also in the
configuration file: the share (absent experts' part left out; the
vocabulary's rows held); the balance bias ``b`` (zero is the published
choice); the order of the fused projections' columns (``[q ; k ; v ; z]``,
``[b ; a]``, ``[q ; γ]``, each part heads-major); no multi-token-prediction
block. Parameter tree as ``torchft_tpu/models/qwen3_next.py::init_params``
makes it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"
ROW_BLOCK = 1024       # query rows of one score block


def norm(x: Any, w: Any, eps: float) -> Any:
    """The zero-centred norm ``N(x; w)`` over the last axis."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + w)


def swiglu(h: Any, m: Dict[str, Any]) -> Any:
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


def _linear(n: Any, m: Dict[str, Any], *, n_key: int, n_value: int,
            key_dim: int, value_dim: int, eps: float) -> Any:
    B, S, _ = n.shape
    Hk, Hv, K, V = n_key, n_value, key_dim, value_dim
    qkvz = n @ m["qkvz_proj"]["kernel"]
    width = 2 * Hk * K + Hv * V
    qkv = conv_silu(qkvz[..., :width], m["conv"]["kernel"])
    z = qkvz[..., width:].reshape(B, S, Hv, V)
    q = _l2(qkv[..., :Hk * K].reshape(B, S, Hk, K)) * K ** -0.5
    k = _l2(qkv[..., Hk * K:2 * Hk * K].reshape(B, S, Hk, K))
    v = qkv[..., 2 * Hk * K:].reshape(B, S, Hv, V)
    ba = n @ m["ba_proj"]["kernel"]
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(m["A_log"]) * jax.nn.softplus(ba[..., Hv:] + m["dt_bias"])
    r = Hv // Hk
    o = gdn_recurrence(jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2),
                       v, g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    y = o * m["o_norm"]["scale"] * jax.nn.silu(z)
    return y.reshape(B, S, Hv * V) @ m["o_proj"]["kernel"]


def rotate(x: Any, theta: float, lanes: int) -> Any:
    """``x [B, S, H, D]``: the first ``lanes`` lanes of every head turned
    (``rotate_half`` within them), the others as they are."""
    S = x.shape[1]
    f = theta ** (-2.0 * jnp.arange(lanes // 2, dtype=jnp.float32) / lanes)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * f[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    head = x[..., :lanes]
    half = jnp.concatenate(
        [-head[..., lanes // 2:], head[..., :lanes // 2]], axis=-1)
    turned = head * jnp.cos(angle) + half * jnp.sin(angle)
    return jnp.concatenate([turned, x[..., lanes:]], axis=-1)


def masked_attention(q: Any, k: Any, v: Any) -> Any:
    """``q [S, D]`` of ONE head on ``k, v [S, D]`` -> ``[S, D]``: the
    softmax of ``q·kᵀ / sqrt(D)`` under ``(t >= s)``, ``ROW_BLOCK`` query
    rows at a time against every key."""
    S, D = q.shape
    block = min(ROW_BLOCK, S)
    assert S % block == 0
    s_pos = jnp.arange(S)[None, :]

    def rows(i: Any) -> Any:
        t_pos = (i * block + jnp.arange(block))[:, None]
        scores = jax.lax.dynamic_slice_in_dim(q, i * block, block) @ k.T
        scores = jnp.where(t_pos >= s_pos, scores / jnp.sqrt(float(D)),
                           -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    return jax.lax.map(rows, jnp.arange(S // block)).reshape(S, D)


def grouped_attention(q: Any, k: Any, v: Any) -> Any:
    """``q [B, S, H, D]`` on ``k, v [B, S, KV, D]`` -> ``[B, S, H, D]``:
    query head ``i`` on key/value head ``i // (H / KV)``, a head at a
    time."""
    B, S, H, D = q.shape
    serves = H // k.shape[2]

    def one_head(i: Any) -> Any:
        b, head = i // H, i % H
        kv = head // serves
        return masked_attention(q[b, :, head], k[b, :, kv], v[b, :, kv])

    o = jax.lax.map(one_head, jnp.arange(B * H))             # [B*H, S, D]
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _full(n: Any, a: Dict[str, Any], *, n_head: int, n_kv: int,
          head_dim: int, theta: float, lanes: int, eps: float) -> Any:
    B, S, _ = n.shape
    H, D = n_head, head_dim
    qg = n @ a["q_proj"]["kernel"]
    q = norm(qg[..., :H * D].reshape(B, S, H, D), a["q_norm"]["scale"], eps)
    gate = jax.nn.sigmoid(qg[..., H * D:])                 # [B, S, H·D]
    k = norm((n @ a["k_proj"]["kernel"]).reshape(B, S, n_kv, D),
             a["k_norm"]["scale"], eps)
    v = (n @ a["v_proj"]["kernel"]).reshape(B, S, n_kv, D)
    o = grouped_attention(rotate(q, theta, lanes), rotate(k, theta, lanes), v)
    return (o.reshape(B, S, H * D) * gate) @ a["o_proj"]["kernel"]


def _experts(m2: Any, m: Dict[str, Any], *, top_k: int, first_expert: int,
             use: Any = None) -> Tuple[Any, Any]:
    """``m2 [N, d]`` -> (y [N, d], the top-k mask [N, E_routed]). With
    ``use`` (a mask of the same shape) the layer is computed on THAT
    selection — the weights are still this function's own logits' softmax
    over it — and the mask returned is still this function's own
    choice."""
    z = m2 @ m["router"]["kernel"]
    biased = z + m["balance_bias"]
    # by index, not by a threshold: ``biased >= its own k-th largest`` asks
    # two evaluations of one sum for equality, and a compiler that fuses
    # them apart (an add folded into the matmul's epilogue) drops the k-th
    _, at = jax.lax.top_k(biased, top_k)
    chosen = jnp.any(jax.nn.one_hot(at, z.shape[-1], dtype=bool), axis=-2)
    gates = jax.nn.softmax(
        jnp.where(chosen if use is None else use, z, -jnp.inf), axis=-1)
    n_held = m["up_proj"]["kernel"].shape[0]
    held = gates[:, first_expert:first_expert + n_held]       # [N, E_held]

    def add_expert(y, args):
        gate, up, down, g = args
        one = {"gate_proj": {"kernel": gate}, "up_proj": {"kernel": up},
               "down_proj": {"kernel": down}}
        return y + swiglu(m2, one) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(m2), (
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
        m["down_proj"]["kernel"], held.T))
    shared = m["shared"]
    return (y + jax.nn.sigmoid(m2 @ shared["gate"]["kernel"])
            * swiglu(m2, shared)), chosen


def cross_entropy(hidden: Any, head: Any, targets: Any) -> Any:
    """The mean cross entropy of ``targets`` under ``logits =
    hidden·head`` (``head [d, V]``), ``ROW_BLOCK`` positions at a time."""
    with jax.default_matmul_precision("highest"):
        head = head.astype(jnp.float32)
        h = hidden.reshape(-1, hidden.shape[-1])
        t = targets.reshape(-1)
        block = min(ROW_BLOCK, h.shape[0])
        assert h.shape[0] % block == 0

        def rows(args):
            logits = args[0] @ head
            logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
            return jnp.take_along_axis(logp, args[1][:, None], axis=-1)

        picked = jax.lax.map(rows, (h.reshape(-1, block, h.shape[-1]),
                                    t.reshape(-1, block)))
        return -jnp.mean(picked)


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *,
          layer_types: Sequence[str], n_key: int, n_value: int, key_dim: int,
          value_dim: int, n_head: int, n_kv: int, head_dim: int,
          theta: float, lanes: int, top_k: int, first_expert: int,
          eps: float, selection: Any = None) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S];
    ``hidden`` [B, S, d], the final-norm states the head reads;
    ``chosen`` [L, B*S, E_routed], the top-k mask of every layer in
    order. ``selection`` (the same shape), where given, is the selection
    every layer is computed on in place of its own: the cell's check
    hands over the system's, so that a near-tie that rounds the other way
    in bf16 is COUNTED (``chosen`` is still the reference's own choice, on
    the stream that selection gave) and does not reach, through the
    mixers' memory, the tokens that follow it."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        x = p["wte"]["embedding"][tokens]
        masks = []
        for i, kind in enumerate(layer_types):
            layer = p[f"layers_{i}"]
            n1 = norm(x, layer["norm_1"]["scale"], eps)
            if kind == LINEAR:
                x = x + _linear(n1, layer["gdn"], n_key=n_key,
                                n_value=n_value, key_dim=key_dim,
                                value_dim=value_dim, eps=eps)
            else:
                assert kind == FULL, kind
                x = x + _full(n1, layer["attn"], n_head=n_head, n_kv=n_kv,
                              head_dim=head_dim, theta=theta, lanes=lanes,
                              eps=eps)
            n2 = norm(x, layer["norm_2"]["scale"], eps)
            y, chosen = _experts(
                n2.reshape(B * S, -1), layer["moe"], top_k=top_k,
                first_expert=first_expert,
                use=None if selection is None else selection[i])
            x = x + y.reshape(x.shape)
            masks.append(chosen)
        hidden = norm(x, p["ln_f"]["scale"], eps)
        return {"loss": cross_entropy(hidden, p["lm_head"]["kernel"],
                                      targets),
                "hidden": hidden, "chosen": jnp.stack(masks)}


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
