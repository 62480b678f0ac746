"""Plain reference of Kimi Linear (Hugging Face ``model_type``
``kimi_linear``; moonshotai/Kimi-Linear-48B-A3B-Instruct's config,
arXiv:2510.26692) for one chip's share of an expert-parallel layer:
experts ``first_expert .. first_expert + E_held`` of each expert layer
and the rows of table and head the parameters hold. Straightforward
``jax.numpy`` in float32 with matmuls at ``highest`` precision: no
kernel, no chunk, no sort, no dispatch, no chunked cross entropy, nothing
imported from the program.

Every layer is ``h = x + mixer(RMSNorm(x))``, ``y = h + mlp(RMSNorm(h))``;
layer ``i`` (from 0) is a KDA layer where ``i + 1`` is in ``kda_layers``,
else MLA; ``i < n_dense`` names the MLP.

KDA (``n`` the normed input, ``H`` heads of ``D`` channels): ``[q̃ ; k̃ ;
v] = silu(conv(n·W_qkv))``, the convolution as ``K`` shifted products,
``c_t = Σ_j w_j ⊙ x_{t-(K-1)+j}`` with zeros before the start, no bias;
per head ``q = q̃ / sqrt(‖q̃‖² + 1e-6) · D^{-1/2}``, ``k = k̃ / sqrt(‖k̃‖²
+ 1e-6)``; ``g = −exp(A_log_h) · softplus(n·W_f↓·W_f↑ + dt_bias)``;
``β = σ(n·W_β)``; then **the recurrence position by position**
(:func:`kda_recurrence`):

    S ← Diag(exp g_t) S;   u = β_t (v_t − Sᵀ k_t);   S ← S + k_t uᵀ;
    o_t = Sᵀ q_t

which is ``S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t-1} + β_t k_t v_tᵀ``
multiplied out; ``y = W_o·[RMSNorm_head(o) ⊙ σ(n·W_g↓·W_g↑)]``.

MLA: ``q = n·W_q`` -> heads of ``nope + rope``; ``[c_kv ; k_r] =
n·W_kva``; ``c_kv = RMSNorm(c_kv)``; heads of ``[k_nope ; v] =
c_kv·W_kvb``; NOTHING IS ROTATED (``mla_use_nope``); ``k = [k_nope ;
k_r]``, one ``k_r`` a token for every head; the full ``[S, S]`` causal
softmax of ``q·k / sqrt(nope + rope)`` one head at a time; ``·W_o``.

Dense MLP: ``(silu(n·W_g) ⊙ n·W_u)·W_d``. Expert MLP, written as **every
held expert on every token**, weighted by an ``[N, E_held]`` matrix that
is zero outside ``sel ∩ held``: ``s = sigmoid(n·W_r)``; ``sel`` = the
``top_k`` largest of ``s + b``; ``g_e = routed_scale · s_e / (Σ_sel s +
1e-20)``; ``y = Σ g_e · SwiGLU_e(n) + SwiGLU_shared(n)``. What the
absent experts would add is left out, and that partial result goes on.

On the CPU ``jax.grad`` of :func:`loss` is the reference gradient (the
balance bias ``b`` gets none: it only selects).

Departures from the published description, each also in the
configuration file: the share; the sizes the config has no key for
(``assumed``). Parameter tree as
``torchft_tpu/models/kimi_linear.py::init_params`` makes it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _swiglu(h: Any, m: Dict[str, Any]) -> Any:
    a = h @ m["gate_proj"]["kernel"]
    return (a * jax.nn.sigmoid(a) * (h @ m["up_proj"]["kernel"])) @ m[
        "down_proj"]["kernel"]


def conv_silu(x: Any, taps: Any) -> Any:
    """``x [B, S, C]``, ``taps [K, C]``: tap ``j`` reads the position
    ``K-1-j`` ago, zeros before the start; then silu. The zeros are
    padded on ONCE and the ``K`` shifted products are static slices of
    that: written with a zero block concatenated in front of each shifted
    slice (``lfm2_f32.short_conv``'s form), or with ``jnp.roll`` and a
    mask, XLA's TPU compiler returned wrong values at positions 1024,
    2048, ... of THIS program unless the convolution's result was also a
    program output (my chip runs, PR 40: 100 % off at those positions
    against the same function on the host CPU, 4e-5 in this form, as a
    ``lax.conv_general_dilated`` and as a scan over the positions)."""
    S, K = x.shape[1], taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    c = jnp.zeros_like(x)
    for j in range(K):
        c = c + taps[j] * padded[:, j:j + S]
    return c * jax.nn.sigmoid(c)


def kda_recurrence(q: Any, k: Any, v: Any, g: Any, beta: Any) -> Any:
    """``q, k, g [B, S, H, K]``, ``v [B, S, H, V]``, ``beta [B, S, H]``
    -> ``o [B, S, H, V]``: one position after the other, the state ``[B,
    H, K, V]`` from zero."""
    with jax.default_matmul_precision("highest"):
        B, _, H, K = q.shape

        def step(S, at):
            qt, kt, vt, gt, bt = at
            S = S * jnp.exp(gt)[..., None]
            u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
            S = S + kt[..., None] * u[..., None, :]
            return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

        _, o = jax.lax.scan(
            step, jnp.zeros((B, H, K, v.shape[3]), jnp.float32),
            tuple(jnp.moveaxis(z.astype(jnp.float32), 1, 0)
                  for z in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1)


def _l2(x: Any) -> Any:
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _kda(n: Any, m: Dict[str, Any], *, n_head: int, eps: float) -> Any:
    B, S, _ = n.shape
    qkv = conv_silu(n @ m["qkv_proj"]["kernel"], m["conv"]["kernel"])
    D = qkv.shape[-1] // (3 * n_head)
    q, k, v = (qkv[..., i * n_head * D:(i + 1) * n_head * D].reshape(
        B, S, n_head, D) for i in range(3))
    f = (n @ m["f_a_proj"]["kernel"]) @ m["f_b_proj"]["kernel"]
    g = -jnp.exp(m["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(B, S, n_head, D) + m["dt_bias"].reshape(n_head, D))
    beta = jax.nn.sigmoid(n @ m["b_proj"]["kernel"])
    o = kda_recurrence(_l2(q) * D ** -0.5, _l2(k), v, g, beta)
    gate = jax.nn.sigmoid(
        (n @ m["g_a_proj"]["kernel"]) @ m["g_b_proj"]["kernel"])
    y = _rms(o, m["o_norm"]["scale"], eps) * gate.reshape(B, S, n_head, D)
    return y.reshape(B, S, n_head * D) @ m["o_proj"]["kernel"]


def _mla(n: Any, a: Dict[str, Any], *, n_head: int, nope: int, rope: int,
         v_dim: int, kv_rank: int, eps: float) -> Any:
    B, S, _ = n.shape
    q = (n @ a["q_proj"]["kernel"]).reshape(B, S, n_head, nope + rope)
    kv_a = n @ a["kv_a_proj"]["kernel"]
    c_kv = _rms(kv_a[..., :kv_rank], a["kv_a_norm"]["scale"], eps)
    k_r = kv_a[..., kv_rank:][:, :, None, :]             # as it is: no RoPE
    kv = (c_kv @ a["kv_b_proj"]["kernel"]).reshape(B, S, n_head, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (B, S, n_head, rope))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))

    def one_head(qkv: Tuple[Any, Any, Any]) -> Any:
        qh, kh, vh = qkv                                  # [S, D]
        s = (qh @ kh.T) / jnp.sqrt(float(nope + rope))
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ vh

    def heads(x4: Any) -> Any:                            # -> [B*H, S, D]
        return x4.transpose(0, 2, 1, 3).reshape(B * n_head, S, x4.shape[-1])

    # one [S, S] score matrix at a time: 8192 fits beside a training state
    o = jax.lax.map(one_head, (heads(q), heads(k), heads(v)))
    o = o.reshape(B, n_head, S, v_dim).transpose(0, 2, 1, 3)
    return o.reshape(B, S, n_head * v_dim) @ a["o_proj"]["kernel"]


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
        jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    n_held = m["up_proj"]["kernel"].shape[0]
    held = gates[:, first_expert:first_expert + n_held]       # [N, E_held]

    def add_expert(y, args):
        gate, up, down, g = args
        one = {"gate_proj": {"kernel": gate}, "up_proj": {"kernel": up},
               "down_proj": {"kernel": down}}
        return y + _swiglu(n, one) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(n), (
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
        m["down_proj"]["kernel"], held.T))
    return y + _swiglu(n, m["shared"]), chosen


def cross_entropy(hidden: Any, head: Any, targets: Any) -> Any:
    with jax.default_matmul_precision("highest"):
        logits = hidden @ head.astype(jnp.float32)
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        return -jnp.mean(
            jnp.take_along_axis(logp, targets[..., None], axis=-1))


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *,
          kda_layers: Sequence[int], n_layer: int, n_dense: int, n_head: int,
          nope: int, rope: int, v_dim: int, kv_rank: int, top_k: int,
          first_expert: int, routed_scale: float, eps: float,
          selection: Optional[Any] = None) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S];
    ``hidden`` [B, S, d], the final-norm states the head reads;
    ``chosen`` [L_e, B*S, E_routed], the top-k mask of every expert layer
    in order. ``selection`` (the same shape), where given, is the
    selection every expert layer is computed on in place of its own: the
    cell's check hands over the system's, so that a near-tie that rounds
    the other way in bf16 is COUNTED (``chosen`` is still the reference's
    own choice, on the stream that selection gave) and does not reach,
    through the delta rule's and attention's memory, the tokens that
    follow it."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        x = p["wte"]["embedding"][tokens]
        masks = []
        for i in range(n_layer):
            layer = p[f"layers_{i}"]
            n = _rms(x, layer["ln_1"]["scale"], eps)
            if i + 1 in kda_layers:
                x = x + _kda(n, layer["kda"], n_head=n_head, eps=eps)
            else:
                x = x + _mla(n, layer["attn"], n_head=n_head, nope=nope,
                             rope=rope, v_dim=v_dim, kv_rank=kv_rank, eps=eps)
            n = _rms(x, layer["ln_2"]["scale"], eps)
            if i < n_dense:
                x = x + _swiglu(n, layer["mlp"])
            else:
                y, chosen = _experts(
                    n.reshape(B * S, -1), layer["moe"], top_k=top_k,
                    first_expert=first_expert, routed_scale=routed_scale,
                    use=None if selection is None else selection[len(masks)])
                x = x + y.reshape(x.shape)
                masks.append(chosen)
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        out = {"loss": cross_entropy(hidden, p["lm_head"]["kernel"], targets),
               "hidden": hidden}
        if masks:
            out["chosen"] = jnp.stack(masks)
        return out


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
