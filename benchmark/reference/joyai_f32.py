"""Plain reference of JoyAI-LLM-Flash (Hugging Face ``model_type``
``joyai_llm_flash``; the config's keys and the layers are the DeepSeek-V3
family's, arXiv:2412.19437) for one chip's share of an expert-parallel
layer: experts ``first_expert .. first_expert + E_held`` of each expert
layer and the rows of table and head the parameters hold. Straightforward
``jax.numpy`` in float32 with matmuls at ``highest`` precision: no kernel,
no sort, no dispatch, no chunked cross entropy, nothing imported from the
program.

MLA (training form). ``h = RMSNorm(x)``; ``c_q = RMSNorm(h·W_qa)``;
``q = c_q·W_qb``, heads of ``[q_nope ; q_rope]``;
``[c_kv ; k_r] = h·W_kva``; ``c_kv = RMSNorm(c_kv)``; heads of
``[k_nope ; v] = c_kv·W_kvb``; RoPE on ``q_rope`` and ``k_r`` over
interleaved pairs ``(x_2i, x_2i+1)``, angle ``s · theta^(-2i/D_rope)``;
``k = [k_nope ; k_r]``, one ``k_r`` a token for every head; the full
``[S, S]`` causal softmax of ``q·k / sqrt(D_nope + D_rope)`` one head at
a time; ``x + (P·v)·W_o``.

Dense layer: ``x + (silu(h·W_g) * (h·W_u))·W_d``, ``h = RMSNorm(x)``.

Expert layer, written as **every held expert on every token**, weighted
by an ``[N, E_held]`` matrix that is zero outside ``sel ∩ held``:
``s = sigmoid(h·W_r)``; ``sel`` = the ``top_k`` largest of ``s + b``;
``g_e = routed_scale · s_e / (sum_sel s + 1e-20)``;
``y = sum_{e in sel ∩ held} g_e·SwiGLU_e(h) + SwiGLU_shared(h)``. What
the absent experts would add is left out, and that partial result goes
on to the next layer.

MTP module: ``h' = [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x^L_i)]·W_eh`` (the
embedding first; ``x^L`` the residual stream before the final norm), one
expert layer, its own final RMSNorm, the shared head.
``loss = CE(main -> targets) + mtp_coef · CE(MTP -> targets rolled left
by one)``, every position kept.

On the CPU ``jax.grad`` of :func:`loss` is the reference gradient (the
balance bias ``b`` gets none: it only selects).

Parameter tree as ``torchft_tpu/models/joyai.py::init_params`` makes it.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope_interleaved(x: Any, theta: float) -> Any:
    """[B, S, H, D]: pair ``i`` is ``(x[2i], x[2i+1])``, turned by the
    angle ``s · theta^(-2i/D)``."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = (jnp.arange(S, dtype=jnp.float32)[:, None]
             * inv_freq[None, :])[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1)
    return turned.reshape(x.shape)


def _swiglu(h: Any, gate: Any, up: Any, down: Any) -> Any:
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _mla(x: Any, layer: Dict[str, Any], *, n_head: int, nope: int, rope: int,
         v_dim: int, kv_rank: int, eps: float, theta: float) -> Any:
    a = layer["attn"]
    B, S, _ = x.shape
    h = _rms(x, layer["ln_1"]["scale"], eps)
    c_q = _rms(h @ a["q_a_proj"]["kernel"], a["q_a_norm"]["scale"], eps)
    q = (c_q @ a["q_b_proj"]["kernel"]).reshape(B, S, n_head, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope_interleaved(q[..., nope:], theta)], axis=-1)
    kv_a = h @ a["kv_a_proj"]["kernel"]
    c_kv = _rms(kv_a[..., :kv_rank], a["kv_a_norm"]["scale"], eps)
    k_r = _rope_interleaved(kv_a[..., kv_rank:][:, :, None, :], theta)
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
    return x + o.reshape(B, S, n_head * v_dim) @ a["o_proj"]["kernel"]


def _moe(h: Any, m: Dict[str, Any], *, top_k: int, first_expert: int,
         routed_scale: float) -> Tuple[Any, Any]:
    """``h [N, d]`` -> (y [N, d], the top-k mask [N, E_routed])."""
    s = jax.nn.sigmoid(h @ m["router"]["kernel"])
    biased = s + m["balance_bias"]
    n_routed = s.shape[-1]
    kth = jnp.sort(biased, axis=-1)[:, n_routed - top_k]
    chosen = biased >= kth[:, None]
    gates = jnp.where(chosen, s, 0.0)
    gates = routed_scale * gates / (
        jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    n_held = m["gate_proj"]["kernel"].shape[0]
    held = gates[:, first_expert:first_expert + n_held]       # [N, E_held]

    def add_expert(y, args):
        gate, up, down, g = args
        return y + _swiglu(h, gate, up, down) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
        m["down_proj"]["kernel"], held.T,
    ))
    sh = m["shared"]
    return y + _swiglu(h, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                       sh["down_proj"]["kernel"]), chosen


def _cross_entropy(h: Any, head: Any, targets: Any) -> Any:
    logits = h @ head
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *, n_layer: int,
          n_dense: int, n_head: int, nope: int, rope: int, v_dim: int,
          kv_rank: int, top_k: int, first_expert: int, routed_scale: float,
          mtp_coef: float, eps: float, rope_theta: float) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S] and its
    parts ``ce`` and ``mtp_ce``; ``hidden`` and ``mtp_hidden`` [B, S, d],
    the final-norm states the head reads; ``chosen`` [L_e, B*S, E_routed],
    the top-k mask of every expert layer, the MTP module's last. Without
    ``params["mtp"]`` there is no second term."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        attn = dict(n_head=n_head, nope=nope, rope=rope, v_dim=v_dim,
                    kv_rank=kv_rank, eps=eps, theta=rope_theta)
        route = dict(top_k=top_k, first_expert=first_expert,
                     routed_scale=routed_scale)

        def expert_layer(layer, x):
            x = _mla(x, layer, **attn)
            h = _rms(x, layer["ln_2"]["scale"], eps)
            y, chosen = _moe(h.reshape(B * S, -1), layer["moe"], **route)
            return x + y.reshape(x.shape), chosen

        x = p["wte"]["embedding"][tokens]
        masks = []
        for i in range(n_layer):
            layer = p[f"layers_{i}"]
            if i < n_dense:
                x = _mla(x, layer, **attn)
                m = layer["mlp"]
                x = x + _swiglu(
                    _rms(x, layer["ln_2"]["scale"], eps),
                    m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                    m["down_proj"]["kernel"])
            else:
                x, chosen = expert_layer(layer, x)
                masks.append(chosen)
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        head = p["lm_head"]["kernel"]
        ce = _cross_entropy(hidden, head, targets)
        out = {"ce": ce, "loss": ce, "hidden": hidden}
        if "mtp" in p:
            t = p["mtp"]
            joined = jnp.concatenate([
                _rms(p["wte"]["embedding"][targets], t["enorm"]["scale"], eps),
                _rms(x, t["hnorm"]["scale"], eps),
            ], axis=-1)
            y, chosen = expert_layer(t["block"], joined @ t["eh_proj"]["kernel"])
            masks.append(chosen)
            out["mtp_hidden"] = _rms(y, t["ln_f"]["scale"], eps)
            out["mtp_ce"] = _cross_entropy(
                out["mtp_hidden"], head, jnp.roll(targets, -1, axis=1))
            out["loss"] = ce + mtp_coef * out["mtp_ce"]
        out["chosen"] = jnp.stack(masks)
        return out


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
