"""Plain reference of SmallThinker (``model_name``
``smallthinker_21b_instruct``; PowerInfer/SmallThinker-21BA3B-Instruct's
config, arXiv:2507.20984) for one chip's share of an expert-parallel
layer: experts ``first_expert .. first_expert + E_held`` of each layer and
the rows of the table and the head the parameters hold. Straightforward
``jax.numpy`` in float32 with matmuls at ``highest`` precision: no
kernel, no sort, no dispatch, no chunked cross entropy, nothing imported
from the program.

Layer ``l`` (``x [B, S, d]``, no bias anywhere):

    n1 = RMSNorm(x; g1)
    z  = n1·W_r                           [N, E_routed]: the router reads
                                          n1, BEFORE attention
    E  = the top_k largest of z + b       b the balance bias: it selects
                                          and never weights
    w  = softmax(z) on E, renormalised    = softmax over the chosen logits
    q, k, v = n1·W_q [H × D], n1·W_k [KV × D], n1·W_v [KV × D]
    rotated[l]:  q, k <- RoPE(theta) over the whole head, rotate_half
                 (``x·cos + [-x_2 ; x_1]·sin``, angle ``t · theta^(-i /
                 (D/2))`` for both halves' channel ``i``)
    a  = softmax(q·kᵀ / sqrt(D) + mask)·v, query head i on key/value head
         i // (H / KV); mask (t >= s), and where windowed[l] also
         (t - s < window): ``window`` keys with itself
    h  = x + a·W_o
    n2 = RMSNorm(h; g2)
    y  = Σ_{e in E, held} w_e · (relu(n2·W_g^e) ⊙ n2·W_u^e)·W_d^e
    out = h + y

The attention is the full ``[S, S]`` softmax a head at a time, in blocks
of rows so that 16 384 positions fit beside a training state. The expert
MLP is written as **every held expert on every token**, weighted by an
``[N, E_held]`` matrix that is zero outside ``E ∩ held``. What the absent
experts would add is left out, and that partial result goes on. A final
RMSNorm; ``logits = hidden·W_head`` (untied); ``loss`` = the mean
next-token cross entropy over the rows held. On the CPU ``jax.grad`` of
:func:`loss` is the reference gradient.

Departures from the published description, each also in the
configuration file: the share (absent experts' part left out; the
vocabulary's rows held); the balance bias ``b`` (zero is the published
choice: the config has no bias); nothing else.

Parameter tree as ``torchft_tpu/models/smallthinker.py::init_params``
makes it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024       # query rows of one score block


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x: Any, theta: float) -> Any:
    """``x [B, S, H, D]``: position ``t``'s channels ``i`` and ``i + D/2``
    turn by ``t · theta^(-2i / D)``."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def masked_attention(q: Any, k: Any, v: Any,
                     window: Optional[int] = None) -> Any:
    """``q [S, D]`` of ONE head on ``k, v [S, D]`` -> ``[S, D]``: the
    softmax of ``q·kᵀ / sqrt(D)`` under ``(t >= s)`` and, with a
    ``window``, ``(t - s < window)``, ``ROW_BLOCK`` query rows at a time
    against every key."""
    S, D = q.shape
    block = min(ROW_BLOCK, S)
    assert S % block == 0
    s_pos = jnp.arange(S)[None, :]

    def rows(i: Any) -> Any:
        t_pos = (i * block + jnp.arange(block))[:, None]
        keep = t_pos >= s_pos
        if window is not None:
            keep = keep & (t_pos - s_pos < window)
        scores = jax.lax.dynamic_slice_in_dim(q, i * block, block) @ k.T
        scores = jnp.where(keep, scores / jnp.sqrt(float(D)), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    return jax.lax.map(rows, jnp.arange(S // block)).reshape(S, D)


def _attention(n: Any, a: Dict[str, Any], *, n_head: int, n_kv: int,
               head_dim: int, theta: float, rotated: bool,
               window: Optional[int]) -> Any:
    B, S, _ = n.shape
    D = head_dim
    q = (n @ a["q_proj"]["kernel"]).reshape(B, S, n_head, D)
    k = (n @ a["k_proj"]["kernel"]).reshape(B, S, n_kv, D)
    v = (n @ a["v_proj"]["kernel"]).reshape(B, S, n_kv, D)
    if rotated:
        q, k = _rotary(q, theta), _rotary(k, theta)
    serves = n_head // n_kv

    def one_head(i: Any) -> Any:
        b, head = i // n_head, i % n_head
        kv = head // serves
        return masked_attention(q[b, :, head], k[b, :, kv], v[b, :, kv],
                                window)

    o = jax.lax.map(one_head, jnp.arange(B * n_head))        # [B*H, S, D]
    o = o.reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return o.reshape(B, S, n_head * D) @ a["o_proj"]["kernel"]


def reglu(h: Any, gate: Any, up: Any, down: Any) -> Any:
    return (jnp.maximum(h @ gate, 0.0) * (h @ up)) @ down


def _experts(n1: Any, n2: Any, m: Dict[str, Any], *, top_k: int,
             first_expert: int, routed_scale: float = 1.0,
             use: Any = None) -> Tuple[Any, Any]:
    """``n1, n2 [N, d]`` -> (y [N, d], the top-k mask [N, E_routed]): the
    router scores ``n1``, the experts read ``n2``. With ``use`` (a mask
    of the same shape) the layer is computed on THAT selection — the
    weights are still the softmax of this function's own logits over it
    — and the mask returned is still this function's own choice."""
    z = n1 @ m["router"]["kernel"]
    biased = z + m["balance_bias"]
    n_routed = z.shape[-1]
    kth = jnp.sort(biased, axis=-1)[:, n_routed - top_k]
    chosen = biased >= kth[:, None]
    taken = chosen if use is None else use
    gates = routed_scale * jax.nn.softmax(
        jnp.where(taken, z, -jnp.inf), axis=-1)              # 0 outside
    n_held = m["up_proj"]["kernel"].shape[0]
    held = gates[:, first_expert:first_expert + n_held]       # [N, E_held]

    def add_expert(y, args):
        gate, up, down, g = args
        return y + reglu(n2, gate, up, down) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(n2), (
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
        m["down_proj"]["kernel"], held.T))
    return y, chosen


def cross_entropy(hidden: Any, head: Any, targets: Any) -> Any:
    """The mean cross entropy of ``targets`` under ``logits =
    hidden·head`` (``head [d, V]``), ``ROW_BLOCK`` positions at a time:
    32 768 positions' logits over 37 984 rows are 5 GB in float32."""
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
          windowed: Sequence[int], rotated: Sequence[int], window: int,
          n_head: int, n_kv: int, head_dim: int, theta: float, top_k: int,
          first_expert: int, routed_scale: float, eps: float,
          selection: Any = None) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S];
    ``hidden`` [B, S, d], the final-norm states the head reads;
    ``chosen`` [L, B*S, E_routed], the top-k mask of every layer in
    order. ``selection`` (the same shape), where given, is the selection
    every layer is computed on in place of its own: the cell's check
    hands over the system's, so that a near-tie that rounds the other
    way in bf16 is COUNTED (``chosen`` is still the reference's own
    choice, on the stream that selection gave) and does not reach,
    through attention's memory, the tokens that follow it."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        x = p["wte"]["embedding"][tokens]
        masks = []
        for i, (is_windowed, is_rotated) in enumerate(zip(windowed, rotated)):
            layer = p[f"layers_{i}"]
            n1 = _rms(x, layer["norm_1"]["scale"], eps)
            x = x + _attention(
                n1, layer["attn"], n_head=n_head, n_kv=n_kv,
                head_dim=head_dim, theta=theta, rotated=bool(is_rotated),
                window=window if is_windowed else None)
            n2 = _rms(x, layer["norm_2"]["scale"], eps)
            y, chosen = _experts(
                n1.reshape(B * S, -1), n2.reshape(B * S, -1), layer["moe"],
                top_k=top_k, first_expert=first_expert,
                routed_scale=routed_scale,
                use=None if selection is None else selection[i])
            x = x + y.reshape(x.shape)
            masks.append(chosen)
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        return {"loss": cross_entropy(hidden, p["lm_head"]["kernel"],
                                      targets),
                "hidden": hidden, "chosen": jnp.stack(masks)}


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
