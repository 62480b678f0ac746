"""Plain reference of Laguna (``model_type`` ``laguna``;
poolside/Laguna-XS.2's config) for one chip's share of an expert-parallel
layer: experts ``first_expert .. first_expert + E_held`` of each sparse
layer and the rows of the table and the head the parameters hold.
Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: no kernel, no sort, no dispatch, no chunked cross entropy,
nothing imported from the program.

Layer ``l`` (``x [B, S, d]``, no bias anywhere, no norm on q or k), with
``H_l`` its own number of query heads (the width of its ``W_γ``):

    n1 = RMSNorm(x; g1)
    q, k, v = n1·W_q [H_l × D], n1·W_k [KV × D], n1·W_v [KV × D]
    sliding layer:  q, k <- R(theta_s) over the whole head
    full layer:     q, k <- R_yarn over the first D_r = partial · D
                    lanes, the other lanes unchanged
    R: x·cos + rotate_half(x)·sin within the turned lanes, the angle of
       position t at lane pair i is t · f_i, f_i = theta^(-2i / D_r)
    R_yarn: f'_i = (f_i / factor) · r_i + f_i · (1 - r_i),
       r_i = clip((i - lo) / (hi - lo), 0, 1),
       lo = floor(c(beta_fast)), hi = ceil(c(beta_slow)),
       c(beta) = D_r · ln(original / (2 pi beta)) / (2 ln theta);
       cos and sin are both multiplied by attention_factor
    a  = softmax(q·kᵀ / sqrt(D) + mask)·v, query head i on key/value head
         i // (H_l / KV); mask (t >= s), on a sliding layer also
         (t - s < window): ``window`` keys with itself
    γ  = sigmoid(n1·W_γ)                 [B, S, H_l]: a gate a head
    h  = x + (γ ⊙ a)·W_o
    n2 = RMSNorm(h; g2)
    dense layer:   out = h + (silu(n2·W_g) ⊙ n2·W_u)·W_d
    sparse layer:  s = sigmoid(n2·W_r)   [N, E_routed]
                   E = the top_k largest of s + b   (b selects only)
                   w = routed_scale · s / Σ_{E} s    on E, 0 elsewhere
                   out = h + Σ_{e in E, held} w_e · MLP_e(n2) + MLP_s(n2)

The attention is the full ``[S, S]`` softmax a head at a time, in blocks
of rows. The expert MLP is written as **every held expert on every
token**, weighted by an ``[N, E_held]`` matrix that is zero outside ``E ∩
held``; what the absent experts would add is left out, and that partial
result goes on. A final RMSNorm; ``logits = hidden·W_head`` (untied);
``loss`` = the mean next-token cross entropy over the rows held. On the
CPU ``jax.grad`` of :func:`loss` is the reference gradient.

Departures from the published description, each also in the
configuration file: the share (absent experts' part left out; the
vocabulary's rows held); the balance bias ``b`` (zero is the published
choice); three readings the config leaves open (``assumed``: the gate a
head, sigmoid router scores, no norm on q or k); nothing else.

Parameter tree as ``torchft_tpu/models/laguna.py::init_params`` makes it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024       # query rows of one score block


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_ramp(lanes: int, theta: float, original: int, beta_fast: float,
              beta_slow: float) -> Tuple[int, int]:
    """``(lo, hi)`` of the ramp over ``lanes / 2`` frequencies."""
    def c(beta: float) -> float:
        return lanes * math.log(original / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    last = lanes // 2 - 1
    return (min(max(math.floor(c(beta_fast)), 0), last),
            min(max(math.ceil(c(beta_slow)), 0), last))


def frequencies(lanes: int, rope: Dict[str, Any]) -> Any:
    """``rope``: ``theta`` and, for YaRN, ``factor``, ``original``,
    ``beta_fast``, ``beta_slow`` (``factor`` None or absent: plain)."""
    i = jnp.arange(lanes // 2, dtype=jnp.float32)
    f = rope["theta"] ** (-2.0 * i / lanes)
    if rope.get("factor") is None:
        return f
    lo, hi = yarn_ramp(lanes, rope["theta"], rope["original"],
                       rope["beta_fast"], rope["beta_slow"])
    r = jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f / rope["factor"] * r + f * (1.0 - r)


def rotate(x: Any, rope: Dict[str, Any]) -> Any:
    """``x [B, S, H, D]`` through one kind of layer's rotation: the first
    ``partial · D`` lanes turned, the others as they are."""
    S, D = x.shape[1], x.shape[-1]
    lanes = int(D * rope.get("partial", 1.0))
    f = frequencies(lanes, rope)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * f[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    m = rope.get("attention_factor", 1.0)
    head = x[..., :lanes]
    half = jnp.concatenate(
        [-head[..., lanes // 2:], head[..., :lanes // 2]], axis=-1)
    turned = head * (jnp.cos(angle) * m) + half * (jnp.sin(angle) * m)
    return jnp.concatenate([turned, x[..., lanes:]], axis=-1)


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


def grouped_attention(q: Any, k: Any, v: Any,
                      window: Optional[int] = None) -> Any:
    """``q [B, S, H, D]`` on ``k, v [B, S, KV, D]`` -> ``[B, S, H, D]``:
    query head ``i`` on key/value head ``i // (H / KV)``, a head at a
    time."""
    B, S, H, D = q.shape
    serves = H // k.shape[2]

    def one_head(i: Any) -> Any:
        b, head = i // H, i % H
        kv = head // serves
        return masked_attention(q[b, :, head], k[b, :, kv], v[b, :, kv],
                                window)

    o = jax.lax.map(one_head, jnp.arange(B * H))             # [B*H, S, D]
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _attention(n: Any, a: Dict[str, Any], *, n_kv: int, head_dim: int,
               rope: Dict[str, Any], window: Optional[int]) -> Any:
    B, S, _ = n.shape
    D = head_dim
    n_head = a["gate"]["kernel"].shape[-1]
    q = (n @ a["q_proj"]["kernel"]).reshape(B, S, n_head, D)
    k = (n @ a["k_proj"]["kernel"]).reshape(B, S, n_kv, D)
    v = (n @ a["v_proj"]["kernel"]).reshape(B, S, n_kv, D)
    o = grouped_attention(rotate(q, rope), rotate(k, rope), v, window)
    gate = jax.nn.sigmoid(n @ a["gate"]["kernel"])            # [B, S, H]
    return (o * gate[..., None]).reshape(B, S, n_head * D) \
        @ a["o_proj"]["kernel"]


def swiglu(h: Any, m: Dict[str, Any]) -> Any:
    return (jax.nn.silu(h @ m["gate_proj"]["kernel"])
            * (h @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]


def _experts(n2: Any, m: Dict[str, Any], *, top_k: int, first_expert: int,
             routed_scale: float, use: Any = None) -> Tuple[Any, Any]:
    """``n2 [N, d]`` -> (y [N, d], the top-k mask [N, E_routed]). With
    ``use`` (a mask of the same shape) the layer is computed on THAT
    selection — the weights are still this function's own scores,
    renormalised over it — and the mask returned is still this function's
    own choice."""
    s = jax.nn.sigmoid(n2 @ m["router"]["kernel"])
    biased = s + m["balance_bias"]
    n_routed = s.shape[-1]
    kth = jnp.sort(biased, axis=-1)[:, n_routed - top_k]
    chosen = biased >= kth[:, None]
    taken = jnp.where(chosen if use is None else use, s, 0.0)
    gates = routed_scale * taken / jnp.sum(taken, axis=-1, keepdims=True)
    n_held = m["up_proj"]["kernel"].shape[0]
    held = gates[:, first_expert:first_expert + n_held]       # [N, E_held]

    def add_expert(y, args):
        gate, up, down, g = args
        one = {"gate_proj": {"kernel": gate}, "up_proj": {"kernel": up},
               "down_proj": {"kernel": down}}
        return y + swiglu(n2, one) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(n2), (
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
        m["down_proj"]["kernel"], held.T))
    return y + swiglu(n2, m["shared"]), chosen


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
          windowed: Sequence[int], window: int, n_kv: int, head_dim: int,
          rope_full: Dict[str, Any], rope_swa: Dict[str, Any], top_k: int,
          first_expert: int, routed_scale: float, eps: float,
          selection: Any = None) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S];
    ``hidden`` [B, S, d], the final-norm states the head reads;
    ``chosen`` [L_sparse, B*S, E_routed], the top-k mask of every sparse
    layer in order (a layer is sparse where its parameters hold ``moe``,
    and its head count is the width of its gate). ``selection`` (the same
    shape), where given, is the selection every sparse layer is computed
    on in place of its own: the cell's check hands over the system's, so
    that a near-tie that rounds the other way in bf16 is COUNTED
    (``chosen`` is still the reference's own choice, on the stream that
    selection gave) and does not reach, through attention's memory, the
    tokens that follow it."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        x = p["wte"]["embedding"][tokens]
        masks = []
        for i, is_windowed in enumerate(windowed):
            layer = p[f"layers_{i}"]
            n1 = _rms(x, layer["norm_1"]["scale"], eps)
            x = x + _attention(
                n1, layer["attn"], n_kv=n_kv, head_dim=head_dim,
                rope=rope_swa if is_windowed else rope_full,
                window=window if is_windowed else None)
            n2 = _rms(x, layer["norm_2"]["scale"], eps)
            if "moe" not in layer:
                x = x + swiglu(n2, layer["mlp"])
                continue
            y, chosen = _experts(
                n2.reshape(B * S, -1), layer["moe"], top_k=top_k,
                first_expert=first_expert, routed_scale=routed_scale,
                use=None if selection is None else selection[len(masks)])
            x = x + y.reshape(x.shape)
            masks.append(chosen)
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        return {"loss": cross_entropy(hidden, p["lm_head"]["kernel"],
                                      targets),
                "hidden": hidden, "chosen": jnp.stack(masks)}


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
