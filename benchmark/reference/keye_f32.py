"""Plain reference of Keye-VL-2.0's language model (``model_type``
``KeyeVL2``; Kwai-Keye/Keye-VL-2.0-30B-A3B's config, whose attention is
DeepSeek sparse attention: arXiv:2512.02556, DeepSeek-V3.2) for one
chip's share of an expert-parallel layer: experts ``first_expert ..
first_expert + E_held`` of each layer and the rows of the table and the
head the parameters hold. Straightforward ``jax.numpy`` in float32 with
matmuls at ``highest`` precision: no kernel, no threshold by counting, no
packed mask inside, no dispatch, no chunked cross entropy, nothing
imported from the program.

Layer ``l`` (``x [B, S, d]``, no bias but the LayerNorm's), ``N(x; g) = x
/ sqrt(mean(x²) + eps) · g``:

    n  = N(x; g1)
    q, k, v = n·W_q [H × D], n·W_k [KV × D], n·W_v [KV × D]
    q_h = N(q_h; g_q), k_g = N(k_g; g_k)       one D-wide weight each
    q, k turned, rotate_half over the whole head: channel pair (i, i + D/2)
        by ``p_c(i)[t] · theta^(-2i / D)``; ``p_0, p_1, p_2`` the temporal,
        height and width streams, ``c(i)`` the section of ``sections`` (16,
        24, 24 pairs) that holds ``i``
    n̄  = stop_gradient(n)
    qI = n̄·W_qI [HI × DI],  kI = LayerNorm(n̄·W_kI) [DI],
    w  = n̄·W_w / sqrt(HI · DI);  the first ``index_rope`` lanes of each qI
        head and of kI turned by p_0 at ``theta^(-2i / index_rope)``
    I[t, s] = Σ_j w[t, j] · relu(qI[t, j] · kI[s])
    S_t = the keys of the min(t + 1, topk) largest I[t, s], s <= t
          (``jax.lax.top_k``)
    P[t, h, s] = softmax over s in S_t of q[t, h] · k[s, h // (H / KV)] / √D
    a  = Σ_{s in S_t} P[t, h, s] · v[s, h // (H / KV)];  h = x + a·W_o
    p̄[t, s] = stop_gradient(mean_h P[t, h, s])
    L_I += mean_t Σ_{s in S_t} p̄[t, s] · (log p̄[t, s]
                                          − log softmax_{S_t}(I[t, ·])[s])
    m  = N(h; g2);  z = m·W_r;  E = the top_k largest of z + b (b the
        balance bias: selects, never weights);  g = softmax(z) on E
    out = h + Σ_{e in E, held} g_e · (silu(m·W_g^e) ⊙ m·W_u^e)·W_d^e

``ROW_BLOCK`` query rows at a time against every key, so that 16 384
positions fit: the ``[S, S]`` arrays exist a block at a time. A final
``N``; ``logits = hidden·W_head`` (untied); ``ce`` = the mean next-token
cross entropy over the rows held; ``loss = ce + kl_weight · L_I``. On the
CPU ``jax.grad`` of :func:`loss` is the reference gradient: ``ce``'s
reaches no parameter of the indexer, ``L_I``'s no other.

A set of keys is handed in and out PACKED, for its size alone (33.5 MB a
sequence of 16 384 where the mask is 268): ``[.., S, S / 32]`` int32,
bit ``b`` of word ``c`` of row ``t`` is key ``s = b · (S / 32) + c``
(:func:`pack_keys`, :func:`unpack_keys`).

Departures from the published description, each also in the
configuration file: the share (absent experts' part left out; the
vocabulary's rows held); the balance bias ``b`` (zero is the published
choice); the items the configuration lists as ``assumed``.

Parameter tree as ``torchft_tpu/models/keye.py::init_params`` makes it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

ROW_BLOCK = 256        # query rows of one block of scores
_WORD = 32


def pack_keys(keep: Any) -> Any:
    *lead, s = keep.shape
    bits = keep.reshape(*lead, _WORD, s // _WORD).astype(jnp.uint32)
    shifts = jnp.arange(_WORD, dtype=jnp.uint32)[:, None]
    return jax.lax.bitcast_convert_type(
        jnp.sum(bits << shifts, axis=-2, dtype=jnp.uint32), jnp.int32)


def unpack_keys(words: Any) -> Any:
    *lead, w = words.shape
    shifts = jnp.arange(_WORD, dtype=jnp.int32)[:, None]
    return (((words[..., None, :] >> shifts) & 1) != 0).reshape(
        *lead, _WORD * w)


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x: Any, scale: Any, bias: Any, eps: float) -> Any:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _turn(x: Any, angle: Any) -> Any:
    """``x [S, heads, D]``, ``angle [S, r]``: channels ``i`` and ``i + r``
    turn by ``angle[t, i]``; channels from ``2r`` on pass."""
    r = angle.shape[-1]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :r], x[..., r:2 * r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * r:]], axis=-1)


def head_angles(positions: Any, theta: float, head_dim: int,
                sections: Sequence[int]) -> Any:
    """``[S, D/2]``: pair ``i``'s angle at position ``t``,
    ``positions[c(i), t] · theta^(-2i / D)``."""
    half = head_dim // 2
    assert sum(sections) == half
    stream = jnp.concatenate([jnp.full((n,), c) for c, n in
                              enumerate(sections)])
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[stream].T * inv[None, :]


def index_scores(qi: Any, ki: Any, w: Any) -> Any:
    """``qi [R, HI, DI]``, ``ki [S, DI]``, ``w [R, HI]`` -> ``I [R, S]``."""
    return jnp.einsum("rhs,rh->rs",
                      jax.nn.relu(jnp.einsum("rhd,sd->rhs", qi, ki)), w)


def top_keys(scores: Any, t_pos: Any, topk: int) -> Any:
    """``[R, S]`` bool: row ``r``'s ``min(t_pos[r] + 1, topk)`` largest
    scores among keys ``s <= t_pos[r]``."""
    R, S = scores.shape
    causal = jnp.arange(S)[None, :] <= t_pos[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, S))
    own = jnp.zeros((R, S), bool).at[jnp.arange(R)[:, None], idx].set(True)
    return own & causal


def sparse_attention(q: Any, k: Any, v: Any, keep: Any) -> Tuple[Any, Any]:
    """``q [H, R, D]`` on ``k, v [KV, S, D]`` under ``keep [R, S]`` ->
    ``(a [H, R, D], pbar [R, S])``: the softmax over each row's kept keys
    and its mean over the heads, a key/value head's group at a time."""
    H, R, D = q.shape
    KV = k.shape[0]

    def group(pbar, args):
        qg, kg, vg = args                       # [H/KV, R, D], [S, D] x 2
        scores = jnp.where(keep[None], qg @ kg.T / jnp.sqrt(float(D)),
                           -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return pbar + jnp.sum(p, axis=0) / H, p @ vg

    pbar, a = jax.lax.scan(group, jnp.zeros(keep.shape, jnp.float32),
                           (q.reshape(KV, H // KV, R, D), k, v))
    return a.reshape(H, R, D), pbar


def index_kl_rows(pbar: Any, scores: Any, keep: Any) -> Any:
    """``[R]``: ``KL(pbar[t] || softmax over keep[t] of scores[t])``."""
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    on = keep & (pbar > 0)
    return jnp.sum(jnp.where(
        on, pbar * (jnp.log(jnp.where(on, pbar, 1.0))
                    - jnp.where(on, log_q, 0.0)), 0.0), axis=-1)


def _attention(n: Any, a: Dict[str, Any], ix: Dict[str, Any], positions: Any,
               *, n_head: int, n_kv: int, head_dim: int, theta: float,
               sections: Sequence[int], index_heads: int, index_rope: int,
               topk: int, eps: float, ln_eps: float,
               keys: Optional[Any]) -> Tuple[Any, Any, Any]:
    """One sequence, ``n [S, d]`` -> ``(a·W_o [S, d], the rows' KL [S], the
    layer's own sets, packed [S, S / 32])``; with ``keys`` (packed, the
    same shape) attention and the KL run on THOSE sets."""
    S, D = n.shape[0], head_dim
    angle = head_angles(positions, theta, D, sections)
    q = _turn(_rms((n @ a["q_proj"]["kernel"]).reshape(S, n_head, D),
                   a["q_norm"]["scale"], eps), angle).transpose(1, 0, 2)
    k = _turn(_rms((n @ a["k_proj"]["kernel"]).reshape(S, n_kv, D),
                   a["k_norm"]["scale"], eps), angle).transpose(1, 0, 2)
    v = (n @ a["v_proj"]["kernel"]).reshape(S, n_kv, D).transpose(1, 0, 2)

    nb = jax.lax.stop_gradient(n)
    half = index_rope // 2
    index_angle = positions[0].astype(jnp.float32)[:, None] * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)[None, :]
    qi = _turn((nb @ ix["q_proj"]["kernel"]).reshape(S, index_heads, -1),
               index_angle)
    ki = _turn(_layer_norm(nb @ ix["k_proj"]["kernel"], ix["k_norm"]["scale"],
                           ix["k_norm"]["bias"], ln_eps)[:, None, :],
               index_angle)[:, 0]
    w = nb @ ix["weights_proj"]["kernel"] / jnp.sqrt(
        float(index_heads * qi.shape[-1]))

    block = min(ROW_BLOCK, S)
    assert S % block == 0

    def rows(i: Any) -> Tuple[Any, Any, Any]:
        at = i * block
        t_pos = at + jnp.arange(block)
        scores = index_scores(
            jax.lax.dynamic_slice_in_dim(qi, at, block),
            ki, jax.lax.dynamic_slice_in_dim(w, at, block))
        own = top_keys(scores, t_pos, topk)
        keep = own if keys is None else unpack_keys(
            jax.lax.dynamic_slice_in_dim(keys, at, block))
        o, pbar = sparse_attention(
            jax.lax.dynamic_slice_in_dim(q, at, block, axis=1), k, v, keep)
        kl = index_kl_rows(jax.lax.stop_gradient(pbar), scores, keep)
        return o.transpose(1, 0, 2).reshape(block, -1), kl, pack_keys(own)

    o, kl, own = jax.lax.map(rows, jnp.arange(S // block))
    return (o.reshape(S, -1) @ a["o_proj"]["kernel"], kl.reshape(S),
            own.reshape(S, -1))


def swiglu(h: Any, gate: Any, up: Any, down: Any) -> Any:
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _experts(m_in: Any, m: Dict[str, Any], *, top_k: int, first_expert: int,
             routed_scale: float = 1.0, use: Any = None) -> Tuple[Any, Any]:
    """``m_in [N, d]`` -> (y [N, d], the top-k mask [N, E_routed]); with
    ``use`` (a mask of that shape) the layer is computed on THAT
    selection, the weights still the softmax of this function's own
    logits over it, the mask returned still this function's own choice."""
    z = m_in @ m["router"]["kernel"]
    biased = z + m["balance_bias"]
    n_routed = z.shape[-1]
    kth = jnp.sort(biased, axis=-1)[:, n_routed - top_k]
    chosen = biased >= kth[:, None]
    taken = chosen if use is None else use
    gates = routed_scale * jax.nn.softmax(
        jnp.where(taken, z, -jnp.inf), axis=-1)
    n_held = m["up_proj"]["kernel"].shape[0]
    held = gates[:, first_expert:first_expert + n_held]

    def add_expert(y, args):
        gate, up, down, g = args
        return y + swiglu(m_in, gate, up, down) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(m_in), (
        m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
        m["down_proj"]["kernel"], held.T))
    return y, chosen


def cross_entropy(hidden: Any, head: Any, targets: Any) -> Any:
    """The mean cross entropy of ``targets`` under ``logits =
    hidden·head`` (``head [d, V]``), 1024 positions at a time."""
    with jax.default_matmul_precision("highest"):
        head = head.astype(jnp.float32)
        h = hidden.reshape(-1, hidden.shape[-1])
        t = targets.reshape(-1)
        block = min(1024, h.shape[0])
        assert h.shape[0] % block == 0

        def rows(args):
            logits = args[0] @ head
            logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
            return jnp.take_along_axis(logp, args[1][:, None], axis=-1)

        picked = jax.lax.map(rows, (h.reshape(-1, block, h.shape[-1]),
                                    t.reshape(-1, block)))
        return -jnp.mean(picked)


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *,
          n_layers: int, n_head: int, n_kv: int, head_dim: int, theta: float,
          sections: Sequence[int], index_heads: int, index_rope: int,
          topk: int, kl_weight: float, top_k: int, first_expert: int,
          routed_scale: float, eps: float, ln_eps: float,
          positions: Optional[Any] = None, selection: Any = None,
          keys: Any = None) -> Dict[str, Any]:
    """``loss`` = ``ce`` + ``kl_weight`` · ``index_kl`` of ``tokens`` [B,
    S] against ``targets``; ``hidden`` [B, S, d]; ``kl`` [L], each layer's
    mean-over-tokens KL; ``chosen`` [L, B*S, E_routed], every layer's own
    top-k mask of experts; ``own_keys`` [L, B, S, S / 32], every layer's
    own sets of keys, packed. ``selection`` (``chosen``'s shape) and
    ``keys`` (``own_keys``' shape), where given, are what every layer is
    computed on in place of its own: the cell's check hands over the
    system's, so that a near-tie that rounds the other way in bf16 is
    COUNTED (``chosen`` and ``own_keys`` are still the reference's own
    choices, on the stream those gave) and does not reach, through
    attention's memory, the tokens that follow. ``positions [3, S]``:
    text's (all ``t``) where none is given."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (3, S))
        x = p["wte"]["embedding"][tokens]
        masks, sets, kls = [], [], []
        for i in range(n_layers):
            layer = p[f"layers_{i}"]
            n = _rms(x, layer["norm_1"]["scale"], eps)
            outs = [_attention(
                n[b], layer["attn"], layer["indexer"], positions,
                n_head=n_head, n_kv=n_kv, head_dim=head_dim, theta=theta,
                sections=sections, index_heads=index_heads,
                index_rope=index_rope, topk=topk, eps=eps, ln_eps=ln_eps,
                keys=None if keys is None else keys[i, b]) for b in range(B)]
            x = x + jnp.stack([o[0] for o in outs])
            kls.append(jnp.mean(jnp.stack([o[1] for o in outs])))
            sets.append(jnp.stack([o[2] for o in outs]))
            y, chosen = _experts(
                _rms(x, layer["norm_2"]["scale"], eps).reshape(B * S, -1),
                layer["moe"], top_k=top_k, first_expert=first_expert,
                routed_scale=routed_scale,
                use=None if selection is None else selection[i])
            x = x + y.reshape(x.shape)
            masks.append(chosen)
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        ce = cross_entropy(hidden, p["lm_head"]["kernel"], targets)
        kl = jnp.stack(kls)
        return {"loss": ce + kl_weight * jnp.sum(kl), "ce": ce, "kl": kl,
                "index_kl": jnp.sum(kl), "hidden": hidden,
                "chosen": jnp.stack(masks), "own_keys": jnp.stack(sets)}


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
