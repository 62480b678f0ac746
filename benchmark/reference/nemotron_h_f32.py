"""Plain reference of Nemotron-H (Hugging Face ``model_type``
``nemotron_h``, arXiv:2504.03624; NVIDIA-Nemotron-3-Nano-30B-A3B's
config) for one chip's share of an expert-parallel layer: experts
``first_expert .. first_expert + E_held`` of each expert layer and the
rows of table and head the parameters hold. Straightforward ``jax.numpy``
in float32 with matmuls at ``highest`` precision: no kernel, no chunked
scan, no sort, no dispatch, no chunked cross entropy, nothing imported
from the program.

Every layer is ``x + mixer(RMSNorm(x))``; which mixer is the letter of
``pattern`` at the layer's place.

``M``, the Mamba-2 mixer. ``[z ; xBC ; dt] = h·W_in`` (widths ``I = H·P``,
``I + 2·G·N``, ``H``). The convolution as ``K`` shifted sums:
``xBC_t <- silu(b + Σ_j w_j ⊙ xBC_{t-(K-1)+j})``, zeros before the start.
``xBC -> x [H, P] ; B [G, N] ; C [G, N]``, head ``h`` reads group ``h //
(H/G)``; ``Δ = softplus(dt + dt_bias)``, ``A = -exp(A_log)``. **The
recurrence itself, position by position** (a ``lax.scan`` over ``t`` with
the ``[B, H, P, N]`` state):

    S_t = exp(Δ_t A)·S_{t-1} + Δ_t · x_t ⊗ B_t,    y_t = S_t·C_t + D·x_t.

``y <- RMSNorm(y ⊙ silu(z))`` over each of the ``G`` groups of ``I/G``
channels alone, times the norm's weight; ``·W_out``.

``*``, the attention mixer. ``q`` -> ``n_head`` heads, ``k, v`` ->
``n_kv`` heads; query head ``i`` reads key/value head ``i // (n_head /
n_kv)``; the full ``[S, S]`` causal softmax of ``q·k / sqrt(D)`` one head
at a time; ``·W_o``. No position embedding.

``E``, the expert mixer, written as **every held expert on every
token**, weighted by an ``[N, E_held]`` matrix that is zero outside ``sel
∩ held``: ``s = sigmoid(h·W_r)``; ``sel`` = the ``top_k`` largest of ``s
+ b``; ``g_e = routed_scale · s_e / (Σ_sel s + 1e-20)``; ``y = Σ g_e ·
relu(h·W_up,e)²·W_down,e + relu(h·W_up,s)²·W_down,s``. What the absent
experts would add is left out, and that partial result goes on.

``loss`` = the mean next-token cross entropy over the rows of the head
held. On the CPU ``jax.grad`` of :func:`loss` is the reference gradient.

Parameter tree as ``torchft_tpu/models/nemotron_h.py::init_params`` makes
it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def _rms(x: Any, scale: Any, eps: float) -> Any:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _relu2(h: Any, up: Any, down: Any) -> Any:
    return jnp.square(jnp.maximum(h @ up, 0.0)) @ down


def recurrence(x: Any, delta: Any, A: Any, Bm: Any, Cm: Any, D: Any) -> Any:
    """The state-space recurrence itself, position by position: ``x [B, S,
    H, P]``, ``delta [B, S, H]``, ``A [H]``, ``Bm, Cm [B, S, G, N]`` (head
    ``h`` reads group ``h // (H/G)``), ``D [H]`` -> ``y [B, S, H, P]``,

        S_t = exp(Δ_t A)·S_{t-1} + Δ_t · x_t ⊗ B_t,    y_t = S_t·C_t + D·x_t,

    a ``lax.scan`` over ``t`` with the ``[B, H, P, N]`` state from zero.
    The positions are taken in stretches of up to 64 behind a
    ``jax.checkpoint``, which changes no value: a ``jax.vjp`` of this
    function then keeps a state a stretch and not one a position (the
    cell's check of the scan's gradients, ``families/nemotron_h.py``)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    # head h reads group h // (H / G)
    Bm = jnp.repeat(Bm, H // Bm.shape[2], axis=2)
    Cm = jnp.repeat(Cm, H // Cm.shape[2], axis=2)

    def position(S_prev, at):
        x_t, d_t, b_t, c_t = at          # [B,H,P] [B,H] [B,H,N] [B,H,N]
        S_t = (jnp.exp(d_t * A)[..., None, None] * S_prev
               + (d_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return S_t, jnp.sum(S_t * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def stretch(S_prev, ats):
        return jax.lax.scan(position, S_prev, ats)

    n = math.gcd(S, 64)
    _, y = jax.lax.scan(            # sums and products only: no matmul
        stretch, jnp.zeros((B, H, P, N), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0).reshape(S // n, n, *a.shape[:1],
                                           *a.shape[2:])
              for a in (x, delta, Bm, Cm)))
    y = jnp.moveaxis(y.reshape(S, B, H, P), 0, 1)
    return y + D[:, None] * x


def _mamba(h: Any, m: Dict[str, Any], *, heads: int, head_dim: int,
           groups: int, state: int, eps: float) -> Any:
    B, S, _ = h.shape
    H, P, G, N = heads, head_dim, groups, state
    inner = H * P
    proj = h @ m["in_proj"]["kernel"]
    z = proj[..., :inner]
    xbc = proj[..., inner:2 * inner + 2 * G * N]
    dt = proj[..., 2 * inner + 2 * G * N:]
    taps = m["conv"]["kernel"]                               # [K, channels]
    K = taps.shape[0]
    conv = jnp.zeros_like(xbc) + m["conv"]["bias"]
    for j in range(K):
        back = K - 1 - j               # tap j reads the position `back` ago
        shifted = jnp.concatenate(
            [jnp.zeros_like(xbc[:, :back]), xbc[:, :S - back]], axis=1)
        conv = conv + taps[j] * shifted
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(B, S, H, P)
    delta = jax.nn.softplus(dt + m["dt_bias"])               # [B, S, H]
    y = recurrence(
        x, delta, -jnp.exp(m["A_log"]),
        xbc[..., inner:inner + G * N].reshape(B, S, G, N),
        xbc[..., inner + G * N:].reshape(B, S, G, N), m["D"])
    gated = (y.reshape(B, S, inner) * jax.nn.silu(z)).reshape(
        B, S, G, inner // G)
    normed = gated / jnp.sqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return (normed.reshape(B, S, inner) * m["norm"]["scale"]) @ m[
        "out_proj"]["kernel"]


def _attention(h: Any, a: Dict[str, Any], *, n_head: int, n_kv: int,
               head_dim: int) -> Any:
    B, S, _ = h.shape
    D = head_dim
    q = (h @ a["q_proj"]["kernel"]).reshape(B, S, n_head, D)
    k = (h @ a["k_proj"]["kernel"]).reshape(B, S, n_kv, D)
    v = (h @ a["v_proj"]["kernel"]).reshape(B, S, n_kv, D)
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    serves = n_head // n_kv

    def one_head(i: Any) -> Any:
        b, head = i // n_head, i % n_head
        kv = head // serves
        s = (q[b, :, head] @ k[b, :, kv].T) / jnp.sqrt(float(D))
        return jax.nn.softmax(
            jnp.where(causal, s, -jnp.inf), axis=-1) @ v[b, :, kv]

    # one [S, S] score matrix at a time: 8192 fits beside a training state
    o = jax.lax.map(one_head, jnp.arange(B * n_head))        # [B*H, S, D]
    o = o.reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return o.reshape(B, S, n_head * D) @ a["o_proj"]["kernel"]


def _experts(h: Any, m: Dict[str, Any], *, top_k: int, first_expert: int,
             routed_scale: float, use: Any = None) -> Tuple[Any, Any]:
    """``h [N, d]`` -> (y [N, d], the top-k mask [N, E_routed]). With
    ``use`` (a mask of the same shape) the layer is computed on THAT
    selection — the weights are still this function's own scores — and
    the mask returned is still this function's own choice."""
    s = jax.nn.sigmoid(h @ m["router"]["kernel"])
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
        up, down, g = args
        return y + _relu2(h, up, down) * g[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        m["up_proj"]["kernel"], m["down_proj"]["kernel"], held.T))
    sh = m["shared"]
    return y + _relu2(h, sh["up_proj"]["kernel"],
                      sh["down_proj"]["kernel"]), chosen


def terms(params: Dict[str, Any], tokens: Any, targets: Any, *, pattern: str,
          ssm_heads: int, ssm_head_dim: int, ssm_groups: int, ssm_state: int,
          n_head: int, n_kv: int, head_dim: int, top_k: int,
          first_expert: int, routed_scale: float, eps: float,
          selection: Any = None) -> Dict[str, Any]:
    """``loss`` of ``tokens`` [B, S] against ``targets`` [B, S];
    ``hidden`` [B, S, d], the final-norm states the head reads;
    ``chosen`` [L_e, B*S, E_routed], the top-k mask of every expert layer
    in the pattern's order. ``selection`` (the same shape), where given,
    is the selection every expert layer is computed on in place of its
    own: the cell's check hands over the system's, so that a near-tie
    that rounds the other way in bf16 is COUNTED (``chosen`` is still the
    reference's own choice, on the stream that selection gave) and does
    not reach, through the scan's memory, the tokens that follow it."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, S = tokens.shape
        x = p["wte"]["embedding"][tokens]
        masks = []
        for i, letter in enumerate(pattern):
            layer = p[f"layers_{i}"]
            h = _rms(x, layer["norm"]["scale"], eps)
            if letter == "M":
                y = _mamba(h, layer["mamba"], heads=ssm_heads,
                           head_dim=ssm_head_dim, groups=ssm_groups,
                           state=ssm_state, eps=eps)
            elif letter == "*":
                y = _attention(h, layer["attn"], n_head=n_head, n_kv=n_kv,
                               head_dim=head_dim)
            elif letter == "E":
                y, chosen = _experts(
                    h.reshape(B * S, -1), layer["moe"], top_k=top_k,
                    first_expert=first_expert, routed_scale=routed_scale,
                    use=None if selection is None else selection[len(masks)])
                y = y.reshape(x.shape)
                masks.append(chosen)
            else:
                raise ValueError(f"no mixer {letter!r}")
            x = x + y
        hidden = _rms(x, p["ln_f"]["scale"], eps)
        logits = hidden @ p["lm_head"]["kernel"]
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
        out = {"loss": ce, "hidden": hidden}
        if masks:
            out["chosen"] = jnp.stack(masks)
        return out


def loss(params: Dict[str, Any], tokens: Any, targets: Any, **kw: Any) -> Any:
    return terms(params, tokens, targets, **kw)["loss"]
