"""What more than one model file computes, in one place: a change here is
a change to every model that imports it, and says so. RMSNorm (``llama``,
``olmoe``, ``joyai``, ``nemotron_h``, ``lfm2``, ``kimi_linear``,
``smallthinker``, ``laguna``, ``qwen3_next``), the token table's lookup (``olmoe`` and
the six below), the rotary embedding over the whole head or its first
lanes at given frequencies (``llama._rope``, so ``olmoe``, ``lfm2``,
``smallthinker`` and ``olmo_hybrid``; ``laguna``'s two rotations), the
repeat of grouped key/value heads (what the attention of
``ops/`` does by index since PR 55: the tests' and the references'
yardstick), the SwiGLU MLP and its dense sublayer (``joyai``,
``lfm2``, ``kimi_linear``, ``laguna``), and what the seven models that hold
ONE CHIP'S SHARE of an expert-parallel layer (``joyai``, ``nemotron_h``,
``lfm2``, ``kimi_linear``, ``smallthinker``, ``laguna``, ``qwen3_next``)
have in common: the router's
balance bias — its key in the parameter tree, the predicate
``optim.with_balance_bias`` partitions the leaves by, and the way a
step's loads reach that rule in the gradient tree at the bias's place —
the routed-expert sublayer, whose router runs once a step (its decision
crosses the layer's checkpoint by name: ``checkpoint_layer``), the record
of a forward pass's expert layers and the terms of the loss.

Those models' whole gradient programs are pinned instruction by
instruction and scope by scope (``tests/test_nemotron_h.py::
test_the_gated_expert_paths_are_what_they_were``): ``make_jaxpr`` keeps
what nobody reads, so a line added here is a line in every pin.
``ops.moe.top_k_routing`` is looked up on ``moe`` at call time: the
``benchmark/tests/*_faults.py`` files put their stand-ins there."""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from torchft_tpu.ops import moe
from torchft_tpu.ops.dsa import KEY_CHOICE

__all__ = ["rms_norm", "embed", "rotary", "repeat_kv", "swiglu",
           "dense_sublayer",
           "BALANCE_BIAS", "is_balance_bias", "loads_as_gradient",
           "ROUTER_CHOICE", "KEY_CHOICE", "checkpoint_layer",
           "routed_sublayer", "routing_record", "share_loss_terms"]


def rms_norm(x, scale, eps: float):
    var = jnp.mean(
        jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True
    )
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("embed")
def embed(cfg, params: Dict, tokens):
    return params["wte"]["embedding"].astype(cfg.dtype)[tokens]


def rotary(x, freqs, factor: float = 1.0):
    """Rotary embedding of ``x [B, S, H, D]`` in the ``rotate_half`` form
    over the head's FIRST ``2·len(freqs)`` lanes: with ``r`` =
    ``len(freqs)``, position ``t`` turns lanes ``i`` and ``i + r`` by
    ``t · freqs[i]``; the lanes beyond ``2r`` pass as they are (a partial
    rotation). ``cos`` and ``sin`` are both multiplied by ``factor``
    (YaRN's ``attention_factor``; 1.0 leaves the table alone). The table
    and the arithmetic are float32. ``models/llama.py::_rope`` is this
    over the whole head at ``theta^(-i / r)``, and traces to the program
    it always traced to (``tests/test_laguna.py`` pins it)."""
    s, d = x.shape[1], x.shape[-1]
    half = freqs.shape[0]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]   # [1, S, 1, r]
    sin = jnp.sin(angles)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    turned = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    ).astype(x.dtype)
    if 2 * half == d:
        return turned
    return jnp.concatenate([turned, x[..., 2 * half:]], axis=-1)


def repeat_kv(kv, n_heads: int):
    """``[B, S, KV, D]`` -> ``[B, S, n_heads, D]``: query head ``i`` reads
    key/value head ``i // (n_heads / KV)``, by a copy. No model's program
    holds it since PR 55 (``ops/flash.py`` and ``ops/attention.py`` read a
    key/value head where it lies): it is what they are held against, in
    the tests, and what a kernel of equal head counts only is handed
    (``examples/train_llama_ring.py``)."""
    return jnp.repeat(kv, n_heads // kv.shape[2], axis=2)


def swiglu(h, m: Dict, dt):
    g = h @ m["gate_proj"]["kernel"].astype(dt)
    u = h @ m["up_proj"]["kernel"].astype(dt)
    return (jax.nn.silu(g) * u) @ m["down_proj"]["kernel"].astype(dt)


@jax.named_scope("mlp")
def dense_sublayer(cfg, x, scale, m: Dict):
    """``x + SwiGLU(RMSNorm(x))``: ``scale`` the norm's weight, ``m`` the
    three matrices."""
    return x + swiglu(rms_norm(x, scale, cfg.rms_eps), m, cfg.dtype)


# the key of a router's balance bias in the parameter tree
BALANCE_BIAS = "balance_bias"


def is_balance_bias(path) -> bool:
    """Whether a ``jax.tree_util`` key path ends at a balance bias: the
    predicate ``optim.with_balance_bias`` partitions the leaves by."""
    return getattr(path[-1], "key", None) == BALANCE_BIAS


# the function's own name stands in every program traced through it
# (``custom_vjp_call[name=...]``) and those programs are pinned by hash
# (tests/test_joyai.py, tests/test_nemotron_h.py): it keeps the name it
# was first traced under
@jax.custom_vjp
def _loads_as_gradient(bias, loads):
    """Adds 0 to the loss; its cotangent for ``bias`` is ``loads``. The
    balance bias has no gradient of its own (it only selects), so its
    place in the gradient tree carries what its update rule reads: the
    step's assignments per expert, averaged over replica groups with the
    gradients."""
    return jnp.zeros((), jnp.float32)


def _loads_fwd(bias, loads):
    return jnp.zeros((), jnp.float32), loads


def _loads_bwd(loads, g):
    return (g * loads).astype(loads.dtype), jnp.zeros_like(loads)


_loads_as_gradient.defvjp(_loads_fwd, _loads_bwd)
loads_as_gradient = _loads_as_gradient


# the one name under which a router's decision crosses a layer's
# ``jax.checkpoint`` (:func:`checkpoint_layer`)
ROUTER_CHOICE = "router_choice"
# and ``ops/dsa.py``'s ``KEY_CHOICE``, under which an attention's choice
# of KEYS does (``models/keye.py``: the packed sets of ``dsa.select`` and
# their log-sum-exp, tagged where they are made): the backward pass and the
# forward run again read the sets the forward pass chose; a set chosen
# again from a stream rounded again would differentiate another function


def checkpoint_layer(run: Callable) -> Callable:
    """``jax.checkpoint`` of a layer that keeps what its router decided:
    the backward pass runs the layer's forward again but for the values
    tagged :data:`ROUTER_CHOICE` (``_route_fwd``: the experts chosen,
    their weights, the chosen scores, the loads — three ``[N, k]`` arrays
    and an ``[E]``) or :data:`KEY_CHOICE`, which the forward pass saves.
    Nothing else is saveable; a layer without a router or a choice of
    keys is checkpointed as by plain ``jax.checkpoint``."""
    return jax.checkpoint(
        run, policy=jax.checkpoint_policies.save_only_these_names(
            ROUTER_CHOICE, KEY_CHOICE))


class _How(NamedTuple):
    """A sublayer's routing: static, and hashable for ``_route``."""
    top_k: int
    score: str          # "sigmoid" | "softmax"
    scale: float
    eps: float
    n_routed: int


def _weigh(how: _How, inputs, bias):
    """``moe.top_k_routing`` on what the router hands it — ``sigmoid`` of
    the logits, or the logits themselves for ``score="softmax"`` — with
    the sublayer's arguments, looked up on ``moe`` at call time: over all
    the experts in the forward pass, over the chosen columns in the
    backward (``_route_bwd``)."""
    if how.score == "sigmoid":
        return moe.top_k_routing(inputs, how.top_k, bias=bias,
                                 renormalise=True, scale=how.scale,
                                 eps=how.eps)
    return moe.top_k_routing(inputs, how.top_k, bias=bias, softmax=True,
                             scale=how.scale)


def _route_fwd(how: _How, r32, kernel, bias):
    scores = jnp.dot(r32, kernel, precision=jax.lax.Precision.HIGHEST)
    inputs = jax.nn.sigmoid(scores) if how.score == "sigmoid" else scores
    weights, experts = _weigh(how, inputs, bias)
    loads = jnp.zeros((how.n_routed,), jnp.float32).at[
        experts.reshape(-1)].add(1.0)
    # the routing's own gather again: one instruction once compiled
    chosen = moe.take_chosen(inputs, experts)
    weights, experts, loads, chosen = (
        checkpoint_name(a, ROUTER_CHOICE)
        for a in (weights, experts, loads, chosen))
    return (weights, experts, loads), (r32, kernel, bias, experts, chosen)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _route(how: _How, r32, kernel, bias):
    """``(weights [N, k], experts [N, k], loads [E])`` of the float32
    router input ``r32 [N, d]``: the scores' matmul, the routing, the
    count. Its backward is ``k`` wide (``_route_bwd``)."""
    return _route_fwd(how, r32, kernel, bias)[0]


def _route_bwd(how: _How, res, cotangents):
    """The weights' cotangent through the weighting of the CHOSEN columns
    alone — ``moe.top_k_routing`` again, on the ``[N, k]`` slice of what
    it was handed, the chosen experts' bias beside it: ``k`` of ``k``
    columns that stand in the order it chose them in come back in that
    order —, through the sigmoid where there is one, put back at its
    columns of a zero ``[N, E]`` (``moe.take_chosen`` transposed), then
    the matmul's two products. The choice differentiated is the one the
    forward pass made; nothing reaches the bias, and the loads are a
    count."""
    r32, kernel, bias, experts, chosen = res
    with jax.named_scope("moe_router"):
        _, pull = jax.vjp(
            lambda c: _weigh(how, c, moe.take_chosen(bias[None], experts))[0],
            chosen)
        g_chosen, = pull(cotangents[0])
        if how.score == "sigmoid":
            g_chosen = g_chosen * chosen * (1 - chosen)
        all_columns = jax.ShapeDtypeStruct(
            (experts.shape[0], how.n_routed), chosen.dtype)
        g_scores, = jax.linear_transpose(
            lambda s: moe.take_chosen(s, experts), all_columns)(g_chosen)
        highest = jax.lax.Precision.HIGHEST
        return (jnp.dot(g_scores, kernel.T, precision=highest),
                jnp.dot(r32.T, g_scores, precision=highest),
                jnp.zeros_like(bias))


_route.defvjp(_route_fwd, _route_bwd)


@jax.named_scope("mlp")
def routed_sublayer(cfg, x, scale, m: Dict, *,
                    shared: Optional[Callable] = None,
                    renorm_eps: float = 1e-20,
                    route_on: Any = None, score: str = "sigmoid",
                    activation: Optional[str] = None) -> Tuple[Any, Dict]:
    """A share's routed-expert sublayer, ``(x + y, record)``: on ``h =
    RMSNorm(x)`` (weight ``scale``), ``s = sigmoid(h·W_r)`` in float32
    over all ``cfg.n_routed_experts``; the ``cfg.top_k`` largest of ``s +
    b`` choose, ``b`` the balance bias; the chosen ``s``, renormalised
    (``renorm_eps`` beside the sum) and scaled by ``cfg.routed_scale``,
    weigh the experts held here (``cfg.first_expert`` on; ``m`` holds
    their ``up_proj`` / ``down_proj`` and, for gated experts,
    ``gate_proj``), and ``shared(h)`` is added where a model has a shared
    expert. The record: ``experts`` [N, top_k], ``loads`` [routed]
    (float32 counts), and ``carrier``, the zero that hands the loads to
    the bias's place in the gradient tree.

    The router runs once a step. From the scores' matmul to ``(weights,
    experts, loads)`` it is one ``custom_vjp`` (``_route``) whose backward
    reads the chosen experts and their scores and nothing else ``[N, E]``
    wide, and those, with the weights and the loads, are tagged
    :data:`ROUTER_CHOICE`: a layer under :func:`checkpoint_layer` keeps
    them, so its backward pass recomputes the norm (the experts read
    ``h`` again) and not the matmul, the top-k or the count. The WEIGHTS
    are among what is kept because the experts' combine reads them in its
    own backward: left out, the recomputation that rebuilds them is the
    whole router again.

    What SmallThinker changes, each a default that leaves the other
    models' traced programs what they are: ``route_on`` [B, S, d], the
    tensor the router scores where it is NOT the experts' input (that
    model's router reads the layer's input norm, before attention; in
    float32, before it is rounded); ``score="softmax"``: the top of the
    logits ``z + b`` choose and the weights are ``softmax`` over the
    chosen ``z`` (= the softmax over all, renormalised) times
    ``cfg.routed_scale``; ``activation``, the expert's, handed to
    ``moe.moe_mlp`` (``"reglu"``)."""
    B, S, d = x.shape
    assert score in ("sigmoid", "softmax"), score
    with jax.named_scope("moe_router"):
        if route_on is None:
            h32 = rms_norm(x.astype(jnp.float32), scale,
                           cfg.rms_eps).reshape(B * S, d)
            r32 = h32
        else:
            r32 = route_on.astype(jnp.float32).reshape(B * S, -1)
        # as models/olmoe.py: the router reads the normed stream before
        # it is rounded to the compute dtype, in true float32
        weights, experts, loads = _route(
            _How(cfg.top_k, score, cfg.routed_scale, renorm_eps,
                 cfg.n_routed_experts),
            r32, m["router"]["kernel"].astype(jnp.float32), m[BALANCE_BIAS])
        carrier = loads_as_gradient(
            m[BALANCE_BIAS], loads.astype(m[BALANCE_BIAS].dtype))
    if route_on is None:
        h = h32.astype(cfg.dtype)
    else:
        # the experts' own norm: the rows the dispatch gathers
        with jax.named_scope("moe_dispatch"):
            h = rms_norm(x, scale, cfg.rms_eps).reshape(B * S, d)
    also = None
    if shared is not None:
        with jax.named_scope("moe_shared"):
            also = shared(h)
    y = moe.moe_mlp(
        h, weights, experts,
        m["gate_proj"]["kernel"] if "gate_proj" in m else None,
        m["up_proj"]["kernel"], m["down_proj"]["kernel"],
        n_routed=cfg.n_routed_experts, first_expert=cfg.first_expert,
        activation=activation,
    )
    if also is not None:
        y = y + also
    return x + y.reshape(B, S, d), {
        "experts": experts, "loads": loads, "carrier": carrier}


def routing_record(records: List[Dict]) -> Dict[str, Any]:
    """The record of a forward pass from its expert layers' records, in
    order: ``experts`` [L_e, N, top_k], ``loads`` [L_e, routed] and
    ``carrier``, the sum of theirs; of a model without an expert layer,
    a zero ``carrier`` alone (made either way: the pinned programs hold
    it)."""
    out: Dict[str, Any] = {"carrier": jnp.zeros((), jnp.float32)}
    if records:
        out = dict(
            experts=jnp.stack([r["experts"] for r in records]),
            loads=jnp.stack([r["loads"] for r in records]),
            carrier=sum(r["carrier"] for r in records),
        )
    return out


def share_loss_terms(cfg, h, rec: Dict, ce, *,
                     more_loss: Optional[Callable] = None,
                     held_share: bool = True) -> Dict[str, Any]:
    """A share's ``loss_terms`` from its final-norm ``hidden`` states
    ``h``, the forward pass's record and the mean next-token cross
    entropy ``ce`` (each model calls its own module's ``ce_from_hidden``,
    through its own head: a faults file replaces it there): ``loss`` =
    ``ce`` + the balance bias's carrier, which adds 0, + ``more_loss(rec)``
    where a model trains on more (JoyAI's MTP term); the routing
    ``experts`` and ``loads``; per expert layer ``rows_held``
    (assignments on this share's experts), ``held_share`` (of all
    ``N·top_k``; JoyAI's terms never had it and its pinned program does
    not compute it) and ``load_max_over_mean``."""
    loss = ce + rec.pop("carrier")
    if more_loss is not None:
        loss = loss + more_loss(rec)
    out = dict(rec, ce=ce, loss=loss, hidden=h)
    if "loads" in rec:
        loads = rec["loads"]
        held = slice(cfg.first_expert, cfg.first_expert + cfg.n_experts_held)
        out["rows_held"] = jnp.sum(loads[:, held], axis=-1)
        if held_share:
            out["held_share"] = out["rows_held"] / jnp.sum(loads, axis=-1)
        out["load_max_over_mean"] = (
            jnp.max(loads, axis=-1) / jnp.mean(loads, axis=-1))
    return out
