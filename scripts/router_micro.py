"""One routed layer's ROUTER alone on the chip: ``value_and_grad`` of the
norm and ``models/common.py::_route`` under the layer's checkpoint, ms a
call, beside the form the program had before PR 64 (the chosen scores by
``take_along_axis``, plain autodiff, plain ``jax.checkpoint``: written out
here as the yardstick), and the router's pieces alone — the top-k, the
gather and the scatter against their compare-and-select forms, the loads'
count, the routing's second call on ``k`` of ``k`` columns.

    python scripts/router_micro.py [E:k:d:score ...] [--pieces]

Defaults: the seven share cells' three shapes at 32 768 rows (``512:10:2048:
sigmoid`` qwen3next, ``256:8:2048:sigmoid`` joyai / kimi / laguna,
``64:6:1792:softmax`` smallthinker). ~3 min on one chip with ``--pieces``;
on the CPU it gives agreement only (``MICRO_ROWS=256``). PERF.md §6, PR 64,
has the readings this was written for."""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from torchft_tpu.models import common
from torchft_tpu.ops import moe

HIGHEST = jax.lax.Precision.HIGHEST
ROWS = int(os.environ.get("MICRO_ROWS", "32768"))


def routing_by_gather(scores, k, bias=None, renormalise=False, scale=1.0,
                      eps=1e-20, softmax=False):
    """``moe.top_k_routing`` with a bias as it stood before PR 64."""
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias).astype(scores.dtype), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if softmax:
        weights = jax.nn.softmax(weights, axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return (weights * scale if scale != 1.0 else weights), experts


def route_before(how, r32, kernel, bias):
    """The router's lines before PR 64: plain autodiff through ``[N, E]``."""
    scores = jnp.dot(r32, kernel, precision=HIGHEST)
    inputs = jax.nn.sigmoid(scores) if how.score == "sigmoid" else scores
    if how.score == "sigmoid":
        weights, experts = routing_by_gather(
            inputs, how.top_k, bias=bias, renormalise=True, scale=how.scale,
            eps=how.eps)
    else:
        weights, experts = routing_by_gather(
            inputs, how.top_k, bias=bias, softmax=True, scale=how.scale)
    loads = jnp.zeros((how.n_routed,), jnp.float32).at[
        experts.reshape(-1)].add(1.0)
    return weights, experts, loads


def layer(route, how, x, scale, kernel, bias, probe):
    """The norm, the router and a stand-in for what reads them: the
    weights are read again in the backward (as the experts' combine reads
    them) and so is ``h`` (the norm is recomputed)."""
    h32 = common.rms_norm(x.astype(jnp.float32), scale, 1e-6)
    weights, experts, loads = route(how, h32, kernel, bias)
    h = h32.astype(jnp.bfloat16)
    y = jnp.sum(weights * weights * probe) + 1e-6 * jnp.sum(
        h.astype(jnp.float32) ** 2)
    return y, (experts, loads, weights)


def timed(fn, *args, calls=20):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - start) / calls * 1e3, 3)


def run(n_routed, k, d, score):
    how = common._How(k, score, 2.5, 1e-20, n_routed)
    keys = jax.random.split(jax.random.key(0), 4)
    args = (jax.random.normal(keys[0], (ROWS, d), jnp.bfloat16),
            jnp.ones((d,), jnp.float32),
            0.02 * jax.random.normal(keys[1], (d, n_routed), jnp.float32),
            0.01 * jax.random.normal(keys[2], (n_routed,), jnp.float32),
            jax.random.normal(keys[3], (ROWS, k), jnp.float32))
    forms = {
        "before_pr64": jax.checkpoint(
            functools.partial(layer, route_before, how)),
        "this_tree": common.checkpoint_layer(
            functools.partial(layer, common._route, how)),
    }
    first = None
    for name, form in forms.items():
        step = jax.jit(jax.value_and_grad(form, argnums=(0, 2), has_aux=True))
        (_, aux), grads = step(*args)
        line = {"experts": n_routed, "top_k": k, "d": d, "score": score,
                "rows": ROWS, "form": name, "ms": timed(step, *args)}
        if first is None:
            first = (aux, grads)
        else:
            line["experts_loads_weights_equal"] = [
                bool(jnp.array_equal(a, b)) for a, b in zip(aux, first[0])]
            line["dx_dW_rel"] = [
                float(jnp.linalg.norm((a - b).astype(jnp.float32))
                      / jnp.linalg.norm(b.astype(jnp.float32)))
                for a, b in zip(grads, first[1])]
        print("ROUTER " + json.dumps(line), flush=True)


def pieces(n_routed, k):
    """The router's pieces alone, ms a call."""
    keys = jax.random.split(jax.random.key(1), 3)
    scores = jax.random.normal(keys[0], (ROWS, n_routed), jnp.float32)
    bias = 0.01 * jax.random.normal(keys[1], (n_routed,), jnp.float32)
    _, experts = jax.lax.top_k(scores, k)
    g = jax.random.normal(keys[2], (ROWS, k), jnp.float32)
    chosen = moe.take_chosen(scores, experts)
    rows = jnp.arange(ROWS)[:, None]

    def second_call(routing):
        return lambda c, b, g: jax.vjp(lambda z: routing(
            jax.nn.sigmoid(z), k, bias=b, renormalise=True, scale=2.5)[0],
            c)[1](g)

    table = {
        "top_k": (lambda s: jax.lax.top_k(s, k), (scores,)),
        "chosen_by_gather": (
            lambda s, e: jnp.take_along_axis(s, e, axis=-1),
            (scores, experts)),
        "chosen_by_select": (moe.take_chosen, (scores, experts)),
        "bias_by_gather": (lambda b, e: b[e], (bias, experts)),
        "bias_by_select": (lambda b, e: moe.take_chosen(b[None], e),
                           (bias, experts)),
        "put_back_by_scatter": (
            lambda g, e: jnp.zeros((ROWS, n_routed), g.dtype).at[
                rows, e].add(g), (g, experts)),
        "put_back_by_select": (
            lambda g, e: jax.linear_transpose(
                lambda s: moe.take_chosen(s, e), scores)(g), (g, experts)),
        "loads_by_scatter": (
            lambda e: jnp.zeros((n_routed,), jnp.float32).at[
                e.reshape(-1)].add(1.0), (experts,)),
        "loads_by_select": (
            lambda e: jnp.sum(
                e[..., None] == jnp.arange(n_routed, dtype=e.dtype),
                axis=(0, 1), dtype=jnp.float32), (experts,)),
        "second_call_by_gather": (
            second_call(routing_by_gather), (chosen, bias[experts], g)),
        "second_call_by_select": (
            second_call(moe.top_k_routing), (chosen, bias[experts], g)),
    }
    for name, (fn, args) in table.items():
        print("PIECE " + json.dumps({
            "experts": n_routed, "top_k": k, "rows": ROWS, "piece": name,
            "ms": timed(jax.jit(fn), *args)}), flush=True)


def main(argv) -> int:
    cases = [a for a in argv if not a.startswith("--")] or [
        "512:10:2048:sigmoid", "256:8:2048:sigmoid", "64:6:1792:softmax"]
    print("DEVICE " + json.dumps({
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind}), flush=True)
    for case in cases:
        n_routed, k, d, score = case.split(":")
        if "--pieces" in argv:
            pieces(int(n_routed), int(k))
        run(int(n_routed), int(k), int(d), score)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
