"""The router of a held-expert sublayer runs once a step (PR 64): what it
decided in the forward pass — the experts, their weights, the chosen
scores, the loads — crosses the layer's ``jax.checkpoint`` by name
(``common.checkpoint_layer``), and its backward (``common._route_bwd``)
reads nothing else ``[N, E]`` wide. Held here, per model, on the compiled
gradient program; and the seam: ``moe.top_k_routing`` is looked up on
``moe`` by the backward as by the forward."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the delta rule of two of the seven at one head a grid step: what these
# programs cost on the CPU is compiling (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("one_head_a_step")

from benchmark.tests import lfm2_faults, smallthinker_faults
from torchft_tpu.models import (
    common,
    joyai,
    kimi_linear,
    laguna,
    lfm2,
    nemotron_h,
    qwen3_next,
    smallthinker,
)
from torchft_tpu.ops import moe

# model -> (module, tiny config, the loss of ``_batch`` on ``init_params(
# key 0)`` with ``remat`` on — the parent's (3928a3e), bit for bit —, and
# the distance |g_remat - g_plain| / |g_plain| over the whole gradient tree
# between the ``remat=True`` and ``remat=False`` programs: the parent's,
# then this PR's. Recorded on the CPU by running ``_loss_and_grads`` on a
# checkout of each commit.
MODELS = {
    "joyai": (joyai, joyai.JOYAI_CONFIGS["joyai_tiny"],
              8.787317276000977, 1.8636e-4, 1.8658e-4),
    "kimi_linear": (
        kimi_linear, kimi_linear.KIMI_LINEAR_CONFIGS["kimi_linear_tiny"],
        6.807103157043457, 1.7725e-2, 8.8424e-4),
    "laguna": (laguna, laguna.LAGUNA_CONFIGS["laguna_tiny"],
               6.55991792678833, 7.3779e-3, 2.3129e-4),
    "lfm2": (lfm2, lfm2.LFM2_CONFIGS["lfm2_tiny"],
             6.605484962463379, 1.1753e-2, 2.7591e-4),
    "nemotron_h": (
        nemotron_h, nemotron_h.NEMOTRON_H_CONFIGS["nemotron_h_tiny"],
        6.658027648925781, 1.0646e-2, 1.9123e-4),
    "qwen3_next": (
        qwen3_next, qwen3_next.QWEN3_NEXT_CONFIGS["qwen3_next_tiny"],
        5.828680515289307, 5.3045e-3, 1.1951e-3),
    "smallthinker": (
        smallthinker, smallthinker.SMALLTHINKER_CONFIGS["smallthinker_tiny"],
        6.523646354675293, 6.4619e-3, 3.0989e-4),
}


def _batch(cfg):
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


@functools.lru_cache(maxsize=None)
def _loss_and_grads(model, remat):
    """``(loss, gradient tree, compiled text)`` of the model's
    ``loss_fn`` at its tiny size, seeded."""
    mod, cfg = MODELS[model][:2]
    cfg = dataclasses.replace(cfg, remat=remat)
    params = mod.init_params(cfg, jax.random.key(0))
    tokens, targets = _batch(cfg)
    program = jax.jit(jax.value_and_grad(
        lambda p: mod.loss_fn(cfg, p, tokens, targets))).lower(
            params).compile()
    loss, grads = program(params)
    return float(loss), grads, program.as_text()


def _distance(a, b) -> float:
    """``|a - b| / |b|`` over two gradient trees, in float64."""
    both = zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    num = den = 0.0
    for x, y in both:
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        num, den = num + np.sum((x - y) ** 2), den + np.sum(y ** 2)
    return float(np.sqrt(num / den))


def _router_ops(text):
    """``{(op, pass): count}`` of the compiled program's instructions
    under ``moe_router``: ``op`` ``"top_k"`` (a ``TopK`` call) or
    ``"dot"``; ``pass`` ``"forward"``, ``"recomputed"`` (under the
    checkpoint's ``rematted_computation``) or ``"backward"``."""
    found = {}
    for line in text.splitlines():
        path = re.search(r'op_name="([^"]*)"', line)
        if path is None or "moe_router" not in path.group(1):
            continue
        if 'custom_call_target="TopK"' in line:
            op = "top_k"
        elif re.search(r" dot\(", line):
            op = "dot"
        else:
            continue
        tokens = re.split(r"[/()]", path.group(1))
        which = ("recomputed" if "rematted_computation" in tokens else
                 "backward" if "transpose" in tokens else "forward")
        found[op, which] = found.get((op, which), 0) + 1
    return found


@pytest.mark.parametrize("model", list(MODELS))
def test_the_router_runs_once_a_step(model) -> None:
    """In the compiled gradient program of ``loss_fn`` with ``remat`` on
    there is ONE top-k over the experts and ONE ``[N, d] x [d, E]``
    product a routed layer, both in the forward pass, and the backward's
    two products: the pass the checkpoint runs again holds nothing of
    them (the parent's held a second top-k and a second product a layer).
    The loss is the parent's bit for bit, and the gradient no further from
    the ``remat=False`` program's than the parent's was (to a hundredth:
    JoyAI's two distances are the rounding of its recomputed bf16 layers,
    1.866e-4 against 1.864e-4; the other six fall 4 to 56 times, because
    the parent's recomputed scores were fused otherwise than the forward
    pass's and a near tie then differentiated another choice than the
    forward pass had made)."""
    mod, cfg, loss_was, parent_distance, distance = MODELS[model]
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    layers = jax.eval_shape(
        lambda p, t: mod.loss_terms(cfg, p, t, t), params, tokens)[
            "loads"].shape[0]
    assert layers >= 1
    loss, grads, text = _loss_and_grads(model, True)
    assert _router_ops(text) == {
        ("top_k", "forward"): layers, ("dot", "forward"): layers,
        ("dot", "backward"): 2 * layers}
    assert loss == loss_was
    plain_loss, plain_grads, _ = _loss_and_grads(model, False)
    assert plain_loss == loss_was
    got = _distance(grads, plain_grads)
    assert got <= parent_distance * 1.01
    assert got == pytest.approx(distance, rel=1e-2)


# -- the seam ----------------------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST
N, D, E, K = 64, 32, 16, 4
# stand-in -> (the faults file that defines it, its name there, the tiny
# configuration that file is handed, the score it stands in for, whether
# its weights read the chosen columns alone)
STAND_INS = {
    "no_renormalise": (lfm2_faults, "no_renormalise",
                       lfm2.LFM2_CONFIGS["lfm2_tiny"], "sigmoid", True),
    "bias_weighting": (lfm2_faults, "bias_weighting",
                       lfm2.LFM2_CONFIGS["lfm2_tiny"], "sigmoid", True),
    "router_bf16": (lfm2_faults, "router_bf16",
                    lfm2.LFM2_CONFIGS["lfm2_tiny"], "sigmoid", True),
    "over_all": (smallthinker_faults, "no_renormalise",
                 smallthinker.SMALLTHINKER_CONFIGS["smallthinker_tiny"],
                 "softmax", False),
}


def _router_inputs():
    keys = jax.random.split(jax.random.key(64), 4)
    return (jax.random.normal(keys[0], (N, D), jnp.float32),
            0.3 * jax.random.normal(keys[1], (D, E), jnp.float32),
            0.05 * jax.random.normal(keys[2], (E,), jnp.float32),
            jax.random.normal(keys[3], (N, K), jnp.float32))


def _routing(how, inputs, bias):
    """The sublayer's call of ``moe.top_k_routing``, as the parent's
    ``routed_sublayer`` wrote it out."""
    k, score, scale, eps, _ = how
    if score == "sigmoid":
        return moe.top_k_routing(inputs, k, bias=bias, renormalise=True,
                                 scale=scale, eps=eps)
    return moe.top_k_routing(inputs, k, bias=bias, softmax=True, scale=scale)


def _plain(how, r32, kernel, bias):
    """The router without the ``custom_vjp``: plain autodiff through all
    ``E`` columns, as the parent's program."""
    scores = jnp.dot(r32, kernel, precision=HIGHEST)
    inputs = jax.nn.sigmoid(scores) if how.score == "sigmoid" else scores
    return _routing(how, inputs, bias)


def _on_the_chosen(how, experts, r32, kernel, bias):
    """The weighting as a function of the chosen columns alone, the choice
    held: what a ``k``-wide backward differentiates."""
    scores = jnp.dot(r32, kernel, precision=HIGHEST)
    inputs = jax.nn.sigmoid(scores) if how.score == "sigmoid" else scores
    return _routing(how, moe.take_chosen(inputs, experts),
                    moe.take_chosen(bias[None], experts))


def _value_and_grads(route, probe, r32, kernel, bias):
    """``((weights, experts), (d r32, d kernel))`` of ``sum(weights *
    probe)``."""
    def loss(r32, kernel):
        weights, experts = route(r32, kernel, bias)[:2]
        return jnp.sum(weights * probe), (weights, experts)
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
        r32, kernel)
    return out, grads


def _rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_the_k_wide_backward_is_plain_autodiff(score) -> None:
    """``common._route`` against the same lines under plain autodiff: the
    outputs bit for bit, the gradient to float32 rounding, for both ways
    of scoring; its loads are the count of the choice and its bias gets
    zeros."""
    how = common._How(K, score, 2.5, 1e-6, E)
    r32, kernel, bias, probe = _router_inputs()
    (w, e), (d_r, d_w) = _value_and_grads(
        functools.partial(common._route, how), probe, r32, kernel, bias)
    (want_w, want_e), (want_r, want_k) = _value_and_grads(
        functools.partial(_plain, how), probe, r32, kernel, bias)
    assert np.array_equal(w, want_w) and np.array_equal(e, want_e)
    assert _rel(d_r, want_r) < 1e-6 and _rel(d_w, want_k) < 1e-6
    loads = common._route(how, r32, kernel, bias)[2]
    assert np.array_equal(loads, np.bincount(np.asarray(e).ravel(),
                                             minlength=E))
    d_bias = jax.grad(lambda b: jnp.sum(
        common._route(how, r32, kernel, b)[0] * probe))(bias)
    assert not np.any(d_bias)


@pytest.mark.parametrize("stand_in", list(STAND_INS))
def test_a_stand_in_for_the_routing_reaches_the_backward(
        stand_in, monkeypatch) -> None:
    """``moe.top_k_routing`` replaced as ``benchmark/tests/lfm2_faults.py``
    and ``smallthinker_faults.py`` replace it: the forward pass changes as
    the plain program's does, bit for bit, and the router's gradient is
    plain autodiff of the stand-in on the chosen columns — which, for the
    three whose weights read the chosen columns alone, is plain autodiff
    of the stand-in itself, and not the sound router's. ``over_all`` weighs
    by the softmax over ALL the logits: its forward is its own, and its
    backward is the stand-in's on the ``k`` logits it is handed — their
    softmax, which is the sound weighting —, not the one over ``E`` columns
    that a ``k``-wide backward cannot see; what ``smallthinker_faults.py``
    reads of that fault is the forward pass."""
    faults, name, cfg, score, chosen_alone = STAND_INS[stand_in]
    how = common._How(K, score, 2.5, 1e-6, E)
    r32, kernel, bias, probe = _router_inputs()
    route = functools.partial(common._route, how)
    (sound_w, sound_e), sound_grads = _value_and_grads(
        route, probe, r32, kernel, bias)
    patches, *_ = faults.fault(name, cfg, None)
    assert [(mod, attr) for mod, attr, _ in patches] == [
        (moe, "top_k_routing")]
    monkeypatch.setattr(moe, "top_k_routing", patches[0][2])
    (w, e), (d_r, d_w) = _value_and_grads(route, probe, r32, kernel, bias)
    (want_w, want_e), plain_grads = _value_and_grads(
        functools.partial(_plain, how), probe, r32, kernel, bias)
    # the forward pass changes as it did
    assert np.array_equal(w, want_w) and np.array_equal(e, want_e)
    assert not np.array_equal(w, sound_w)
    # and the backward is the stand-in's
    _, (chosen_r, chosen_k) = _value_and_grads(
        functools.partial(_on_the_chosen, how, e), probe, r32, kernel, bias)
    assert _rel(d_r, chosen_r) < 1e-6 and _rel(d_w, chosen_k) < 1e-6
    if chosen_alone:
        assert _rel(d_r, plain_grads[0]) < 1e-6
        assert _rel(d_w, plain_grads[1]) < 1e-6
        assert _rel(d_r, sound_grads[0]) > 1e-3
    else:
        assert _rel(d_r, plain_grads[0]) > 1e-3
        assert _rel(d_r, sound_grads[0]) < 1e-6
