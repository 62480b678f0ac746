"""The row buffer of a held share (``ops/moe.py``, PR 39): a layer that
holds ``n_held`` of ``n_routed`` experts gathers, activates and combines
``held_capacity`` rows a pass, in as many passes as its held rows take
— one program, dropless — and ``optim.routing_gauges`` says how many
routers are done in one. On the CPU the
grouped matmuls run in Pallas's interpreter."""

from __future__ import annotations

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu import optim
from torchft_tpu.models import common, joyai, lfm2, nemotron_h
from torchft_tpu.ops import moe
from torchft_tpu.utils.metrics import Metrics

N, K, D, F = 1024, 2, 32, 48
ROUTED, HELD, FIRST = 8, 2, 2
CAPACITY = 1024          # of 2048 assignments: 512 expected x 1.25, to a tile


def _weights(n_held, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (N, D)),
            jax.random.normal(k[1], (n_held, D, F)) * 0.2,
            jax.random.normal(k[2], (n_held, D, F)) * 0.2,
            jax.random.normal(k[3], (n_held, F, D)) * 0.2)


def _routing(held_rows, seed=1):
    """``[N, K]`` weights and expert ids with exactly ``held_rows``
    assignments on experts ``FIRST .. FIRST + HELD``, spread over the
    tokens; a token's two experts differ."""
    rng = np.random.default_rng(seed)
    on_held = np.zeros(N * K, bool)
    on_held[rng.permutation(N * K)[:held_rows]] = True
    held = list(range(FIRST, FIRST + HELD))
    absent = [e for e in range(ROUTED) if e not in held]
    experts = np.empty((N, K), np.int32)
    for n, (a, b) in enumerate(on_held.reshape(N, K)):
        first = rng.choice(held if a else absent)
        experts[n] = first, rng.choice(
            [e for e in (held if b else absent) if e != first])
    weights = jax.nn.softmax(
        jax.random.normal(jax.random.key(seed), (N, K)) * 2.0)
    return weights, jnp.asarray(experts)


def _every_held_expert_on_every_token(h, weights, experts, gate, up, down,
                                      first=FIRST):
    """The plain reference: a matrix that is zero outside the top k."""
    out = 0.0
    for i in range(up.shape[0]):
        w = jnp.sum(jnp.where(experts == first + i, weights, 0), axis=1)
        a = (jnp.square(jax.nn.relu(h @ up[i])) if gate is None
             else jax.nn.silu(h @ gate[i]) * (h @ up[i]))
        out = out + w[:, None] * (a @ down[i])
    return out


def _all_rows(h, weights, experts, gate, up, down):
    """A share over all ``N*k`` rows: what ``moe_mlp`` did before the row
    buffer."""
    local = experts - FIRST
    held = (local >= 0) & (local < HELD)
    return moe._all_rows(h, jnp.where(held, weights, 0),
                         jnp.where(held, local, HELD), gate, up, down, True)


def _row_buffer(h, weights, experts, gate, up, down):
    return moe.moe_mlp(h, weights, experts, gate, up, down, n_routed=ROUTED,
                       first_expert=FIRST)


def _value_and_grads(fn, args, wrt):
    return jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=wrt)(*args)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


@pytest.mark.parametrize("held_rows", [100, CAPACITY, CAPACITY + 1, N * K, 0],
                         ids=["under", "exactly", "one_over", "all", "none"])
@pytest.mark.parametrize("expert", ["swiglu", "relu2"])
def test_the_row_buffer_is_the_full_buffer_and_the_reference(
        expert, held_rows) -> None:
    """Value and every gradient (``h``, the router's weights, ``gate`` /
    ``up`` / ``down``) of the layer over the row buffer, of the same
    share over all ``N*k`` rows (the code before the buffer) and of
    "every held expert on every token" agree, however many passes the
    count of held rows takes (0, 1 or 2 here) — and once more under
    ``jax.checkpoint``, where the layer's own backward pass runs beside
    remat's."""
    assert moe.held_capacity(N * K, HELD, ROUTED) == CAPACITY
    h, gate, up, down = _weights(HELD)
    weights, experts = _routing(held_rows)
    local = experts - FIRST
    assert int(jnp.sum((local >= 0) & (local < HELD))) == held_rows
    args = (h, weights, experts, gate if expert == "swiglu" else None, up,
            down)
    wrt = (0, 1, 3, 4, 5) if expert == "swiglu" else (0, 1, 4, 5)
    with jax.default_matmul_precision("highest"):
        got, got_grads = _value_and_grads(_row_buffer, args, wrt)
        np.testing.assert_allclose(
            _row_buffer(*args),
            _every_held_expert_on_every_token(*args), atol=2e-5)
        np.testing.assert_allclose(_row_buffer(*args), _all_rows(*args),
                                   atol=2e-5)
        again, again_grads = _value_and_grads(
            jax.checkpoint(_row_buffer), args, wrt)
        assert float(again) == float(got)
        for other in (_all_rows, _every_held_expert_on_every_token):
            want, want_grads = _value_and_grads(other, args, wrt)
            assert float(got) == pytest.approx(float(want), abs=2e-3)
            for a, b, c in zip(got_grads, want_grads, again_grads):
                assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a)))
                if held_rows == 0:
                    assert not np.any(a) and not np.any(b)
                else:
                    assert _rel(a, b) < 1e-5 and _rel(c, b) < 1e-5


def test_no_array_of_all_assignments_on_a_shares_path() -> None:
    """Forward and backward of a share's layer hold no ``[N*k, d]`` or
    ``[N, k, d]`` array: the only things of ``N*k`` rows are the sort's
    index vectors and the router weights' gradient."""
    h, gate, up, down = _weights(HELD)
    weights, experts = _routing(100)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(_row_buffer(*a)), argnums=(0, 1, 3, 4, 5)))(
            h, weights, experts, gate, up, down))
    assert "_share_mlp" in text and f"[{CAPACITY},{D}]" in text
    for rows in (f"[{N * K},", f"[{N},{K},"):
        assert rows + f"{D}]" not in text and rows + f"{F}]" not in text


@pytest.mark.parametrize("n_assignments,n_held,n_routed,want", [
    (131072, 8, 32, 40960),       # lfm2-8b-a1b-ep4's call: 0.3125 of N*k
    (196608, 8, 128, 15360),      # nemotron-3-nano-30b-a3b-ep16's
    (262144, 16, 256, 20480),     # joyai-llm-flash-ep16's
    (131072, 32, 32, 131072),     # the whole layer held
    (131072, 30, 32, 131072),     # nearly: the slack passes N*k
    (2048, 2, 8, 1024), (2048, 1, 8, 512), (2048, 5, 8, 2048),
    (256, 4, 8, 256),             # the whole of a smaller problem
    (1000, 1, 8, 512), (300, 1, 8, 300),
])
def test_held_capacity_is_read_off_the_call(n_assignments, n_held, n_routed,
                                            want) -> None:
    """Expected rows x 1.25 up to the row tile, never above ``N*k``, and
    ``N*k`` itself when the share is the whole layer; the same for a
    traced count (``optim.routing_gauges``')."""
    got = moe.held_capacity(n_assignments, n_held, n_routed)
    assert got == want and isinstance(got, int)
    assert got == n_assignments or got % 512 == 0
    assert got >= min(n_assignments, n_assignments * n_held / n_routed)
    traced = jax.jit(lambda n: moe.held_capacity(n, n_held, n_routed))(
        jnp.int32(n_assignments))
    assert int(traced) == want


@pytest.mark.parametrize("split", [(2, 2, 2, 2), (3, 5), (1, 6, 1), (1,) * 8,
                                   (8,)])
@pytest.mark.parametrize("expert", ["swiglu", "relu2"])
def test_the_shares_add_up_to_the_uncut_layer_over_row_buffers(
        expert, split) -> None:
    """At a size where a share of 1, 2 or 3 of 8 moves a row buffer (512
    or 1 024 of 2 048 rows) and one of 5 or more every row: the parts
    all the shares give are the layer with every expert held."""
    h, gate, up, down = _weights(ROUTED, seed=3)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(4), (N, ROUTED)))
    weights, experts = moe.top_k_routing(scores, K, renormalise=True)
    if expert == "relu2":
        gate = None
    with jax.default_matmul_precision("highest"):
        want = _every_held_expert_on_every_token(
            h, weights, experts, gate, up, down, first=0)
        total, first = jnp.zeros_like(want), 0
        for held in split:
            part = slice(first, first + held)
            total = total + moe.moe_mlp(
                h, weights, experts, None if gate is None else gate[part],
                up[part], down[part], n_routed=ROUTED, first_expert=first)
            first += held
    assert first == ROUTED
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.mark.parametrize("held_rows,want", [(0, 0), (1, 1), (100, 1),
                                            (CAPACITY, 1), (CAPACITY + 1, 2),
                                            (N * K, 2)])
def test_the_number_of_passes_is_data(held_rows, want) -> None:
    """None where nothing is held, one where the held rows fit the
    buffer, as many as it takes where they do not; a pass's group sizes
    are the part of each expert's rows that falls in it, and they add up
    to the held rows."""
    _, experts = _routing(held_rows)
    local = experts - FIRST
    held = (local >= 0) & (local < HELD)
    passes = jax.jit(lambda e: moe._passes(CAPACITY, e, HELD))(
        jnp.where(held, local, HELD))
    assert int(passes.count) == want
    assert passes.order.shape == (2 * CAPACITY,)
    seen = 0
    for i in range(2):
        rows, token, live, inside = moe._pass_rows(passes, i, CAPACITY, N, K)
        assert int(jnp.sum(inside)) == int(jnp.sum(live))
        assert np.all(np.asarray(held).reshape(-1)[
            np.asarray(rows)[np.asarray(live)[:, 0]]])
        seen += int(jnp.sum(live))
    assert seen == held_rows


def _neither_shared_nor_gated(cfg, layer, x):
    """What no model binds: relu² experts (no gate matrix) and no shared
    expert in one call."""
    return common.routed_sublayer(
        cfg, x, layer["norm"]["scale"],
        {k: v for k, v in layer["moe"].items() if k != "shared"})


# the three models' bindings of ``common.routed_sublayer``, and the
# function itself
_FAMILIES = {
    "joyai": (joyai, joyai.JOYAI_CONFIGS["joyai_tiny"], joyai._moe_sublayer),
    "nemotron_h": (nemotron_h,
                   nemotron_h.NEMOTRON_H_CONFIGS["nemotron_h_tiny"],
                   nemotron_h._moe_mixer),
    "lfm2": (lfm2, lfm2.LFM2_CONFIGS["lfm2_tiny"], lfm2._moe_mlp),
    "common": (nemotron_h, nemotron_h.NEMOTRON_H_CONFIGS["nemotron_h_tiny"],
               _neither_shared_nor_gated),
}


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_a_models_expert_layer_over_the_buffer_is_the_layer_over_all_rows(
        monkeypatch, family) -> None:
    """The three models that hold a share, at their test size (4 of 8
    experts) but on 1 024 tokens, where the share moves 1 536 of 2 048
    rows: the sublayer and all its gradients are those of the program
    with no row buffer (``held_capacity`` made ``N*k``: the parent's
    program), and the traced program goes through ``_share_mlp``."""
    model, cfg, sublayer = _FAMILIES[family]
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    layer = model.init_params(cfg, jax.random.key(0))["layers_1"]
    x = jax.random.normal(jax.random.key(1), (2, 512, cfg.d_model))

    def traced_anew():      # jax keeps a function's trace: one a program
        def loss(layer, x):
            y, record = sublayer(cfg, layer, x)
            return jnp.sum(jnp.sin(y)), record["loads"]
        return loss

    with jax.default_matmul_precision("highest"):
        loss = traced_anew()
        assert "_share_mlp" in str(jax.make_jaxpr(loss)(layer, x))
        (got, loads), got_grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(layer, x)
        held = float(jnp.sum(loads[:cfg.n_experts_held]))
        assert 0 < held <= moe.held_capacity(
            1024 * cfg.top_k, cfg.n_experts_held, cfg.n_routed_experts) == 1536
        monkeypatch.setattr(moe, "held_capacity", lambda n, held, routed: n)
        loss = traced_anew()
        assert "_share_mlp" not in str(jax.make_jaxpr(loss)(layer, x))
        (want, _), want_grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(layer, x)
    assert float(got) == pytest.approx(float(want), abs=2e-3)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert _rel(a, b) < 1e-5 or not np.any(b)


# -- the counter that says how often the buffer engages ----------------------


def _state(*loads):
    return {"rule": optim.BalanceBiasState(
        {f"layers_{i}": jnp.asarray(x, jnp.float32)
         for i, x in enumerate(loads)})}


# 2 048 assignments over 8 experts, 2 held at 2 - 3: the buffer is 1 024 rows
_FITS = [128, 128, 500, 524, 128, 128, 256, 256]
_OVER = [128, 128, 500, 525, 128, 128, 256, 255]


@pytest.mark.parametrize("loads,want", [
    ([_FITS, _FITS], 1.0), ([_FITS, _OVER], 0.5), ([_OVER, _OVER], 0.0),
    ([_OVER, _FITS, _FITS, _FITS], 0.75),
])
def test_routing_gauges_count_the_routers_that_fit(loads, want) -> None:
    """``moe_row_buffer_share`` from hand-made loads, with
    ``held_capacity`` of each router's own count; eager and jitted (the
    optimizer's program)."""
    assert moe.held_capacity(sum(_FITS), 2, 8) == 1024 == sum(_FITS[2:4])
    gauges = optim.routing_gauges(_state(*loads), (2, 2))
    assert gauges.shape == (3,) and float(gauges[2]) == want
    assert float(gauges[1]) == pytest.approx(0.5, abs=1e-3)
    jitted = jax.jit(lambda s: optim.routing_gauges(s, (2, 2)))(
        _state(*loads))
    assert np.array_equal(gauges, jitted)


def test_the_gauge_is_absent_where_the_share_was_not_said() -> None:
    """Without ``held`` the two gauges of a share are NaN and
    ``OptimizerWrapper._observe_routing`` emits neither; told the share
    it emits both, at the commit after the one that asked."""
    state = _state(_FITS, _OVER)
    skew, share, fits = optim.routing_gauges(state)
    assert float(skew) == pytest.approx(525 / 256)
    assert np.isnan(float(share)) and np.isnan(float(fits))
    for held, emitted in ((None, False), ((2, 2), True)):
        wrapper = types.SimpleNamespace(metrics=Metrics())
        # a gauges' program, its publisher and its pending result (PR 73:
        # one list for the routing's and a model's step statistics)
        wrapper._late_gauges = [[
            jax.jit(lambda s, held=held: optim.routing_gauges(s, held)),
            functools.partial(optim.OptimizerWrapper._publish_routing,
                              wrapper), None]]
        optim.OptimizerWrapper._observe_routing(wrapper, state)
        assert "moe_load_max_over_mean" not in wrapper.metrics.snapshot()
        jax.block_until_ready(wrapper._late_gauges[0][2])
        optim.OptimizerWrapper._observe_routing(wrapper, state)
        seen = wrapper.metrics.snapshot()
        assert seen["moe_load_max_over_mean"] == pytest.approx(525 / 256)
        assert ("moe_held_share" in seen) == emitted
        assert ("moe_row_buffer_share" in seen) == emitted
        if emitted:
            assert seen["moe_row_buffer_share"] == 0.5


# -- the third activation (PR 50) ---------------------------------------------


def _reglu_on_every_token(h, weights, experts, gate, up, down, first):
    """``relu(h·W_g) ⊙ (h·W_u)``: every held expert on every token."""
    out = 0.0
    for i in range(up.shape[0]):
        w = jnp.sum(jnp.where(experts == first + i, weights, 0), axis=1)
        out = out + w[:, None] * (
            (jax.nn.relu(h @ gate[i]) * (h @ up[i])) @ down[i])
    return out


@pytest.mark.parametrize("path,held_rows", [
    ("row_buffer", 100), ("row_buffer", CAPACITY + 1), ("row_buffer", 0),
    ("all_rows", 100), ("whole_layer", N * K)])
def test_reglu_is_a_static_choice_through_every_path(path, held_rows) -> None:
    """``activation="reglu"`` through ``moe_mlp``'s three ways — the row
    buffer and its hand-written backward in one pass, two and none, a
    share over all ``N*k`` rows, a layer that holds every expert —, value
    and every gradient against "every held expert on every token"; the
    same call without it is SwiGLU, another result; and another name is
    refused."""
    n_held, first, routed = {
        "row_buffer": (HELD, FIRST, ROUTED), "all_rows": (HELD, FIRST, ROUTED),
        "whole_layer": (ROUTED, 0, ROUTED)}[path]
    h, gate, up, down = _weights(n_held)
    weights, experts = _routing(held_rows)

    def layer(h, weights, experts, gate, up, down, activation="reglu"):
        if path == "all_rows":
            local = experts - first
            held = (local >= 0) & (local < n_held)
            return moe._all_rows(
                h, jnp.where(held, weights, 0),
                jnp.where(held, local, n_held), gate, up, down, True,
                activation)
        return moe.moe_mlp(h, weights, experts, gate, up, down,
                           n_routed=routed, first_expert=first,
                           activation=activation)

    def reference(h, weights, experts, gate, up, down):
        return _reglu_on_every_token(h, weights, experts, gate, up, down,
                                     first)

    args, wrt = (h, weights, experts, gate, up, down), (0, 1, 3, 4, 5)
    with jax.default_matmul_precision("highest"):
        got, got_grads = _value_and_grads(layer, args, wrt)
        want, want_grads = _value_and_grads(reference, args, wrt)
        np.testing.assert_allclose(layer(*args), reference(*args), atol=2e-5)
        text = str(jax.make_jaxpr(layer)(*args))
        assert ("_share_mlp" in text) == (path == "row_buffer")
        if held_rows:
            silu = layer(*args, activation=None)
            assert float(jnp.max(jnp.abs(silu - layer(*args)))) > 1e-2
    assert float(got) == pytest.approx(float(want), abs=2e-3)
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a)))
        if held_rows == 0:
            assert not np.any(a) and not np.any(b)
        else:
            assert _rel(a, b) < 1e-5
    if path != "all_rows":          # moe_mlp refuses another name
        with pytest.raises(AssertionError):
            layer(*args, activation="gelu")
