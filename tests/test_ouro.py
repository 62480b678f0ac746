"""``models/ouro.py`` on the CPU at the tiny size, seeded weights: the system
against ``benchmark/reference/ouro_f32.py`` — the objective, every pass's
per-token cross entropy, the exit distribution and every gradient leaf, in
f32 and in the cell's precision —, a layer's gradient as the sum over its
four uses, the exit distribution's remainder, one pass as a plain model,
and ``ops/xent.py``'s weighted sweep against the dense log-softmax."""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import ouro as family
from benchmark.reference import ouro_f32
from torchft_tpu.models import ouro
from torchft_tpu.models.ouro import OURO_CONFIGS, init_params, loss_fn, loss_terms
from torchft_tpu.ops.xent import chunked_cross_entropy, weighted_cross_entropy

BF16 = OURO_CONFIGS["ouro_tiny"]
# float32 compute: the comparison is of the mathematics, not of bf16
TINY = dataclasses.replace(BF16, dtype=jnp.float32)
# the cell's own program: a checkpoint a layer-step, the head in two tiles
CELL = dataclasses.replace(BF16, remat=True, xent_chunks=2)
SEQ = 40


def batch(cfg, seed=1, rows=2, seq=SEQ):
    tokens = jax.random.randint(jax.random.key(seed), (rows, seq), 0,
                                cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def seeded(cfg, seed=0):
    """Initial weights with the norms' weights drawn and the gate's bias
    moved as the cell's check seeds them: a norm left out would otherwise
    show nowhere."""
    return family.seed_check_weights(
        init_params(cfg, jax.random.key(seed)), seed)


def side(terms_fn, params):
    """Terms and the loss's gradient tree, one jitted program."""
    def run(p):
        (_, terms), grads = jax.value_and_grad(
            lambda q: (lambda t: (t["loss"], t))(terms_fn(q)),
            has_aux=True)(p)
        return terms, grads
    return jax.jit(run)(params)


@functools.lru_cache(maxsize=None)
def both_sides(bf16=False):
    """The system — f32 compute, or the cell's bf16 program — and the
    reference on the check's seeded weights and one batch, each once a
    process."""
    params, (tok, tgt) = seeded(TINY), batch(TINY)
    ref = functools.partial(ouro_f32.terms, tokens=tok, targets=tgt,
                            row_block=8, **family.reference_dims(TINY))
    if bf16:
        system = side(lambda p: loss_terms(CELL, p, tok, tgt), params)
    else:
        with jax.default_matmul_precision("highest"):
            system = side(lambda p: loss_terms(
                dataclasses.replace(TINY, remat=True, xent_chunks=2), p, tok,
                tgt), params)
    return system, side(lambda p: ref(p), params)


def leaves(tree):
    return {jax.tree_util.keystr(path): np.asarray(x) for path, x in
            jax.tree_util.tree_leaves_with_path(tree)
            if "exit_stats" not in jax.tree_util.keystr(path)}


def distances(got, want):
    gap = np.abs(np.asarray(got["nll"]) - np.asarray(want["nll"]))
    return (abs(float(got["loss"]) - float(want["loss"])),
            np.sqrt(np.mean(gap ** 2)), gap.max(),
            np.abs(np.asarray(got["p"]) - np.asarray(want["p"])).max())


def test_the_f32_system_is_the_reference() -> None:
    """Scan over the passes, checkpoints, the weighted sweep in two tiles
    and the survival product in logs against four unrolled passes, a dense
    head and the product itself: f32 reassociation through sixteen
    sublayers, read 5e-7 in the loss, 4e-6 in a token's loss, 3e-7 in p,
    2e-6 of a gradient leaf's norm."""
    (got, grads), (want, grads_ref) = both_sides()
    loss, _, worst, p = distances(got, want)
    assert loss < 1e-5 and worst < 5e-5 and p < 5e-6
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5)
    mine, theirs = leaves(grads), leaves(grads_ref)
    assert mine.keys() == theirs.keys() and len(mine) == 2 * 11 + 5
    for name, g in theirs.items():
        assert np.linalg.norm(g) > 1e-3, name        # every leaf is reached
        assert np.linalg.norm(mine[name] - g) <= 2e-5 * np.linalg.norm(g), name


def test_the_bf16_system_is_the_reference_to_bf16s_rounding() -> None:
    """The cell's precision and program: sixteen sublayers of a 64-wide
    stream rounded to bf16 (2^-9 relative a rounding) move a token's loss by
    0.019 rms (0.085 at the worst token), p by 0.010, the objective by 4e-3
    and a gradient leaf by up to 6 % of its norm; each limit is about twice
    its reading. A system a precision lower (an fp8 stream) reads five
    times these at the cell's width (families/ouro.py) and is held at this
    one in test_ouro_family."""
    (got, grads), (want, grads_ref) = both_sides(bf16=True)
    loss, rms, worst, p = distances(got, want)
    assert loss < 0.01 and rms < 0.04 and worst < 0.2 and p < 0.025
    mine, theirs = leaves(grads), leaves(grads_ref)
    for name, g in theirs.items():
        assert np.linalg.norm(mine[name] - g) <= 0.12 * np.linalg.norm(g), name


def test_a_layers_gradient_is_the_sum_of_its_four_uses() -> None:
    """The loop against four COPIES of the stack, a copy a pass: the same
    loss, and a leaf's gradient in the loop is the sum of the copies'. No
    copy's gradient is the whole (each pass pulls its own way)."""
    params, (tok, tgt) = seeded(TINY), batch(TINY)
    stack = {k: v for k, v in params.items() if k.startswith("layers_")}
    rest = {k: v for k, v in params.items() if k not in stack}

    def unlooped(copies):
        x = ouro.embed(TINY, rest, tok)
        streams, gates = [], []
        for layers in copies:
            for i in range(TINY.n_layers):
                x = ouro._layer(TINY, layers[f"layers_{i}"], x,
                                attn_fn=ouro._local_causal_attention)
            x, (stream, gate) = ouro._pass_end(TINY, rest, x)
            streams.append(stream)
            gates.append(gate)
        p, log_p = ouro.exit_distribution(
            jnp.stack(gates).reshape(TINY.ut_steps, -1))
        weighted, nll = ouro._pass_losses(TINY, rest, jnp.stack(streams), tgt,
                                          p / tgt.size)
        return ouro._mix(TINY, p, log_p, weighted, nll)[0]

    with jax.default_matmul_precision("highest"):
        loss, copies = jax.jit(jax.value_and_grad(unlooped))(
            [stack] * TINY.ut_steps)
    got, grads = both_sides()[0]
    assert float(loss) == pytest.approx(float(got["loss"]), abs=1e-5)
    for name, whole in leaves({k: grads[k] for k in stack}).items():
        parts = [leaves(c)[name] for c in copies]
        assert np.linalg.norm(sum(parts) - whole) <= 2e-5 * np.linalg.norm(
            whole), name
        if whole.ndim == 2:
            assert all(np.linalg.norm(part - whole) > 0.05 *
                       np.linalg.norm(whole) for part in parts), name


def test_the_exit_probabilities_add_to_one_and_the_last_is_the_remainder():
    gate = 3.0 * jax.random.normal(jax.random.key(4), (4, 50))
    gate = gate.at[0, 0].set(60.0).at[:, 1].set(-60.0)   # saturated both ways
    p, log_p = ouro.exit_distribution(gate)
    lam = np.asarray(jax.nn.sigmoid(gate), np.float64)
    stay = np.cumprod(1.0 - lam[:3], axis=0)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], atol=1e-6)
    np.testing.assert_allclose(p[1:3], lam[1:3] * stay[:2], atol=1e-6)
    np.testing.assert_allclose(p[3], stay[2], atol=1e-6)      # not λ_4 S_3
    assert np.all(np.isfinite(np.asarray(p * log_p)))
    assert float(p[0, 0]) == 1.0 and float(p[3, 1]) == 1.0
    # the last row of logits (λ_T, which the published code computes too)
    # is read by nothing
    again, _ = ouro.exit_distribution(gate.at[3].set(7.0))
    np.testing.assert_array_equal(again, p)
    terms = both_sides()[0][0]
    np.testing.assert_allclose(terms["p"].sum(axis=0), 1.0, atol=1e-6)
    # the three statistics are what they are called
    passes = np.arange(1, 5)[:, None]
    np.testing.assert_allclose(terms["stats"], [
        np.mean(np.sum(passes * terms["p"], axis=0)),
        -np.mean(np.sum(terms["p"] * np.log(terms["p"]), axis=0)),
        np.mean(terms["nll"][3])], rtol=1e-5)


def test_one_pass_is_a_plain_model_under_a_mean_cross_entropy() -> None:
    """``total_ut_steps`` 1: the gate has nothing to weigh (``p_1`` is the
    remainder, 1), the objective is the mean cross entropy of one pass over
    the stack — ``llama``'s shape with the sandwich norms — and the gate's
    leaves get no gradient."""
    once = dataclasses.replace(TINY, ut_steps=1)
    params, (tok, tgt) = seeded(once), batch(once)
    terms, grads = side(lambda p: loss_terms(once, p, tok, tgt), params)
    assert terms["p"].shape == (1, tok.size) and np.all(
        np.asarray(terms["p"]) == 1.0)
    logits = terms["hidden"].reshape(-1, once.d_model) @ params["lm_head"][
        "kernel"]
    dense = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                 tgt.reshape(-1, 1), axis=-1)
    assert float(terms["loss"]) == pytest.approx(float(jnp.mean(dense)),
                                                 abs=2e-5)
    np.testing.assert_allclose(terms["stats"][:2], [1.0, 0.0], atol=1e-7)
    assert not np.any(grads["exit_gate"]["kernel"])
    assert not np.any(grads["exit_gate"]["bias"])
    want = ouro_f32.loss(params, tok, tgt, row_block=8,
                         **family.reference_dims(once))
    assert float(terms["loss"]) == pytest.approx(float(want), abs=2e-5)


def test_the_statistics_ride_the_gradient_tree_and_move_nothing() -> None:
    """The leaf ``exit_stats`` reads nowhere in the loss; what arrives as
    its gradient is the step's three statistics."""
    terms, grads = both_sides()[0]
    np.testing.assert_array_equal(grads[ouro.EXIT_STATS], terms["stats"])
    params, (tok, tgt) = seeded(TINY), batch(TINY)
    moved = dict(params, **{ouro.EXIT_STATS: params[ouro.EXIT_STATS] + 3.0})
    assert float(loss_fn(TINY, moved, tok, tgt)) == float(
        loss_fn(TINY, params, tok, tgt))


def _dense(x, w, targets, weights):
    nll = -jnp.take_along_axis(jax.nn.log_softmax(x @ w, axis=-1),
                               targets[:, None], axis=-1)[:, 0]
    return jnp.sum(weights * nll), nll


@pytest.mark.parametrize("n, chunks, tiles", [(48, 3, "divides"),
                                              (47, 4, "pads")])
def test_the_weighted_sweep_is_the_dense_log_softmax(n, chunks, tiles):
    """Value, every row's loss, ``dx``, ``dW`` and the weights' gradient
    (the rows' losses), at an ``N`` the tiles divide and at a prime one
    whose last tile is padded; a cotangent on the rows' losses is dropped
    (the module's docstring)."""
    k = jax.random.split(jax.random.key(n), 4)
    x = jax.random.normal(k[0], (n, 16))
    w = 0.3 * jax.random.normal(k[1], (16, 96))
    targets = jax.random.randint(k[2], (n,), 0, 96)
    weights = jax.random.uniform(k[3], (n,)) / n

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, w, wt: fn(x, w, targets, wt), argnums=(0, 1, 2),
            has_aux=True))(x, w, weights)

    (total, nll), grads = both(
        lambda *a: weighted_cross_entropy(*a, chunks))
    (want, nll_ref), grads_ref = both(_dense)
    assert float(total) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(nll, nll_ref, rtol=1e-5, atol=1e-6)
    for g, g_ref in zip(grads, grads_ref):
        np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(grads[2], nll_ref, rtol=1e-5, atol=1e-6)
    # uniform weights 1 / N: the mean sweep's value and gradients
    mean, mean_grads = jax.jit(jax.value_and_grad(
        lambda x, w: chunked_cross_entropy(x, w, targets, chunks),
        argnums=(0, 1)))(x, w)
    (flat, _), flat_grads = both(lambda x, w, t, wt: weighted_cross_entropy(
        x, w, t, jnp.full_like(wt, 1.0 / n), chunks))
    assert float(flat) == pytest.approx(float(mean), rel=1e-6)
    for g, g_ref in zip(flat_grads[:2], mean_grads):
        np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-8)
    dropped = jax.grad(lambda x: jnp.sum(
        weighted_cross_entropy(x, w, targets, weights, chunks)[1]))(x)
    assert not np.any(dropped)


# sha256 of the jaxpr (source positions cut out) of ``chunked_cross_entropy``
# and of its gradient, recorded on the parent of PR 73 (4f1cfa7) BEFORE the
# weighted sweep went in beside it: ``c111m``, ``c1p3b`` and ``olmoe`` call
# it and their programs must not change. Regenerate on purpose only.
_MEAN_SWEEP = {
    "value": "fa5f253238ff7c191f27be15207c2f3a5eea3a7fa0e8df981138725d95705dea",
    "gradient":
        "bf2c674c94ad5d131d669b7fbcbc182d76c871951954622093d76393a901821d",
}


@pytest.mark.parametrize("what", list(_MEAN_SWEEP))
def test_the_mean_sweep_traces_to_the_program_it_traced_to(what) -> None:
    x, w = jnp.zeros((40, 16), jnp.float32), jnp.zeros((16, 96), jnp.float32)
    t = jnp.zeros((40,), jnp.int32)

    def value(x, w):
        return chunked_cross_entropy(x, w, t, 3)

    fn = value if what == "value" else jax.value_and_grad(value,
                                                          argnums=(0, 1))
    text = re.sub(r"/[^ ]*?\.py:\d+", "", str(jax.make_jaxpr(fn)(x, w)))
    assert hashlib.sha256(text.encode()).hexdigest() == _MEAN_SWEEP[what]
