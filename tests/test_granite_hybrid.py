"""``models/granite_hybrid.py`` on the CPU at the tiny size, seeded weights:
the system against ``benchmark/reference/granite_hybrid_f32.py`` — loss,
final hidden state and every gradient leaf, in f32 and in the cell's
precision —, each of the four multipliers, the tied table's two uses, the
vocabulary's share and the scan's own comparison under its stand-ins. The scan runs TWO head blocks a group here
(``ops/ssd.py::_STEP_LANES`` at one lane tile: sixteen heads of 16)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import granite_hybrid as family
from benchmark.reference import granite_hybrid_f32
from benchmark.tests import granite_faults as faults
from torchft_tpu.models import granite_hybrid
from torchft_tpu.models.granite_hybrid import (
    ATTENTION, GRANITE_HYBRID_CONFIGS, MAMBA, init_params, loss_fn,
    loss_terms,
)
from torchft_tpu.ops import ssd

BF16 = GRANITE_HYBRID_CONFIGS["granite_hybrid_tiny"]
# float32 compute: the comparison is of the mathematics, not of bf16
TINY = dataclasses.replace(BF16, dtype=jnp.float32)
# the cell's own program: a checkpoint a layer, the fused cross entropy
CELL = dataclasses.replace(BF16, remat=True, xent_chunks=2)
SEQ = 40            # two and a half chunks of 16: a ragged end


@pytest.fixture(scope="module", autouse=True)
def two_head_blocks_a_group():
    patch = pytest.MonkeyPatch()
    patch.setattr(ssd, "_STEP_LANES", 128)
    assert ssd._head_block(TINY.ssm_heads, TINY.ssm_head_dim, True) == (8, 8)
    yield
    patch.undo()


def batch(cfg, seed=1, rows=2, seq=SEQ):
    tokens = jax.random.randint(jax.random.key(seed), (rows, seq), 0,
                                cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def seeded(cfg, seed=0):
    """Initial weights with the norms' weights, ``D``, ``A_log`` and
    ``dt_bias`` drawn and ``W_q``, ``W_k`` sharpened as the cell's check
    seeds them: a weight left out would otherwise show nowhere, and a flat
    softmax hides its scale."""
    return family.seed_check_weights(
        init_params(cfg, jax.random.key(seed)), seed)


def side(terms_fn, params):
    """Terms and the loss's gradient tree, one jitted program (eager, the
    interpreter's kernels run operation by operation)."""
    @jax.jit
    def run(p):
        terms, pull = jax.vjp(terms_fn, p)
        return terms, pull({"loss": jnp.ones(()),
                            "hidden": jnp.zeros_like(terms["hidden"])})[0]
    return run(params)


_CACHE = {}


def both_sides():
    """System (f32 and bf16 compute) and reference on the same weights
    and batch, once a module."""
    if not _CACHE:
        params, (tok, tgt) = seeded(TINY), batch(TINY)
        _CACHE.update(
            f32=side(lambda p: loss_terms(TINY, p, tok, tgt), params),
            bf16=side(lambda p: loss_terms(CELL, p, tok, tgt), params),
            ref=side(lambda p: granite_hybrid_f32.terms(
                p, tok, tgt, row_block=16, **family.reference_dims(TINY)),
                params))
    return _CACHE


def token_errors(h, h_ref):
    h, h_ref = (np.asarray(z, np.float32).reshape(-1, z.shape[-1])
                for z in (h, h_ref))
    return np.linalg.norm(h - h_ref, axis=-1) / np.linalg.norm(h_ref, axis=-1)


def leaf_errors(grads, grads_ref):
    return {jax.tree_util.keystr(path): float(
        jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(grads_ref))}


def test_the_tiny_configuration_is_a_period_in_miniature() -> None:
    assert TINY.layer_types.count(MAMBA) == 3
    assert TINY.layer_types.count(ATTENTION) == 1
    assert TINY.ssm_groups == 1 and TINY.n_heads // TINY.n_kv_heads == 2
    published = granite_hybrid.GraniteHybridConfig()
    assert published.layer_types[:10] == (MAMBA,) * 5 + (ATTENTION,) + (
        MAMBA,) * 4 and published.n_layers == 40
    assert published.conv_dim == 4352 and published.ssm_inner == 4096
    assert published.attention_multiplier == 1 / 64 != published.head_dim ** -0.5


def test_f32_system_is_the_reference() -> None:
    """Forward, loss and every leaf's gradient: the system in f32 differs
    from the reference by the order of f32 sums alone (the chunked scan
    against the recurrence, the fused cross entropy against the plain
    one): 2e-5 of a leaf's norm is five times what the widest leaf reads
    (4.0e-6) and twenty times the hidden state's 9.6e-7."""
    sides = both_sides()
    (got, grads), (want, grads_ref) = sides["f32"], sides["ref"]
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-5
    assert token_errors(got["hidden"], want["hidden"]).max() < 2e-5
    errors = leaf_errors(grads, grads_ref)
    assert len(errors) == 3 * 13 + 9 + 2
    assert max(errors.values()) < 2e-5, max(errors.items(), key=lambda e: e[1])


def test_bf16_system_is_the_reference_to_bf16s_rounding() -> None:
    """The repo's precision (bf16 activations and matmul operands, f32
    inside norms, the scan's state and the softmax), as the cell runs it
    (a ``jax.checkpoint`` a layer, the fused cross entropy in two chunks:
    neither changes a number beyond the order of sums): a bf16 rounding is
    2^-9 relative and four layers of two sublayers add a dozen of them,
    so a token's hidden state stands within 2 % rms and 4 % at most
    (read: 1.16 % and 2.2 %), the loss within 1e-2 (read: 3.5e-4) and a
    gradient leaf within 12 % of its norm (the widest read 7.1 %: the first
    layer's ``dt_bias``, a 16-vector through every decay; then 5.8 %, the
    attention layer's norm and its sharpened ``W_k``) — the mildest
    multiplier's fault, the softmax at ``head_dim^-1/2``, moves a token's
    hidden state by 18 % and the others by over 100 %."""
    sides = both_sides()
    (got, grads), (want, grads_ref) = sides["bf16"], sides["ref"]
    errors = token_errors(got["hidden"], want["hidden"])
    assert np.sqrt(np.mean(errors ** 2)) < 0.02, np.sqrt(np.mean(errors ** 2))
    assert errors.max() < 0.04, errors.max()
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-2
    leaves = leaf_errors(grads, grads_ref)
    assert max(leaves.values()) < 0.12, max(leaves.items(), key=lambda e: e[1])
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads))


FAULTY = {
    "embedding_multiplier": dict(embedding_multiplier=1.0),
    "residual_multiplier": dict(residual_multiplier=1.0),
    "attention_multiplier": dict(attention_multiplier=BF16.head_dim ** -0.5),
    "logits_scaling": dict(logits_scaling=1.0),
}


@pytest.mark.parametrize("name", list(FAULTY))
def test_each_multipliers_fault_fails_the_comparison(name) -> None:
    """A multiplier at the value a plain decoder would have, in the f32
    system, against the sound reference: the hidden state moves by
    hundreds of times the f32 agreement — but for ``logits_scaling``,
    which the loss alone sees."""
    want = both_sides()["ref"][0]
    params, (tok, tgt) = seeded(TINY), batch(TINY)
    cfg = dataclasses.replace(TINY, **FAULTY[name])
    got = jax.jit(lambda p: loss_terms(cfg, p, tok, tgt))(params)
    worst = token_errors(got["hidden"], want["hidden"]).max()
    gap = abs(float(got["loss"]) - float(want["loss"]))
    if name == "logits_scaling":
        assert worst < 2e-5 and gap > 1e-2, (worst, gap)
    else:
        assert worst > 5e-3, worst


def test_the_tables_gradient_is_the_sum_of_both_uses(monkeypatch) -> None:
    """ONE table: gathered (times ``embedding_multiplier``) and read as the
    head. With the gather behind a ``stop_gradient`` what is left is the
    head's part, dense over the rows; the rest is the gather's, zero on
    every row no token names; the reference's gradient is their sum."""
    params, (tok, tgt) = seeded(TINY), batch(TINY)
    total = both_sides()["f32"][1]["wte"]["embedding"]
    embed = granite_hybrid._embed
    monkeypatch.setattr(
        granite_hybrid, "_embed", lambda cfg, p, t: embed(
            cfg, jax.lax.stop_gradient(p), t))
    head = jax.jit(jax.grad(lambda p: loss_fn(TINY, p, tok, tgt)))(
        params)["wte"]["embedding"]
    gather = np.asarray(total - head)
    named = np.zeros(TINY.vocab_size, bool)
    named[np.asarray(tok).ravel()] = True
    assert np.all(np.abs(np.asarray(head)).sum(-1) > 0)
    assert np.all(np.abs(gather[~named]).max(-1) < 1e-6)
    assert np.all(np.abs(gather[named]).max(-1) > 1e-4)
    np.testing.assert_allclose(
        total, both_sides()["ref"][1]["wte"]["embedding"], atol=2e-6)


def test_a_vocabulary_share_is_the_slice_of_the_uncut_model() -> None:
    """Two shares of the tiny table (rows 0-127 and 128-255), ids drawn
    from the first: the share's hidden states are the uncut model's, its
    logits are the uncut logits' columns, and the two shares' logsumexp
    terms add up to the uncut cross entropy."""
    params = seeded(TINY)
    half = TINY.vocab_size // 2
    cut = dataclasses.replace(TINY, vocab_size=half)
    tok, tgt = batch(cut)
    table = params["wte"]["embedding"]

    def share(rows):
        p = dict(params, wte={"embedding": table[rows]})
        return p

    whole = jax.jit(lambda p: loss_terms(TINY, p, tok, tgt))(params)
    first = jax.jit(lambda p: loss_terms(cut, p, tok, tgt))(
        share(slice(0, half)))
    np.testing.assert_allclose(first["hidden"], whole["hidden"], atol=1e-6)
    h = np.asarray(whole["hidden"], np.float64)
    logits = h @ np.asarray(table, np.float64).T / TINY.logits_scaling
    lse = [np.log(np.exp(logits[..., rows]).sum(-1))
           for rows in (slice(0, half), slice(half, None))]
    target = np.take_along_axis(logits, np.asarray(tgt)[..., None], -1)[..., 0]
    assert float(first["loss"]) == pytest.approx(
        float(np.mean(lse[0] - target)), abs=1e-5)
    assert float(whole["loss"]) == pytest.approx(
        float(np.mean(np.logaddexp(*lse) - target)), abs=1e-5)


@pytest.mark.parametrize("name", ("sound", "scan_state_bf16",
                                  "wrong_head_block", "db_of_one_block"))
def test_the_scans_comparison_tells_its_stand_ins_apart(name) -> None:
    """``scan_comparison`` at sixteen heads on one group, two head blocks
    of eight, f32 operands so that what shows is
    the stand-in and not the results' rounding: the kernels agree with the
    recurrence (the loop that rounds nothing is
    ``tests/test_nemotron_h_family.py``'s); a state in bf16, a head
    block under another's decays and ``dB`` / ``dC`` of one block alone do
    not — the last in ``dB`` and ``dC`` ONLY."""
    cfg = TINY
    scan_fn, passes = faults.SCAN_VARIANTS[name]
    seen = jax.device_get(jax.jit(lambda: family.scan_comparison(scan_fn)(
        *family.scan_inputs(cfg, 7, 1, 48)))())
    over = {n for n in family.SCAN_LEAVES if not float(seen[n]) <= 1e-4}
    assert (not over) == passes, seen
    if name == "db_of_one_block":
        assert over == {"dB", "dC"}, seen
