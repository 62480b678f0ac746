"""``torchft_tpu/models/keye.py`` and ``torchft_tpu/ops/dsa.py`` at the
small size: the model against its plain float32 reference
(``benchmark/reference/keye_f32.py``) — hidden state, both terms of the
loss, every gradient leaf, the sets of keys —, which leaves each term
reaches, the three-stream rotation, what crosses a layer's checkpoint,
and the new kernels in the interpreter against the ``jnp`` form. The
family's side (the configuration, the share, the cell's comparisons and
their faults, the loop) is ``tests/test_keye_family.py``'s: two files so
that the two run on two of tier-1's workers."""

import dataclasses
import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

import family_kit as kit
import one_program

from benchmark.families import keye as family
from benchmark.reference import keye_f32
from benchmark.tests import keye_faults
from torchft_tpu.models import common, keye
from torchft_tpu.ops import dsa
from torchft_tpu.ops.attention import reference_attention
from torchft_tpu.ops.ssm_pointwise import rotary_tables
from torchft_tpu.utils.metrics import TRACED

CFG = keye.KEYE_CONFIGS["keye_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
S = 64
_batch = kit.batch
TEXT, IMAGE = None, "image"


def _positions(which):
    return None if which is TEXT else keye_faults.image_positions(S)


@functools.lru_cache(maxsize=None)
def _params(seed=3):
    """Seeded weights with the balance biases AND the indexer's LayerNorm
    bias away from zero."""
    return family.seed_check_params(
        kit.seeded_params(keye, CFG32, seed), seed)


@functools.lru_cache(maxsize=None)
def _both(which):
    """``(system terms, reference terms)`` on one batch, f32."""
    tokens, targets = _batch(3)
    positions = _positions(which)
    got = jax.jit(lambda p: keye.loss_terms(
        CFG32, p, tokens, targets, None, positions))(_params())
    want = jax.jit(lambda p: keye_f32.terms(
        p, tokens, targets, positions=positions,
        **family.reference_dims(CFG32)))(_params())
    return jax.device_get(got), jax.device_get(want)


@functools.lru_cache(maxsize=None)
def _grads(term, side, cfg=CFG32):
    """The gradient tree of ``term`` (``loss`` | ``ce`` | ``index_kl``) of
    the system or of the reference."""
    tokens, targets = _batch(3)
    if side == "system":
        fn = lambda p: keye.loss_terms(cfg, p, tokens, targets)[term]  # noqa: E731
    else:
        fn = lambda p: keye_f32.terms(  # noqa: E731
            p, tokens, targets, **family.reference_dims(cfg))[term]
    return jax.device_get(jax.jit(jax.grad(fn))(_params()))


def _leaves(tree):
    return [(jax.tree_util.keystr(path), np.asarray(x)) for path, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _is_indexer(name):
    return "['indexer']" in name


@pytest.mark.parametrize("which", [TEXT, IMAGE])
def test_the_model_is_its_reference(which) -> None:
    """Hidden state, cross entropy, each layer's KL, the experts and THE
    SETS OF KEYS, on text's streams and on three streams that differ."""
    got, want = _both(which)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=2e-5)
    assert got["ce"] == pytest.approx(want["ce"], abs=2e-6)
    np.testing.assert_allclose(got["kl"], want["kl"], rtol=1e-4)
    assert got["loss"] == pytest.approx(
        want["ce"] + np.sum(want["kl"]), abs=5e-6)
    assert np.array_equal(got["sel"], want["own_keys"])
    taken = np.zeros(want["chosen"].shape, bool)
    np.put_along_axis(taken, got["experts"], True, axis=-1)
    assert np.array_equal(taken, want["chosen"])
    size = np.sum(np.asarray(dsa.unpack(got["sel"])), axis=-1)
    assert np.array_equal(size, np.broadcast_to(
        np.minimum(np.arange(S) + 1, CFG.index_topk), size.shape))
    # most queries choose: 12 of up to 64 keys
    np.testing.assert_allclose(got["selected_share"], 702 / 2080, rtol=1e-6)


def test_every_gradient_leaf_is_the_references() -> None:
    got, want = _grads("loss", "system"), _grads("loss", "reference")
    for (name, a), (_, b) in zip(_leaves(got), _leaves(want)):
        if name.endswith("['balance_bias']"):
            continue        # carries the loads (common.loads_as_gradient)
        assert np.linalg.norm(a - b) <= 2e-5 * np.linalg.norm(b) + 1e-9, name
        assert np.any(b), name


@pytest.mark.parametrize("side", ["system", "reference"])
def test_each_term_reaches_its_own_leaves_alone(side) -> None:
    """The cross entropy's gradient is EXACTLY zero on every leaf of the
    indexer (the set is not differentiable and the indexer's input is
    detached); the KL term's is exactly zero on every other leaf and not
    on the indexer's. In the system and in the reference."""
    for name, g in _leaves(_grads("ce", side)):
        assert np.any(g) != _is_indexer(name) or name.endswith(
            "['balance_bias']"), name
    for name, g in _leaves(_grads("index_kl", side)):
        assert np.any(g) == _is_indexer(name), name


def test_a_fault_fails_the_indexers_input_not_detached(monkeypatch) -> None:
    monkeypatch.setattr(keye, "_detach", lambda x: x)
    tokens, targets = _batch(3)
    grads = jax.jit(jax.grad(lambda p: keye.loss_terms(
        CFG32, p, tokens, targets)["index_kl"]))(_params())
    assert any(np.any(g) for name, g in _leaves(grads)
               if not _is_indexer(name))


def test_a_fault_fails_the_kl_term_left_out() -> None:
    off = dataclasses.replace(CFG32, index_kl_weight=0.0)
    want = _grads("loss", "reference")
    got = _grads("loss", "system", off)
    for (name, a), (_, b) in zip(_leaves(got), _leaves(want)):
        if _is_indexer(name):
            assert not np.any(a) and np.any(b), name


def test_the_table_of_three_streams() -> None:
    """Pair ``i`` turns by ITS stream's position: 2 pairs temporal, 3
    height, 3 width at the small size. On text's streams the table is the
    one-stream table of ``rotary_tables`` bit for bit; on streams that
    differ it is the direct formula, and swapping height and width
    changes exactly the pairs of those two sections."""
    half = CFG.head_dim // 2
    freqs = CFG.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    text = jnp.broadcast_to(jnp.arange(S), (3, S))
    for a, b in zip(keye.mrope_tables(CFG, text),
                    rotary_tables(freqs, S, CFG.head_dim)):
        assert np.array_equal(a, b)
    pos = keye_faults.image_positions(S)
    assert len({tuple(np.asarray(p)) for p in pos}) == 3
    cos, sin = (np.asarray(t) for t in keye.mrope_tables(CFG, pos))
    stream = [0, 0, 1, 1, 1, 2, 2, 2]
    for i in range(half):
        angle = np.asarray(pos[stream[i]], np.float32) * np.float32(freqs[i])
        np.testing.assert_allclose(cos[:, i], np.cos(angle), atol=1e-6)
        np.testing.assert_allclose(cos[:, half + i], np.cos(angle), atol=1e-6)
        np.testing.assert_allclose(sin[:, i], -np.sin(angle), atol=1e-6)
        np.testing.assert_allclose(sin[:, half + i], np.sin(angle), atol=1e-6)
    swapped = np.asarray(keye.mrope_tables(CFG, pos[jnp.array([0, 2, 1])])[0])
    moved = [i for i in range(half) if not np.array_equal(
        swapped[:, i], cos[:, i])]
    # (the slowest pairs' angles are too small to tell apart in f32)
    assert set(moved) <= {2, 3, 4, 5, 6, 7} and {2, 3, 4, 5} <= set(moved)
    np.testing.assert_allclose(
        keye_f32.head_angles(pos, CFG.rope_theta, CFG.head_dim,
                             CFG.mrope_section),
        np.asarray(pos, np.float32)[stream].T * np.asarray(freqs)[None],
        rtol=1e-6)


# ``ops/dsa.py``'s three calls as kernels in the interpreter, several query
# blocks a sequence: what ``_attn_mixer`` takes as ``ops``
KERNELS = types.SimpleNamespace(**{
    name: functools.partial(getattr(dsa, name), block_q=16, interpret=True)
    for name in ("select", "attend", "index_kl")})


def _kl_grad_calls():
    return TRACED.snapshot().get("dsa_kl_grad_calls", 0)


@functools.lru_cache(maxsize=None)
def _program(remat, kernels=False, kl_weight=1.0):
    """``(loss, gradients, compiled text, index_kl calls traced with their
    gradients)`` of the gradient program, on :data:`KERNELS` or on the
    ``jnp`` forms (the CPU's)."""
    cfg = dataclasses.replace(CFG32, remat=remat, index_kl_weight=kl_weight)
    tokens, targets = _batch(3)
    ops = KERNELS if kernels else None
    before = _kl_grad_calls()
    program = jax.jit(jax.value_and_grad(
        lambda p: keye.loss_fn(cfg, p, tokens, targets, ops))).lower(
            _params()).compile()
    traced = _kl_grad_calls() - before
    loss, grads = program(_params())
    return float(loss), jax.device_get(grads), program.as_text(), traced


def _passes(text, scope, is_counted):
    """How many of the compiled program's operations under ``scope`` that
    ``is_counted(path, line)`` takes stand in the forward pass, under the
    checkpoint's ``rematted_computation`` and in the backward."""
    found = {}
    for line in text.splitlines():
        path = re.search(r'op_name="([^"]*)"', line)
        if (path is None or scope not in path.group(1)
                or not is_counted(path.group(1), line)):
            continue
        # (``transpose(`` is the backward pass; a path can END in the
        # primitive ``transpose``)
        which = ("recomputed" if "rematted_computation" in path.group(1) else
                 "backward" if "transpose(" in path.group(1) else "forward")
        found[which] = found.get(which, 0) + 1
    return found


def test_forward_and_backward_read_one_set_under_remat() -> None:
    """The selection runs ONCE a layer and step: in the compiled gradient
    program with ``remat`` on, every ``sort`` under ``dsa_select`` stands
    in the forward pass — none under the checkpoint's
    ``rematted_computation``, none in the backward —, as many as without
    ``remat``; the sets cross the checkpoint by name
    (``common.KEY_CHOICE``). Loss and gradients equal the ``remat=False``
    program's."""
    loss, grads, text, _ = _program(True)
    plain_loss, plain_grads, plain_text, _ = _program(False)

    def sorts(text):
        return _passes(text, "dsa_select",
                       lambda path, line: re.search(r" sort\(", line))

    assert set(sorts(text)) == {"forward"}
    assert sorts(text) == sorts(plain_text)
    assert loss == plain_loss
    for (name, a), (_, b) in zip(_leaves(grads), _leaves(plain_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7, err_msg=name)
    assert common.KEY_CHOICE != common.ROUTER_CHOICE


def test_the_kl_term_is_differentiated_where_it_is_evaluated(capsys) -> None:
    """``dsa_kl`` runs ONCE a layer and step: in the compiled gradient
    program with ``remat`` on and the kernels in the interpreter,
    every loop and matmul the kernel's call lowers to stands in the forward
    pass — none under ``rematted_computation``, none in the backward —, as
    much of it as with ``remat`` off; ``index_kl`` is traced with its
    gradients once a layer. Loss and gradients equal the ``remat=False``
    program's and the ``jnp`` form's. What the term keeps across the
    checkpoint is shaped like the indexer's PARAMETERS (five leaves a
    layer), not like the kernel's ``dqi``, ``dki``, ``dw``."""
    loss, grads, text, traced = _program(True, True)
    plain_loss, plain_grads, plain_text, plain_traced = _program(False,
                                                                 True)
    form_loss, form_grads, _, form_traced = _program(True)

    def kernel(text):
        # the kernel's loops and matmuls (XLA's copies between them vary)
        return _passes(text, "dsa_kl", lambda path, line: (
            "jit(_kl_call)" in path and re.search(r" (while|dot)\(", line)))

    assert set(kernel(text)) == {"forward"} and kernel(text)["forward"] > 0
    assert kernel(text) == kernel(plain_text)
    # nothing of the scope is left for the backward pass: it scales the
    # parameters' cotangents, which crossed the checkpoint (``KEY_CHOICE``)
    assert set(_passes(text, "dsa_kl", lambda path, line: True)) == {
        "forward"}
    assert traced == plain_traced == CFG.n_layers and form_traced == 0
    cfg = dataclasses.replace(CFG32, remat=True)
    tokens, targets = _batch(3)
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        lambda p: keye.loss_fn(cfg, p, tokens, targets, KERNELS), _params())
    named = [line for line in capsys.readouterr().out.splitlines()
             if f"named '{common.KEY_CHOICE}'" in line]
    assert not [line for line in named if "_kl_fwd" in line]
    kept = [re.match(r"\w+\[([\d,]*)\]", line).group(1)
            for line in named if "keye.py" in line]
    ix = jax.tree_util.tree_leaves(_params()["layers_0"]["indexer"])
    assert sorted(kept) == sorted(
        [",".join(map(str, leaf.shape)) for leaf in ix] * CFG.n_layers)
    assert loss == plain_loss
    assert loss == pytest.approx(form_loss, rel=1e-6)
    for (name, a), (_, b), (_, c) in zip(_leaves(grads), _leaves(plain_grads),
                                         _leaves(form_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7, err_msg=name)
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=1e-7, err_msg=name)


def test_below_topk_positions_it_is_plain_causal_attention() -> None:
    """A query with ``t < topk`` keeps every key at or before it: its rows
    of the packed set ARE the causal rows, and its output is, BIT FOR
    BIT, the output under the all-causal set; that set's output is plain
    causal attention's."""
    x = family.kernel_inputs(CFG32, 5, 2, S)
    sel, _ = dsa.select(x["qi"], x["ki"], x["w"], CFG.index_topk)
    causal = jnp.broadcast_to(family.causal_words(S), sel.shape)
    k = CFG.index_topk
    assert np.array_equal(sel[:, :k], causal[:, :k])
    assert not np.array_equal(sel[:, k:], causal[:, k:])
    assert np.array_equal(dsa.pack(jnp.tril(jnp.ones((S, S), bool))),
                          family.causal_words(S))
    o, _ = dsa.attend(x["q"], x["k"], x["v"], sel)
    o_all, _ = dsa.attend(x["q"], x["k"], x["v"], causal)
    assert np.array_equal(o[:, :, :k], o_all[:, :, :k])
    assert not np.allclose(o[:, :, k:], o_all[:, :, k:], atol=1e-3)
    plain = reference_attention(*(x[n].transpose(0, 2, 1, 3)
                                  for n in ("q", "k", "v")))
    np.testing.assert_allclose(o_all.transpose(0, 2, 1, 3), plain,
                               atol=2e-6)


def test_the_packed_set() -> None:
    """Bit ``b`` of word ``c`` of a row is key ``b · (S / 32) + c``."""
    keep = jax.random.bernoulli(jax.random.key(0), 0.3, (2, S, S))
    words = dsa.pack(keep)
    assert words.shape == (2, S, S // 32) and words.dtype == jnp.int32
    assert np.array_equal(dsa.unpack(words), keep)
    assert np.array_equal(keye_f32.unpack_keys(words), keep)
    assert np.array_equal(keye_f32.pack_keys(keep), words)
    one = np.zeros((1, S), bool)
    one[0, 31 * 2 + 1] = True
    assert np.array_equal(np.asarray(dsa.pack(one)),
                          [[0, np.int32(-2 ** 31)]])
    with pytest.raises(ValueError, match="no multiple of the 32 keys"):
        dsa.select(jnp.zeros((1, 1, 48, 8)), jnp.zeros((1, 48, 8)),
                   jnp.zeros((1, 48, 1)), 4)


def _coarse_inputs():
    """The kernels' inputs with index operands on a coarse grid, so that
    scores TIE at the rank-``topk`` boundary."""
    x = family.kernel_inputs(CFG, 0, 2, S)
    coarse = lambda a: (jnp.round(a.astype(jnp.float32) * 2) / 2  # noqa: E731
                        ).astype(a.dtype)
    return dict(x, qi=coarse(x["qi"]), ki=coarse(x["ki"]),
                w=coarse(x["w"] * 8))


@functools.lru_cache(maxsize=None)
def _kernels_and_forms():
    """The three calls in the interpreter (several query blocks a
    sequence) and in their ``jnp`` form, values and gradients, ONE jitted
    program each."""
    x = _coarse_inputs()

    def run(x, interpret):
        kw = dict(block_q=16, interpret=interpret)
        sel, lse_i = dsa.select(x["qi"], x["ki"], x["w"], CFG.index_topk,
                                **kw)
        (o, lse), pull = jax.vjp(
            lambda q, k, v: dsa.attend(q, k, v, sel, **kw),
            x["q"], x["k"], x["v"])
        dq, dk, dv = pull((x["do"], jnp.zeros_like(lse)))
        kl, (dqi, dki, dw) = jax.value_and_grad(
            lambda qi, ki, w: dsa.index_kl(
                x["q"], x["k"], lse, qi, ki, w, sel, lse_i, **kw),
            argnums=(0, 1, 2))(x["qi"], x["ki"], x["w"])
        return dict(sel=sel, lse_i=lse_i, o=o, lse=lse, dq=dq, dk=dk, dv=dv,
                    kl=kl, dqi=dqi, dki=dki, dw=dw)

    return (jax.device_get(jax.jit(lambda x: run(x, True))(x)),
            jax.device_get(jax.jit(lambda x: run(x, None))(x)), x)


def test_the_kernels_in_the_interpreter_are_the_jnp_forms() -> None:
    got, want, _ = _kernels_and_forms()
    assert np.array_equal(got["sel"], want["sel"])
    for name, tol in (("lse_i", 1e-5), ("lse", 1e-5), ("kl", 1e-5),
                      ("dw", 1e-4), ("o", 1e-2), ("dq", 1e-2), ("dk", 1e-2),
                      ("dv", 1e-2), ("dqi", 1e-2), ("dki", 1e-2)):
        a, b = (np.asarray(z[name], np.float32) for z in (got, want))
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b), name


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("case", ["cotangent_3", "kl_weight_0.25",
                                  "evaluated", "under_a_checkpoint"])
def test_index_kl_scaled_and_not_differentiated(case) -> None:
    """The backward rule is the cotangent's product and nothing else: ``3
    · index_kl`` through the interpreter gives three times the forward
    rule's gradients, rounded once, and the ``jnp`` form's scaled so;
    ``index_kl_weight`` 0.25 through ``loss_fn`` a quarter of the term's
    own gradient on every leaf of the indexer. NOT differentiated the
    call lowers the kernel that computes no gradient (one result, the
    rows' KL) and its value is the differentiated call's bit for bit. And
    the call ALONE under ``common.checkpoint_layer`` (no model's unit
    around it) is not made again either: its three gradients carry the
    name."""
    if case == "kl_weight_0.25":
        _, grads, _, traced = _program(True, True, 0.25)
        assert traced == CFG.n_layers
        checked = 0
        for (name, a), (_, b) in zip(_leaves(grads),
                                     _leaves(_grads("index_kl", "system"))):
            if _is_indexer(name):
                np.testing.assert_allclose(a, 0.25 * b, rtol=2e-4, atol=1e-7,
                                           err_msg=name)
                checked += 1
        assert checked == 5 * CFG.n_layers
        return
    got, want, x = _kernels_and_forms()

    def kl(qi, ki, w):
        return dsa.index_kl(x["q"], x["k"], got["lse"], qi, ki, w,
                            got["sel"], got["lse_i"], block_q=16,
                            interpret=True)

    operands = (x["qi"], x["ki"], x["w"])
    if case == "evaluated":
        before = _kl_grad_calls()
        calls = list(_pallas_calls(jax.make_jaxpr(kl)(*operands).jaxpr))
        both = list(_pallas_calls(jax.make_jaxpr(jax.value_and_grad(
            kl, argnums=(0, 1, 2)))(*operands).jaxpr))
        assert _kl_grad_calls() == before + 1
        assert [len(c.outvars) for c in calls] == [1]
        assert [len(c.outvars) for c in both] == [4]
        assert calls[0].outvars[0].aval.shape == x["qi"].shape[:1] + (1, S)
        assert np.array_equal(jax.jit(kl)(*operands), got["kl"])
        return
    if case == "under_a_checkpoint":
        text = jax.jit(jax.grad(common.checkpoint_layer(kl), argnums=(
            0, 1, 2))).lower(*operands).compile().as_text()
        loops = _passes(text, "jit(_kl_call)", lambda path, line: re.search(
            r" (while|dot)\(", line))
        assert set(loops) == {"forward"} and loops["forward"] > 0
        return
    value, grads = jax.jit(jax.value_and_grad(
        lambda *a: 3.0 * kl(*a), argnums=(0, 1, 2)))(*operands)
    assert np.array_equal(value, 3.0 * got["kl"])
    for name, tol, g in zip(("dqi", "dki", "dw"), (1e-2, 1e-2, 1e-4), grads):
        once = jnp.asarray(got[name])
        assert np.array_equal(
            g, (3.0 * once.astype(jnp.float32)).astype(once.dtype)), name
        a, b = np.asarray(g, np.float32), 3.0 * np.asarray(want[name],
                                                           np.float32)
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b), name


def test_ties_go_to_the_lower_key_and_every_set_is_exact() -> None:
    """On coarse operands rows tie at the boundary: the set still has
    exactly ``min(t + 1, topk)`` members, no key after its query, and of
    the keys that tie with the last kept one the LOWER positions are
    kept."""
    got, _, x = _kernels_and_forms()
    keep = np.asarray(dsa.unpack(got["sel"]))
    scores = np.asarray(dsa.index_scores(x["qi"], x["ki"], x["w"]))
    t = np.arange(S)
    assert np.array_equal(keep.sum(-1), np.broadcast_to(
        np.minimum(t + 1, CFG.index_topk), keep.shape[:2]))
    assert not np.any(keep & (t[None, :] > t[:, None]))
    tied_rows = 0
    for b in range(keep.shape[0]):
        for row in range(CFG.index_topk, S):
            kept = np.flatnonzero(keep[b, row])
            low = scores[b, row, kept].min()
            out = np.flatnonzero(~keep[b, row, :row + 1])
            assert np.all(scores[b, row, out] <= low)
            ties_out = out[scores[b, row, out] == low]
            ties_in = kept[scores[b, row, kept] == low]
            if len(ties_out):
                tied_rows += 1
                assert ties_in.max() < ties_out.min()
    assert tied_rows > 0


# -- ``dsa_fwd`` a chunk of k tiles a grid step ------------------------------

CHUNK_S = 96        # tiles of 3 keys, 6 q blocks of 16 rows: block 0 ends in
#                     tile 5, block 1 in tile 10 — at 2 and 4 tiles a step
#                     both rows' last chunks are partly above the diagonal,
#                     and a row's live tiles leave a lone one behind the pairs


def _off(a, b):
    a, b = (np.asarray(z, np.float32) for z in (a, b))
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@functools.lru_cache(maxsize=None)
def _chunk_inputs():
    """Four query heads on two key heads (of the small model's sixteen on
    two), ``select``'s sets with tile 0 taken out of rows 40 - 59: those
    rows have no chosen key in their first tile."""
    x = family.kernel_inputs(CFG, 7, 2, CHUNK_S)
    sel, _ = dsa.select(x["qi"], x["ki"], x["w"], CFG.index_topk)
    rows = (jnp.arange(CHUNK_S) >= 40) & (jnp.arange(CHUNK_S) < 60)
    sel = jnp.where(rows[None, :, None], sel & ~1, sel)
    kept = np.asarray(dsa.unpack(sel))[:, 40:60]
    assert not np.any(kept[..., :3]) and np.all(np.sum(kept, axis=-1) >= 9)
    return x["q"][:, :4], x["k"], x["v"], x["do"][:, :4], sel


@functools.lru_cache(maxsize=None)
def _chunked(n):
    """``attend`` at ``n`` k tiles a grid step in the interpreter (``None``:
    the ``jnp`` form): ``o``, ``lse``, ``dq``, ``dk``, ``dv`` as ONE jitted
    program, ``dsa_fwd``'s grid and the gauge its trace left."""
    q, k, v, do, sel = _chunk_inputs()
    kw = {} if n is None else dict(block_q=16, interpret=True)

    def run(q, k, v, do):
        (o, lse), pull = jax.vjp(
            lambda q, k, v: dsa.attend(q, k, v, sel, **kw), q, k, v)
        return (o, lse) + pull((do, jnp.zeros_like(lse)))

    rule = dsa._choose_chunk
    dsa._choose_chunk = lambda *shape: n
    try:
        calls = one_program.pallas_calls(run, q, k, v, do)
        traced = TRACED.snapshot().get("dsa_fwd_chunk_tiles")
        out = jax.device_get(jax.jit(run)(q, k, v, do))
    finally:
        dsa._choose_chunk = rule
    grid = calls["dsa_fwd"].params["grid_mapping"].grid if calls else None
    return dict(zip(("o", "lse", "dq", "dk", "dv"), out)), grid, traced


@pytest.mark.parametrize("n", [1, 2, 4])
def test_the_forward_sweeps_a_chunk_of_k_tiles_a_grid_step(n) -> None:
    """At ``n`` tiles a step the k axis is ``32 / n`` steps and a chunk
    that crosses the diagonal computes its live tiles only: ``o`` and
    ``lse`` are the ``jnp`` form's at this file's tolerances, rows with no
    chosen key in their first tile among them, on four query heads of two
    key heads; against the one-tile kernel ``lse`` agrees to f32's rounding
    and ``o`` to bf16's (a PAIR of tiles is one update of the statistics:
    the same f32 sums met in another order); and the gradient through
    ``attend`` — ``dsa_dq`` and ``dsa_dkv`` read the chunked forward's
    ``lse`` — is the one-tile forward's."""
    got, grid, traced = _chunked(n)
    want, _, _ = _chunked(None)
    one, one_grid, _ = _chunked(1)
    assert traced == n and grid == (2 * 4, CHUNK_S // 16, dsa.WORD // n)
    assert one_grid[2] == dsa.WORD

    for name, tol in (("lse", 1e-5), ("o", 1e-2), ("dq", 1e-2), ("dk", 1e-2),
                      ("dv", 1e-2)):
        assert _off(got[name], want[name]) <= tol, name
    quiet = slice(40, 60)
    assert _off(got["o"][:, :, quiet], want["o"][:, :, quiet]) <= 1e-2
    assert _off(got["lse"][:, :, quiet], want["lse"][:, :, quiet]) <= 1e-5
    assert _off(got["lse"], one["lse"]) <= 2e-7
    for name in ("o", "dq", "dk", "dv"):
        assert _off(got[name], one[name]) <= 4e-3, name


@pytest.mark.parametrize("shape, want", [
    ((16384, 128, 128, 2, 512), 32),     # the cell's call: the whole row
    ((32768, 128, 128, 2, 512), 32),
    ((65536, 128, 128, 2, 512), 8),      # 16 tiles of 2 048 keys do not fit
    ((4096, 128, 128, 2, 512), 32),
    ((2048, 128, 128, 2, 512), 1),       # tiles narrower than a lane tile
    ((64, 16, 16, 2, 16), 1),            # the CPU tests' sizes
    ((CHUNK_S, 16, 16, 2, 16), 1),
])
def test_the_chunk_is_a_pure_function_of_the_shape(shape, want,
                                                   monkeypatch) -> None:
    """The longest rung of the ladder (descending, divisors of the 32
    tiles, 1 last) whose estimate fits ``_PARAMS``' VMEM limit; 1 where a
    tile is narrower than a lane tile. The estimate grows with the chunk,
    and under a smaller limit the rule takes a shorter rung."""
    ladder = dsa._CHUNK_LADDER
    assert list(ladder) == sorted(ladder, reverse=True) and ladder[-1] == 1
    assert all(dsa.WORD % n == 0 for n in ladder)
    assert max(dsa._STRAIGHT) <= 4 and dsa._STRAIGHT[-1] == 1
    n = dsa._choose_chunk(*shape)
    assert n == want and n in ladder
    seq_len, d, dv, itemsize, block_q = shape
    width = seq_len // dsa.WORD

    def estimate(n):
        return dsa._forward_vmem_estimate(d, dv, itemsize, block_q, width, n)

    limit = dsa._PARAMS.vmem_limit_bytes
    if n > 1:
        assert estimate(n) <= limit
        longer = [m for m in ladder if m > n]
        assert all(estimate(m) > limit for m in longer)
        monkeypatch.setattr(dsa, "_PARAMS", pltpu.CompilerParams(
            vmem_limit_bytes=estimate(n) - 1))
        assert dsa._choose_chunk(*shape) == ladder[ladder.index(n) + 1]
    assert [estimate(m) for m in ladder] == sorted(
        (estimate(m) for m in ladder), reverse=True)


def test_the_gauge_says_what_the_cells_call_took() -> None:
    """``TRACED["dsa_fwd_chunk_tiles"]`` after a trace of ``attend`` at
    the cell's shape (2 rows of 16 384, 32 | 4 heads of 128; nothing
    runs): more than one tile a step, the rule's count, and the grid's k
    axis that many times shorter; at this file's sizes, which ask the
    rule, 1."""
    B, H, KV, L, D = 2, 32, 4, 16384, 128
    q = jax.ShapeDtypeStruct((B, H, L, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, KV, L, D), jnp.bfloat16)
    sel = jax.ShapeDtypeStruct((B, L, L // 32), jnp.int32)
    calls = one_program.pallas_calls(
        lambda q, k, v, sel: dsa._attend(q, k, v, sel, D ** -0.5, 512,
                                         False), q, k, k, sel)
    n = TRACED.snapshot()["dsa_fwd_chunk_tiles"]
    assert n == dsa._choose_chunk(L, D, D, 2, 512) >= 2
    assert calls["dsa_fwd"].params["grid_mapping"].grid == (
        B * H, L // 512, dsa.WORD // n)
    assert [v.aval.shape for v in calls["dsa_fwd"].outvars] == [
        (B * H, L, D), (B * H, 1, L)]

    def dots(jaxpr):
        return sum((eqn.primitive.name == "dot_general") + sum(
            dots(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    # a straight-line group is traced as a loop of ONE update (two
    # matmuls) that lowering lays out: the body does not grow with the
    # chunk (what a call site costs to compile is ``setup_s``)
    assert dots(calls["dsa_fwd"].params["jaxpr"]) == 2 * len(dsa._STRAIGHT)
    x = family.kernel_inputs(CFG, 1, 1, S)
    small = jax.ShapeDtypeStruct((1, S, S // 32), jnp.int32)
    jax.make_jaxpr(lambda q, k, v, sel: dsa.attend(
        q, k, v, sel, block_q=8, interpret=True))(
            x["q"], x["k"], x["v"], small)
    assert TRACED.snapshot()["dsa_fwd_chunk_tiles"] == 1


# -- ``dsa_dq``, ``dsa_dkv``, ``dsa_kl`` more than a tile a grid step ---------

SWEPT_ROWS = 8      # 12 q blocks of 8 rows over ``CHUNK_S``'s tiles of 3 keys:
#                     block 0 ends in tile 2, block 1 in tile 5, block 3 in
#                     tile 10 — a row's last chunk of 2, 4 or 8 tiles is
#                     partly above the diagonal, its live tiles leave a
#                     lone one behind the groups of four and the pairs, and
#                     a k tile's first chunk of 4 q blocks starts inside it


def _a_rounding_apart(a, b):
    """bf16 leaves equal or one rounding apart (the same f32 sums met in
    another order round to neighbours at most; where terms cancel, to
    within f32's rounding of the leaf's largest value)."""
    a, b = (np.asarray(z, np.float32) for z in (a, b))
    gap = np.abs(a - b)
    ulp = 2.0 ** -7 * np.maximum(np.abs(a), np.abs(b))
    return bool(np.all(gap <= np.maximum(ulp, 1e-6 * np.abs(b).max())))


def _with_rule(name, answer, run, *args):
    """``run`` traced and run with ``dsa.<name>`` answering ``answer`` —
    the seam ``_chunked`` uses —: its ``pallas_call``s, the gauges its
    trace left and its results."""
    rule = getattr(dsa, name)
    setattr(dsa, name, lambda *shape: answer)
    try:
        calls = one_program.pallas_calls(run, *args)
        traced = TRACED.snapshot()
        out = jax.device_get(jax.jit(run)(*args))
    finally:
        setattr(dsa, name, rule)
    return calls, traced, out


@functools.lru_cache(maxsize=None)
def _swept_backward(tiles):
    """The gradient through ``attend`` in the interpreter at ``tiles``
    (``dsa_dq``'s k tiles, ``dsa_dkv``'s k tiles and q blocks a grid
    step), ``SWEPT_ROWS`` rows a q block: ``dq``, ``dk``, ``dv`` as ONE
    jitted program, the two kernels' grids and gauges."""
    q, k, v, do, sel = _chunk_inputs()

    def run(q, k, v, do):
        (_, lse), pull = jax.vjp(lambda q, k, v: dsa.attend(
            q, k, v, sel, block_q=SWEPT_ROWS, interpret=True), q, k, v)
        return pull((do, jnp.zeros_like(lse)))

    calls, traced, out = _with_rule("_choose_backward", tiles, run, q, k, v,
                                    do)
    return (dict(zip(("dq", "dk", "dv"), out)),
            {name: calls[name].params["grid_mapping"].grid
             for name in ("dsa_dq", "dsa_dkv")},
            {name: traced[f"{name}_chunk_tiles"]
             for name in ("dsa_dq", "dsa_dkv")})


@functools.lru_cache(maxsize=None)
def _swept_kl(straight):
    """``index_kl``'s value and three gradients at ``straight`` heads a
    straight-line group in the interpreter (``None``: the ``jnp`` form) on
    ``_chunk_inputs``' four heads and sets — ``lse`` the ``jnp``
    attention's, ``lse_i`` the sets' own —, and ``dsa_kl``'s gauge."""
    q, k, _, _, sel = _chunk_inputs()
    x = family.kernel_inputs(CFG, 7, 2, CHUNK_S)
    lse = jnp.asarray(_chunked(None)[0]["lse"])
    lse_i = jax.nn.logsumexp(jnp.where(dsa.unpack(sel), dsa.index_scores(
        x["qi"], x["ki"], x["w"]), -jnp.inf), axis=-1)
    kw = {} if straight is None else dict(block_q=16, interpret=True)

    def run(qi, ki, w):
        kl, grads = jax.value_and_grad(lambda qi, ki, w: dsa.index_kl(
            q, k, lse, qi, ki, w, sel, lse_i, **kw), argnums=(0, 1, 2))(
                qi, ki, w)
        return (kl,) + grads

    _, traced, out = _with_rule("_choose_kl", straight, run, x["qi"],
                                x["ki"], x["w"])
    return (dict(zip(("kl", "dqi", "dki", "dw"), out)),
            traced.get("dsa_kl_group_heads"))


SWEPT = [(2, 2, 1), (8, 1, 4), (4, 2, 4)]
KL_HEADS = 16       # heads a straight-line group of ``dsa_kl`` at the cell


@pytest.mark.parametrize("tiles", SWEPT)
def test_dq_sweeps_a_chunk_of_k_tiles_a_grid_step(tiles) -> None:
    """At ``n`` k tiles a step ``dsa_dq``'s k axis is ``32 / n`` steps, a
    chunk that crosses the diagonal computes its live tiles only, and
    ``dq`` is the one-tile body's (bf16: equal or one rounding apart — two
    tiles a matmul are the same f32 sums met in another order) and the
    ``jnp`` form's under this file's limit, the rows with no chosen key in
    their first tile among them."""
    n = tiles[0]
    got, grids, traced = _swept_backward(tiles)
    one, one_grids, one_traced = _swept_backward((1, 1, 1))
    want, _, _ = _chunked(None)
    blocks = CHUNK_S // SWEPT_ROWS
    assert traced["dsa_dq"] == n and one_traced["dsa_dq"] == 1
    assert grids["dsa_dq"] == (2 * 4, blocks, dsa.WORD // n)
    assert one_grids["dsa_dq"] == (2 * 4, blocks, dsa.WORD)
    assert _a_rounding_apart(got["dq"], one["dq"])
    assert _off(got["dq"], one["dq"]) <= 1e-4
    assert _off(got["dq"], want["dq"]) <= 1e-2
    quiet = slice(40, 60)
    assert _off(got["dq"][:, :, quiet], want["dq"][:, :, quiet]) <= 1e-2


@pytest.mark.parametrize("tiles", SWEPT)
def test_dkv_takes_k_tiles_against_a_chunk_of_q_blocks_a_grid_step(
        tiles) -> None:
    """At ``t`` k tiles against ``c`` q blocks a step ``dsa_dkv``'s grid is
    ``(B * KV, 32 / t, blocks / c, group)``, a column's first chunk starts
    inside it, and ``dk``, ``dv`` are the one-tile body's (equal or one
    rounding apart) and the ``jnp`` form's under this file's limit."""
    _, t, c = tiles
    got, grids, traced = _swept_backward(tiles)
    one, one_grids, one_traced = _swept_backward((1, 1, 1))
    want, _, _ = _chunked(None)
    blocks = CHUNK_S // SWEPT_ROWS
    assert traced["dsa_dkv"] == t * c and one_traced["dsa_dkv"] == 1
    assert grids["dsa_dkv"] == (2 * 2, dsa.WORD // t, blocks // c, 2)
    assert one_grids["dsa_dkv"] == (2 * 2, dsa.WORD, blocks, 2)
    for name in ("dk", "dv"):
        assert _a_rounding_apart(got[name], one[name]), name
        assert _off(got[name], one[name]) <= 1e-4, name
        assert _off(got[name], want[name]) <= 1e-2, name


@pytest.mark.parametrize("straight", [2, 3, 4])
def test_kl_meets_its_heads_in_straight_line_groups(straight) -> None:
    """At ``g`` heads a straight-line group (four query heads and the
    indexer's three: at 2 two groups and a group with a head left over, at
    3 a head left over and one group, at 4 one group and the leftovers
    alone) the value and ``dw`` (f32) are the one-head loop's to 1e-6,
    ``dqi`` and ``dki`` equal or one rounding apart, and all four the
    ``jnp`` form's under this file's limits."""
    got, traced = _swept_kl(straight)
    one, one_traced = _swept_kl(1)
    want, _ = _swept_kl(None)
    assert traced == straight and one_traced == 1
    for name in ("kl", "dw"):
        assert _off(got[name], one[name]) <= 1e-6, name
    for name in ("dqi", "dki"):
        assert _a_rounding_apart(got[name], one[name]), name
    for name, tol in (("kl", 1e-5), ("dw", 1e-4), ("dqi", 1e-2),
                      ("dki", 1e-2)):
        assert _off(got[name], want[name]) <= tol, name


CELL = (16384, 128, 128, 2, 512)     # keye2's call: seq, d, dv, itemsize, rows


@pytest.mark.parametrize("shape, want", [
    (CELL, (32, 2, 8)),                  # the whole row; 2 tiles x 8 q blocks
    ((32768, 128, 128, 2, 512), (32, 2, 2)),
    ((65536, 128, 128, 2, 512), (2, 2, 1)),
    ((4096, 128, 128, 2, 512), (32, 2, 8)),
    ((2048, 128, 128, 2, 512), (1, 1, 1)),   # tiles narrower than a lane tile
    ((64, 16, 16, 2, 16), (1, 1, 1)),        # the CPU tests' sizes
    ((CHUNK_S, 16, 16, 2, SWEPT_ROWS), (1, 1, 1)),
])
def test_the_backward_counts_are_a_pure_function_of_the_shape(
        shape, want, monkeypatch) -> None:
    """``dsa_dq``'s k tiles a step: the longest rung of the forward's
    ladder whose estimate fits ``_PARAMS``' VMEM limit; ``dsa_dkv``'s
    ``_COLUMN_TILES`` k tiles against the longest rung of
    ``_COLUMN_LADDER`` q blocks that fits; all 1 where a tile is narrower
    than a lane tile. Each estimate grows with its count, and under a
    smaller limit each rule falls a rung."""
    got = dsa._choose_backward(*shape)
    assert got == want == dsa._choose_backward(*shape)
    seq_len, d, dv, itemsize, block_q = shape
    width = seq_len // dsa.WORD
    ladder, column = dsa._CHUNK_LADDER, dsa._COLUMN_LADDER
    assert list(column) == sorted(column, reverse=True) and column[-1] == 1

    def row(n):
        return dsa._backward_vmem_estimate(d, dv, itemsize, block_q, width, n)

    def col(t, n):
        return dsa._column_vmem_estimate(d, dv, itemsize, block_q, width, t,
                                         n)

    assert [row(n) for n in ladder] == sorted(map(row, ladder), reverse=True)
    assert [col(2, n) for n in column] == sorted(
        (col(2, n) for n in column), reverse=True)
    assert col(2, 1) > col(1, 1)
    limit = dsa._PARAMS.vmem_limit_bytes
    n, t, c = got
    if n > 1:
        assert row(n) <= limit
        assert all(row(m) > limit for m in ladder if m > n)
        monkeypatch.setattr(dsa, "_PARAMS", pltpu.CompilerParams(
            vmem_limit_bytes=row(n) - 1))
        assert dsa._choose_backward(*shape)[0] == ladder[ladder.index(n) + 1]
    if (t, c) != (1, 1):
        assert t == dsa._COLUMN_TILES and col(t, c) <= limit
        assert all(col(t, m) > limit for m in column if m > c)
        monkeypatch.setattr(dsa, "_PARAMS", pltpu.CompilerParams(
            vmem_limit_bytes=col(t, c) - 1))
        fell = dsa._choose_backward(*shape)[1:]
        assert fell == ((t, column[column.index(c) + 1]) if c > 1
                        else (1, 1))


@pytest.mark.parametrize("shape, want", [
    ((16384, 32, 4, 128, 16, 64, 2, 512), KL_HEADS),   # keye2's call
    ((32768, 32, 4, 128, 16, 64, 2, 512), KL_HEADS),
    ((4096, 32, 4, 128, 16, 64, 2, 512), KL_HEADS),
    ((2048, 32, 4, 128, 16, 64, 2, 512), 1),  # narrower than a lane tile
    ((64, 16, 2, 16, 3, 8, 2, 16), 1),        # the CPU tests' sizes
])
def test_the_kl_group_is_a_pure_function_of_the_shape(shape, want,
                                                      monkeypatch) -> None:
    """``dsa_kl``'s heads a straight-line group: the longest rung of
    ``_KL_LADDER`` whose estimate fits; 1 (the loop of one head) where a
    tile is narrower than a lane tile; a rung less under a smaller
    limit."""
    ladder = dsa._KL_LADDER
    assert list(ladder) == sorted(ladder, reverse=True) and ladder[-1] == 1
    got = dsa._choose_kl(*shape)
    assert got == want == dsa._choose_kl(*shape)
    seq_len, *widths, block_q = shape

    def estimate(n):
        return dsa._kl_vmem_estimate(*widths, block_q, seq_len // dsa.WORD, n)

    assert [estimate(n) for n in ladder] == sorted(map(estimate, ladder),
                                                   reverse=True)
    if got > 1:
        assert estimate(got) <= dsa._PARAMS.vmem_limit_bytes
        monkeypatch.setattr(dsa, "_PARAMS", pltpu.CompilerParams(
            vmem_limit_bytes=estimate(got) - 1))
        assert dsa._choose_kl(*shape) == ladder[ladder.index(got) + 1]


def test_the_gauges_say_what_the_cells_backward_and_kl_took() -> None:
    """``TRACED``'s three gauges after a trace of the cell's gradient
    through ``attend`` and of ``index_kl`` with its gradients (2 rows of
    16 384, 32 | 4 heads of 128, an indexer of 16 x 64; nothing runs): the
    rules' counts, more than one tile a step, the grids that many times
    shorter, and bodies that do not grow with the counts — a straight-line
    group is traced as a loop of ONE update (what a call site costs to
    compile is ``setup_s``). At this file's sizes, which ask the rules,
    every gauge reads 1."""
    B, H, KV, L, D, HI, DI = 2, 32, 4, 16384, 128, 16, 64
    bf, f32 = jnp.bfloat16, jnp.float32
    q, k = (jax.ShapeDtypeStruct((B, n, L, D), bf) for n in (H, KV))
    sel = jax.ShapeDtypeStruct((B, L, L // 32), jnp.int32)
    qi = jax.ShapeDtypeStruct((B, HI, L, DI), bf)
    ki = jax.ShapeDtypeStruct((B, L, DI), bf)
    w = jax.ShapeDtypeStruct((B, L, HI), f32)
    lse = jax.ShapeDtypeStruct((B, H, L), f32)
    lse_i = jax.ShapeDtypeStruct((B, L), f32)

    def run(q, k, v, sel, lse, qi, ki, w, lse_i):
        o, pull = jax.vjp(lambda q, k, v: dsa._attend(
            q, k, v, sel, D ** -0.5, 512, False)[0], q, k, v)
        return pull(o), jax.grad(lambda qi, ki, w: dsa.index_kl(
            q, k, lse, qi, ki, w, sel, lse_i, block_q=512, interpret=False),
            argnums=(0, 1, 2))(qi, ki, w)

    use = dsa._use_kernels
    dsa._use_kernels = lambda interpret: (True, False)   # traced, never run
    try:
        calls = one_program.pallas_calls(run, q, k, k, sel, lse, qi, ki, w,
                                         lse_i)
    finally:
        dsa._use_kernels = use
    traced = TRACED.snapshot()
    n, t, c = dsa._choose_backward(*CELL)
    heads = dsa._choose_kl(L, H, KV, D, HI, DI, 2, 512)
    assert (traced["dsa_dq_chunk_tiles"], traced["dsa_dkv_chunk_tiles"],
            traced["dsa_kl_group_heads"]) == (n, t * c, heads)
    assert min(n, t * c, heads) >= 2

    def grid(name):
        return calls[name].params["grid_mapping"].grid

    assert grid("dsa_dq") == (B * H, L // 512, dsa.WORD // n)
    assert grid("dsa_dkv") == (B * KV, dsa.WORD // t, L // 512 // c, H // KV)
    assert grid("dsa_kl") == (B, L // 512, dsa.WORD)

    def dots(jaxpr):
        return sum((eqn.primitive.name == "dot_general") + sum(
            dots(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    groups = len(dsa._STRAIGHT)
    assert dots(calls["dsa_dq"].params["jaxpr"]) == 3 * groups
    assert dots(calls["dsa_dkv"].params["jaxpr"]) == 4 * groups
    assert dots(calls["dsa_kl"].params["jaxpr"]) == 1 + 1 + 3
    x = family.kernel_inputs(CFG, 1, 1, S)
    small = jax.ShapeDtypeStruct((1, S, S // 32), jnp.int32)

    def tiny(q, k, v, sel, qi, ki, w):
        (o, lse), pull = jax.vjp(lambda q, k, v: dsa.attend(
            q, k, v, sel, block_q=8, interpret=True), q, k, v)
        return pull((o, lse)), jax.grad(lambda qi: dsa.index_kl(
            q, k, lse, qi, ki, w, sel, lse[:, 0], block_q=8,
            interpret=True))(qi)

    jax.make_jaxpr(tiny)(x["q"], x["k"], x["v"], small, x["qi"], x["ki"],
                         x["w"])
    traced = TRACED.snapshot()
    assert [traced[name] for name in (
        "dsa_dq_chunk_tiles", "dsa_dkv_chunk_tiles",
        "dsa_kl_group_heads")] == [1, 1, 1]


# -- the kernels compile for the chip ----------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_for_a_described_v5e(one_chip) -> None:
    """The five kernels at the cell's shapes (2 rows of 16 384, 32 | 4
    heads of 128, an indexer of 16 x 64) pass the chip's compiler: tile
    alignment and VMEM are what the interpreter cannot see."""
    from jax.experimental.compilation_cache import compilation_cache

    B, H, KV, L, D, HI, DI = 2, 32, 4, 16384, 128, 16, 64
    bf, f32 = jnp.bfloat16, jnp.float32

    def on(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, k = on((B, H, L, D), bf), on((B, KV, L, D), bf)
    qi, ki, w = on((B, HI, L, DI), bf), on((B, L, DI), bf), on((B, L, HI), f32)
    sel, lse = on((B, L, L // 32), jnp.int32), on((B, H, L), f32)
    lse_i = on((B, L), f32)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        names = set()
        for fn, args in (
            (lambda a, b, c: dsa._index_call(a, b, c, 2048, 128, False),
             (qi, ki, w)),
            (lambda a, b, c, d: jax.vjp(
                lambda a, b, c: dsa._attend(a, b, c, d, D ** -0.5, 512,
                                            False)[0], a, b, c)[1](a),
             (q, k, k, sel)),
            (lambda *a: dsa._kl_call(*a, D ** -0.5, 512, True, dsa._choose_kl(
                L, H, KV, D, HI, DI, 2, 512), False),
             (q, k, lse, qi, ki, w, sel, lse_i)),
        ):
            text = jax.jit(fn).lower(*args).compile().as_text()
            names |= set(re.findall(r"dsa_[a-z]+", text))
        assert {"dsa_select", "dsa_fwd", "dsa_dq", "dsa_dkv",
                "dsa_kl"} <= names
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
