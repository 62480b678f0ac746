"""SmallThinker (models/smallthinker.py: grouped-query attention that is
windowed or full and rotated or not by two lists of the config, a router
that reads the stream BEFORE attention and weighs by the softmax over the
chosen logits, ReGLU experts of which a share is held, an untied head)
against the plain float32 reference the benchmark keeps
(benchmark/reference/smallthinker_f32.py), at a small size on the CPU: d
48, two periods ``[0, 1, 1, 1] × 2`` of both lists, 14 query heads on 2
key/value heads of 8, a window of 20 keys in S 64 (no multiple of a tile
edge), 8 routed experts of width 24 of which 4 are held, top 2, seeded
random weights. The family (benchmark/families/smallthinker.py), the
optimizer and the fault-tolerant loop are tests/test_smallthinker_family.py's."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark.families import smallthinker as family
from benchmark.reference import smallthinker_f32
from benchmark.tests import smallthinker_faults
from benchmark.tests.smallthinker_faults import FAULTS, with_leaf
from torchft_tpu.models import common, smallthinker
from torchft_tpu.ops import moe
from torchft_tpu.ops.attention import causal_attention

CFG = smallthinker.SMALLTHINKER_CONFIGS["smallthinker_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
BIAS = smallthinker.BALANCE_BIAS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, S, E = CFG.d_model, 64, CFG.n_routed_experts
_params = functools.partial(kit.seeded_params, smallthinker)
_batch = kit.batch


def _reference(cfg):
    return functools.partial(smallthinker_f32.terms,
                             **family.reference_dims(cfg))


# -- against the reference ---------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_compute_equals_the_reference(seed) -> None:
    params, (tokens, targets) = _params(CFG32, seed), _batch(seed)
    with jax.default_matmul_precision("highest"):
        got = smallthinker.loss_terms(CFG32, params, tokens, targets)
    want = _reference(CFG32)(params, tokens, targets)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), abs=2e-5)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5)
    chosen = jnp.any(jax.nn.one_hot(got["experts"], E, dtype=bool), axis=-2)
    assert np.array_equal(chosen, want["chosen"])
    assert got["loads"].shape == (8, E)
    assert float(jnp.sum(got["loads"])) == 8 * 2 * S * CFG.top_k


def test_f32_gradients_equal_the_reference_in_every_leaf() -> None:
    """Every leaf but the balance bias (whose place carries the loads):
    both kinds of attention layer through the band mask and RoPE, the
    router through the softmax over the chosen logits (its gradient comes
    from the experts' weights alone, and reaches ``g1`` and the stream
    BEFORE attention), the held ReGLU experts, table and head apart."""
    params, (tokens, targets) = _params(CFG32, 2), _batch(2)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: smallthinker.loss_fn(
            CFG32, p, tokens, targets))(params)
    want = jax.grad(lambda p: smallthinker_f32.loss(
        p, tokens, targets, **family.reference_dims(CFG32)))(params)
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(want)) == 8 * 11 + 3
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if path[-1].key == BIAS:
            assert float(jnp.sum(g)) == 2 * S * CFG.top_k, name   # the loads
            continue
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * scale, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1])
def test_bf16_compute_agrees_with_the_reference(seed) -> None:
    """bf16 compute, 128 tokens, the cell's own comparison: the reference
    is computed on the top-2 sets the system took, its own choice is
    counted beside it, and every token is compared. The band: eight
    layers of bf16 rounding (2^-8 a result) on a stream of unit size."""
    params, (tokens, targets) = _params(CFG, seed), _batch(seed)
    seen = family.per_token_errors(CFG, params, params, tokens, targets)
    assert seen["error"].shape == (128,)
    assert float(seen["disagreement"]) < 0.1
    assert abs(float(seen["loss"]) - float(seen["reference_loss"])) < 2e-2
    assert np.sqrt(np.mean(seen["error"] ** 2)) < 0.03
    assert seen["error"].max() < 0.08


def test_the_reference_follows_a_selection_and_still_says_its_own() -> None:
    params, (tokens, targets) = _params(CFG32, 6), _batch(6)
    ref = _reference(CFG32)
    own = ref(params, tokens, targets)
    again = ref(params, tokens, targets, selection=own["chosen"])
    np.testing.assert_allclose(again["hidden"], own["hidden"], atol=1e-6)
    assert np.array_equal(again["chosen"], own["chosen"])
    other = jnp.roll(own["chosen"], 1, axis=-1)       # every set moved on
    moved = ref(params, tokens, targets, selection=other)
    assert float(jnp.max(jnp.abs(moved["hidden"] - own["hidden"]))) > 1e-2
    # layer 0's router reads the embedding's norm: the same stream
    assert np.array_equal(moved["chosen"][0], own["chosen"][0])
    assert not np.array_equal(moved["chosen"][1], own["chosen"][1])


@pytest.mark.parametrize("windowed", [(0,), (1,), (1, 0)])
@pytest.mark.parametrize("rotated", [(0,), (1,), (0, 1)])
def test_the_two_lists_are_two_axes_of_the_config(windowed, rotated) -> None:
    """``windowed[l]`` chooses a layer's mask and ``rotated[l]`` its
    position signal, independently: every combination agrees with the
    reference, and each list moves the result by itself."""
    n = max(len(windowed), len(rotated))
    windowed, rotated = (windowed * n)[:n], (rotated * n)[:n]
    cfg = dataclasses.replace(CFG32, windowed=windowed, rotated=rotated)
    params = smallthinker.init_params(cfg, jax.random.key(0))
    assert set(params) == {"wte", "ln_f", "lm_head"} | {
        f"layers_{i}" for i in range(n)}
    tokens, targets = _batch(0)
    with jax.default_matmul_precision("highest"):
        got = smallthinker.loss_terms(cfg, params, tokens, targets)
        flipped = [smallthinker.loss_terms(dataclasses.replace(
            cfg, **{name: tuple(1 - b for b in getattr(cfg, name))}),
            params, tokens, targets) for name in ("windowed", "rotated")]
    want = _reference(cfg)(params, tokens, targets)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), abs=2e-5)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5)
    for other in flipped:
        assert float(jnp.max(jnp.abs(
            other["hidden"] - got["hidden"]))) > 1e-3
    hash(cfg)       # the step-program store keys on it
    with pytest.raises(AssertionError):
        dataclasses.replace(CFG32, windowed=(0, 1), rotated=(0,))


# -- attention ---------------------------------------------------------------


def test_a_key_value_head_serves_seven_consecutive_query_heads() -> None:
    """``common.repeat_kv`` (the yardstick) at this model's 7 : 1: query
    heads 0-6 read key/value head 0 and 7-13 head 1; the model's own seam
    of that name copies nothing (PR 55: the attention reads head ``i //
    7`` where it lies); silencing key/value head 1's values silences
    exactly the last seven query heads' rows of ``W_o``."""
    kv = jnp.arange(2 * 3 * 2 * 4, dtype=jnp.float32).reshape(2, 3, 2, 4)
    out = common.repeat_kv(kv, 14)
    assert out.shape == (2, 3, 14, 4)
    for head in range(14):
        assert np.array_equal(out[:, :, head], kv[:, :, head // 7])
    assert smallthinker.repeat_kv(kv, 14) is kv
    layer = _params(CFG32, 3)["layers_1"]
    x = jax.random.normal(jax.random.key(1), (2, S, D), jnp.float32)

    def out_of(lay):
        return smallthinker._attn_mixer(
            CFG32, True, True, lay, x, attn_fn=causal_attention)[0] - x

    hd = CFG.head_dim
    half = with_leaf({"l": layer}, "l", ("attn", "v_proj", "kernel"),
                     lambda w: w.at[:, hd:].set(0))["l"]
    first7 = with_leaf({"l": layer}, "l", ("attn", "o_proj", "kernel"),
                       lambda w: w.at[7 * hd:].set(0))["l"]
    assert layer["attn"]["o_proj"]["kernel"].shape == (14 * hd, D)
    np.testing.assert_allclose(out_of(half), out_of(first7), atol=1e-5)
    assert float(jnp.max(jnp.abs(out_of(half) - out_of(layer)))) > 1e-3


def test_a_windowed_position_sees_its_last_window_keys_and_no_more() -> None:
    """Position ``t`` of a windowed layer reads keys ``t - 19 … t``: a
    change 20 positions back leaves it alone, one 19 back does not; a full
    layer reads them all."""
    layer = _params(CFG32, 4)["layers_1"]
    x = jax.random.normal(jax.random.key(2), (1, S, D), jnp.float32)
    there = x.at[:, 10].add(1.0)

    def out_of(windowed, stream):
        return smallthinker._attn_mixer(
            CFG32, windowed, False, layer, stream,
            attn_fn=causal_attention)[0] - stream

    moved = jnp.max(jnp.abs(out_of(True, there) - out_of(True, x)), axis=-1)[0]
    assert float(jnp.max(moved[:10])) == 0.0
    assert np.all(np.asarray(moved[10:30]) > 0)        # 10 … 29 see key 10
    np.testing.assert_allclose(moved[30:], 0.0, atol=1e-7)
    full = jnp.max(jnp.abs(out_of(False, there) - out_of(False, x)), axis=-1)
    assert np.all(np.asarray(full[0, 10:]) > 0)


# -- the router reads the stream before attention ----------------------------


def _routing_of(monkeypatch, layer, x):
    """``(weights, experts)`` a layer hands ``moe.moe_mlp``."""
    seen = []
    real = moe.moe_mlp

    def spy(h, weights, experts, *a, **kw):
        seen.append((np.asarray(weights), np.asarray(experts)))
        return real(h, weights, experts, *a, **kw)

    monkeypatch.setattr(moe, "moe_mlp", spy)
    smallthinker._layer(CFG32, True, True, layer, x,
                        attn_fn=causal_attention)
    monkeypatch.setattr(moe, "moe_mlp", real)
    return seen[0]


def test_the_router_reads_the_stream_before_attention(monkeypatch) -> None:
    """Perturbing THIS layer's ``W_o`` (what attention adds) leaves this
    layer's chosen experts and their weights bit-equal: the router scored
    ``n1``. Perturbing ``g1`` does not; and the experts' input does move
    with ``W_o``."""
    layer = _params(CFG32, 5)["layers_2"]
    x = jax.random.normal(jax.random.key(3), (2, S, D), jnp.float32)
    base = _routing_of(monkeypatch, layer, x)
    other_o = with_leaf({"l": layer}, "l", ("attn", "o_proj", "kernel"),
                        lambda w: w * 3.0 + 0.1)["l"]
    after = _routing_of(monkeypatch, other_o, x)
    assert np.array_equal(base[0], after[0])
    assert np.array_equal(base[1], after[1])
    other_g = with_leaf({"l": layer}, "l", ("norm_1", "scale"),
                        lambda s: s.at[:8].multiply(3.0))["l"]
    scaled = _routing_of(monkeypatch, other_g, x)
    assert not np.array_equal(base[0], scaled[0])
    assert not np.array_equal(base[1], scaled[1])
    run = functools.partial(smallthinker._layer, CFG32, True, True, x=x,
                            attn_fn=causal_attention)
    assert float(jnp.max(jnp.abs(run(other_o)[0] - run(layer)[0]))) > 1e-2
    # the weights are the softmax over the chosen logits: they sum to one
    np.testing.assert_allclose(base[0].sum(-1), 1.0, atol=1e-6)


def test_the_default_sublayer_scores_its_own_input_by_sigmoid() -> None:
    """``routed_sublayer`` without the three new arguments is what the
    other four models call (their whole programs are pinned in
    tests/test_nemotron_h.py); with ``route_on`` the experts' input it is
    the same routing."""
    layer = _params(CFG32, 5)["layers_2"]
    x = jax.random.normal(jax.random.key(3), (2, S, D), jnp.float32)
    own, rec = common.routed_sublayer(
        CFG32, x, layer["norm_2"]["scale"], layer["moe"])
    n2 = common.rms_norm(x, layer["norm_2"]["scale"], CFG.rms_eps)
    same, rec2 = common.routed_sublayer(
        CFG32, x, layer["norm_2"]["scale"], layer["moe"], route_on=n2)
    assert np.array_equal(rec["experts"], rec2["experts"])
    np.testing.assert_allclose(own, same, atol=1e-6)
    soft, _ = common.routed_sublayer(
        CFG32, x, layer["norm_2"]["scale"], layer["moe"], score="softmax")
    assert float(jnp.max(jnp.abs(soft - own))) > 1e-3
    with pytest.raises(AssertionError):
        common.routed_sublayer(CFG32, x, layer["norm_2"]["scale"],
                               layer["moe"], score="tanh")


# -- the experts -------------------------------------------------------------


def test_reglu_experts_are_the_plain_sum_over_experts() -> None:
    """``(relu(x·W_g) ⊙ x·W_u)·W_d`` through the grouped matmuls, against
    every expert on every row picked by its group, and against the
    reference's own ``reglu``; silu in its place is another result."""
    k = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(k[0], (64, 16), jnp.float32)
    gate = jax.random.normal(k[1], (4, 16, 24), jnp.float32) * 0.3
    up = jax.random.normal(k[2], (4, 16, 24), jnp.float32) * 0.3
    down = jax.random.normal(k[3], (4, 24, 16), jnp.float32) * 0.3
    sizes = jnp.array([10, 0, 30, 24], jnp.int32)
    got = moe.reglu_experts(x, gate, up, down, sizes)
    which = jnp.repeat(jnp.arange(4), sizes, total_repeat_length=64)
    with jax.default_matmul_precision("highest"):
        every = jnp.einsum(
            "enf,efd->end",
            jax.nn.relu(jnp.einsum("nd,edf->enf", x, gate))
            * jnp.einsum("nd,edf->enf", x, up), down)
        ref = jnp.stack([smallthinker_f32.reglu(x, gate[e], up[e], down[e])
                         for e in range(4)])
    want = every[which, jnp.arange(64)]
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(every, ref, atol=1e-5)
    silu = moe.swiglu_experts(x, gate, up, down, sizes)
    assert float(jnp.max(jnp.abs(silu - got))) > 1e-2


def _layer_and_stream(seed):
    params = _params(CFG32, seed)
    x = jax.random.normal(jax.random.key(50 + seed), (2, S, D), jnp.float32)
    return params["layers_3"], x


def _full_layer(layer, seed):
    """The same layer with all 8 routed experts: the held 4 and 4 more."""
    extra = smallthinker.init_params(
        dataclasses.replace(CFG32, first_expert=4), jax.random.key(900 + seed)
    )["layers_3"]["moe"]
    full = jax.tree_util.tree_map(lambda a: a, layer)
    for name in ("gate_proj", "up_proj", "down_proj"):
        full["moe"][name] = {"kernel": jnp.concatenate(
            [layer["moe"][name]["kernel"], extra[name]["kernel"]])}
    return full


def _norms(layer, x, n1_from):
    """``(n1, n2)`` as ``[N, d]``: the router's input is the norm of
    ANOTHER stream than the experts' (here a seeded one: the layer's
    input before attention)."""
    n1 = smallthinker_f32._rms(n1_from, layer["norm_1"]["scale"], CFG.rms_eps)
    n2 = smallthinker_f32._rms(x, layer["norm_2"]["scale"], CFG.rms_eps)
    return n1.reshape(-1, D), n2.reshape(-1, D)


@pytest.mark.parametrize("split", [(2, 2, 2, 2), (1,) * 8, (4, 4), (3, 5),
                                   (1, 6, 1), (8,)])
def test_the_shares_add_up_to_the_uncut_layer(split) -> None:
    """The routed parts that all the shares give (4 chips of the
    deployment hold 16 each of 64 at ``first_expert`` 0 / 16 / 32 / 48;
    here 4 shares of 2, and uneven ones), with everything every chip
    computes alike — the residual stream, both norms, the router on
    ``n1`` — counted once, are the reference's expert MLP with every
    expert held."""
    layer, x = _layer_and_stream(7)
    before = jax.random.normal(jax.random.key(77), x.shape, jnp.float32)
    full = _full_layer(layer, 7)
    n1, n2 = _norms(full, x, before)
    with jax.default_matmul_precision("highest"):
        want, _ = smallthinker_f32._experts(
            n1, n2, full["moe"], top_k=CFG.top_k, first_expert=0)
        total, first = jnp.zeros_like(want), 0
        for held in split:
            cfg = dataclasses.replace(CFG32, first_expert=first,
                                      n_experts_held=held)
            share = jax.tree_util.tree_map(lambda a: a, full)
            for name in ("gate_proj", "up_proj", "down_proj"):
                share["moe"][name] = {"kernel": full["moe"][name]["kernel"][
                    first:first + held]}
            y, rec = smallthinker._moe_mlp(cfg, share, x, n1.reshape(x.shape))
            total = total + (y - x).reshape(-1, D)
            first += held
        assert first == CFG.n_routed_experts
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_the_four_vocabulary_slices_add_up_to_the_uncut_head() -> None:
    """The cell holds rows 0 … V/4 of the table and of the head. With ids
    drawn below V/4 the layers see the same stream whatever is held, and
    the four slices' logsumexp terms and target logits give the uncut
    head's cross entropy: the layers counted once, nothing stands in for
    a slice."""
    ways, rows = 4, CFG.vocab_size
    whole_cfg = dataclasses.replace(CFG32, vocab_size=ways * rows)
    whole = _params(whole_cfg, 8)
    held = jax.tree_util.tree_map(lambda a: a, whole)
    held["wte"] = {"embedding": whole["wte"]["embedding"][:rows]}
    held["lm_head"] = {"kernel": whole["lm_head"]["kernel"][:, :rows]}
    tok, tgt = _batch(8)                        # ids below the slice's rows
    with jax.default_matmul_precision("highest"):
        uncut = smallthinker.loss_terms(whole_cfg, whole, tok, tgt)
        share = smallthinker.loss_terms(CFG32, held, tok, tgt)
    np.testing.assert_allclose(share["hidden"], uncut["hidden"], atol=1e-6)
    # what each of the four chips would hand to the exchange
    h = share["hidden"]
    lse, target = [], jnp.zeros(tgt.shape, jnp.float32)
    for w in range(ways):
        head = whole["lm_head"]["kernel"][:, w * rows:(w + 1) * rows]
        logits = jnp.einsum("bsd,dv->bsv", h, head, precision="highest")
        lse.append(jax.nn.logsumexp(logits, axis=-1))
        local = tgt - w * rows
        mine = (local >= 0) & (local < rows)
        target += jnp.where(mine, jnp.take_along_axis(
            logits, jnp.clip(local, 0, rows - 1)[..., None], axis=-1)[..., 0],
            0.0)
    combined = jnp.mean(jax.nn.logsumexp(jnp.stack(lse), axis=0) - target)
    assert float(combined) == pytest.approx(float(uncut["ce"]), abs=2e-5)
    # the share's own loss is over ITS rows: slice 0's term alone
    assert float(share["ce"]) == pytest.approx(
        float(jnp.mean(lse[0] - target)), abs=2e-5)


def test_every_assignment_held_and_none_held_run_one_program() -> None:
    layer, x = _layer_and_stream(9)
    before = jax.random.normal(jax.random.key(79), x.shape, jnp.float32)
    n1, n2 = _norms(layer, x, before)
    run = jax.jit(functools.partial(smallthinker._moe_mlp, CFG32))
    seen = []
    for sign in (+1.0, -1.0, 0.0):
        layer["moe"][BIAS] = (
            sign * 100.0 * (jnp.arange(E) < 4)).astype(jnp.float32)
        y, rec = run(layer, x, n1.reshape(x.shape))
        seen.append(int(jnp.sum(rec["loads"][:4])))
        with jax.default_matmul_precision("highest"):
            want, _ = smallthinker_f32._experts(
                n1, n2, layer["moe"], top_k=CFG.top_k, first_expert=0)
        np.testing.assert_allclose((y - x).reshape(-1, D), want, atol=2e-5)
    assert seen[0] == 2 * S * CFG.top_k and seen[1] == 0
    assert 0 < seen[2] < seen[0]
    assert run._cache_size() == 1


# -- the faults of the cell's check ------------------------------------------


# one period: every kind of layer, half the dispatch of the tiny size
ONE = dataclasses.replace(CFG32, windowed=CFG.windowed[:4],
                          rotated=CFG.rotated[:4])


@functools.lru_cache(maxsize=None)
def _sound():
    """Seeded weights, a batch and the reference's terms on them: the
    same for every fault."""
    params, (tokens, targets) = _params(ONE, 5), _batch(5)
    return params, tokens, targets, _reference(ONE)(params, tokens, targets)


@pytest.mark.parametrize("fault", FAULTS + smallthinker_faults.UNLISTED)
def test_a_fault_fails_the_comparison(monkeypatch, fault) -> None:
    """Each fault moves what the cell's checks compare by far more than
    f32 rounding: the test of the reference's teeth at this size. A fault
    of the window also moves the windowed call's own comparison."""
    params, tokens, targets, want = _sound()
    patches, weights, cfg, swa_fn = smallthinker_faults.fault(
        fault, ONE, params)
    for patch in patches:
        monkeypatch.setattr(*patch)
    got = smallthinker.loss_terms(cfg or ONE, weights or params, tokens,
                                  targets)
    chosen = jnp.any(jax.nn.one_hot(got["experts"], E, dtype=bool), axis=-2)
    moved = max(
        abs(float(got["loss"]) - float(want["loss"])),
        float(jnp.max(jnp.abs(got["hidden"] - want["hidden"]))),
        float(jnp.mean(jnp.any(chosen != want["chosen"], axis=-1))),
    )
    # rounding to 8 (bf16) or 4 (e4m3) bits in one place of a tiny model
    floor = 5e-4 if fault in smallthinker_faults.ROUNDING else 1e-2
    assert moved > floor, (fault, moved)
    assert (swa_fn is not None) == (fault in smallthinker_faults.WINDOW)
    if swa_fn is not None:
        alone = jax.jit(family.swa_comparison(ONE, swa_fn))(
            *family.swa_inputs(ONE, 5, S))
        assert not family.judge_swa(alone)["ok"], alone
