"""``models/qwen3_next.py`` on the CPU at the tiny size, seeded weights:
the system against ``benchmark/reference/qwen3_next_f32.py`` — loss, final
hidden state and every gradient leaf, in f32 and in the cell's precision
—, the delta-rule mixer against the recurrence with two value heads a key
head, with one and with four (q and k handed to the scan at the key heads,
copied only for a stand-in at one of the model's two seams), the rotation
over a quarter of the head, both gates,
the faults of ``benchmark/tests/qwen3next_faults.py`` that the CPU can
show under the cell's own comparison, and the expert share tied to the
model."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import qwen3_next as family
from benchmark.reference import qwen3_next_f32
from benchmark.tests import qwen3next_faults as faults
from benchmark.tests.lfm2_faults import patched
from one_program import pallas_calls
from torchft_tpu.models import common, qwen3_next
from torchft_tpu.models.qwen3_next import (
    FULL, LINEAR, QWEN3_NEXT_CONFIGS, Qwen3NextConfig, init_params,
    loss_terms,
)
from torchft_tpu.ops import kda, ssm_pointwise
from torchft_tpu.ops.attention import causal_attention
from torchft_tpu.utils.metrics import TRACED



@pytest.fixture(scope="module", autouse=True)
def one_key_head_a_step(one_head_a_step):
    """The model's tests are not about how many heads share a grid step
    (``conftest.one_head_a_step``), but the delta rule's grid step holds
    whole KEY heads: the tiny model's two value heads a key head take the
    rung of two, the smallest on which q and k are read where they lie
    (at one head a step the op would copy them, and say so)."""
    patch = pytest.MonkeyPatch()
    patch.setattr(kda, "_GDN_LADDER", (2,))
    jax.clear_caches()
    yield
    patch.undo()
    jax.clear_caches()


BF16 = QWEN3_NEXT_CONFIGS["qwen3_next_tiny"]
# float32 compute: the comparison is of the mathematics, not of bf16
TINY = dataclasses.replace(BF16, dtype=jnp.float32)
SEQ = 40            # two and a half chunks of 16: a ragged end
BIAS = qwen3_next.BALANCE_BIAS


def dims(cfg):
    return family.reference_dims(cfg)


def batch(cfg, seed=1, rows=2, seq=SEQ):
    tokens = jax.random.randint(jax.random.key(seed), (rows, seq), 0,
                                cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def seeded(cfg, seed=0):
    """Initial weights with what initialises to a constant (the balance
    biases, the zero-centred norm weights, ``w_V``) drawn as the cell's
    check seeds it: a norm's weight left out would otherwise show
    nowhere."""
    return family.seed_check_weights(
        init_params(cfg, jax.random.key(seed)), seed)


_CACHE = {}


def both_sides():
    """System (f32 and bf16 compute) and reference on the same weights
    and batch, once a module: terms and gradient trees."""
    if not _CACHE:
        params, (tok, tgt) = seeded(TINY), batch(TINY)

        def side(terms_fn):
            # jitted: eager, the interpreter's kernels run operation by
            # operation
            @jax.jit
            def run(p):
                terms, pull = jax.vjp(
                    lambda p: {k: v for k, v in terms_fn(p).items()
                               if k in ("loss", "hidden")}, p)
                return terms, pull({
                    "loss": jnp.ones(()),
                    "hidden": jnp.zeros_like(terms["hidden"])})[0]
            return run(params)

        got, grads = side(lambda p: loss_terms(TINY, p, tok, tgt))
        low, grads_low = side(lambda p: loss_terms(BF16, p, tok, tgt))
        want, grads_ref = side(lambda p: qwen3_next_f32.terms(
            p, tok, tgt, **dims(TINY)))
        _CACHE.update(params=params, got=got, want=want, low=low, grads=grads,
                      grads_low=grads_low, grads_ref=grads_ref)
    return _CACHE


def leaf_paths(cfg=TINY):
    """Every leaf a gradient reaches: the balance bias's place carries the
    step's loads (``common.loads_as_gradient``), not a gradient."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_leaves_with_path(shapes)
            if path[-1].key != BIAS]


def leaf(tree, path):
    return {jax.tree_util.keystr(p): g
            for p, g in jax.tree_util.tree_leaves_with_path(tree)}[path]


def test_the_tiny_cut_holds_both_kinds_of_layer():
    assert TINY.layer_types == (LINEAR, LINEAR, LINEAR, FULL)
    published = Qwen3NextConfig()
    assert published.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 12
    assert (published.n_key_heads, published.n_value_heads, published.key_dim,
            published.value_dim) == (16, 32, 128, 128)
    assert (published.n_heads, published.n_kv_heads, published.head_dim,
            published.rotary_lanes) == (16, 2, 256, 64)
    # two value heads a key head; the widths differ and are no lane tile
    assert TINY.n_value_heads == 2 * TINY.n_key_heads
    assert TINY.key_dim != TINY.value_dim and TINY.rotary_lanes == 4
    params = init_params(TINY, jax.random.key(0))
    assert set(params["layers_0"]) == {"gdn", "moe", "norm_1", "norm_2"}
    assert set(params["layers_3"]) == {"attn", "moe", "norm_1", "norm_2"}
    gdn, attn = params["layers_0"]["gdn"], params["layers_3"]["attn"]
    # ONE fused projection each: q, k, v, z; b, a; q with its gate
    assert gdn["qkvz_proj"]["kernel"].shape == (48, 2 * 24 + 2 * 96)
    assert gdn["ba_proj"]["kernel"].shape == (48, 8)
    assert gdn["conv"]["kernel"].shape == (4, 2 * 24 + 96)
    assert gdn["A_log"].shape == gdn["dt_bias"].shape == (4,)
    assert attn["q_proj"]["kernel"].shape == (48, 2 * 6 * 16)
    assert attn["k_proj"]["kernel"].shape == (48, 2 * 16)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert params["layers_0"]["moe"]["shared"]["gate"]["kernel"].shape == (
        48, 1)
    # the zero-centred weights start at 0, the delta rule's plain one at 1
    for zero in (params["ln_f"], params["layers_0"]["norm_1"],
                 params["layers_3"]["norm_2"], attn["q_norm"], attn["k_norm"]):
        assert not np.any(zero["scale"])
    assert np.all(gdn["o_norm"]["scale"] == 1.0)
    with pytest.raises(AssertionError):
        dataclasses.replace(TINY, layer_types=("sliding_attention",))
    with pytest.raises(AssertionError):
        dataclasses.replace(TINY, n_value_heads=3)


def test_loss_and_hidden_state_equal_the_references():
    both = both_sides()
    assert float(both["got"]["loss"]) == pytest.approx(
        float(both["want"]["loss"]), abs=2e-5)
    np.testing.assert_allclose(both["got"]["hidden"], both["want"]["hidden"],
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("path", leaf_paths())
def test_every_gradient_leaf_equals_the_references(path):
    both = both_sides()
    got, want = leaf(both["grads"], path), leaf(both["grads_ref"], path)
    assert float(jnp.max(jnp.abs(want))) > 0, "a leaf no gradient reaches"
    np.testing.assert_allclose(
        got, want, atol=2e-4 * float(jnp.max(jnp.abs(want))), rtol=2e-4)


@pytest.mark.parametrize("path", leaf_paths())
def test_every_gradient_leaf_in_the_cells_precision(path):
    """bf16 compute with the f32 islands the model file names: every
    leaf's gradient is f32 and lies along the reference's (48 channels at
    an init of 0.125 make bf16 loud)."""
    both = both_sides()
    got, want = leaf(both["grads_low"], path), leaf(both["grads_ref"], path)
    assert got.dtype == jnp.float32
    cosine = float(jnp.vdot(got, want)
                   / (jnp.linalg.norm(got) * jnp.linalg.norm(want)))
    assert cosine > 0.8, cosine


def test_the_cells_precision_follows_the_reference():
    both = both_sides()
    assert float(both["low"]["loss"]) == pytest.approx(
        float(both["want"]["loss"]), abs=3e-2)
    h, h_ref = (np.asarray(both[k]["hidden"], np.float32).reshape(-1, 48)
                for k in ("low", "want"))
    error = np.linalg.norm(h - h_ref, axis=-1) / np.linalg.norm(h_ref, axis=-1)
    assert float(np.sqrt(np.mean(error ** 2))) < 0.1


def test_remat_and_chunked_cross_entropy_change_nothing():
    both = both_sides()
    tok, tgt = batch(TINY)
    other = dataclasses.replace(TINY, remat=True, xent_chunks=4)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: qwen3_next.loss_fn(other, p, tok, tgt)))(both["params"])
    assert float(loss) == pytest.approx(float(both["got"]["loss"]), abs=1e-6)
    for path in leaf_paths():
        np.testing.assert_allclose(
            leaf(grads, path), leaf(both["grads"], path), atol=1e-6,
            rtol=1e-5)


def _mixer_alone(heads):
    """A one-layer cut of the tiny model with ``heads`` = (key, value)
    heads, its seeded layer and an input."""
    cfg = dataclasses.replace(TINY, n_key_heads=heads[0],
                              n_value_heads=heads[1],
                              layer_types=(LINEAR,))
    return (cfg, seeded(cfg, 3)["layers_0"],
            jax.random.normal(jax.random.key(4), (2, SEQ, cfg.d_model)))


def _reference_mixer(cfg, layer, x):
    d = dims(cfg)
    with jax.default_matmul_precision("highest"):
        return x + qwen3_next_f32._linear(
            qwen3_next_f32.norm(x, layer["norm_1"]["scale"], cfg.rms_eps),
            layer["gdn"], n_key=d["n_key"], n_value=d["n_value"],
            key_dim=d["key_dim"], value_dim=d["value_dim"], eps=cfg.rms_eps)


def _counted(fn, *args):
    """``(fn(*args), how far the two value-group counters moved)``."""
    names = ("gdn_value_group_calls", "gdn_value_group_copies")
    before = TRACED.snapshot()
    out = fn(*args)
    after = TRACED.snapshot()
    return out, tuple(after.get(n, 0) - before.get(n, 0) for n in names)


@pytest.fixture
def a_key_head_and_its_value_heads_a_step(monkeypatch):
    """Another rung than the file's, where a key head has more than two
    value heads; whoever moves the ladder clears jax's caches
    (``conftest.one_head_a_step``)."""
    moved = []

    def to(rung):
        if kda._GDN_LADDER != (rung,):
            monkeypatch.setattr(kda, "_GDN_LADDER", (rung,))
            jax.clear_caches()
            moved.append(rung)
    yield to
    if moved:
        jax.clear_caches()


@pytest.mark.parametrize("heads", [(2, 4), (2, 2), (1, 4)],
                         ids=lambda h: f"{h[0]}k{h[1]}v")
def test_the_mixer_equals_the_recurrence_whatever_the_value_groups(
        heads, a_key_head_and_its_value_heads_a_step):
    """The delta-rule mixer alone against the reference's (the recurrence
    position by position): two value heads a key head, one, and four; the
    counter moves where the heads differ and there alone. Where they
    differ q and k reach ``gdn_fwd`` at the KEY heads — its q operand is
    ``H_k·K`` wide, no array ``[B, S, H_v, K]`` stands anywhere in the
    mixer's program, and nothing was copied (a grid step of one key head
    and its value heads: the file's rung, four at ``1k4v``)."""
    Hk, Hv = heads
    a_key_head_and_its_value_heads_a_step(max(2, Hv // Hk))
    cfg, layer, x = _mixer_alone(heads)
    got, moved = _counted(jax.jit(
        lambda l, x: qwen3_next._gdn_mixer(cfg, l, x)), layer, x)
    assert moved == (Hk != Hv, 0)
    want = _reference_mixer(cfg, layer, x)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    assert float(jnp.max(jnp.abs(want - x))) > 1e-2
    def mixer(l, x):
        return qwen3_next._gdn_mixer(cfg, l, x)

    q_operand = pallas_calls(mixer, layer, x)["gdn_fwd"].invars[0].aval
    assert q_operand.shape[-1] == Hk * cfg.key_dim
    if Hk != Hv:
        assert f"[2,{SEQ},{Hv},{cfg.key_dim}]" not in str(
            jax.make_jaxpr(mixer)(layer, x))


def _value_heads_swapped(gdn, cfg):
    """The mixer's weights with value heads 1 and 2 of four exchanged,
    wherever a value head has columns, rows or entries of its own: the
    published map ``h // 2`` on them is the modulo map ``h % 2`` on the
    weights as they were, and the mixer's output is the same sum."""
    Hk, Hv, K, V = (cfg.n_key_heads, cfg.n_value_heads, cfg.key_dim,
                    cfg.value_dim)
    assert (Hk, Hv) == (2, 4)
    heads = np.array([0, 2, 1, 3])

    def by_head(z, axis, start, width):
        """The ``Hv·width`` entries from ``start`` on along ``axis``."""
        z = np.moveaxis(np.array(z), axis, -1)
        part = z[..., start:start + Hv * width]
        z[..., start:start + Hv * width] = part.reshape(
            part.shape[:-1] + (Hv, width))[..., heads, :].reshape(part.shape)
        return jnp.asarray(np.moveaxis(z, -1, axis))

    v0 = 2 * Hk * K
    qkvz = by_head(gdn["qkvz_proj"]["kernel"], 1, v0, V)            # v
    qkvz = by_head(qkvz, 1, v0 + Hv * V, V)                         # z
    ba = by_head(by_head(gdn["ba_proj"]["kernel"], 1, 0, 1), 1, Hv, 1)
    return dict(
        gdn, qkvz_proj={"kernel": qkvz}, ba_proj={"kernel": ba},
        conv={"kernel": by_head(gdn["conv"]["kernel"], 1, v0, V)},
        A_log=by_head(gdn["A_log"], 0, 0, 1),
        dt_bias=by_head(gdn["dt_bias"], 0, 0, 1),
        o_proj={"kernel": by_head(gdn["o_proj"]["kernel"], 0, 0, V)})


@pytest.mark.parametrize("seam", ["_value_groups", "_gdn_scan"])
def test_a_stand_in_at_either_seam_is_handed_the_copy(seam):
    """The two seams the faults file patches, two value heads a key head.
    ``value_heads_modulo`` (``jnp.tile`` in ``_value_groups``' place): the
    map is no longer the kernels' own, so the mixer copies through it,
    counts the copy, and its result is the modulo map's — the reference's
    on weights whose value heads are exchanged to match —, not the
    published one's. A stand-in in ``_gdn_scan``'s place (the fault
    ``state_bf16``'s recurrence takes equal head counts) does not say it
    takes key heads: it is handed q and k at the VALUE heads, as it always
    was, and the result is the normal path's."""
    cfg, layer, x = _mixer_alone((2, 4))
    handed = []

    def stand_in(q, k, v, g, beta):
        handed.append((q.shape, k.shape, v.shape))
        return kda.gdn_scan(q, k, v, g, beta)

    patches = {
        "_value_groups": faults.fault("value_heads_modulo", cfg)[0],
        "_gdn_scan": ((qwen3_next, "_gdn_scan", stand_in),),
    }[seam]
    with patched(patches):
        got, moved = _counted(jax.jit(
            lambda l, x: qwen3_next._gdn_mixer(cfg, l, x)), layer, x)
    assert moved == (1, 1)
    published = _reference_mixer(cfg, layer, x)
    if seam == "_gdn_scan":
        wide = (2, SEQ, 4, cfg.key_dim)
        assert handed == [(wide, wide, (2, SEQ, 4, cfg.value_dim))]
        np.testing.assert_allclose(got, published, atol=5e-5, rtol=5e-5)
        return
    want = _reference_mixer(
        cfg, dict(layer, gdn=_value_heads_swapped(layer["gdn"], cfg)), x)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    assert float(jnp.max(jnp.abs(got - published))) > 1e-2


@pytest.mark.parametrize("seq", [SEQ, 13], ids=["tiles_of_8", "ragged"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_heads_l2_norm_is_the_plain_one_in_another_view(seq, dtype):
    """``_heads_normed`` does its arithmetic on the view the flat array
    has in memory (tiles of 8 positions); the numbers are
    ``_l2_normed``'s on ``[B, S, heads, K]``, forward and backward, bit
    for bit, with the scale and without; a length that is no multiple of
    8 takes the plain form."""
    z = jax.nn.silu(jax.random.normal(
        jax.random.key(5), (2, seq, 3 * 12))).astype(dtype)
    do = jax.random.normal(jax.random.key(6), (2, seq, 3, 12)).astype(dtype)
    for scale in (12 ** -0.5, None):
        def plain(z):
            x = qwen3_next._l2_normed(z.reshape(2, seq, 3, 12))
            return (x if scale is None else x * scale).astype(dtype)

        want, pull = jax.vjp(jax.jit(plain), z)
        got, pull_got = jax.vjp(jax.jit(
            lambda z: qwen3_next._heads_normed(z, 3, scale, dtype)), z)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(pull_got(do)[0], pull(do)[0])


def test_value_head_h_reads_key_head_h_over_r():
    x = jnp.arange(2 * 3 * 2 * 4, dtype=jnp.float32).reshape(2, 3, 2, 4)
    got = qwen3_next._value_groups(x, 6)
    assert got.shape == (2, 3, 6, 4)
    for h in range(6):
        assert np.array_equal(got[:, :, h], x[:, :, h // 3])


def test_the_rotation_turns_a_quarter_of_the_head_and_passes_the_rest():
    """``ops/ssm_pointwise.py::rotary`` from this model's table against
    the reference's rotation: lanes 0 .. D/4 turned in pairs ``(i, i +
    D/8)``, the others bit for bit; the published frequencies."""
    f = qwen3_next.rotation_freqs(Qwen3NextConfig())
    assert f.shape == (32,) and f[0] == 1.0
    np.testing.assert_allclose(f, 1e7 ** (-np.arange(32) / 32.0), rtol=1e-6)
    cfg = dataclasses.replace(TINY, head_dim=32)      # 8 lanes turned
    assert cfg.rotary_lanes == 8
    x = jax.random.normal(jax.random.key(0), (2, SEQ, 3 * 32))
    table = ssm_pointwise.rotary_tables(
        jnp.asarray(qwen3_next.rotation_freqs(cfg)), SEQ, 32)
    got = ssm_pointwise.rotary(x, *table, 4).transpose(0, 2, 1, 3)
    want = qwen3_next_f32.rotate(x.reshape(2, SEQ, 3, 32), cfg.rope_theta, 8)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.array_equal(got[..., 8:], x.reshape(2, SEQ, 3, 32)[..., 8:])
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :8]
                                 - x.reshape(2, SEQ, 3, 32)[:, 1:, :, :8]))) > .1
    # position 0 is not turned at all
    np.testing.assert_allclose(got[:, 0], x.reshape(2, SEQ, 3, 32)[:, 0],
                               atol=1e-6)


def test_the_attention_gate_is_one_sigmoid_an_element():
    o = jax.random.normal(jax.random.key(0), (2, 5, 3, 4))
    logits = jax.random.normal(jax.random.key(1), (2, 5, 12))
    got = qwen3_next.attn_gate(o, logits)
    np.testing.assert_allclose(
        got, o.reshape(2, 5, 12) * jax.nn.sigmoid(logits), rtol=1e-6)
    # one logit moves one channel of one position
    moved = qwen3_next.attn_gate(o, logits.at[1, 2, 7].add(1.0))
    assert np.argwhere(np.asarray(moved != got)).tolist() == [[1, 2, 7]]
    # and the gate's columns are the second half of the one projection
    layer = seeded(TINY, 5)["layers_3"]
    x = jax.random.normal(jax.random.key(2), (1, 8, 48))
    table = ssm_pointwise.rotary_tables(
        jnp.asarray(qwen3_next.rotation_freqs(TINY)), 8, TINY.head_dim)
    run = jax.jit(lambda l: qwen3_next._attn_mixer(
        TINY, l, x, table, attn_fn=causal_attention))
    base = run(layer)
    w = layer["attn"]["q_proj"]["kernel"]
    shut = jax.tree_util.tree_map(lambda a: a, layer)
    shut["attn"] = dict(layer["attn"], q_proj={
        "kernel": w.at[:, 96:].set(0.0)})       # sigmoid(0) = 1/2 everywhere
    with patched(((qwen3_next, "attn_gate",
                   lambda o, g: o.reshape(*o.shape[:2], -1)),)):
        whole = jax.jit(lambda l: qwen3_next._attn_mixer(
            TINY, l, x, table, attn_fn=causal_attention))(layer)
    np.testing.assert_allclose(run(shut) - x, 0.5 * (whole - x), atol=1e-5)
    assert float(jnp.max(jnp.abs(base - run(shut)))) > 1e-3


def test_the_shared_expert_stands_behind_one_sigmoid_a_token():
    m = seeded(TINY, 6)["layers_0"]["moe"]["shared"]
    h = jax.random.normal(jax.random.key(3), (10, 48))
    got = qwen3_next._shared_expert(TINY, m, h)
    gate = jax.nn.sigmoid(h @ m["gate"]["kernel"])
    assert gate.shape == (10, 1)
    np.testing.assert_allclose(got, gate * common.swiglu(h, m, jnp.float32),
                               rtol=1e-5, atol=1e-6)


def test_the_sound_system_passes_the_tight_limits(monkeypatch):
    """In f32 the sound system stands far inside limits a hundred times
    tighter than the cell's: the faults below fail the CELL's."""
    params, (tok, tgt) = seeded(TINY, 2), batch(TINY, 2)
    seen = family.per_token_errors(TINY, params, params, tok, tgt, 2)
    for name in ("HIDDEN_REL_L2_RMS_MAX", "HIDDEN_REL_L2_MAX",
                 "REFERENCE_LOSS_ATOL"):
        monkeypatch.setattr(family, name, getattr(family, name) / 100)
    monkeypatch.setattr(family, "TOP_K_DISAGREEMENT_MAX", 0.0)
    verdict = family.judge(seen)
    assert verdict["ok"], verdict
    assert verdict["tokens"] == 2 * SEQ and len(verdict["held_share"]) == 4
    lo, hi, slow, fast = verdict["beta_decay"]
    assert 0 < lo < hi < 1 and 0 < slow <= fast <= 1


@functools.lru_cache(maxsize=None)
def _reference_on_a_selection():
    """The reference on a handed selection, one program for every fault
    (no patch reaches it: it imports nothing from the program)."""
    return jax.jit(lambda p, tok, tgt, taken: {
        k: v for k, v in qwen3_next_f32.terms(
            p, tok, tgt, selection=taken, **dims(TINY)).items()})


def _faulty_side(patches, system_cfg):
    """``family.comparison``'s numbers from TWO programs, so that a fault
    compiles the system alone: the patched system's terms, then the
    reference on the system's top-k sets."""
    params, (tok, tgt) = seeded(TINY, 2), batch(TINY, 2)
    with patched(patches):
        got = jax.jit(lambda p: loss_terms(
            system_cfg or TINY, p, tok, tgt))(params)
    taken = jnp.any(jax.nn.one_hot(
        got["experts"], TINY.n_routed_experts, dtype=bool), axis=-2)
    want = _reference_on_a_selection()(params, tok, tgt, taken)
    h, h_ref = (np.asarray(z["hidden"], np.float32).reshape(-1, TINY.d_model)
                for z in (got, want))
    return {
        "error": np.linalg.norm(h - h_ref, axis=-1)
        / np.linalg.norm(h_ref, axis=-1),
        "disagreement": np.mean(np.any(
            np.asarray(taken) != np.asarray(want["chosen"]), axis=-1)),
        "loss": got["loss"], "reference_loss": want["loss"],
        "held_share": got["held_share"],
        "load_max_over_mean": got["load_max_over_mean"],
    }


CPU_FAULTS = ("value_heads_modulo", "beta_doubled", "gate_before_norm",
              "norm_plain_weight", "rope_half_head", "attn_gate_a_head",
              "attn_gate_dropped", "shared_gate_dropped", "attention_fp8")


@pytest.mark.parametrize("name", CPU_FAULTS)
def test_a_fault_fails_the_cells_comparison(name):
    """The faults of ``benchmark/tests/qwen3next_faults.py`` that this
    size can show (a chunk's edge and a near-tie of 512 logits it cannot),
    against the sound reference under the cell's own limits, in float32 so
    that nothing but the fault is seen; one that strikes the flash call
    fails that call's own comparison too."""
    assert name in faults.FAULTS and len(faults.FAULTS) == 11
    patches, system_cfg, attn_fn, scan_fn, flash_fn = faults.fault(name, TINY)
    assert scan_fn is None
    if flash_fn is None:
        assert not family.judge(_faulty_side(patches, system_cfg))["ok"]
        return
    # one attention layer of four, 40 positions: the whole model's limits
    # stand wide of it here; the call's own comparison does not
    alone = jax.jit(family.flash_comparison(TINY, 2, SEQ, flash_fn))(
        np.uint32(2))
    assert not family.judge_flash(jax.device_get(alone))["ok"]


def test_the_state_rounded_at_chunk_edges_fails_the_scans_own_limits():
    """``state_bf16`` at a length that crosses edges (the fault rounds
    every ``CHUNK`` positions): the whole model's limits may not see it,
    the scan's own comparison does."""
    _, _, _, scan_fn, _ = faults.fault("state_bf16", TINY)
    args, do = family.gdn_inputs(TINY, np.uint32(1), 1, 3 * faults.CHUNK)
    sound = jax.device_get(jax.jit(family.gdn_comparison())(args, do))
    assert family.judge_gdn(sound)["ok"], sound
    seen = jax.device_get(jax.jit(family.gdn_comparison(scan_fn))(args, do))
    # two edges at 12 | 24-wide heads read 0.0005 - 0.0009 where the
    # cell's 63 at 128 | 128 read 0.0024 - 0.0035 (the family's header):
    # hundreds of times the sound f32 reading in every leaf, and over a
    # limit as tight against ITS sound reading as the cell's is against
    # the cell's (2 x)
    for name in family.GDN_LEAVES:
        assert seen[name] > 100 * sound[name], name
        assert seen[name] > 2 * max(sound[name], 1e-4), name


E = 32      # routed experts of the share test's layer
CFG_E = dataclasses.replace(TINY, n_routed_experts=E, n_experts_held=E,
                            top_k=5, layer_types=(LINEAR,))


@pytest.mark.parametrize("split", [(8, 8, 8, 8), (5, 11, 16), (32,)],
                         ids=lambda s: "+".join(map(str, s)))
def test_the_shares_add_up_to_the_uncut_layer(split) -> None:
    """The routed parts that all the shares give (16 chips of the
    deployment hold 32 each of 512; here 4 shares of 8 of 32, and uneven
    ones), with everything every chip computes alike — the mixer, both
    norms, the router and its bias, the shared expert behind its gate —
    counted once, are the reference's layer with every expert held."""
    full = seeded(CFG_E, 7)["layers_0"]
    x = jax.random.normal(jax.random.key(8), (2, SEQ, CFG_E.d_model))
    d = dims(CFG_E)
    with jax.default_matmul_precision("highest"):
        n1 = qwen3_next_f32.norm(x, full["norm_1"]["scale"], CFG_E.rms_eps)
        h = x + qwen3_next_f32._linear(
            n1, full["gdn"], n_key=d["n_key"], n_value=d["n_value"],
            key_dim=d["key_dim"], value_dim=d["value_dim"], eps=CFG_E.rms_eps)
        n2 = qwen3_next_f32.norm(
            h, full["norm_2"]["scale"], CFG_E.rms_eps).reshape(-1, 48)
        y, chosen = qwen3_next_f32._experts(
            n2, full["moe"], top_k=CFG_E.top_k, first_expert=0)
        want = h + y.reshape(h.shape)
        shared = full["moe"]["shared"]
        alike = h + (jax.nn.sigmoid(n2 @ shared["gate"]["kernel"])
                     * qwen3_next_f32.swiglu(n2, shared)).reshape(h.shape)
    assert np.all(np.sum(np.asarray(chosen), axis=-1) == CFG_E.top_k)
    total, first = jnp.zeros_like(want), 0
    for held in split:
        cfg = dataclasses.replace(CFG_E, first_expert=first,
                                  n_experts_held=held)
        share = jax.tree_util.tree_map(lambda a: a, full)
        for name in ("gate_proj", "up_proj", "down_proj"):
            share["moe"][name] = {"kernel": full["moe"][name]["kernel"][
                first:first + held]}
        out, rec = jax.jit(lambda l, x, cfg=cfg: qwen3_next._layer(
            cfg, LINEAR, l, x, None, attn_fn=causal_attention))(share, x)
        assert rec["loads"].shape == (E,)         # routes over all of them
        total = total + (out - alike)       # this share's routed part
        first += held
    assert first == E
    np.testing.assert_allclose(total + alike, want, atol=3e-5)
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
