"""Laguna (models/laguna.py: attention whose query head count, mask and
rotation follow the kind of layer, a gate a head, a dense first layer,
then sigmoid-routed experts of which a share is held beside a shared
expert, an untied head) against the plain float32 reference the benchmark
keeps (benchmark/reference/laguna_f32.py), at a small size on the CPU: d
48, layers full / sliding / sliding / full / sliding with 4 or 6 query
heads on 2 key/value heads of 32 (groups of 2 and 3), a window of 20 keys
in S 64, a YaRN rotation over half a head beside a plain one, 8 routed
experts of width 24 of which 4 are held, top 2, seeded random weights.
Also the rotation helper of models/common.py against the formulas written
out, the pins of the rotated programs that were there before it, and the
mixer's kernels (ops/ssm_pointwise.py: the rotation and the gate a head on
either side of the attention call) against the jnp mixer the model had. The
family (benchmark/families/laguna.py), the optimizer and the
fault-tolerant loop are tests/test_laguna_family.py's."""

import dataclasses
import functools
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark.families import laguna as family
from benchmark.reference import laguna_f32
from torchft_tpu.models import (
    common,
    laguna,
    lfm2,
    llama,
    olmo_hybrid,
    olmoe,
    smallthinker,
)
from torchft_tpu.ops.attention import causal_attention
from torchft_tpu.utils.metrics import TRACED

CFG = laguna.LAGUNA_CONFIGS["laguna_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
# three layers that hold every part once: full and dense, sliding and
# sparse, full and sparse (what the float32 programs compile; the cell's
# precision runs all five)
CFG3 = dataclasses.replace(CFG32, windowed=(0, 1, 0), heads=(4, 6, 4),
                           sparse=(0, 1, 1))
BIAS = laguna.BALANCE_BIAS
D, S, E = CFG.d_model, 64, CFG.n_routed_experts
_params = functools.partial(kit.seeded_params, laguna)
_batch = kit.batch


def _reference(cfg):
    return functools.partial(laguna_f32.terms, **family.reference_dims(cfg))


# -- against the reference ---------------------------------------------------


@functools.lru_cache(maxsize=None)
def _both_sides(cfg):
    """The system's and the reference's ``loss_terms`` of ``cfg``, one
    compiled program each (the eager ops of five layers take minutes)."""
    def system(params, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return laguna.loss_terms(cfg, params, tokens, targets)

    return jax.jit(system), jax.jit(_reference(cfg))


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_compute_equals_the_reference(seed) -> None:
    params, (tokens, targets) = _params(CFG3, seed), _batch(seed)
    system, reference = _both_sides(CFG3)
    got = system(params, tokens, targets)
    want = reference(params, tokens, targets)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), abs=2e-5)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5)
    chosen = jnp.any(jax.nn.one_hot(got["experts"], E, dtype=bool), axis=-2)
    assert np.array_equal(chosen, want["chosen"])
    # two sparse layers of three: the dense first layer routes nothing
    assert got["loads"].shape == (2, E)
    assert float(jnp.sum(got["loads"])) == 2 * 2 * S * CFG.top_k


def test_f32_gradients_equal_the_reference_in_every_leaf() -> None:
    """Every leaf but the balance bias (whose place carries the loads):
    both kinds of attention layer at their own head counts through the
    band mask, both rotations and the gate, the dense MLP, the router
    through the renormalised sigmoid scores, the held experts and the
    shared one, table and head apart."""
    params, (tokens, targets) = _params(CFG3, 2), _batch(2)

    @jax.jit
    def system(p):
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda p: laguna.loss_fn(
                CFG3, p, tokens, targets))(p)

    got = system(params)
    want = jax.jit(jax.grad(lambda p: laguna_f32.loss(
        p, tokens, targets, **family.reference_dims(CFG3))))(params)
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    # 7 leaves of norms and attention a layer; 3 of the dense MLP; 3 + 3
    # + router + bias of a sparse one; table, final norm, head
    assert len(flat) == len(jax.tree_util.tree_leaves(want)) == (
        3 * 7 + 3 + 2 * 8 + 3)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if path[-1].key == BIAS:
            assert float(jnp.sum(g)) == 2 * S * CFG.top_k, name   # the loads
            continue
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * scale, err_msg=name)


@functools.lru_cache(maxsize=None)
def _comparison():
    return jax.jit(family.comparison(CFG))


@pytest.mark.parametrize("seed", [0, 1])
def test_the_cells_precision_agrees_with_the_reference(seed) -> None:
    """bf16 compute, 128 tokens, the cell's own comparison: the reference
    is computed on the top-2 sets the system took, its own choice is
    counted beside it, and every token is compared. The band: five layers
    of bf16 rounding (2^-8 a result) on a stream of unit size."""
    params, (tokens, targets) = _params(CFG, seed), _batch(seed)
    seen = jax.device_get(_comparison()(params, params, tokens, targets))
    assert seen["error"].shape == (128,)
    assert float(seen["disagreement"]) < 0.1
    assert abs(float(seen["loss"]) - float(seen["reference_loss"])) < 2e-2
    assert np.sqrt(np.mean(seen["error"] ** 2)) < 0.03
    assert seen["error"].max() < 0.08
    lo, hi = seen["gate_range"]
    assert 0.0 < lo < 0.5 < hi < 1.0


def test_remat_on_equals_off() -> None:
    """``jax.checkpoint`` a layer changes what is kept, not what is
    computed (float32 compute: in bf16 the compiler rounds the two
    programs' fused intermediates at different places)."""
    cfg = CFG3
    params, (tokens, targets) = _params(cfg, 3), _batch(3)

    def grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: laguna.loss_fn(cfg, p, tokens, targets)))(params)

    (off, g_off) = grads(cfg)
    (on, g_on) = grads(dataclasses.replace(cfg, remat=True))
    assert float(on) == pytest.approx(float(off), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_on),
                    jax.tree_util.tree_leaves(g_off)):
        np.testing.assert_allclose(
            a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))))


# -- the rotation ------------------------------------------------------------

# YaRN's 32 frequencies at the published numbers (theta 5e5 over 64 lanes,
# factor 64, original 4096, beta_fast 64, beta_slow 1: lo 5, hi 16), from
# the formulas written out in float64 by hand: f_i = 5e5^(-i/32); r_i =
# clip((i - 5) / 11, 0, 1); f'_i = f_i / 64 · r_i + f_i · (1 - r_i)
_YARN_TABLE = [
    1, 0.6636012, 0.4403666, 0.2922278, 0.1939227, 0.1286874, 0.07775503,
    0.04652705, 0.02751009, 0.01602251, 0.009150584, 0.005088901,
    0.00272439, 0.001374836, 0.0006249547, 0.0002240097, 2.209709e-05,
    1.466365e-05, 9.730819e-06, 6.457384e-06, 4.285128e-06, 2.843616e-06,
    1.887027e-06, 1.252234e-06, 8.309837e-07, 5.514418e-07, 3.659375e-07,
    2.428366e-07, 1.611466e-07, 1.069371e-07, 7.09636e-08, 4.709153e-08,
]


def test_the_yarn_table_at_the_published_numbers() -> None:
    rot = laguna.LagunaConfig().rope_full
    assert rot.attention_factor == pytest.approx(0.1 * math.log(64) + 1)
    assert rot.attention_factor == pytest.approx(1.4158883083359672)
    assert laguna.yarn_ramp(rot, 64) == (5, 16)
    got = laguna.rotation_freqs(rot, 128)
    assert got.shape == (32,) and got.dtype == np.float32
    np.testing.assert_allclose(got, _YARN_TABLE, rtol=2e-6)
    # written out once more, independently of the table
    i = np.arange(32)
    f = 5e5 ** (-i / 32.0)
    r = np.clip((i - 5) / 11.0, 0, 1)
    np.testing.assert_allclose(got, f / 64 * r + f * (1 - r), rtol=1e-6)
    # below the ramp the plain frequencies, above it all of them / 64
    np.testing.assert_allclose(got[:6], f[:6], rtol=1e-6)
    np.testing.assert_allclose(got[16:], f[16:] / 64, rtol=1e-6)
    # the reference computes its own
    want = laguna_f32.frequencies(64, family.reference_dims(
        laguna.LagunaConfig())["rope_full"])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the sliding layers' plain rotation over the whole head
    swa = laguna.rotation_freqs(laguna.LagunaConfig().rope_swa, 128)
    np.testing.assert_allclose(swa, 1e4 ** (-np.arange(64) / 64.0), rtol=1e-6)


def test_a_partial_rotation_turns_the_first_lanes_and_passes_the_rest() -> None:
    x = jax.random.normal(jax.random.key(5), (2, 16, 3, 32), jnp.float32)
    freqs = jnp.asarray([1.0, 0.3, 0.05, 0.002], jnp.float32)
    got = common.rotary(x, freqs, 1.25)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    # the formula written out: lanes i and i + 4 turn by t · f_i, cos and
    # sin both times the factor
    t = np.arange(16, dtype=np.float32)[None, :, None, None]
    c, s = 1.25 * np.cos(t * freqs), 1.25 * np.sin(t * freqs)
    a, b = np.asarray(x[..., :4]), np.asarray(x[..., 4:8])
    np.testing.assert_allclose(got[..., :4], a * c - b * s, atol=1e-5)
    np.testing.assert_allclose(got[..., 4:8], b * c + a * s, atol=1e-5)
    # position 0 is scaled and not turned; the factor's square is on a
    # turned logit
    np.testing.assert_allclose(got[:, 0, :, :8], 1.25 * x[:, 0, :, :8],
                               atol=1e-6)
    # over the whole head at theta^(-i / half) it is ``llama._rope``
    y = jax.random.normal(jax.random.key(6), (1, 8, 2, 16), jnp.bfloat16)
    whole = 1e4 ** (-jnp.arange(8, dtype=jnp.float32) / 8)
    np.testing.assert_array_equal(common.rotary(y, whole),
                                  llama._rope(y, 1e4))
    # and the reference's rotation agrees on both kinds
    for rot in (CFG.rope_full, CFG.rope_swa):
        dims = family._rope_dims(rot)
        mine = common.rotary(
            x, jnp.asarray(laguna.rotation_freqs(rot, 32)),
            rot.attention_factor)
        np.testing.assert_allclose(mine, laguna_f32.rotate(x, dims),
                                   atol=2e-5)


# sha256 of the gradient program's jaxpr (source positions cut out) of the
# models whose rotation goes through ``llama._rope``, and of that function
# alone, recorded on the parent of PR 59 (6b2008f) BEFORE
# ``common.rotary`` went in: an existing cell's program must not change
# (its ``setup_s`` would pay a compile). ``lfm2``'s is the hash
# tests/test_nemotron_h.py holds; regenerate on purpose only. ``lfm2``
# and ``smallthinker`` recorded again at PR 64, on purpose: their router
# stands under ``common._route``'s ``custom_vjp`` (tests/test_nemotron_h.py
# says what moved); the rotation's equations are what they were, and
# ``olmo_hybrid``, ``olmoe``, ``llama`` and ``rope`` did not move.
_ROTATED_PROGRAMS = {
    "lfm2": (lfm2, lfm2.LFM2_CONFIGS["lfm2_tiny"],
             "17705a7f94c4d118162cf201d27d7bd33b029aaeed363f876adce845c24d3e6e"),
    "smallthinker": (
        smallthinker, smallthinker.SMALLTHINKER_CONFIGS["smallthinker_tiny"],
        "cf402921e4911f94db5dfa0d808e8f578d263e09db7891d9e151e13c8ab39371"),
    "olmo_hybrid": (
        olmo_hybrid, next(iter(olmo_hybrid.OLMO_HYBRID_CONFIGS.values())),
        "dad60e9925e0e041e6a6cb92ed70b871d27430c1697a0519cd2c11f369cedafb"),
    "olmoe": (olmoe, olmoe.OLMOE_CONFIGS["olmoe_tiny"],
              "3c40614299f885d4c7d1230c9b2a6a7a68c7e055e6686e838d81f49e710ed6b8"),
}
_LLAMA_PROGRAM = \
    "3754f4e678329a56dde19219b55a00b8df0fc39a36ea5eee8a3c668462f7d1a7"
_ROPE_PROGRAM = \
    "a0ea5e65bb1b8bd340d17b2a66b28fce6cfec8ec9138e171d57874957fd33e81"


def _jaxpr_hash(fn, *args) -> str:
    text = re.sub(r"/[^ ]*?\.py:\d+", "", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("model", ["rope", "llama", *_ROTATED_PROGRAMS])
def test_the_rotated_programs_are_what_they_were(model) -> None:
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    if model == "rope":
        x = jax.ShapeDtypeStruct((2, 64, 4, 16), jnp.bfloat16)
        assert _jaxpr_hash(lambda x: llama._rope(x, 1e4), x) == _ROPE_PROGRAM
        return
    if model == "llama":
        cfg = llama.LLAMA_CONFIGS["llama_tiny"]
        params = jax.eval_shape(
            lambda: llama.llama_init_params(cfg, jax.random.key(0)))
        grad = jax.grad(lambda p, a, b: llama.llama_loss_fn(cfg, p, a, b))
        assert _jaxpr_hash(grad, params, tokens, tokens) == _LLAMA_PROGRAM
        return
    mod, cfg, program = _ROTATED_PROGRAMS[model]
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    grad = jax.grad(lambda p, a, b: mod.loss_fn(cfg, p, a, b))
    assert _jaxpr_hash(grad, params, tokens, tokens) == program


# -- one layer ---------------------------------------------------------------


def _layer_and_stream(seed, index=1, held=None):
    """Layer ``index`` of seeded weights (1: sliding and sparse; 3: full
    and sparse), with all ``held`` experts if given, and a stream."""
    cfg = CFG32 if held is None else dataclasses.replace(
        CFG32, first_expert=0, n_experts_held=held)
    layer = _params(cfg, seed)[f"layers_{index}"]
    x = jax.random.normal(jax.random.key(200 + seed), (2, S, D), jnp.float32)
    return layer, x


def _attend(layer, x, windowed, attn_fn=causal_attention):
    with jax.default_matmul_precision("highest"):
        return laguna._attn_mixer(
            CFG32, windowed, layer, x,
            laguna.rotation_tables(CFG32, x.shape[1])[windowed],
            attn_fn=attn_fn)


def test_a_sliding_position_sees_its_last_window_keys_and_no_more() -> None:
    """``window`` 20 counts the position itself: moving the stream at
    position ``t - 20`` leaves position ``t``'s attention output as it
    was, moving it at ``t - 19`` does not; a full layer sees both."""
    layer, x = _layer_and_stream(4, index=1)
    full_layer, _ = _layer_and_stream(4, index=3)
    t, W = 50, CFG.window
    bump = jax.random.normal(jax.random.key(9), (D,), jnp.float32)
    for kind, lay, windowed in (("swa", layer, True),
                                ("full", full_layer, False)):
        base = _attend(lay, x, windowed)
        outside = _attend(lay, x.at[:, t - W].add(bump), windowed)
        inside = _attend(lay, x.at[:, t - W + 1].add(bump), windowed)
        moved_out = float(jnp.max(jnp.abs(outside[:, t] - base[:, t])))
        moved_in = float(jnp.max(jnp.abs(inside[:, t] - base[:, t])))
        assert moved_in > 1e-4, kind
        if windowed:
            assert moved_out == 0.0
        else:
            assert moved_out > 1e-4


def test_the_head_count_and_the_grouping_follow_the_layer() -> None:
    """A sliding layer hands the attention 6 query heads on 2 key/value
    heads (3 consecutive ones a key/value head), a full layer 4 (2 each),
    and nothing is copied: k and v arrive at their own head count."""
    seen = []

    def spy(q, k, v, window=None):
        seen.append((q.shape[2], k.shape[2], v.shape[2], window))
        return causal_attention(q, k, v, window=window)

    # traced, not run: the shapes are the program's
    jax.eval_shape(
        lambda p, t: laguna.forward_hidden(CFG32, p, t, attn_fn=spy)[0],
        jax.eval_shape(lambda: laguna.init_params(CFG32, jax.random.key(0))),
        jax.ShapeDtypeStruct((2, S), jnp.int32))
    assert seen == [(4, 2, 2, None), (6, 2, 2, 20), (6, 2, 2, 20),
                    (4, 2, 2, None), (6, 2, 2, 20)]
    shapes = jax.eval_shape(
        lambda: laguna.init_params(CFG, jax.random.key(0)))
    for i, h in enumerate(CFG.heads):
        a = shapes[f"layers_{i}"]["attn"]
        assert a["q_proj"]["kernel"].shape == (D, h * CFG.head_dim)
        assert a["o_proj"]["kernel"].shape == (h * CFG.head_dim, D)
        assert a["gate"]["kernel"].shape == (D, h)
        assert a["k_proj"]["kernel"].shape == (D, 2 * CFG.head_dim)
    assert "mlp" in shapes["layers_0"] and "moe" not in shapes["layers_0"]
    assert all("moe" in shapes[f"layers_{i}"] for i in range(1, 5))
    # query head 3 of a sliding layer reads key/value head 1: moving
    # key/value head 0's keys leaves its output alone
    layer, x = _layer_and_stream(5, index=1)
    moved = jax.tree_util.tree_map(lambda a: a, layer)
    k = layer["attn"]["k_proj"]["kernel"]
    moved["attn"]["k_proj"] = {"kernel": k.at[:, :CFG.head_dim].add(0.3)}
    outs = []
    for lay in (layer, moved):
        heads = []

        def keep(q, k, v, window=None):
            o = causal_attention(q, k, v, window=window)
            heads.append(o)
            return o

        _attend(lay, x, True, keep)
        outs.append(heads[0])
    delta = jnp.max(jnp.abs(outs[0] - outs[1]), axis=(0, 1, 3))   # a head
    assert np.all(delta[:3] > 1e-4) and np.all(delta[3:] == 0.0)


def test_the_gate_is_one_sigmoid_a_head_on_the_attention_output() -> None:
    layer, x = _layer_and_stream(6, index=3)
    H = CFG.heads[3]
    base = _attend(layer, x, False) - x
    # a gate weight of zero is a gate of 0.5 on every head
    half = jax.tree_util.tree_map(lambda a: a, layer)
    half["attn"]["gate"] = {"kernel": jnp.zeros((D, H))}
    ungated = (_attend(half, x, False) - x) * 2.0
    # written out: the heads' outputs times sigmoid(n1 W_γ), then W_o
    n1 = common.rms_norm(x, layer["norm_1"]["scale"], CFG.rms_eps)
    with jax.default_matmul_precision("highest"):
        gamma = jax.nn.sigmoid(n1 @ layer["attn"]["gate"]["kernel"])
        w_o = layer["attn"]["o_proj"]["kernel"].reshape(H, CFG.head_dim, D)
        # head h's part of the ungated output, through its rows of W_o
        only = []
        for h in range(H):
            one = jax.tree_util.tree_map(lambda a: a, half)
            one["attn"]["o_proj"] = {"kernel": (
                w_o * (jnp.arange(H) == h)[:, None, None]
            ).reshape(H * CFG.head_dim, D)}
            only.append((_attend(one, x, False) - x) * 2.0)
    want = sum(gamma[..., h:h + 1] * only[h] for h in range(H))
    np.testing.assert_allclose(sum(only), ungated, atol=1e-5)
    np.testing.assert_allclose(base, want, atol=1e-5)
    assert float(jnp.max(jnp.abs(base - 0.5 * ungated))) > 1e-3


@pytest.mark.parametrize("split", [(2, 2, 2, 2), (4, 4), (3, 5), (8,)],
                         ids=lambda s: "+".join(map(str, s)))
def test_the_shares_add_up_to_the_uncut_layer(split) -> None:
    """The routed parts that all the shares give (8 chips of the
    deployment hold 32 each of 256; here 4 shares of 2 of 8, and uneven
    ones), with everything every chip computes alike — attention at the
    layer's head count, the gate, both norms, the router and its bias, the
    shared expert — counted once, are the reference's layer with every
    expert held."""
    full, x = _layer_and_stream(7, index=1, held=E)
    dims = family.reference_dims(CFG32)
    with jax.default_matmul_precision("highest"):
        n1 = laguna_f32._rms(x, full["norm_1"]["scale"], CFG.rms_eps)
        h = x + laguna_f32._attention(
            n1, full["attn"], n_kv=dims["n_kv"], head_dim=dims["head_dim"],
            rope=dims["rope_swa"], window=dims["window"])
        n2 = laguna_f32._rms(h, full["norm_2"]["scale"], CFG.rms_eps)
        y, _ = laguna_f32._experts(
            n2.reshape(-1, D), full["moe"], top_k=CFG.top_k, first_expert=0,
            routed_scale=CFG.routed_scale)
        want = h + y.reshape(h.shape)
        alike = h + laguna_f32.swiglu(n2, full["moe"]["shared"])
        total, first = jnp.zeros_like(want), 0
        for held in split:
            cfg = dataclasses.replace(CFG32, first_expert=first,
                                      n_experts_held=held)
            share = jax.tree_util.tree_map(lambda a: a, full)
            for name in ("gate_proj", "up_proj", "down_proj"):
                share["moe"][name] = {"kernel": full["moe"][name]["kernel"][
                    first:first + held]}
            out, rec = laguna._layer(
                cfg, True, True, share, x,
                laguna.rotation_tables(cfg, x.shape[1])[True],
                                     attn_fn=causal_attention)
            total = total + (out - alike)       # this share's routed part
            first += held
        assert first == E
    np.testing.assert_allclose(total + alike, want, atol=3e-5)
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05


# -- the kernels on either side of the attention call --------------------------

# full and dense, then sliding and sparse, at the cell's precision: both
# rotations and both head counts
CFG2 = dataclasses.replace(CFG, windowed=(0, 1), heads=(4, 6), sparse=(0, 1))


def _jnp_mixer(cfg, windowed, layer, x, table, *, attn_fn):
    """``laguna._attn_mixer`` as it was until PR 62, kept as the form the
    kernels are held to: ``common.rotary`` on ``[B, S, H, D]`` with its own
    table a call, and the gate as an f32 multiply with a cast each way."""
    a, dt = layer["attn"], cfg.dtype
    B, S, _ = x.shape
    KV, D = cfg.n_kv_heads, cfg.head_dim
    H = a["gate"]["kernel"].shape[-1]
    rot = cfg.rope_swa if windowed else cfg.rope_full
    n32 = common.rms_norm(x.astype(jnp.float32), layer["norm_1"]["scale"],
                          cfg.rms_eps)
    n = n32.astype(dt)
    q = (n @ a["q_proj"]["kernel"].astype(dt)).reshape(B, S, H, D)
    k = (n @ a["k_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
    v = (n @ a["v_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
    freqs = jnp.asarray(laguna.rotation_freqs(rot, D))
    q = common.rotary(q, freqs, rot.attention_factor)
    k = common.rotary(k, freqs, rot.attention_factor)
    o = attn_fn(q, k, v, window=cfg.window if windowed else None)
    gate = laguna.head_gate(n32, a["gate"]["kernel"])
    o = (o.astype(jnp.float32) * gate[..., None]).astype(dt)
    return x + o.reshape(B, S, H * D) @ a["o_proj"]["kernel"].astype(dt)


@functools.lru_cache(maxsize=None)
def _loss_and_gradients(mixer=None):
    """``(loss, gradients)`` of ``CFG2`` on seed 7 as one program, with
    ``mixer`` in ``_attn_mixer``'s place while it is traced."""
    params, (tokens, targets) = _params(CFG2, 7), _batch(7)
    patch = pytest.MonkeyPatch()
    if mixer is not None:
        patch.setattr(laguna, "_attn_mixer", mixer)
    try:
        return jax.jit(jax.value_and_grad(
            lambda p: laguna.loss_fn(CFG2, p, tokens, targets)))(params)
    finally:
        patch.undo()


def test_the_mixers_kernels_give_the_jnp_mixers_loss_and_gradients() -> None:
    """The rotation's and the gate's kernels compute what the jnp passes
    computed, in bf16 with f32 inside: the loss and every gradient leaf of
    two layers. Not to the bit in one program — where the CPU's compiler
    fuses a product into the rotation's sum on one side, an element in some
    thousands of q, k or their cotangents rounds the other way
    (tests/test_ssm_pointwise.py), and a leaf downstream of it moves by a
    place of bf16 (read: the loss equal, the leaves within 2^-8 of their
    largest element; on the chip the kernels' results are the jnp form's
    bits) — a wrong rotation or gate moves them by tenths."""
    loss, grads = _loss_and_gradients()
    want, wants = _loss_and_gradients(_jnp_mixer)
    assert float(loss) == pytest.approx(float(want), rel=2e-4)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(wants)) == (
        2 * 7 + 3 + 8 + 3)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(wants)):
        name = jax.tree_util.keystr(path)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        if path[-1].key == BIAS:
            np.testing.assert_array_equal(g, w, err_msg=name)   # the loads
            continue
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=1e-2 * scale, err_msg=name)


def test_the_mixers_kernels_are_counted_and_the_gate_stays_a_seam(
        monkeypatch) -> None:
    """A forward trace of the five layers engages the gate's kernel five
    times and the rotation's ten (q and k a layer), whatever stands in
    the attention's place; and what the gate IS remains
    ``laguna.head_gate``, which ``benchmark/tests/laguna_faults.py``
    patches by name: ones in its place change the loss."""
    names = ("head_gate_kernel_calls", "rotary_kernel_calls")

    def seen():
        snap = TRACED.snapshot()
        return np.array([snap.get(n, 0) for n in names])

    shapes = jax.eval_shape(
        lambda: laguna.init_params(CFG, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((2, S), jnp.int32)
    before = seen()
    jax.eval_shape(lambda p, t: laguna.forward_hidden(CFG, p, t)[0],
                   shapes, tokens)
    assert tuple(seen() - before) == (5, 10)
    sound, _ = _loss_and_gradients()
    monkeypatch.setattr(laguna, "head_gate", lambda n, w: jnp.ones(
        (*n.shape[:-1], w.shape[-1]), jnp.float32))
    params, (tok, tgt) = _params(CFG2, 7), _batch(7)
    dropped = jax.jit(lambda p: laguna.loss_fn(CFG2, p, tok, tgt))(params)
    assert abs(float(dropped) - float(sound)) > 1e-3
