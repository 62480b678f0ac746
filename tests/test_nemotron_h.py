"""Nemotron-H (models/nemotron_h.py: a Mamba-2 mixer over ops/ssd.py, a
GQA attention mixer without rotary embedding, a sigmoid top-k expert
mixer of relu² experts with a balance bias and a shared expert; a layer
is ONE mixer, which one is a letter of the config's pattern) against the
plain float32 reference the benchmark keeps
(benchmark/reference/nemotron_h_f32.py: the recurrence position by
position), at a small size on the CPU: d 64, pattern ``ME*E``, 4 scan
heads of 16 in 2 groups with a state of 16, 4 query heads on 2 key/value
heads, 8 routed experts of width 32 of which 4 are held, top 2, S 64,
seeded random weights. And the family through the one step maker, the
one optimizer and the fault-tolerant loop: ``test_nemotron_h_family.py``
(which says why that is a file of its own)."""

import dataclasses
import functools
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_kit as kit

from benchmark.families import nemotron_h as family
from benchmark.reference import nemotron_h_f32
from benchmark.tests.nemotron_faults import with_leaf
from torchft_tpu.models import joyai, kimi_linear, lfm2, nemotron_h, olmoe
from torchft_tpu.ops import moe
from torchft_tpu.ops.attention import reference_attention

CFG = nemotron_h.NEMOTRON_H_CONFIGS["nemotron_h_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
BIAS = nemotron_h.BALANCE_BIAS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_params = functools.partial(kit.seeded_params, nemotron_h)
_batch = kit.batch


def _chosen(experts, n_routed):
    return jnp.any(jax.nn.one_hot(experts, n_routed, dtype=bool), axis=-2)


def _reference(cfg):
    return functools.partial(nemotron_h_f32.terms,
                             **family.reference_dims(cfg))


# -- against the reference ---------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_compute_equals_the_reference(seed) -> None:
    params, (tokens, targets) = _params(CFG32, seed), _batch(seed)
    got = jax.jit(functools.partial(nemotron_h.loss_terms, CFG32))(
        params, tokens, targets)
    want = jax.jit(_reference(CFG32))(params, tokens, targets)
    assert np.array_equal(_chosen(got["experts"], CFG.n_routed_experts),
                          want["chosen"])
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), abs=2e-5)
    assert float(got["loss"]) == float(got["ce"])     # the carrier adds 0
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5)
    # what the check line prints: rows on this share's experts, their
    # share of all assignments, and the busiest expert over the mean
    loads = np.asarray(got["loads"])
    assert loads.shape == (2, 8)
    assert loads.sum(axis=-1).tolist() == [2 * 64 * CFG.top_k] * 2
    assert np.array_equal(got["rows_held"], loads[:, :4].sum(axis=-1))
    assert np.allclose(got["held_share"],
                       loads[:, :4].sum(axis=-1) / (2 * 64 * CFG.top_k))
    assert np.all((0 < got["held_share"]) & (got["held_share"] < 1))
    assert np.allclose(got["load_max_over_mean"],
                       loads.max(axis=-1) / loads.mean(axis=-1))


def test_f32_gradients_equal_the_reference() -> None:
    """A leaf of each kind of layer, and every other one too; the balance
    bias has no gradient of the loss: its place carries the loads."""
    params, (tokens, targets) = _params(CFG32, 3), _batch(3)
    got = jax.jit(jax.grad(functools.partial(nemotron_h.loss_fn, CFG32)))(
        params, tokens, targets)
    want = jax.jit(jax.grad(functools.partial(
        nemotron_h_f32.loss, **family.reference_dims(CFG32))))(
            params, tokens, targets)
    terms = nemotron_h.loss_terms(CFG32, params, tokens, targets)
    assert np.array_equal(got["layers_1"]["moe"][BIAS], terms["loads"][0])
    assert np.array_equal(got["layers_3"]["moe"][BIAS], terms["loads"][1])
    assert not np.any(kit.bias_leaves(want))
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    seen = set()
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        if path[-1].key == BIAS:
            continue
        w = flat_want[path]
        err = float(jnp.linalg.norm((g - w).ravel())
                    / (jnp.linalg.norm(w.ravel()) + 1e-30))
        assert err < 2e-4, (jax.tree_util.keystr(path), err)
        assert float(jnp.linalg.norm(w.ravel())) > 0, path
        seen.add(jax.tree_util.keystr(path))
    for kind in ("in_proj", "conv']['kernel", "conv']['bias", "dt_bias",
                 "A_log", "['D']", "mamba']['norm", "out_proj", "q_proj",
                 "k_proj", "v_proj", "o_proj", "router", "up_proj",
                 "down_proj", "shared", "wte", "lm_head", "ln_f"):
        assert any(kind in s for s in seen), kind


@pytest.mark.parametrize("seed", [0, 1])
def test_bf16_compute_agrees_with_the_reference(seed) -> None:
    """bf16 compute, 128 tokens, the cell's own comparison: the
    reference is computed on the top-2 sets the system took, its own
    choice is counted beside it, and every token is compared."""
    params, (tokens, targets) = _params(CFG, seed), _batch(seed)
    seen = family.per_token_errors(CFG, params, params, tokens, targets)
    assert seen["error"].shape == (128,)
    assert float(seen["disagreement"]) < 0.1
    assert abs(float(seen["loss"]) - float(seen["reference_loss"])) < 2e-2
    assert np.sqrt(np.mean(seen["error"] ** 2)) < 0.03
    assert seen["error"].max() < 0.08


def test_the_reference_follows_a_selection_and_still_says_its_own() -> None:
    """``selection`` is what the expert layers are computed on; ``chosen``
    stays the reference's own choice. Its own selection handed back
    changes nothing; another one moves the hidden state, and the first
    expert layer (whose input no selection has touched) still reports
    the same choice."""
    params, (tokens, targets) = _params(CFG32, 6), _batch(6)
    ref = _reference(CFG32)
    own = ref(params, tokens, targets)
    again = ref(params, tokens, targets, selection=own["chosen"])
    assert np.array_equal(again["hidden"], own["hidden"])
    assert np.array_equal(again["chosen"], own["chosen"])
    other = jnp.roll(own["chosen"], 1, axis=-1)       # every set moved on
    moved = ref(params, tokens, targets, selection=other)
    assert float(jnp.max(jnp.abs(moved["hidden"] - own["hidden"]))) > 1e-2
    assert np.array_equal(moved["chosen"][0], own["chosen"][0])
    assert not np.array_equal(moved["chosen"][1], own["chosen"][1])


# -- the three mixers, each alone --------------------------------------------


def test_a_key_value_head_serves_consecutive_query_heads() -> None:
    """Query heads 0-1 read key/value head 0 and 2-3 head 1: the
    attention is handed the two key/value heads as they are (PR 55: no
    copy a query head), and silencing value head 1 silences exactly
    query heads 2-3's rows of ``W_o``."""
    params, x = _params(CFG32, 2), jax.random.normal(
        jax.random.key(3), (1, 16, 64), jnp.float32)
    layer = params["layers_2"]
    seen = {}

    def spy(q, k, v):
        seen["q"], seen["k"], seen["v"] = q, k, v
        return reference_attention(q, k, v)

    nemotron_h._attn_mixer(CFG32, layer, x, attn_fn=spy)
    assert seen["q"].shape == (1, 16, 4, 16)
    assert seen["k"].shape == seen["v"].shape == (1, 16, 2, 16)
    assert not np.array_equal(seen["k"][:, :, 0], seen["k"][:, :, 1])

    def out_of(lay):
        return nemotron_h._attn_mixer(
            CFG32, lay, x, attn_fn=reference_attention) - x

    hd = CFG32.head_dim
    half = with_leaf({"l": layer}, "l", ("attn", "v_proj", "kernel"),
                     lambda w: w.at[:, hd:].set(0))["l"]
    first2 = with_leaf({"l": layer}, "l", ("attn", "o_proj", "kernel"),
                       lambda w: w.at[2 * hd:].set(0))["l"]
    np.testing.assert_allclose(out_of(half), out_of(first2), atol=1e-5)
    assert float(jnp.max(jnp.abs(out_of(half) - out_of(layer)))) > 1e-3
    # no position embedding: a sequence and the same sequence moved two
    # places to the right give the same outputs two places on, as far as
    # each still sees all it saw
    moved = jnp.concatenate([x[:, :2], x[:, :-2]], axis=1)
    a = nemotron_h._attn_mixer(CFG32, layer, x[:, :1],
                               attn_fn=reference_attention)
    b = nemotron_h._attn_mixer(CFG32, layer, moved[:, 2:3],
                               attn_fn=reference_attention)
    np.testing.assert_allclose(a - x[:, :1], b - moved[:, 2:3], atol=1e-5)


def test_the_scan_starts_from_zero_every_sequence() -> None:
    """Nothing is carried between the rows of a batch or between calls:
    row 1 alone is row 1 of the batch."""
    params, (tokens, _) = _params(CFG32, 4), _batch(4)
    both, _ = nemotron_h.forward_hidden(CFG32, params, tokens)
    alone, _ = nemotron_h.forward_hidden(CFG32, params, tokens[1:])
    np.testing.assert_allclose(both[1:], alone, atol=2e-5)


def test_the_mamba_mixer_runs_the_four_fused_kernels() -> None:
    """The stages around the scan are ``ops/ssm_pointwise.py``'s, forward
    and backward: the mixer's gradient program holds the four kernels by
    name, one call each, and no array ``K - 1`` positions longer than the
    sequence — the convolution's zeros before the sequence are the
    kernel's halo, not a padded copy in HBM (the pads that are left are
    ``ops/ssd.py``'s, to whole chunks)."""
    layer = jax.eval_shape(
        lambda: nemotron_h.init_params(CFG, jax.random.key(0)))["layers_0"]
    x = jax.ShapeDtypeStruct((2, 64, CFG.d_model), CFG.dtype)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, a: jnp.sum(nemotron_h._mamba_mixer(CFG, p, a).astype(
            jnp.float32))))(layer, x))
    for kernel in ("ssm_conv_fwd", "ssm_conv_bwd", "ssm_gate_fwd",
                   "ssm_gate_bwd", "ssd_fwd", "ssd_bwd"):
        assert len(re.findall(rf"\bname={kernel}\b", text)) == 1, kernel
    assert f"[2,{64 + CFG.conv_kernel - 1}," not in text
    assert "[2,64," in text


def test_the_pattern_is_data_of_the_config() -> None:
    for pattern in ("M", "*E", "EM*M"):
        cfg = dataclasses.replace(CFG32, pattern=pattern)
        params = nemotron_h.init_params(cfg, jax.random.key(0))
        kinds = [next(k for k in params[f"layers_{i}"] if k != "norm")
                 for i in range(len(pattern))]
        assert kinds == [nemotron_h.MIXERS[c] for c in pattern]
        tokens, targets = _batch(0)
        got = nemotron_h.loss_terms(cfg, params, tokens, targets)
        want = _reference(cfg)(params, tokens, targets)
        assert float(got["loss"]) == pytest.approx(float(want["loss"]),
                                                   abs=2e-5)
        assert ("loads" in got) == ("E" in pattern)
    with pytest.raises(AssertionError):
        dataclasses.replace(CFG32, pattern="M-E")


# -- the held share ----------------------------------------------------------


def _layer_and_stream(seed):
    params = _params(CFG32, seed)
    x = jax.random.normal(jax.random.key(50 + seed), (2, 64, 64), jnp.float32)
    return params["layers_3"], x


def _full_layer(layer, seed):
    """The same layer with all 8 routed experts: the held 4 and 4 more."""
    extra = nemotron_h.init_params(
        dataclasses.replace(CFG32, first_expert=4), jax.random.key(900 + seed)
    )["layers_3"]["moe"]
    full = jax.tree_util.tree_map(lambda a: a, layer)
    for name in ("up_proj", "down_proj"):
        full["moe"][name] = {"kernel": jnp.concatenate(
            [layer["moe"][name]["kernel"], extra[name]["kernel"]])}
    return full


@pytest.mark.parametrize("split", [(1,) * 8, (2, 2, 2, 2), (4, 4), (3, 5),
                                   (1, 6, 1), (8,)])
def test_the_shares_add_up_to_the_uncut_layer(split) -> None:
    """The routed parts that all the shares give (16 chips of the
    deployment hold 8 each of 128; here 8 shares of 1, and uneven ones),
    with the shared expert counted once, are the reference's expert
    mixer with every expert held."""
    layer, x = _layer_and_stream(7)
    full = _full_layer(layer, 7)
    h = nemotron_h_f32._rms(x, full["norm"]["scale"], CFG.rms_eps).reshape(
        -1, 64)
    with jax.default_matmul_precision("highest"):
        want, _ = nemotron_h_f32._experts(
            h, full["moe"], top_k=CFG.top_k, first_expert=0,
            routed_scale=CFG.routed_scale)
        shared = nemotron_h._relu2(h, full["moe"]["shared"], jnp.float32)
        total, first = jnp.zeros_like(want), 0
        for held in split:
            cfg = dataclasses.replace(CFG32, first_expert=first,
                                      n_experts_held=held)
            share = jax.tree_util.tree_map(lambda a: a, full)
            for name in ("up_proj", "down_proj"):
                share["moe"][name] = {"kernel": full["moe"][name]["kernel"][
                    first:first + held]}
            y, rec = nemotron_h._moe_mixer(cfg, share, x)
            total = total + (y - x).reshape(-1, 64) - shared
            first += held
        assert first == CFG.n_routed_experts
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 0.1   # the routed part


def test_every_assignment_held_and_none_held_run_one_program() -> None:
    """Dropless for every routing: a bias that sends every assignment to
    the held experts, and one that sends none there, are ordinary inputs
    of the one compiled program, and both agree with the reference."""
    layer, x = _layer_and_stream(9)
    run = jax.jit(functools.partial(nemotron_h._moe_mixer, CFG32))
    seen = []
    for sign in (+1.0, -1.0, 0.0):
        bias = sign * 10.0 * (jnp.arange(8) < 4)
        layer["moe"][BIAS] = bias.astype(jnp.float32)
        y, rec = run(layer, x)
        seen.append(int(jnp.sum(rec["loads"][:4])))
        h = nemotron_h_f32._rms(x, layer["norm"]["scale"], CFG.rms_eps)
        with jax.default_matmul_precision("highest"):
            want, _ = nemotron_h_f32._experts(
                h.reshape(-1, 64), layer["moe"], top_k=CFG.top_k,
                first_expert=0, routed_scale=CFG.routed_scale)
        np.testing.assert_allclose((y - x).reshape(-1, 64), want, atol=2e-5)
        assert bool(jnp.all(jnp.isfinite(y)))
    assert seen[0] == 2 * 64 * CFG.top_k and seen[1] == 0
    assert 0 < seen[2] < seen[0]
    assert run._cache_size() == 1
    # the gradient of a share that holds nothing of a batch is that of
    # the shared expert alone: finite, and zero for the routed weights
    layer["moe"][BIAS] = -10.0 * (jnp.arange(8) < 4).astype(jnp.float32)
    grads = jax.grad(lambda l: jnp.sum(
        nemotron_h._moe_mixer(CFG32, l, x)[0] ** 2))(layer)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))
    assert not np.any(grads["moe"]["up_proj"]["kernel"])
    assert np.any(grads["moe"]["shared"]["up_proj"]["kernel"])


def _scope_paths(jaxpr, out):
    """Every ``jax.named_scope`` path an equation of ``jaxpr`` carries,
    nested jaxprs included (the jaxpr's text does not hold them)."""
    for eqn in jaxpr.eqns:
        out.add(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scope_paths(sub, out)
    return out


def _jaxpr_hash(fn, *args):
    """The sha256 of ``fn``'s jaxpr (file paths cut) and the sorted set of
    its scope paths, from one trace."""
    closed = jax.make_jaxpr(fn)(*args)
    text = re.sub(r"/[^ ]*?\.py:\d+", "", str(closed))
    return (hashlib.sha256(text.encode()).hexdigest(),
            sorted(_scope_paths(closed.jaxpr, set())))


# model -> (module, tiny config, sha256 of the gradient jaxpr's text with
# file paths cut, sha256 of its sorted set of scope paths joined by "\n",
# how many paths). Recorded by running this file's _jaxpr_hash on a
# checkout of the commit named, nothing compiled:
# - olmoe, joyai: programs at d58585e, the parent of PR 33
#   (tests/test_joyai.py pins olmoe's under the same hash). Read again
#   across PR 39 (c6c2ccf -> the row buffer of a held share): the same,
#   because at this size the buffer would be all N*k rows and such a call
#   takes the path it took (tests/test_moe_rows.py holds the models at a
#   size where it does not)
# - nemotron_h: program at 2d59480 (tests/test_lfm2.py held it until
#   PR 46)
# - lfm2, kimi, and all five sets of scope paths: at 7be2396, the parent
#   of PR 46, before models/common.py took the routed sublayer and the
#   share's loss_terms. The scope readers of benchmark/readers classify
#   device time by these paths.
# - nemotron_h again at PR 68: ``ops/ssd.py``'s grid took a fourth axis of
#   head blocks (one step at this model's groups), so the two
#   ``pallas_call``s' grids, index maps and scratch changed and the program
#   with them, on purpose; the scope paths did not. What the kernels COMPUTE
#   at Nemotron's widths is held by ``tests/test_ssd.py`` against the
#   parent's recorded outputs. Was 6bff4ba2…94a39e7 (program at 2d59480)
# - kimi again at PR 48: ops/kda.py's builders went under an inner
#   jax.jit (four heads a grid step), so the two kernels' equations stand
#   inside jit(_forward) / jit(_backward) and carry "kda_fwd" / "kda_bwd"
#   where they carried "jvp(attn)/kda_core/kda_fwd" and
#   "transpose(jvp(attn))/kda_core/kda_bwd" (the pjit equation carries
#   the outer path; on the device the two join:
#   .../kda_core/jit(_forward)/kda_fwd/pallas_call); the other 50 paths
#   are those of 7be2396
# - nemotron_h and lfm2 again at PR 55: k and v reach the attention at
#   their own head count, so the two repeats, their transposes' sums and
#   the H-wide einsums are out of both programs and this CPU trace's
#   reference groups the query heads in its einsums. Four of each model's
#   paths are named after an einsum and follow it ("gqa_core/bqhd,bkhd->
#   bhqk" -> "gqa_core/bqngd,bknd->bngqk", and the other, under jvp and
#   its transpose); the other 40 and 28 are those of 7be2396, and on the
#   device the flash call under gqa_core carries the names it carried.
#   olmoe, joyai and kimi (equal head counts) did not move
# - joyai, nemotron_h, lfm2 and kimi again at PR 64: the router of
#   models/common.py::routed_sublayer stands under a custom_vjp (_route:
#   the scores' matmul, moe.top_k_routing, the loads' count) whose outputs
#   and chosen scores pass through four ``name`` equations
#   (router_choice), and top_k_routing takes the chosen scores by compare
#   and select (moe.take_chosen) where it gathered: the gather, its
#   scatter-add in the transpose and the [N, E]-wide backward of the
#   sigmoid and the top-k are out of the four programs. The backward
#   rule's equations (the routing again on the [N, k] chosen columns, the
#   select's transpose, the two products) stand under five new paths a
#   model (ten in joyai, whose MTP block routes too):
#   "transpose(jvp(mlp))/moe_router/moe_router" and, under it, "/jvp()",
#   "/transpose()", "/transpose(jvp())" and
#   "/transpose(transpose(jvp(mlp)))/moe_router/moe_router" — each holds
#   moe_router, the token benchmark/readers/moe_scopes.py classifies by —,
#   and no path of the earlier sets went. The tiny configurations have
#   remat off, so these programs hold no checkpoint equation and no
#   policy: tests/test_router_once.py holds the remat=True programs, seven
#   models. olmoe (its own _moe_sublayer; top_k_routing without a bias is
#   lax.top_k) did not move
PROGRAMS_THAT_WERE = {
    "olmoe": (
        olmoe, olmoe.OLMOE_CONFIGS["olmoe_tiny"],
        "3c40614299f885d4c7d1230c9b2a6a7a68c7e055e6686e838d81f49e710ed6b8",
        "4f8d4efc3b1fb55ff5284b37019772ac887b59510bdbc56c1490d1234741739c",
        24),
    "joyai": (
        joyai, joyai.JOYAI_CONFIGS["joyai_tiny"],
        "83bed6ba00118309b8e4e3bd482aeeaf6d2c400b196f24c95a49a86e24915486",
        "a986964025d2e54fa276c6dd2847394ccefa4fbf71ff454a674f40b3b53bd09f",
        78),
    "nemotron_h": (
        nemotron_h, CFG,
        "cff06fccab8e161728678cf742f31d52b64c8c2c7cf6050947ad85e632f5f4e0",
        "f4bd4e403a7ce0556c35bc44b42638afb2bcb7a77f43084432d2541d92ea5bf3",
        49),
    "lfm2": (
        lfm2, lfm2.LFM2_CONFIGS["lfm2_tiny"],
        "17705a7f94c4d118162cf201d27d7bd33b029aaeed363f876adce845c24d3e6e",
        "fc9fc0fcd379279e7fadc81e6bce88257a18c4fe8c76ad2fde05f48983d2b517",
        37),
    "kimi": (
        kimi_linear, kimi_linear.KIMI_LINEAR_CONFIGS["kimi_linear_tiny"],
        "0a8eec0715756b2b70ae0e76b03eeba3c91312a19bbc9a447db4ab33e82b86c9",
        "fb66e4791c929a6ba0c6a89bab11d59f9493a4b380b84c9bdb297ddfed172d29",
        57),
}


@pytest.mark.parametrize("model", list(PROGRAMS_THAT_WERE))
def test_the_gated_expert_paths_are_what_they_were(model) -> None:
    """Each sparse model's whole gradient program traces to the jaxpr the
    commit named above traced — same instructions, same order, so the
    outputs' bits follow — under the same ``jax.named_scope`` paths."""
    mod, cfg, program, scopes, n_scopes = PROGRAMS_THAT_WERE[model]
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    grad = jax.grad(lambda p, a, b: mod.loss_fn(cfg, p, a, b))
    got, paths = _jaxpr_hash(grad, params, tokens, tokens)
    assert got == program
    assert (hashlib.sha256("\n".join(paths).encode()).hexdigest(),
            len(paths)) == (scopes, n_scopes), paths


def test_relu2_experts_are_the_plain_sum_over_experts() -> None:
    """The two-matrix expert through the grouped matmuls, against every
    expert on every row weighted by a one-hot."""
    k = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(k[0], (64, 16), jnp.float32)
    up = jax.random.normal(k[1], (4, 16, 24), jnp.float32) * 0.3
    down = jax.random.normal(k[2], (4, 24, 16), jnp.float32) * 0.3
    sizes = jnp.array([10, 0, 30, 24], jnp.int32)
    got = moe.relu2_experts(x, up, down, sizes)
    which = jnp.repeat(jnp.arange(4), sizes, total_repeat_length=64)
    with jax.default_matmul_precision("highest"):
        every = jnp.einsum(
            "enf,efd->end", jnp.square(jax.nn.relu(
                jnp.einsum("nd,edf->enf", x, up))), down)
    want = every[which, jnp.arange(64)]
    np.testing.assert_allclose(got, want, atol=1e-4)
