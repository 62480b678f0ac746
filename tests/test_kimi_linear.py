"""Kimi Linear (models/kimi_linear.py: a channel-wise gated delta-rule
mixer over ops/kda.py::kda_scan behind one 4-tap convolution, JoyAI's
latent attention without a q latent and without rotation, a dense SwiGLU
or JoyAI's sparse sublayer with a shared expert; the mixer of a layer is
named by two lists of the config, its MLP by ``i < n_dense_layers``)
against the plain float32 reference the benchmark keeps
(benchmark/reference/kimi_linear_f32.py), at a small size on the CPU:
d 64, layers ``K K M K`` with one dense MLP (the family's tests: ``K M K``), 4 heads of 16, 8 routed
experts of width 32 of which 4 are held, top 2, S 32, seeded random
weights. Then the family (benchmark/families/kimi_linear.py) through the
one step maker, the one optimizer and the fault-tolerant loop, and the
three routing gauges of the optimizer wrapper's sink."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import family_kit as kit
from benchmark import kda_flops
from benchmark.families import kimi_linear as family
from benchmark.reference import kimi_linear_f32
from benchmark.tests import kimi_faults
from benchmark.tests.kimi_faults import FAULTS
from torchft_tpu import optim
from torchft_tpu.models import joyai, kimi_linear

# the model's tests are not about how many heads share a grid step
pytestmark = pytest.mark.usefixtures("one_head_a_step")
CFG = kimi_linear.KIMI_LINEAR_CONFIGS["kimi_linear_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
# K (dense MLP), M (experts): the faults' size
TWO32 = dataclasses.replace(CFG32, kda_layers=(1,), full_attn_layers=(2,))
BIAS = kimi_linear.BALANCE_BIAS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_params = functools.partial(kit.seeded_params, kimi_linear)
_batch = functools.partial(kit.batch, seq=32)


def _reference(cfg):
    return jax.jit(functools.partial(kimi_linear_f32.terms,
                                     **family.reference_dims(cfg)))


def _system(cfg):
    """``loss_terms`` at f32 ``highest``, jitted (traced when called: a
    fault's patches are in place by then)."""
    def run(params, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return kimi_linear.loss_terms(cfg, params, tokens, targets)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _reference_at(seed):
    """The sound reference of the two-layer model on seed ``seed``'s
    weights and batch, once."""
    return _reference(TWO32)(_params(TWO32, seed), *_batch(seed))


# -- against the reference ---------------------------------------------------


@pytest.mark.parametrize("seed", [0])
def test_f32_compute_equals_the_reference(seed) -> None:
    params, (tokens, targets) = _params(CFG32, seed), _batch(seed)
    got = _system(CFG32)(params, tokens, targets)
    want = _reference(CFG32)(params, tokens, targets)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), abs=2e-5)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5)
    chosen = jnp.any(jax.nn.one_hot(got["experts"], 8, dtype=bool), axis=-2)
    assert np.array_equal(chosen, want["chosen"])
    assert got["loads"].shape == (3, 8)
    assert float(jnp.sum(got["loads"])) == 3 * 64 * CFG.top_k


def test_f32_gradients_equal_the_reference() -> None:
    """Every leaf but the balance bias (whose place carries the loads):
    the scan's and the convolution's kernels' backward, the l2 norms, the
    decay through ``A_log`` and ``dt_bias``, the gate after the head
    norm, unrotated latent attention, the held and the shared experts."""
    params, (tokens, targets) = _params(CFG32, 2), _batch(2)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(
            lambda p: kimi_linear.loss_fn(CFG32, p, tokens, targets)))(params)
    want = jax.jit(jax.grad(lambda p: kimi_linear_f32.loss(
        p, tokens, targets, **family.reference_dims(CFG32))))(params)
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(want)) == 76
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if getattr(path[-1], "key", None) == BIAS:
            assert float(jnp.sum(g)) == 64 * CFG.top_k, name   # the loads
            continue
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * scale, err_msg=name)


@pytest.mark.parametrize("seed", [1])
def test_bf16_compute_agrees_with_the_reference(seed) -> None:
    """bf16 compute, 64 tokens, the cell's own comparison: the reference
    is computed on the top-2 sets the system took, its own choice is
    counted beside it, and every token is compared."""
    params, (tokens, targets) = _params(CFG, seed), _batch(seed)
    seen = family.per_token_errors(CFG, params, params, tokens, targets)
    assert seen["error"].shape == (64,)
    assert float(seen["disagreement"]) < 0.1
    assert abs(float(seen["loss"]) - float(seen["reference_loss"])) < 2e-2
    assert np.sqrt(np.mean(seen["error"] ** 2)) < 0.04
    assert seen["error"].max() < 0.1


def test_the_reference_follows_a_selection_and_still_says_its_own() -> None:
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


# -- what the config's two lists and the share choose ------------------------


@pytest.mark.parametrize("kda,full,n_dense", [
    ((), (1, 2), 2), ((2,), (1,), 1), ((1,), (2,), 0)])
def test_the_mixers_are_two_lists_and_the_mlp_a_count(kda, full, n_dense):
    cfg = dataclasses.replace(CFG32, kda_layers=kda, full_attn_layers=full,
                              n_dense_layers=n_dense)
    hash(cfg)       # the step-program store keys on it
    params, (tokens, targets) = _params(cfg, 3), _batch(3, rows=1)
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        assert ("kda" in layer) == (i + 1 in kda)
        assert ("attn" in layer) == (i + 1 in full)
        assert ("mlp" in layer) == (i < n_dense)
        assert ("moe" in layer) == (i >= n_dense)
    got = _system(cfg)(params, tokens, targets)
    want = _reference(cfg)(params, tokens, targets)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=5e-5)
    assert ("loads" in got) == (n_dense < cfg.n_layers)
    with pytest.raises(AssertionError):
        dataclasses.replace(CFG, kda_layers=(1, 2), full_attn_layers=(4,))


def test_latent_attention_is_joyais_without_a_q_latent_or_a_turn() -> None:
    """The MLA layer is ``models/joyai.py::mla_sublayer`` under this
    config: one ``q_proj`` where JoyAI has three q leaves, and no cos or
    sin anywhere in the program; with a theta (the faults' stand-in) the
    same function turns the 8 shared channels."""
    params = _params(CFG32, 4)
    layer = params["layers_2"]
    assert set(layer["attn"]) == {"q_proj", "kv_a_proj", "kv_a_norm",
                                  "kv_b_proj", "o_proj"}
    assert layer["attn"]["q_proj"]["kernel"].shape == (64, 4 * 24)
    assert layer["attn"]["kv_a_proj"]["kernel"].shape == (64, 32 + 8)
    x = jax.random.normal(jax.random.key(2), (1, 32, 64), jnp.float32)
    run = functools.partial(joyai.mla_sublayer, attn_fn=(
        kimi_linear._local_causal_attention))
    text = str(jax.make_jaxpr(lambda a: run(CFG32, layer, a))(x))
    assert " cos " not in text and " sin " not in text
    turned = dataclasses.replace(CFG32, rope_theta=10000.0)
    assert " cos " in str(jax.make_jaxpr(lambda a: run(turned, layer, a))(x))
    assert float(jnp.max(jnp.abs(
        run(turned, layer, x) - run(CFG32, layer, x)))) > 1e-3


def _made_outside_kernels(jaxpr, keep):
    """``(primitive, aval)`` of every output that ``keep`` takes of the
    equations that compute (a call's are its body's), through every
    nested jaxpr but a kernel's own."""
    seen = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        inner = list(jax.core.jaxprs_in_params(eqn.params))
        for sub in inner:
            seen += _made_outside_kernels(sub, keep)
        if not inner:
            seen += [(eqn.primitive.name, v.aval) for v in eqn.outvars
                     if keep(v.aval)]
    return seen


def test_the_delta_rule_mixer_runs_the_kernels_and_gates_after_the_norm():
    params = _params(CFG, 4)
    x = jax.random.normal(jax.random.key(2), (2, 32, 64), jnp.bfloat16)
    run = functools.partial(kimi_linear._kda_sublayer, CFG,
                            params["layers_0"])

    def loss(a):
        return jnp.sum(run(a).astype(jnp.float32))

    text = str(jax.make_jaxpr(run)(x))
    # ONE convolution, the norms and the decay, the scan, the gate
    for kernel in ("ssm_conv_fwd", "kda_qkg_fwd", "kda_fwd", "kda_ogate_fwd"):
        assert text.count(f"name={kernel}") == 1, kernel
    grad = str(jax.make_jaxpr(jax.grad(loss))(x))
    for kernel in ("ssm_conv_bwd", "kda_qkg_bwd", "kda_bwd", "kda_ogate_bwd"):
        assert grad.count(f"name={kernel}") == 1, kernel
    # bf16 compute: of the stream's size [B, S, H·D] nothing f32 is made
    # outside a kernel; the decays and their cotangent (the kernels'
    # own results) are only laid out anew. Eight heads, so that H·D is
    # not d_model
    wide = dataclasses.replace(CFG, n_heads=8)
    layer = kimi_linear.init_params(wide, jax.random.key(4))["layers_0"]
    sub = functools.partial(kimi_linear._kda_sublayer, wide, layer)

    def stream_f32(aval):
        return aval.dtype == jnp.float32 and aval.size == 2 * 32 * 8 * 16

    for fn in (sub, jax.grad(lambda a: jnp.sum(sub(a).astype(jnp.float32)))):
        made = _made_outside_kernels(jax.make_jaxpr(fn)(x).jaxpr, stream_f32)
        assert {name for name, _ in made} <= {"reshape", "pad", "slice"}, made
    o = jax.random.normal(jax.random.key(3), (1, 8, 4, 16), jnp.float32)
    gate = jax.random.normal(jax.random.key(4), (1, 8, 4, 16), jnp.float32)
    scale = jnp.linspace(0.5, 1.5, 16)
    got = kimi_linear._gated_head_norm(o, scale, gate, 1e-5)
    normed = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) * scale
    np.testing.assert_allclose(got, normed * jax.nn.sigmoid(gate), atol=1e-6)


# -- the held share ----------------------------------------------------------


def _layer_and_stream(seed):
    params = _params(CFG32, seed)
    x = jax.random.normal(jax.random.key(50 + seed), (2, 32, 64), jnp.float32)
    return params["layers_3"], x


def _full_layer(layer, seed):
    """The same layer with all 8 routed experts: the held 4 and 4 more."""
    extra = kimi_linear.init_params(
        dataclasses.replace(CFG32, first_expert=4), jax.random.key(900 + seed)
    )["layers_3"]["moe"]
    full = jax.tree_util.tree_map(lambda a: a, layer)
    for name in ("gate_proj", "up_proj", "down_proj"):
        full["moe"][name] = {"kernel": jnp.concatenate(
            [layer["moe"][name]["kernel"], extra[name]["kernel"]])}
    return full


@pytest.mark.parametrize("split", [(2, 2, 2, 2), (1,) * 8, (4, 4), (3, 5),
                                   (1, 6, 1), (8,)])
def test_the_shares_add_up_to_the_uncut_layer(split) -> None:
    """The routed parts that all the shares give (32 chips of the
    deployment hold 8 each of 256; here 4 shares of 2, and uneven ones),
    with everything every chip computes alike — the residual stream, the
    norm, the router, the SHARED EXPERT — counted once, are the
    reference's expert MLP with every expert held."""
    layer, x = _layer_and_stream(7)
    full = _full_layer(layer, 7)
    n = kimi_linear_f32._rms(x, full["ln_2"]["scale"], CFG.rms_eps
                             ).reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        want, _ = kimi_linear_f32._experts(
            n, full["moe"], top_k=CFG.top_k, first_expert=0,
            routed_scale=CFG.routed_scale)
        shared = kimi_linear_f32._swiglu(n, full["moe"]["shared"])
        total, first = shared, 0
        for held in split:
            cfg = dataclasses.replace(CFG32, first_expert=first,
                                      n_experts_held=held)
            share = jax.tree_util.tree_map(lambda a: a, full)
            for name in ("gate_proj", "up_proj", "down_proj"):
                share["moe"][name] = {"kernel": full["moe"][name]["kernel"][
                    first:first + held]}
            y, _ = kimi_linear._moe_sublayer(cfg, share, x)
            # every share computes the shared expert: counted once, above
            total = total + (y - x).reshape(-1, 64) - shared
            first += held
        assert first == CFG.n_routed_experts
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 0.1


# -- the faults of the cell's check ------------------------------------------


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_comparison(monkeypatch, fault) -> None:
    """Each listed fault moves what the cell's checks compare by far more
    than f32 rounding: the test of the reference's teeth at this size
    (two layers, one of each mixer and of each MLP: a program to compile
    a fault). A fault inside the scan also moves the scan's own
    comparison."""
    params = _params(TWO32, 5)
    tokens, targets = _batch(5)
    want = _reference_at(5)
    patches, weights, cfg, scan_fn = kimi_faults.fault(fault, TWO32, params)
    for patch in patches:
        monkeypatch.setattr(*patch)
    got = _system(cfg or TWO32)(weights or params, tokens, targets)
    chosen = jnp.any(jax.nn.one_hot(got["experts"], 8, dtype=bool), axis=-2)
    moved = max(
        abs(float(got["loss"]) - float(want["loss"])),
        float(jnp.max(jnp.abs(got["hidden"] - want["hidden"]))),
        float(jnp.mean(jnp.any(chosen != want["chosen"], axis=-1))),
    )
    # rounding to 8 (bf16) or 4 (e4m3) bits in one place of a tiny model
    floor = 3e-4 if fault in kimi_faults.ROUNDING else 1e-2
    assert moved > floor, (fault, moved)
    assert (scan_fn is not None) == (fault in kimi_faults.IN_THE_SCAN)
    if scan_fn is not None:
        # f32 operands and 32 positions here: the cell's limits stand
        # above the one bf16 rounding of each result, ten times this
        monkeypatch.setattr(family, "KDA_REL_L2_MAX",
                            {n: 1e-3 for n in family.KDA_LEAVES})
        sound = kit.sound(("kimi_linear", "kda", 5), lambda: jax.jit(
            family.kda_comparison())(*family.kda_inputs(CFG32, 5, 32)))
        assert family.judge_kda(sound)["ok"], sound
        alone = jax.jit(family.kda_comparison(scan_fn))(
            *family.kda_inputs(CFG32, 5, 32))
        assert not family.judge_kda(alone)["ok"], alone


def test_the_faults_stand_in_is_sound_without_its_fault(monkeypatch):
    """The jnp scan that stands in for the kernels under the state's
    fault, with nothing rounded, is the kernels' result."""
    params, (tokens, targets) = _params(CFG32, 5), _batch(5)
    want = _system(CFG32)(params, tokens, targets)
    monkeypatch.setattr(kimi_linear, "_kda_scan", kimi_faults.jnp_scan)
    got = _system(CFG32)(params, tokens, targets)
    np.testing.assert_allclose(got["hidden"], want["hidden"], atol=2e-5)
    sound = jax.jit(family.kda_comparison(kimi_faults.jnp_scan))(
        *family.kda_inputs(CFG, 5, 32))
    assert family.judge_kda(sound)["ok"], sound


def test_the_cells_own_check_of_the_scan() -> None:
    """``kda_comparison`` + ``judge_kda`` at the small size: the sound
    kernels pass leaf by leaf, the worst head's (bf16 operands: the one
    rounding of each result), every leaf has a limit that judges it
    alone."""
    sound = jax.device_get(jax.jit(family.kda_comparison())(
        *family.kda_inputs(CFG, 3, 32)))
    assert set(sound) == set(family.KDA_LEAVES)
    verdict = family.judge_kda(sound)
    assert verdict["ok"] and verdict["kda_over"] == []
    for name in family.KDA_LEAVES:
        over = dict(sound, **{name: 1.5 * family.KDA_REL_L2_MAX[name]})
        assert family.judge_kda(over)["kda_over"] == [name]


def test_check_reference_is_both_comparisons(monkeypatch) -> None:
    """The family's ``check_reference`` — what ``jobs/steady.py`` calls —
    carries the whole model's verdict and the scan's, and is ``ok`` only
    where both are (the tiny configuration, bf16 compute; the whole
    model's limits are set for the cell's size)."""
    monkeypatch.setattr(family, "KDA_SEQ", 32)
    monkeypatch.setattr(family, "MOE_ROWS", 256)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_RMS_MAX", 0.05)
    monkeypatch.setattr(family, "HIDDEN_REL_L2_MAX", 0.15)
    monkeypatch.setattr(family, "TOP_K_DISAGREEMENT_MAX", 0.1)
    monkeypatch.setattr(family, "REFERENCE_LOSS_ATOL", 2e-2)
    model, device = kit.tiny("kimi_linear"), jax.devices()[0]
    params = family.init_state(model, 5, device)["params"]
    seen = family.check_reference(model, params, 5, device)
    assert seen["ok"], seen
    assert {"hidden_rel_l2_rms", "top8_disagreement", "held_share",
            "kda_rel_l2", "moe_rel_l2_rms"} <= set(seen)
    assert seen["kda_over"] == [] and 0 < seen["moe_rows"] <= 256
    # benchmark/run.py prints 600 characters of a check
    assert len(json.dumps({k: v for k, v in seen.items() if k != "ok"})) < 580
    monkeypatch.setattr(family, "KDA_REL_L2_MAX",
                        dict(family.KDA_REL_L2_MAX, dg=0.0))
    again = family.check_reference(model, params, 5, device)
    assert not again["ok"] and again["kda_over"] == ["dg"]
    assert again["hidden_rel_l2_rms"] == seen["hidden_rel_l2_rms"]
    monkeypatch.undo()
    monkeypatch.setattr(family, "MOE_REL_L2_RMS_MAX", 0.0)
    assert not family.judge_moe({"moe": 1e-9, "rows": 5})["ok"]


def test_the_held_experts_by_themselves(monkeypatch) -> None:
    """``moe_comparison``: the first expert layer's routed part alone on
    the tokens that take a held expert — exact in f32, one bf16 rounding
    in the compute dtype, and far off under the two faults of the held
    experts, which the whole model's hidden state dilutes."""
    monkeypatch.setattr(family, "MOE_ROWS", 256)
    params = family.seed_balance_bias(_params(CFG, 8), 8)
    seed = np.uint32(8)
    exact = jax.jit(family.moe_comparison(CFG32))(params, params, seed)
    assert float(exact["moe"]) < 1e-5 and 0 < int(exact["rows"]) < 256
    run = jax.jit(family.moe_comparison(CFG))
    sound = run(params, params, seed)
    assert family.judge_moe(sound)["ok"], sound
    for name, least in (("expert_dropped", 0.1), ("fp8_experts", 0.02)):
        _, weights, _, _ = kimi_faults.fault(name, CFG, params)
        faulty = run(weights, params, seed)
        assert float(faulty["moe"]) > least, (name, faulty)
        assert not family.judge_moe(faulty)["ok"]


def test_the_cells_own_comparison_at_the_small_size() -> None:
    params, (tokens, targets) = _params(CFG32, 4), _batch(4)
    seen = family.per_token_errors(CFG32, params, params, tokens, targets)
    verdict = family.judge(seen)
    assert verdict["ok"] and verdict["top8_disagreement"] == 0.0
    assert verdict["tokens"] == 64
    assert verdict["hidden_rel_l2_max"] < 1e-4
    assert len(verdict["rows_held"]) == len(verdict["held_share"]) == 3
    assert all(0 < s < 1 for s in verdict["held_share"])
    unbiased = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x)
        if getattr(p[-1], "key", None) == BIAS else x, params)
    assert not family.judge(family.per_token_errors(
        CFG32, unbiased, params, tokens, targets))["ok"]
    turned = dataclasses.replace(CFG32, rope_theta=10000.0)
    assert not family.judge(family.per_token_errors(
        CFG32, params, params, tokens, targets, system_cfg=turned))["ok"]
    seeded = family.seed_balance_bias(params, 3)
    assert seeded["wte"]["embedding"] is params["wte"]["embedding"]
    assert all(np.any(b) for b in kit.bias_leaves(seeded))


# -- the family, the optimizer and the fault-tolerant loop --------------------


def test_the_family_builds_the_configuration_and_refuses_what_it_cannot():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b-ep32.json")) as f:
        config = json.load(f)
    model = family.build(config)
    cfg = model.cfg
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_experts_held) == (
        256, 0, 8)
    # the model's own layers 1 - 5: K K K M K, the first dense
    assert (cfg.kda_layers, cfg.full_attn_layers) == ((1, 2, 3, 5), (4,))
    published = config["published"]["linear_attn_config"]
    assert [n for n in published["kda_layers"] if n <= 5] == [1, 2, 3, 5]
    assert [n for n in published["full_attn_layers"] if n <= 5] == [4]
    assert (cfg.n_layers, cfg.n_dense_layers) == (5, 1)
    # every published width
    assert (cfg.d_model, cfg.n_heads, cfg.kda_head_dim, cfg.kda_rank,
            cfg.conv_kernel) == (2304, 32, 128, 128, 4)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.q_lora_rank, cfg.rope_theta) == (
        128, 64, 128, 512, 0, None)
    assert (cfg.d_ff, cfg.d_expert, cfg.top_k, cfg.routed_scale,
            cfg.rms_eps, cfg.vocab_size) == (9216, 1024, 8, 2.446, 1e-5,
                                             20480)
    assert config["reduced"] == ["num_hidden_layers", "linear_attn_config",
                                 "num_experts", "vocab_size"]
    assert config["share"]["chips_sharing_a_layer"] == 32
    assert {"published", "share", "assumed", "departures"} <= set(config)
    assert model.tx.held_experts == (0, 8)
    shapes = jax.eval_shape(
        lambda: kimi_linear.init_params(cfg, jax.random.key(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(602.4e6, rel=1e-4)          # the issue's count

    def size(layer):
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes[layer]))

    assert size("layers_0") == pytest.approx(103.2e6, rel=1e-3)
    assert size("layers_1") == pytest.approx(103.8e6, rel=1e-3)
    assert size("layers_3") == pytest.approx(93.4e6, rel=1e-3)
    assert shapes["wte"]["embedding"].size == 20480 * 2304
    assert shapes["lm_head"]["kernel"].size == 20480 * 2304
    # benchmark/kda_flops.py: 2.31 GFLOP a token
    parts = kda_flops.train_flops_per_token(**kda_flops.config_dims(config))
    assert parts["total"] == pytest.approx(2.31e9, rel=5e-3)
    assert parts["kda_proj"] == 6 * 4 * kda_flops.kda_params(
        2304, 32, 128, 128)
    assert kda_flops.kda_params(2304, 32, 128, 128) == pytest.approx(
        39.4e6, rel=2e-3)
    assert kda_flops.mla_params(2304, 32, 512, 128, 64, 128) == \
        pytest.approx(29.1e6, rel=2e-3)
    assert parts["kda_core"] == 3 * 4 * 32 * 7 * 128 * 128
    assert parts["mla_core"] == 3 * 1 * 32 * 320 * 8193
    assert parts["routed_held"] == 6 * 4 * 0.25 * 3 * 2304 * 1024
    assert kda_flops.kda_bytes_per_token(
        "kda_fwd", n_heads=32, head_dim=128) == 32 * (3 * 256 + 512 + 4 + 256)
    assert kda_flops.kda_bytes_per_token(
        "kda_bwd", n_heads=32, head_dim=128) == 32 * (
        3 * 256 + 512 + 4 + 256 + 3 * 256 + 512 + 4)
    assert model.flops_per_token == parts["total"]
    for key, value in (("mla_use_nope", False), ("q_lora_rank", 1536),
                       ("num_shared_experts", 2), ("num_hidden_layers", 6),
                       ("moe_router_activation_func", "softmax"),
                       ("num_nextn_predict_layers", 1),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    holes = dict(config, linear_attn_config=dict(
        config["linear_attn_config"], kda_layers=[1, 2, 3, 6]))
    with pytest.raises(ValueError, match="linear_attn_config"):
        family.build(holes)


def test_the_warm_up_is_a_schedule_and_the_rule_keeps_the_loads() -> None:
    """Step ``c`` runs at ``peak·(c + 1)/warm`` and the count is a leaf
    of the optimizer state; matrices (the taps among them) take weight
    decay, norms, ``A_log`` and ``dt_bias`` none; the bias rule's state
    is the loads it last saw — no moments — and ``routing_gauges`` reads
    the held share and the skew from it."""
    model = kit.tiny("kimi_linear")
    params = kimi_linear.init_params(model.cfg, jax.random.key(0))
    opt = model.tx.init(params)
    counts = [x for x in jax.tree_util.tree_leaves(opt)
              if x.shape == () and jnp.issubdtype(x.dtype, jnp.integer)]
    assert counts and all(int(c) == 0 for c in counts)
    held_loads = jnp.array([4.0, 2, 1, 1, 0, 0, 0, 0])      # all on 0 - 3
    grads = jax.tree_util.tree_map_with_path(
        lambda p, x: held_loads if getattr(p[-1], "key", None) == BIAS
        else jnp.ones_like(x), params)
    sizes, update = [], jax.jit(model.tx.update)
    for _ in range(6):
        updates, opt = update(grads, opt, params)
        sizes.append(float(jnp.max(jnp.abs(
            updates["layers_0"]["kda"]["conv"]["kernel"]))))
    ratios = [s / sizes[-1] for s in sizes]      # warm-up 8: (c + 1) / 8
    assert ratios[0] == pytest.approx(1 / 6, rel=0.1)
    assert ratios[2] == pytest.approx(3 / 6, rel=0.1)
    states = [s for s in jax.tree_util.tree_leaves(
        opt, is_leaf=lambda x: isinstance(x, optim.BalanceBiasState))
        if isinstance(s, optim.BalanceBiasState)]
    assert len(states) == 1
    kept = jax.tree_util.tree_leaves(states[0].loads)
    assert len(kept) == 2 and all(np.array_equal(k, held_loads) for k in kept)
    skew, share, fits = optim.routing_gauges(opt, model.tx.held_experts)
    assert float(skew) == pytest.approx(4.0) and float(share) == 1.0
    assert float(fits) == 1.0       # 8 assignments: the buffer is all of them
    assert optim.routing_gauges(optax.adam(1e-3).init(params)) is None
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = update(zero, model.tx.init(params), params)
    assert np.any(updates["layers_0"]["kda"]["conv"]["kernel"])    # decay
    assert np.any(updates["lm_head"]["kernel"])
    assert not np.any(updates["layers_0"]["ln_1"]["scale"])
    assert not np.any(updates["layers_0"]["kda"]["A_log"])
    assert not np.any(updates["layers_0"]["kda"]["dt_bias"])
    assert not np.any(updates["layers_0"]["kda"]["o_norm"]["scale"])


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size; and the
    optimizer wrapper's three routing gauges arrive on its sink without a
    wait (read at a later commit than the one that asked)."""
    with kit.ft_steps(kit.tiny("kimi_linear")) as run:
        assert all(np.any(b) for b in kit.bias_leaves(run.params))
        seen = kit.routing_gauges(run)
        assert 0.0 < seen["moe_held_share"] < 1.0
        assert seen["moe_load_max_over_mean"] >= 1.0
        assert seen["moe_row_buffer_share"] == 1.0


def test_two_groups_hold_one_state_and_a_healed_one_gets_it() -> None:
    """grad -> average_gradients -> step across two replica groups that
    see different batches; the second starts from other weights, behind,
    and gets the first's parameters — ``A_log``, ``dt_bias`` and the taps
    among them —, bias, loads and count only by the heal. At rest on one
    step the sha256 of parameters and optimizer state are equal."""
    with kit.two_groups_one_healed(kit.tiny("kimi_linear")) as run:
        for leaf in ("A_log", "dt_bias"):
            a, b = (np.asarray(g.state["params"]["layers_0"]["kda"][leaf])
                    for g in run.groups)
            assert np.array_equal(a, b)
        biases = [kit.bias_leaves(jax.device_get(g.state["params"]))
                  for g in run.groups]
        for a, b in zip(*biases):
            assert np.any(a) and np.array_equal(a, b)
        # the classic path reports the gauges too
        assert "moe_load_max_over_mean" in run.first.opt.metrics.snapshot()


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("kimi_linear")
