"""OLMoE (models/olmoe.py, ops/moe.py) against the plain float32
reference the benchmark keeps (benchmark/reference/olmoe_f32.py: every
expert on every token, no sort, no kernel), at a small size on the CPU:
d 64, 4 heads, 8 experts of width 32, top 2, 2 layers, S 64, seeded
random weights. And the family through the one step maker and the
fault-tolerant loop."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import family_kit as kit

from benchmark.reference import olmoe_f32
from torchft_tpu.models import CONFIGS, make_grad_step, make_train_step
from torchft_tpu.models import init_params as gpt_init_params
from torchft_tpu.models import olmoe
from torchft_tpu.ops import moe
from torchft_tpu.utils import profiling

CFG = olmoe.OLMOE_CONFIGS["olmoe_tiny"]
CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)
REF_KW = dict(
    n_layer=CFG.n_layers, n_head=CFG.n_heads, top_k=CFG.top_k,
    eps=CFG.rms_eps, rope_theta=CFG.rope_theta, lb_coef=CFG.lb_coef,
    z_coef=CFG.z_coef,
)
reference_terms = jax.jit(functools.partial(olmoe_f32.terms, **REF_KW))
reference_grad = jax.jit(jax.value_and_grad(
    functools.partial(olmoe_f32.loss, **REF_KW)))

# bf16 compute against the f32 reference, 256 tokens. Loss: bf16 rounds
# every operation by 2^-9 relative and 256 positions average little of it
# away: |diff| up to 2.3e-3 over 8 seeds; 1e-2 is four times that.
# Gradients, per leaf, ||got - want||_2 / ||want||_2: dense leaves agree to
# 1-3 %; the router's gradient is carried by the tokens near a tie between
# the 2nd and 3rd expert, where the system (bf16 residual stream) and the
# reference route differently (1-2 % of tokens), and reads 6-15 % over 6
# seeds. 0.2 is above that and below what any of the four faults of
# ``test_a_fault_fails_the_comparison`` does (fp8 weights 0.31, a dropped
# expert 0.29-0.65, renormalised top-k 0.93+, no QK-norm exactly 1).
BF16_LOSS_ATOL = 1e-2
BF16_GRAD_REL_L2 = 0.2


def _batch(seed, rows=4):
    tokens = jax.random.randint(jax.random.key(100 + seed), (rows, 64), 0, 512)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _system_grad(cfg, params, tokens, targets):
    return jax.jit(jax.value_and_grad(functools.partial(olmoe.loss_fn, cfg)))(
        params, tokens, targets)


def _leaf_errors(got, want):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm((a - b).ravel())
                           / (jnp.linalg.norm(b.ravel()) + 1e-30)),
        got, want)
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(errs)[0]}


def _agrees(system, reference):
    """The comparison: loss and every gradient leaf of the bf16 system
    against the reference's. Returns (ok, what was seen)."""
    (loss, grads), (want_loss, want) = system, reference
    errors = _leaf_errors(grads, want)
    seen = {"loss_diff": abs(float(loss) - float(want_loss)),
            "worst_leaf": max(errors.items(), key=lambda kv: kv[1])}
    ok = (seen["loss_diff"] <= BF16_LOSS_ATOL
          and seen["worst_leaf"][1] <= BF16_GRAD_REL_L2)
    return ok, seen


# -- against the reference ---------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_compute_equals_the_reference(seed) -> None:
    """Same mathematics, different order of the sums: float32 rounds at
    6e-8 relative, logits are of order 1 and pass through two layers of
    sums over 64 and 32 terms; 1e-5 absolute is some thirty roundings.
    A wrong RoPE convention, norm placement or routing weight moves a
    logit by 1e-2 and more."""
    params = olmoe.init_params(CFG32, jax.random.key(seed))
    tokens, targets = _batch(seed, rows=2)
    want = reference_terms(params, tokens, targets)

    @jax.jit
    def system(p, tok, tgt):
        h, _ = olmoe.forward_hidden(CFG32, p, tok)
        terms = olmoe.loss_terms(CFG32, p, tok, tgt)
        return h @ p["lm_head"]["kernel"], terms

    logits, got = system(params, tokens, targets)
    np.testing.assert_allclose(logits, want["logits"], atol=1e-5, rtol=0)
    for name in ("loss", "ce", "load_balance", "router_z"):
        np.testing.assert_allclose(got[name], want[name], atol=1e-5, rtol=0)
    # and the routing itself: the same top-2 set for every token and layer
    chosen = jnp.any(jax.nn.one_hot(got["experts"], CFG.n_experts,
                                    dtype=bool), axis=-2)
    assert bool(jnp.all(chosen == want["chosen"]))


def test_f32_gradients_equal_the_reference() -> None:
    params = olmoe.init_params(CFG32, jax.random.key(0))
    tokens, targets = _batch(0, rows=2)
    _, want = reference_grad(params, tokens, targets)
    _, got = _system_grad(CFG32, params, tokens, targets)
    # 1e-5 of each leaf's norm: reassociation only (measured 4e-7 - 1.2e-6)
    assert max(_leaf_errors(got, want).values()) < 1e-5


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bf16_compute_agrees_with_the_reference(seed) -> None:
    params = olmoe.init_params(CFG, jax.random.key(seed))
    batch = _batch(seed)
    ok, seen = _agrees(_system_grad(CFG, params, *batch),
                       reference_grad(params, *batch))
    assert ok, seen


_TOP_K = moe.top_k_routing


def _renormalised(probs, k):
    w, e = _TOP_K(probs, k)
    return w / jnp.sum(w, axis=-1, keepdims=True), e


def _one_expert_dropped(probs, k):
    w, e = _TOP_K(probs, k)
    return jnp.where(e == 3, 0.0, w), e


def _with_fault(monkeypatch, fault, params):
    """The weights the faulty system runs on; the other three faults are
    patched into the program."""
    if fault == "fp8_weights":
        # rounded outside any jit: inside one, XLA may keep the excess
        # precision of a convert pair
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, params)
    if fault == "dropped_expert":
        monkeypatch.setattr(moe, "top_k_routing", _one_expert_dropped)
    elif fault == "renormalised_top_k":
        monkeypatch.setattr(moe, "top_k_routing", _renormalised)
    elif fault == "no_qk_norm":
        monkeypatch.setattr(olmoe, "_qk_norm", lambda x, scale, eps: x)
    return params


@pytest.mark.parametrize("fault", ["fp8_weights", "dropped_expert",
                                   "renormalised_top_k", "no_qk_norm"])
def test_a_fault_fails_the_comparison(monkeypatch, fault) -> None:
    """The tolerance of the bf16 comparison is tight enough: computing
    with every matrix rounded to fp8 (e4m3: 3 mantissa bits against
    bf16's 7; the activations stay bf16, so full fp8 compute moves it
    more), losing one expert's assignments, renormalising the top-k
    weights, or leaving the QK-norm out each fails it."""
    params = olmoe.init_params(CFG, jax.random.key(0))
    tokens, targets = _batch(0)
    reference = reference_grad(params, tokens, targets)
    assert _agrees(_system_grad(CFG, params, tokens, targets), reference)[0]
    faulty = _with_fault(monkeypatch, fault, params)
    ok, seen = _agrees(_system_grad(CFG, faulty, tokens, targets), reference)
    assert not ok, seen


@pytest.mark.parametrize("fault", [None, "fp8_weights", "dropped_expert",
                                   "renormalised_top_k", "no_qk_norm"])
def test_the_cells_own_comparison_passes_sound_and_fails_each_fault(
        monkeypatch, fault) -> None:
    """What ``olmoe-solo-steady`` calls ``correct``
    (``benchmark/families/olmoe.py``: every token's final hidden state
    against the reference's, the top-k sets, the loss), with the limits
    it has — measured on the chip at the published widths — here at the
    small size: the sound program passes, each fault fails."""
    from benchmark.families import olmoe as family

    params = olmoe.init_params(CFG, jax.random.key(0))
    batch = _batch(0)
    system_params = _with_fault(monkeypatch, fault, params)
    seen = family.judge(
        family.per_token_errors(CFG, system_params, params, *batch))
    assert seen["ok"] == (fault is None), seen
    if fault is not None:
        # by the per-token comparison alone, several times over its limit
        assert seen["hidden_rel_l2_rms"] > 3 * family.HIDDEN_REL_L2_RMS_MAX


def test_dropless_under_an_extreme_router() -> None:
    """A rank-one router sends every token to one of two pairs of experts
    (by the sign of one projection): four experts are empty, two groups
    hold nearly all rows. No capacity, so the all-experts reference is
    matched as under any other routing."""
    params = olmoe.init_params(CFG32, jax.random.key(3))
    u = jax.random.normal(jax.random.key(4), (CFG.d_model, 1))
    c = jnp.linspace(-3.0, 3.0, CFG.n_experts)[None, :]
    for i in range(CFG.n_layers):
        params[f"layers_{i}"]["moe"]["router"]["kernel"] = u * c
    tokens, targets = _batch(3, rows=2)
    got = jax.jit(functools.partial(olmoe.loss_terms, CFG32))(
        params, tokens, targets)
    want = reference_terms(params, tokens, targets)
    used = np.unique(np.asarray(got["experts"]))
    assert set(used) <= {0, 1, 6, 7} and len(used) >= 2
    for name in ("loss", "ce", "load_balance", "router_z"):
        # the z term is in the hundreds under logits of +-3 |h.u|
        np.testing.assert_allclose(got[name], want[name], atol=2e-5,
                                   rtol=1e-6)
    _, want_g = reference_grad(params, tokens, targets)
    _, got_g = _system_grad(CFG32, params, tokens, targets)
    errors = _leaf_errors(got_g, want_g)
    assert max(errors.values()) < 1e-4, max(errors.items(), key=lambda kv: kv[1])
    # an empty expert gets exactly no gradient
    for name in ("gate_proj", "up_proj", "down_proj"):
        g = got_g["layers_0"]["moe"][name]["kernel"]
        assert float(jnp.max(jnp.abs(g[3]))) == 0.0


# -- the expert path by itself -----------------------------------------------


@pytest.mark.parametrize("sizes", [
    [32, 32, 32, 32, 32, 32, 32, 32],       # even
    [0, 100, 0, 50, 6, 0, 100, 0],          # empty groups
    [0, 0, 256, 0, 0, 0, 0, 0],             # one group holds every row
    [255, 0, 0, 0, 0, 0, 0, 1],
])
def test_grouped_matmul_against_a_per_expert_loop(sizes) -> None:
    x = jax.random.normal(jax.random.key(0), (256, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (8, 64, 32), jnp.float32)
    cot = jax.random.normal(jax.random.key(2), (256, 32), jnp.float32)
    edges = np.concatenate([[0], np.cumsum(sizes)])

    def loop(x, w):
        return jnp.concatenate([
            x[a:b] @ w[g] for g, (a, b) in enumerate(zip(edges, edges[1:]))
        ])

    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = moe.grouped_matmul(x, w, group_sizes)
    np.testing.assert_allclose(got, loop(x, w), atol=1e-4, rtol=0)
    got_g = jax.grad(lambda x, w: jnp.sum(
        moe.grouped_matmul(x, w, group_sizes) * cot), argnums=(0, 1))(x, w)
    want_g = jax.grad(lambda x, w: jnp.sum(loop(x, w) * cot),
                      argnums=(0, 1))(x, w)
    for g, r in zip(got_g, want_g):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=0)


def test_sort_gather_combine_are_inverse_and_their_gradients_gathers() -> None:
    n, k, e, d = 32, 2, 8, 16
    experts = jax.random.randint(jax.random.key(0), (n, k), 0, e)
    weights = jax.random.uniform(jax.random.key(1), (n, k))
    h = jax.random.normal(jax.random.key(2), (n, d))
    dispatch = moe.sort_by_expert(experts, e)
    flat = np.asarray(experts).reshape(-1)
    assert np.all(np.diff(flat[np.asarray(dispatch.order)]) >= 0)
    assert np.array_equal(np.asarray(dispatch.inverse)[
        np.asarray(dispatch.order)], np.arange(n * k))
    assert np.array_equal(dispatch.group_sizes, np.bincount(flat, minlength=e))

    def through(h, weights):   # identity experts: y_t = sum_j w_tj h_t
        return moe.combine(moe.gather_tokens(h, dispatch), weights, dispatch)

    def plain(h, weights):
        return h * jnp.sum(weights, axis=1, keepdims=True)

    np.testing.assert_allclose(through(h, weights), plain(h, weights),
                               atol=1e-6)
    cot = jax.random.normal(jax.random.key(3), (n, d))
    got = jax.grad(lambda *a: jnp.sum(through(*a) * cot), argnums=(0, 1))(
        h, weights)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot), argnums=(0, 1))(
        h, weights)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-5)
    # no scatter of rows in either direction: gathers only
    text = jax.jit(jax.grad(lambda *a: jnp.sum(through(*a) * cot),
                            argnums=(0, 1))).lower(h, weights).as_text()
    assert "scatter" not in text


def test_one_compile_serves_every_routing() -> None:
    tx = optax.adamw(1e-3)
    step = make_train_step(CFG, tx, donate=False, loss=olmoe.loss_fn)
    route = jax.jit(lambda p, tok: olmoe.forward_hidden(CFG, p, tok)[1][
        "experts"])
    params = olmoe.init_params(CFG, jax.random.key(0))
    opt_state = tx.init(params)
    routings = []
    for i in range(3):
        tokens, targets = _batch(10 + i, rows=2)
        routings.append(np.bincount(
            np.asarray(route(params, tokens)).reshape(-1), minlength=8))
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        assert np.isfinite(float(loss))
    assert step._cache_size() == 1
    assert not np.array_equal(routings[0], routings[1])
    assert not np.array_equal(routings[1], routings[2])


# -- the one step maker ------------------------------------------------------


def _family(name):
    if name == "gpt":
        cfg = CONFIGS["tiny"]
        return cfg, gpt_init_params(cfg, jax.random.key(0)), {}
    return CFG, olmoe.init_params(CFG, jax.random.key(0)), {
        "loss": olmoe.loss_fn}


@pytest.mark.parametrize("family", ["gpt", "olmoe"])
def test_step_programs_keep_their_names_and_scopes(family) -> None:
    cfg, params, kw = _family(family)
    tx = optax.adamw(1e-3)
    tokens, targets = _batch(0, rows=2)
    train = make_train_step(cfg, tx, donate=False, **kw)
    grad = make_grad_step(cfg, **kw)
    assert (train.name, grad.name) == ("tft_train_step", "tft_grad_step")
    # (its own tx: a program nobody else has, so it has never run)
    assert train.scope_table() == {}            # before the first call
    train(params, tx.init(params), tokens, targets)
    grad(params, tokens, targets)
    table, grad_table = train.scope_table(), grad.scope_table()
    assert table and grad_table
    # and the shapes it first ran on, for whoever counts its operations
    assert profiling.step_args("tft_train_step")[2].shape == tokens.shape
    paths = set(table.values())
    # (the CPU compiler leaves some operations a bare op_name)
    assert any(p.startswith("jit(tft_train_step)/") for p in paths)
    assert any(p.startswith("jit(tft_grad_step)/")
               for p in grad_table.values())
    assert not any("tft_grad_step" in p for p in paths)
    for scope in ("opt_update", "attn", "mlp", "lm_head_xent", "embed"):
        assert any(scope in p.replace("(", "/").replace(")", "/").split("/")
                   for p in paths), scope
    assert not any("opt_update" in p for p in grad_table.values())
    inner = {"moe_router", "moe_dispatch", "moe_experts", "moe_combine"}
    found = {s for s in inner if any(f"/{s}" in p for p in paths)}
    assert found == (inner if family == "olmoe" else set())
    # the sparse sublayer's inner scopes all lie under mlp
    assert all("mlp" in p for p in paths if any(s in p for s in inner))


@pytest.mark.parametrize("family", ["gpt", "olmoe"])
def test_microbatched_grad_step_equals_the_whole_batch(family) -> None:
    cfg, params, kw = _family(family)
    if family == "olmoe":   # f32: the slices' sums are the whole sum
        cfg = CFG32
    else:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    tokens, targets = _batch(1, rows=4)
    if family == "gpt":
        loss1, g1 = make_grad_step(cfg, **kw)(params, tokens, targets)
        loss2, g2 = make_grad_step(cfg, microbatches=2, **kw)(
            params, tokens, targets)
        np.testing.assert_allclose(loss1, loss2, rtol=1e-5)
        assert max(_leaf_errors(g2, g1).values()) < 1e-4
        return
    # the load-balancing term is a product of two batch means, so two
    # half batches do not average to the whole; cross entropy does
    ce = lambda c, p, t, y, a=None: olmoe.loss_terms(c, p, t, y, a)["ce"]  # noqa: E731
    loss1, g1 = make_grad_step(cfg, loss=ce)(params, tokens, targets)
    loss2, g2 = make_grad_step(cfg, microbatches=2, loss=ce)(
        params, tokens, targets)
    np.testing.assert_allclose(loss1, loss2, rtol=1e-5)
    assert max(_leaf_errors(g2, g1).values()) < 1e-4


# -- through the fault-tolerant loop -----------------------------------------


def test_three_ft_steps_equal_three_plain_steps_bit_for_bit() -> None:
    """The cell's ``plain_worker`` check at the small size: Manager +
    OptimizerWrapper.fused_step dispatch the very program the plain
    worker runs."""
    with kit.ft_steps(kit.tiny("olmoe")) as run:
        assert all(np.isfinite(run.losses))


def test_two_groups_on_the_classic_path_one_healed_from_the_other() -> None:
    """grad -> average_gradients -> step across two replica groups; the
    second starts from other weights, behind, and gets to the first's
    state only by a heal. At rest on one step the sha256 of parameters
    and optimizer state are equal: expert-shaped leaves ([8, 64, 32])
    through ddp.py and checkpointing.py, which this family did not
    touch."""
    with kit.two_groups_one_healed(kit.tiny("olmoe"), tail=2) as run:
        losses = jax.device_get([r["loss"] for g in run.groups
                                 for r in g.records if r["committed"]])
        assert all(np.isfinite(float(x)) for x in losses)


def test_the_loop_scenarios_built_one_step_program() -> None:
    kit.assert_built_once("olmoe")
