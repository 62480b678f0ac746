"""Model tests: transformer forward/train numerics, sharded variants,
ResNet-18, toy MLP."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from torchft_tpu.models import (
    CONFIGS,
    count_params,
    forward,
    init_params,
    init_linear,
    linear_forward,
    loss_fn,
    make_train_step,
)
from torchft_tpu.parallel import ft_mesh, make_ring_attention, shard_pytree, tp_rules_gpt


TINY = CONFIGS["tiny"]


def _data(cfg, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len)),
        dtype=jnp.int32,
    )
    targets = jnp.roll(tokens, -1, axis=1)
    return tokens, targets


def test_transformer_forward_shapes_and_param_count() -> None:
    params = init_params(TINY, jax.random.key(0))
    tokens, _ = _data(TINY)
    logits = forward(TINY, params, tokens)
    assert logits.shape == (2, TINY.max_seq_len, TINY.vocab_size)
    assert logits.dtype == jnp.float32
    n = count_params(params)
    assert n > 100_000  # tiny config ~ a few hundred k


def test_transformer_train_step_reduces_loss() -> None:
    params = init_params(TINY, jax.random.key(0))
    tx = optax.adam(1e-2)
    step = make_train_step(TINY, tx, donate=False)
    opt_state = tx.init(params)
    tokens, targets = _data(TINY)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_transformer_125m_param_count() -> None:
    # structural check without materializing: shape-only eval
    cfg = CONFIGS["125m"]
    shapes = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.key(0)
    )
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert 120e6 < n < 180e6, n


def test_transformer_sharded_dp_fsdp_tp() -> None:
    # full train step over a data×fsdp×tensor mesh, tiny shapes
    mesh = ft_mesh({"data": 2, "fsdp": 2, "tensor": 2})
    params = init_params(TINY, jax.random.key(0))
    params = shard_pytree(params, mesh, tp_rules=tp_rules_gpt())
    tx = optax.sgd(1e-2)
    step = make_train_step(TINY, tx, donate=False)
    opt_state = tx.init(params)
    tokens, targets = _data(TINY, batch=4)
    batch_sharding = NamedSharding(mesh, P("data", None))
    tokens = jax.device_put(tokens, batch_sharding)
    targets = jax.device_put(targets, batch_sharding)
    params2, opt_state2, loss = step(params, opt_state, tokens, targets)
    assert np.isfinite(float(loss))

    # numerics match the unsharded step
    params_r = init_params(TINY, jax.random.key(0))
    opt_r = tx.init(params_r)
    _, _, loss_r = make_train_step(TINY, tx, donate=False)(
        params_r, opt_r, jax.device_get(tokens), jax.device_get(targets)
    )
    np.testing.assert_allclose(float(loss), float(loss_r), rtol=2e-2)


def test_transformer_ring_attention_matches_local() -> None:
    mesh = ft_mesh({"seq": 8})
    cfg = TINY
    params = init_params(cfg, jax.random.key(1))
    tokens, targets = _data(cfg)
    ring_fn = make_ring_attention(mesh, "seq", causal=True)

    loss_local = loss_fn(cfg, params, tokens, targets)
    with mesh:
        loss_ring = jax.jit(
            lambda p, t, y: loss_fn(cfg, p, t, y, attn_fn=ring_fn)
        )(params, tokens, targets)
    np.testing.assert_allclose(
        float(loss_ring), float(loss_local), rtol=5e-3
    )


def test_linear_toy() -> None:
    params = init_linear(jax.random.key(0), 2, 3)
    out = linear_forward(params, jnp.ones((4, 2)))
    assert out.shape == (4, 3)


def test_resnet18_forward_and_step() -> None:
    flax = pytest.importorskip("flax")
    from torchft_tpu.models.resnet import create_resnet18

    model, variables = create_resnet18(jax.random.key(0))
    x = jnp.ones((2, 32, 32, 3))
    logits, _ = model.apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    assert logits.shape == (2, 10)
    n = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(variables["params"])
    )
    assert 10e6 < n < 13e6  # ResNet-18 ~11M params


# ------------------------------------------------------------- llama family


def test_llama_forward_and_grads() -> None:
    from torchft_tpu.models import (
        LLAMA_CONFIGS, llama_init_params, llama_loss_fn,
    )

    cfg = LLAMA_CONFIGS["llama_tiny"]
    params = llama_init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, cfg.max_seq_len)), jnp.int32
    )
    targets = jnp.roll(tokens, -1, axis=1)
    loss, grads = jax.value_and_grad(
        lambda p: llama_loss_fn(cfg, p, tokens, targets)
    )(params)
    assert np.isfinite(float(loss))
    gnorm = sum(
        float(jnp.sum(g.astype(jnp.float32) ** 2))
        for g in jax.tree_util.tree_leaves(grads)
    )
    assert np.isfinite(gnorm) and gnorm > 0
    # GQA present: kv projection narrower than q projection
    l0 = params["layers"][0]["attn"]
    assert l0["k_proj"]["kernel"].shape[1] < l0["q_proj"]["kernel"].shape[1]


def test_llama_trains_and_flash_matches() -> None:
    import optax

    from torchft_tpu.models import (
        LLAMA_CONFIGS, llama_init_params, llama_loss_fn,
    )
    from torchft_tpu.ops.attention import reference_attention
    from torchft_tpu.ops.flash import flash_attention

    cfg = LLAMA_CONFIGS["llama_tiny"]
    params = llama_init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, cfg.max_seq_len)), jnp.int32
    )
    targets = jnp.roll(tokens, -1, axis=1)

    # the flash kernel (interpret) and the reference are both handed K and
    # V at their own head count (2 of 4 here) and copy nothing
    def flash_fn(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64,
                               block_k=64, interpret=True)

    def ref_fn(q, k, v):
        return reference_attention(q, k, v, causal=True)

    l_ref = llama_loss_fn(cfg, params, tokens, targets, attn_fn=ref_fn)
    l_fl = llama_loss_fn(cfg, params, tokens, targets, attn_fn=flash_fn)
    # bf16 activations: kernel-formulation noise only
    assert abs(float(l_ref) - float(l_fl)) < 2e-2

    # a few SGD steps reduce the loss
    tx = optax.adam(3e-3)
    opt = tx.init(params)
    loss_fn = jax.jit(
        jax.value_and_grad(lambda p: llama_loss_fn(cfg, p, tokens, targets))
    )
    losses = []
    for _ in range(8):
        loss, grads = loss_fn(params)
        losses.append(float(loss))
        updates, opt = tx.update(grads, opt, params)
        params = optax.apply_updates(params, updates)
    assert losses[-1] < losses[0] - 0.1, losses


def test_llama_tp_sharding_rules_apply() -> None:
    from torchft_tpu.models import LLAMA_CONFIGS, llama_init_params
    from torchft_tpu.parallel import ft_mesh, shard_pytree, tp_rules_gpt

    cfg = LLAMA_CONFIGS["llama_tiny"]
    params = llama_init_params(cfg, jax.random.key(0))
    mesh = ft_mesh({"fsdp": 2, "tensor": 2}, devices=jax.devices()[:4])
    sharded = shard_pytree(params, mesh, tp_rules=tp_rules_gpt())
    l0 = sharded["layers"][0]
    # Megatron layout via the SAME rules the GPT family uses:
    # q/k/v column-parallel, o row-parallel, gate/up column, down row
    def spec(x):
        return x.sharding.spec

    assert spec(l0["attn"]["q_proj"]["kernel"])[1] == "tensor"
    assert spec(l0["attn"]["o_proj"]["kernel"])[0] == "tensor"
    assert spec(l0["mlp"]["gate_proj"]["kernel"])[1] == "tensor"
    assert spec(l0["mlp"]["down_proj"]["kernel"])[0] == "tensor"


def test_grad_accumulation_matches_full_batch() -> None:
    # microbatched make_grad_step must equal the full-batch grads exactly
    # (same mean semantics; equal slice sizes)
    import numpy as np

    from torchft_tpu.models import CONFIGS, init_params, make_grad_step

    cfg = CONFIGS["tiny"]
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(9)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, cfg.max_seq_len)), jnp.int32
    )
    targets = jnp.roll(tokens, -1, axis=1)

    l1, g1 = make_grad_step(cfg)(params, tokens, targets)
    l4, g4 = make_grad_step(cfg, microbatches=4)(params, tokens, targets)
    # bf16 activations: slicing the batch changes matmul tiling, so
    # agreement is at bf16 reassociation level, not exact
    np.testing.assert_allclose(float(l1), float(l4), atol=1e-3, rtol=1e-4)
    flat1, _ = jax.tree_util.tree_flatten(g1)
    flat4, _ = jax.tree_util.tree_flatten(g4)
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=0.05
        )


def test_grad_accumulation_rejects_ragged_batch() -> None:
    import pytest as _pytest

    from torchft_tpu.models import CONFIGS, init_params, make_grad_step

    cfg = CONFIGS["tiny"]
    params = init_params(cfg, jax.random.key(0))
    tokens = jnp.zeros((3, cfg.max_seq_len), jnp.int32)
    with _pytest.raises(ValueError, match="microbatches"):
        make_grad_step(cfg, microbatches=2)(params, tokens, tokens)


# -- K and V at their own head count (PR 55) ---------------------------------


@pytest.mark.parametrize("kw", [{}, {"window": 9}, {"causal": False}],
                         ids=["causal", "window", "unmasked"])
def test_reference_attention_groups_the_query_heads(kw) -> None:
    """``reference_attention`` at ``KV < H`` is itself on ``repeat_kv``-ed
    operands (query head ``i`` reads key/value head ``i // group``), value
    and all three gradients: ``causal_attention`` means one thing on both
    backends."""
    from torchft_tpu.models.common import repeat_kv
    from torchft_tpu.ops.attention import reference_attention

    rng = np.random.default_rng(5)
    q, k, v, cot = (jnp.asarray(rng.standard_normal(s), jnp.float32)
                    for s in ((2, 32, 14, 8), (2, 32, 2, 8), (2, 32, 2, 12),
                              (2, 32, 14, 12)))

    def both(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(cot))

    got = both(lambda q, k, v: reference_attention(q, k, v, **kw))
    want = both(lambda q, k, v: reference_attention(
        q, repeat_kv(k, 14), repeat_kv(v, 14), **kw))
    assert got[0].shape == (2, 32, 14, 12)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def _zoo(name):
    """``(module's loss_fn, its init_params, tiny config, KV heads a flash
    call is handed or None where they are the query heads')``."""
    from torchft_tpu.models import (
        joyai, lfm2, llama, nemotron_h, olmoe, phi4flash, smallthinker,
        transformer,
    )

    return {
        "smallthinker": lambda: (
            smallthinker.loss_fn, smallthinker.init_params,
            smallthinker.SMALLTHINKER_CONFIGS["smallthinker_tiny"], 2),
        "lfm2": lambda: (lfm2.loss_fn, lfm2.init_params,
                         lfm2.LFM2_CONFIGS["lfm2_tiny"],
                         lfm2.LFM2_CONFIGS["lfm2_tiny"].n_kv_heads),
        "nemotron_h": lambda: (
            nemotron_h.loss_fn, nemotron_h.init_params,
            nemotron_h.NEMOTRON_H_CONFIGS["nemotron_h_tiny"],
            nemotron_h.NEMOTRON_H_CONFIGS["nemotron_h_tiny"].n_kv_heads),
        # a call a half: pairs of key/value heads
        "phi4flash": lambda: (
            phi4flash.loss_fn, phi4flash.init_params,
            phi4flash.PHI4FLASH_CONFIGS["phi4flash_tiny"], 2),
        "llama": lambda: (llama.llama_loss_fn, llama.llama_init_params,
                          llama.LLAMA_CONFIGS["llama_tiny"], 2),
        "transformer": lambda: (transformer.loss_fn, transformer.init_params,
                                TINY, None),
        "olmoe": lambda: (olmoe.loss_fn, olmoe.init_params,
                          olmoe.OLMOE_CONFIGS["olmoe_tiny"], None),
        "joyai": lambda: (joyai.loss_fn, joyai.init_params,
                          joyai.JOYAI_CONFIGS["joyai_tiny"], None),
    }[name]()


@pytest.mark.parametrize("regime", ["resident", "streamed"])
@pytest.mark.parametrize("model", [
    "smallthinker", "lfm2", "nemotron_h", "phi4flash", "llama",
    "transformer", "olmoe", "joyai"])
def test_a_models_program_holds_no_copy_of_k_or_v(model, regime) -> None:
    """The gradient program of every model whose key/value heads serve
    several query heads, traced as a TPU traces it (the flash kernels,
    here through the interpreter; nothing runs): every flash kernel is
    handed K and V ``B · KV`` rows tall — there is no ``[B, S, H, D]`` copy
    to hand it — and ``flash_calls_grouped`` counts every call; the models
    of equal head counts engage none of it. Where K and V stream (the
    long-context cells' regime, here by the call's own threshold) the
    backward is the one kernel ``flash_bwd`` (PR 74), handed the same K
    and V."""
    from test_flash import (
        _KERNELS_OF, _REGIMES, _flash_calls, _traced_flash_calls,
    )
    from torchft_tpu.ops.flash import flash_attention

    loss, init, cfg, kv_heads = _zoo(model)
    batch, seq = 2, 64
    params = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def attn_fn(q, k, v, window=None):
        return flash_attention(q, k, v, window=window, interpret=True,
                               _resident_kv_bytes=_REGIMES[regime])

    before = _traced_flash_calls()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, a, b: loss(cfg, p, a, b, attn_fn)))(params, tokens, tokens)
    calls, grouped = _traced_flash_calls() - before
    assert calls > 0
    assert grouped == (calls if kv_heads else 0)
    found = _flash_calls(jaxpr.jaxpr)
    assert set(found) == set(_KERNELS_OF[regime])
    for name, eqns in found.items():
        for eqn in eqns:
            tables = eqn.params["grid_mapping"].num_index_operands
            q, k, v = (x.aval for x in eqn.invars[tables:tables + 3])
            assert k.shape[0] == v.shape[0], name
            if kv_heads:
                assert k.shape[0] == batch * kv_heads < q.shape[0], name
            else:
                assert k.shape[0] == q.shape[0], name
