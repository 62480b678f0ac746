"""Tests for the parallel layer on an 8-device virtual CPU mesh:
ft_mesh axes, FSDP/TP sharding rules, ring attention exactness."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from torchft_tpu.parallel import (
    FTMesh,
    ft_mesh,
    fsdp_sharding,
    make_ring_attention,
    make_sharding_fn,
    shard_pytree,
    tp_rules_gpt,
)


def test_ft_mesh_axes_and_infer() -> None:
    mesh = ft_mesh({"data": 2, "fsdp": -1})
    assert mesh.shape == {"data": 2, "fsdp": 4}
    with pytest.raises(ValueError, match="need"):
        ft_mesh({"data": 3, "fsdp": 4})


def test_ft_mesh_replica_axis_is_virtual() -> None:
    from unittest.mock import MagicMock

    mesh = ft_mesh({"data": 8})
    manager = MagicMock()
    manager.num_participants.return_value = 3
    ftm = FTMesh(manager, mesh)
    assert ftm.num_replicas() == 3
    # the managed VIEW includes the virtual axis (ref ManagedDeviceMesh
    # shape :1210-1214) but the COMPILED mesh never does
    assert ftm.axis_names == ("replica", "data")
    assert "replica" not in ftm.mesh.axis_names
    with pytest.raises(ValueError, match="virtual replica"):
        FTMesh(manager, ft_mesh({"replica": 8}))
    manager.num_participants.return_value = 0
    assert ftm.num_replicas() == 1  # reported >=1 (ref pg.py:1187-1202)


def test_ft_mesh_composition_surface() -> None:
    # getitem / size / coordinate / flatten / get_comm parity with the
    # reference's ManagedDeviceMesh (process_group.py:1086-1261),
    # rendered as axis selections over one physical mesh.
    from unittest.mock import MagicMock

    from torchft_tpu.comm.context import ManagedCommContext

    mesh = ft_mesh({"data": 2, "fsdp": 4})
    manager = MagicMock()
    manager.num_participants.return_value = 3
    manager.participating_rank.return_value = 2
    ftm = FTMesh(manager, mesh)

    # shape/size include the virtual axis
    assert ftm.shape == {"replica": 3, "data": 2, "fsdp": 4}
    assert ftm.size() == 24
    assert ftm.size("replica") == 3 and ftm.size("fsdp") == 4
    assert ftm.ndim == 3

    # getitem: replica selection -> FTMesh view NARROWED to the selected
    # in-group axes; in-group-only -> pspec names
    sub = ftm[("replica", "fsdp")]
    assert isinstance(sub, FTMesh)
    assert sub.shape == {"replica": 3, "fsdp": 4}
    assert sub.size() == 12  # not 24: "data" is outside the view
    with pytest.raises(KeyError):
        sub.axis_size("data")
    rep_only = ftm["replica"]
    assert rep_only.axis_names == ("replica",)
    assert rep_only.size() == 3
    with pytest.raises(ValueError, match="replica-only"):
        rep_only.sharding(None)
    assert ftm["fsdp"] == "fsdp"
    assert ftm[("data", "fsdp")] == ("data", "fsdp")
    with pytest.raises(KeyError):
        ftm["bogus"]

    # get_comm: replica axis -> Manager-backed context; in-group -> name
    assert isinstance(ftm.get_comm("replica"), ManagedCommContext)
    assert isinstance(ftm.get_comm(), ManagedCommContext)
    assert ftm.get_comm("data") == "data"

    # flatten fragment usable inside a PartitionSpec
    frag = ftm.flattened_spec("data", "fsdp")
    assert frag == ("data", "fsdp")
    s = ftm.sharding(frag, None)
    assert s.spec == P(("data", "fsdp"), None)
    with pytest.raises(ValueError, match="virtual"):
        ftm.flattened_spec("replica")

    # coordinate: device indices + replica rank
    dev = mesh.devices[1][2]
    coord = ftm.coordinate(dev)
    assert coord == {"replica": 2, "data": 1, "fsdp": 2}


def test_fsdp_sharding_largest_dim() -> None:
    mesh = ft_mesh({"fsdp": 8})
    s = fsdp_sharding(mesh, (16, 128))
    assert s.spec == P(None, "fsdp")  # 128 is the largest divisible dim
    s = fsdp_sharding(mesh, (64, 6))
    assert s.spec == P("fsdp", None)
    # too small to shard -> replicated
    s = fsdp_sharding(mesh, (3, 5))
    assert s.spec == P(None, None)
    s = fsdp_sharding(mesh, ())
    assert s.spec == P()


def test_tp_plus_fsdp_composition() -> None:
    mesh = ft_mesh({"fsdp": 2, "tensor": 4})
    fn = make_sharding_fn(mesh, tp_rules_gpt())
    params = {
        "layers_0": {
            "attn": {"q_proj": {"kernel": jnp.zeros((64, 64))}},
            "mlp": {"down_proj": {"kernel": jnp.zeros((256, 64))}},
        },
        "ln_f": {"scale": jnp.zeros((64,))},
    }
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {
        "/".join(str(getattr(k, "key", k)) for k in path): fn(path, leaf).spec
        for path, leaf in flat
    }
    # q_proj column-parallel on tensor, fsdp takes the other dim
    assert specs["layers_0/attn/q_proj/kernel"] == P("fsdp", "tensor")
    # down_proj row-parallel
    assert specs["layers_0/mlp/down_proj/kernel"][0] == "tensor"
    # norm scale: no tensor dim; fsdp may take the (divisible) vector dim
    assert "tensor" not in jax.tree_util.tree_leaves(
        [specs["ln_f/scale"]]
    )


def test_shard_pytree_places_arrays() -> None:
    mesh = ft_mesh({"fsdp": 8})
    params = {"w": jnp.ones((32, 16)), "b": jnp.ones((8,))}
    sharded = shard_pytree(params, mesh, fsdp_axis="fsdp", tp_rules=None)
    assert isinstance(sharded["w"].sharding, NamedSharding)
    assert sharded["w"].sharding.spec == P("fsdp", None)
    np.testing.assert_allclose(np.asarray(sharded["w"]), np.ones((32, 16)))


def _reference_attention(q, k, v, causal, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        S = q.shape[1]
        mask = np.tril(np.ones((S, S), dtype=bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal) -> None:
    mesh = ft_mesh({"seq": 8})
    B, S, H, D = 2, 64, 4, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)

    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))

    ring = jax.jit(make_ring_attention(mesh, "seq", causal=causal))
    out = ring(qs, ks, vs)
    expected = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5
    )
    # output stays sequence-sharded
    assert out.sharding.spec == P(None, "seq", None, None)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_blocks_matches_reference(causal) -> None:
    # flash-block ring (pallas local blocks + logaddexp stream merge,
    # future blocks skipped at block granularity) must be EXACT vs dense
    # attention, like the einsum ring. Interpret mode: no TPU in tests.
    mesh = ft_mesh({"seq": 4}, devices=jax.devices()[:4])
    B, S, H, D = 2, 64, 2, 16
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))

    ring = jax.jit(make_ring_attention(
        mesh, "seq", causal=causal, block_impl="flash",
        block_q=8, block_k=8, interpret=True,
    ))
    out = ring(qs, ks, vs)
    expected = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5
    )
    assert out.sharding.spec == P(None, "seq", None, None)


def test_ring_attention_flash_blocks_match_einsum_blocks() -> None:
    # the two block implementations are interchangeable numerically
    mesh = ft_mesh({"seq": 8})
    B, S, H, D = 1, 64, 2, 8
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out_e = jax.jit(make_ring_attention(mesh, "seq", causal=True))(
        qs, ks, vs
    )
    out_f = jax.jit(make_ring_attention(
        mesh, "seq", causal=True, block_impl="flash", block_q=8, block_k=8,
        interpret=True,
    ))(qs, ks, vs)
    np.testing.assert_allclose(
        np.asarray(out_e), np.asarray(out_f), atol=2e-5, rtol=2e-5
    )


def test_ring_attention_long_context_grad() -> None:
    # differentiate through the ring (training path), check vs reference
    mesh = ft_mesh({"seq": 8})
    B, S, H, D = 1, 32, 2, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))

    ring = make_ring_attention(mesh, "seq", causal=True)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_blocks_grad(causal) -> None:
    # the flash ring's custom VJP (ring-structured FlashAttention-2
    # backward: global lse/delta, dk/dv accumulators rotating with their
    # kv blocks) must produce EXACT gradients vs dense attention
    mesh = ft_mesh({"seq": 4}, devices=jax.devices()[:4])
    B, S, H, D = 2, 64, 2, 16
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))

    ring = make_ring_attention(
        mesh, "seq", causal=causal, block_impl="flash",
        block_q=8, block_k=8, interpret=True,
    )

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            _reference_attention(q, k, v, causal=causal) ** 2
        )

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


def test_ring_attention_flash_grad_matches_einsum_grad() -> None:
    # flash and einsum ring backwards are interchangeable (training can
    # switch block_impl without a trajectory break)
    mesh = ft_mesh({"seq": 8})
    B, S, H, D = 1, 64, 2, 8
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=jnp.float32)
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))

    ring_e = make_ring_attention(mesh, "seq", causal=True)
    ring_f = make_ring_attention(
        mesh, "seq", causal=True, block_impl="flash", block_q=8, block_k=8,
        interpret=True,
    )

    ge = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(ring_e(q, k, v) ** 2), argnums=(0, 1, 2)
    ))(qs, ks, vs)
    gf = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(ring_f(q, k, v) ** 2), argnums=(0, 1, 2)
    ))(qs, ks, vs)
    for a, b in zip(ge, gf):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


def test_flash_ring_rides_on_lse_and_delta_as_bhs_rows() -> None:
    # parallel/ring.py's two surfaces of ops/flash.py after PR 51 (the
    # kernels hand lse and delta about as [BH, 1, S] rows): the forward
    # still gives lse [B, H, S] f32 — the log-sum-exp of the scaled scores
    # itself —, the block backward still takes lse and delta so, and the
    # flash ring's forward and gradients are the einsum-block ring's.
    from torchft_tpu.ops.flash import (
        flash_attention_with_lse, flash_block_attention_bwd,
    )

    mesh = ft_mesh({"seq": 4}, devices=jax.devices()[:4])
    B, S, H, D = 2, 64, 2, 16
    rng = np.random.default_rng(51)
    q, k, v, g = (jnp.asarray(rng.standard_normal((B, S, H, D)),
                              dtype=jnp.float32) for _ in range(4))

    out, lse = flash_attention_with_lse(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    assert lse.shape == (B, H, S) and lse.dtype == jnp.float32
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / D ** 0.5
    scores = jnp.where(np.tril(np.ones((S, S), dtype=bool)), scores,
                       -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(scores, axis=-1)),
        atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_reference_attention(q, k, v, True)),
        atol=2e-5, rtol=2e-5)
    delta = jnp.sum(g * out, axis=-1).transpose(0, 2, 1)
    assert delta.shape == lse.shape
    grads = flash_block_attention_bwd(
        q, k, v, g, lse, delta, causal=True, block_q=16, block_k=16,
        interpret=True)
    want = jax.grad(
        lambda q, k, v: jnp.sum(_reference_attention(q, k, v, True) * g),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)

    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    rings = [make_ring_attention(mesh, "seq", causal=True, **kw)
             for kw in ({}, dict(block_impl="flash", block_q=8, block_k=8,
                                 interpret=True))]
    blockwise, flash = (
        (jax.jit(ring)(qs, ks, vs), *jax.jit(jax.grad(
            lambda q, k, v, ring=ring: jnp.sum(ring(q, k, v) * g),
            argnums=(0, 1, 2)))(qs, ks, vs)) for ring in rings)
    for a, b in zip(flash, blockwise):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)
