"""``ops/flash.py`` with K and V at their own head count (PR 55; interpret
mode on the CPU): (a) the grouped call against the call on ``repeat_kv``-ed
operands, (b) the shapes it refuses, (c) equal head counts trace to what
they traced to, (d) the counter. A file of its own beside
``tests/test_flash.py``, whose helpers it takes: the 90 cases of (a) are a
worker's share of the run by themselves."""

import pytest

import jax
import jax.numpy as jnp

from test_flash import (
    _KERNELS_OF, _MASKS, _REGIMES, _eqns, _flash_calls, _rand,
    _traced_flash_calls,
)
from torchft_tpu.models.common import repeat_kv
from torchft_tpu.ops.attention import reference_attention
from torchft_tpu.ops.flash import flash_attention


@pytest.mark.parametrize("widths", [(64, 64), (128, 128), (64, 128)],
                         ids=["64", "128", "64-128"])
@pytest.mark.parametrize("mask", sorted(_MASKS))
@pytest.mark.parametrize("regime", sorted(_REGIMES))
@pytest.mark.parametrize("group", [1, 2, 4, 7, 16])
def test_a_key_value_head_is_read_where_it_lies(group, regime, mask,
                                                widths) -> None:
    """Query head ``i`` reads key/value head ``i // group`` inside the
    kernels' index maps: ``out`` and ``dq`` are the call's on
    ``repeat_kv``-ed operands bit for bit (the same tiles of the same
    operands), and ``dk`` / ``dv`` — the group's sum taken in float32 in
    VMEM and rounded once, where the repeated path rounds a head's
    gradient and XLA sums the copies — stand at the file's bf16 tolerance
    from the float32 reference's gradients summed over the copies in
    float32, and no further from them than the repeated path does.

    Crosses, at the blocks passed (64 x 128): two k blocks and four q
    blocks (S 256 is the least that holds two k blocks), the causal
    diagonal inside a tile, the window's edge (200 keys: a band that
    starts inside the first k block for the last q blocks) and ``KV < H``
    with two key/value heads wherever the group leaves room (a query head
    must be able to read the WRONG one)."""
    dqk, dv = widths
    b, s, kv = 1, 256, 2 if group < 7 else 1
    h = kv * group
    q, k, v, cot = (_rand(shape, i + 90, jnp.bfloat16) for i, shape in
                    enumerate(((b, s, h, dqk), (b, s, kv, dqk),
                               (b, s, kv, dv), (b, s, h, dv))))

    def flash(q, k, v):
        return flash_attention(
            q, k, v, block_q=64, block_k=128, interpret=True,
            _resident_kv_bytes=_REGIMES[regime], **_MASKS[mask],
        )

    def repeated(q, k, v):
        return flash(q, repeat_kv(k, h), repeat_kv(v, h))

    def out_and_grads(fn, q, k, v, cot):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(cot))

    def reference(q, k, v, cot):
        """The float32 reference on the copies; its gradients summed over
        them."""
        grads = jax.grad(lambda k_, v_: jnp.sum(reference_attention(
            q, k_, v_, **_MASKS[mask]) * cot), argnums=(0, 1))(
                repeat_kv(k, h), repeat_kv(v, h))
        return [x.reshape(b, s, kv, group, -1).sum(axis=3) for x in grads]

    # ONE program a case — the grouped call, the repeated call and the
    # reference, forward and backward: at S 256 compiling is what a case
    # costs, and three programs' fixed parts are paid once. At group 1 the
    # copies are the operands and the two calls one program.
    @jax.jit
    def everything(q, k, v, cot):
        got = out_and_grads(flash, q, k, v, cot)
        want = out_and_grads(repeated, q, k, v, cot) if group > 1 else got
        return got, want, reference(
            *(x.astype(jnp.float32) for x in (q, k, v, cot)))

    got, want, (ref_dk, ref_dv) = everything(q, k, v, cot)
    for name, a, b_ in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16 and a.shape == b_.shape, name
    assert jnp.array_equal(got[0], want[0])
    assert jnp.array_equal(got[1], want[1])

    for name, a, r, ref in (("dk", got[2], want[2], ref_dk),
                            ("dv", got[3], want[3], ref_dv)):
        err, err_repeated = (
            jnp.abs(x.astype(jnp.float32) - ref) for x in (a, r))
        assert float(err.max()) <= 0.02 * float(jnp.abs(ref).max()), name
        # one rounding of an f32 sum against a sum of rounded terms
        assert float(jnp.sqrt(jnp.mean(err ** 2))) <= 1.0001 * float(
            jnp.sqrt(jnp.mean(err_repeated ** 2))), name


def test_query_heads_are_a_multiple_of_the_key_value_heads() -> None:
    q, k = _rand((1, 128, 6, 32), 0), _rand((1, 128, 4, 32), 1)
    with pytest.raises(ValueError, match="multiple of the key/value heads"):
        flash_attention(q, k, k, interpret=True)
    # as many value heads as key heads
    with pytest.raises(ValueError, match="must be as many"):
        flash_attention(q, k[:, :, :2], k[:, :, :3], interpret=True)
    with pytest.raises(ValueError, match="multiple of the key/value heads"):
        reference_attention(q, k, k)


def _index_map_primitives(eqn):
    """The primitives of every index map of a ``pallas_call``, nested
    calls' included."""
    return {e.primitive.name
            for mapping in eqn.params["grid_mapping"].block_mappings
            for e, _ in _eqns(mapping.index_map_jaxpr.jaxpr)}


@pytest.mark.parametrize("mask", sorted(_MASKS))
@pytest.mark.parametrize("regime", sorted(_REGIMES))
def test_equal_head_counts_trace_to_the_grids_they_had(regime, mask) -> None:
    """``group == 1`` is left to the instruction (the three pinned jaxprs
    above hold two of these six programs to the letter): grids of the rank
    they had, two tables under the mask and none without, index maps that
    pass the leading index through and read a table — no division, no
    multiply-add. A grouped call differs in exactly that: the key/value
    maps divide. The backward is ``flash_bwd`` (PR 74), a ROW sweep in
    either regime: its grid is dq's whatever the group, its accumulators
    (dk's and dv's a whole head long; dq's a q block where K and V stream)
    are scratch at every group, and a grouped call differs in its
    key/value maps alone — K's and V's blocks and dk's and dv's whole-head
    output blocks at ``b // group``."""
    kernels = _KERNELS_OF[regime]

    def calls(heads, kv_heads):
        q = jnp.zeros((1, 512, heads, 64), jnp.bfloat16)
        k = jnp.zeros((1, 512, kv_heads, 64), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(
                q, k, v, block_q=128, block_k=256, interpret=True,
                _resident_kv_bytes=_REGIMES[regime], **_MASKS[mask],
            ).astype(jnp.float32)), argnums=(0, 1, 2)))(q, k, k)
        found = _flash_calls(jaxpr.jaxpr)
        assert {n: len(c) for n, c in found.items()} == dict.fromkeys(
            kernels, 1)
        return {n: c[0].params["grid_mapping"] for n, c in found.items()
                }, {n: _index_map_primitives(c[0]) for n, c in found.items()}

    streamed_tables = 2 * (regime == "streamed" and mask != "unmasked")
    rank = 3 if (regime, mask) == ("streamed", "unmasked") else 2
    grids, primitives = calls(4, 4)
    for name in kernels:
        assert len(grids[name].grid) == rank, name
        assert grids[name].grid[0] == 4
        assert grids[name].num_index_operands == streamed_tables, name
        assert primitives[name] <= {"get"}, (name, primitives[name])
        assert bool(primitives[name]) == bool(streamed_tables)
    scratch = {n: g.num_scratch_operands for n, g in grids.items()}
    assert scratch["flash_bwd"] == (3 if regime == "streamed" else 2)

    grouped, primitives = calls(4, 2)
    for name in kernels:
        assert grouped[name].grid == grids[name].grid, name
        assert primitives[name] - {"get"}, name          # b // group
    # dk and dv leave KV heads wide, a whole head an output block
    assert [(out.shape, tuple(edge.block_size for edge in m.block_shape))
            for out, m in zip(
                grouped["flash_bwd"].out_shapes,
                grouped["flash_bwd"].block_mappings_output)] == [
        ((4, 512, 64), (1, 128, 64)), ((2, 512, 64), (1, 512, 64)),
        ((2, 512, 64), (1, 512, 64))]
    assert {n: g.num_scratch_operands for n, g in grouped.items()
            } == scratch


def test_the_wrapper_counts_the_calls_it_traces() -> None:
    q, k = (jnp.zeros((1, 128, h, 32), jnp.bfloat16) for h in (4, 2))

    def trace(k):
        before = _traced_flash_calls()
        jax.make_jaxpr(lambda q, k: flash_attention(
            q, k, k, interpret=True))(q, k)
        return tuple(_traced_flash_calls() - before)

    assert trace(q) == (1, 0)
    assert trace(k) == (1, 1)
