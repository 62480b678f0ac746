"""``ops/kda.py``: the chunked gated delta rule with a decay per key
channel against the recurrence itself, position by position — forward and
every gradient, whatever the chunk. Interpreter-mode Pallas on the CPU, so
the shapes are small. The kernels at the cell's widths are compiled for a
described v5e as well (no chip: the compiler's verdict alone)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.kimi_linear_f32 import kda_recurrence
from torchft_tpu.ops import kda
from torchft_tpu.ops.kda import _choose_chunk, kda_scan


LEAVES = ("dq", "dk", "dv", "dg", "dbeta")


def scan(q, k, v, g, beta, chunk=None):
    """``kda_scan`` at a chunk of the test's choosing: the public function
    takes none (it picks one from the sequence length)."""
    if chunk is None:
        return kda_scan(q, k, v, g, beta)
    return kda._kda(q, k, v, g, beta, chunk, kda._interpret())


def inputs(seed, b, s, h, kd, vd, decay=1.0, common=0.5):
    """Keys with a common component (as a silu's output has), l2-normed;
    log-decays ``-decay·softplus(z)`` a channel; steps in (0, 1)."""
    k = jax.random.split(jax.random.key(seed), 6)
    f32 = jnp.float32

    def l2(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = l2(jax.random.normal(k[0], (b, s, h, kd), f32)) * kd ** -0.5
    key = l2(jax.random.normal(k[1], (b, s, h, kd), f32) + common)
    v = jax.random.normal(k[2], (b, s, h, vd), f32)
    g = -decay * jax.nn.softplus(jax.random.normal(k[3], (b, s, h, kd), f32))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (b, s, h), f32))
    do = jax.random.normal(k[5], (b, s, h, vd), f32)
    return (q, key, v, g, beta), do


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("s,chunk,decay", [
    (40, 8, 1.0),        # bands only, a ragged end
    (64, 16, 1.0),       # one level
    (100, 32, 0.1),      # two levels, slow decays, a ragged end
    (96, 64, 0.3),       # three levels, more chunk than sequence is padded
    (256, 128, 0.3),     # the cell's chunk
    (128, None, 0.02),   # the public function's own choice
])
def test_the_scan_is_the_recurrence_whatever_the_chunk(s, chunk, decay):
    args, do = inputs(s, 2, s, 2, 16, 16, decay)
    with jax.default_matmul_precision("highest"):
        want, pull_ref = jax.vjp(kda_recurrence, *args)
        got, pull = jax.vjp(lambda *a: scan(*a, chunk=chunk), *args)
        grads, grads_ref = pull(do), pull_ref(do)
    assert rel(got, want) < 2e-6
    for name, a, b in zip(LEAVES, grads, grads_ref):
        assert rel(a, b) < 5e-6, name


def test_key_and_value_widths_may_differ() -> None:
    args, do = inputs(3, 1, 48, 3, 16, 32)
    with jax.default_matmul_precision("highest"):
        want, pull_ref = jax.vjp(kda_recurrence, *args)
        got, pull = jax.vjp(lambda *a: scan(*a, chunk=16), *args)
    assert got.shape == (1, 48, 3, 32) and rel(got, want) < 2e-6
    for name, a, b in zip(LEAVES, pull(do), pull_ref(do)):
        assert rel(a, b) < 5e-6, name


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_decay_that_underflows_gives_zero_never_inf_or_nan(chunk) -> None:
    """Half the channels forget everything within a position (``g`` -200:
    ``exp`` is 0 in f32, and ``exp(+200·16)`` would be inf): every
    exponent the kernels take is <= 0, so the result is finite and is the
    recurrence's, gradients too."""
    args, do = inputs(11, 1, 64, 2, 16, 16)
    q, k, v, g, beta = args
    lane = jnp.arange(16) % 2 == 0
    g = jnp.where(lane, -200.0, g)
    g = g.at[:, 20:24].set(-300.0)        # and four positions wipe the state
    args = (q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        want, pull_ref = jax.vjp(kda_recurrence, *args)
        got, pull = jax.vjp(lambda *a: scan(*a, chunk=chunk), *args)
        grads, grads_ref = pull(do), pull_ref(do)
    assert bool(jnp.all(jnp.isfinite(got)))
    # an exponent is a difference of two cumulative sums: its absolute
    # error is 2^-24 of the chunk's total log-decay (here 1.4e4)
    assert rel(got, want) < 2e-4
    for name, a, b in zip(LEAVES, grads, grads_ref):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < 1e-3, name
    # a wiped state: position 24 sees nothing of the positions before 20
    moved = scan(q.at[:, :20].set(0.3), k.at[:, :20].set(0.2),
                 v.at[:, :20].set(5.0), g, beta, chunk=chunk)
    np.testing.assert_allclose(moved[:, 24:], got[:, 24:], atol=1e-6)


def test_no_decay_and_no_step_are_the_plain_rules() -> None:
    """``g = 0, β = 1``: the delta rule without a gate — a key written
    twice is overwritten, not summed; ``β = 0``: nothing is written."""
    (q, k, v, g, beta), _ = inputs(5, 1, 32, 1, 16, 16)
    zero = jnp.zeros_like(g)
    k = k.at[:, 1].set(k[:, 0])                       # the same key twice
    o = scan(k, k, v, zero, jnp.ones_like(beta), chunk=16)
    # reading with the key just written gives the value just written
    np.testing.assert_allclose(o[0, 1, 0], v[0, 1, 0], atol=1e-5)
    nothing = scan(q, k, v, g, jnp.zeros_like(beta), chunk=16)
    assert float(jnp.max(jnp.abs(nothing))) == 0.0


def test_bf16_in_bf16_out_f32_decays() -> None:
    args, do = inputs(7, 1, 64, 2, 16, 16)
    q, k, v, g, beta = args
    bf = jnp.bfloat16
    got, pull = jax.vjp(kda_scan, q.astype(bf), k.astype(bf), v.astype(bf),
                        g, beta)
    grads = pull(do.astype(bf))
    assert got.dtype == bf
    assert [x.dtype for x in grads] == [bf, bf, bf, jnp.float32, jnp.float32]
    rounded = tuple(x.astype(bf).astype(jnp.float32) for x in (q, k, v))
    want = kda_recurrence(*rounded, g, beta)
    assert rel(got.astype(jnp.float32), want) < 5e-3


def test_the_chunk_and_what_is_refused() -> None:
    assert [_choose_chunk(s) for s in (1, 16, 17, 64, 100, 128, 8192)] == [
        16, 16, 32, 64, 128, 128, 128]
    (q, k, v, g, beta), _ = inputs(0, 1, 16, 2, 16, 16)
    with pytest.raises(ValueError, match="do not fit"):
        kda_scan(q, k, v, g[..., :8], beta)
    with pytest.raises(ValueError, match="do not fit"):
        kda_scan(q, k, v, g, beta[:, :8])


# -- several heads a grid step ------------------------------------------------


def both_ways(args, do, chunk):
    """``(o, dq, dk, dv, dg, dβ)`` of ``kda._kda`` at ``chunk``."""
    o, pull = jax.vjp(
        lambda *a: kda._kda(*a, chunk, kda._interpret()), *args)
    return (o,) + pull(do)


@pytest.fixture
def ladder(monkeypatch):
    """Sets the rungs ``ops/kda.py`` may choose from. jit keeps a traced
    body a shape, so whoever moves the ladder clears jax's caches."""
    def to(*rungs):
        monkeypatch.setattr(kda, "_LADDER", rungs)
        jax.clear_caches()
    yield to
    jax.clear_caches()


@pytest.mark.parametrize("heads,b,s,h,chunk", [
    (2, 1, 40, 4, 16),       # a ragged end
    (4, 1, 40, 4, 16),
    (2, 2, 48, 8, 16),       # two batch rows
    (4, 2, 48, 8, 16),
    (2, 1, 256, 4, 128),     # the cell's chunk
    (4, 1, 256, 4, 128),
    (4, 2, 200, 8, 128),     # two rows, a ragged end
    (2, 2, 200, 8, 128),
])
def test_heads_that_share_a_step_change_no_bit(ladder, heads, b, s, h, chunk):
    """A head's mathematics does not know what else its grid step holds:
    ``o`` and the five gradients are the one-head-a-step kernels' bit for
    bit."""
    args, do = inputs(s + h, b, s, h, 16, 32, 0.3)
    ladder(1)
    assert kda._heads_a_step(h, chunk, 16, 32) == 1
    want = both_ways(args, do, chunk)
    ladder(heads)
    assert kda._heads_a_step(h, chunk, 16, 32) == heads
    got = both_ways(args, do, chunk)
    for name, a, w in zip(("o",) + LEAVES, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w), name)


@pytest.mark.parametrize("h", [3, 5, 6])
def test_a_head_count_takes_the_largest_rung_that_divides_it(h) -> None:
    """3 and 5 heads: no rung but 1; 6: two a step, not four. Each is
    the recurrence."""
    args, do = inputs(h, 1, 48, h, 16, 16)
    assert kda._heads_a_step(h, 16, 16, 16) == {3: 1, 5: 1, 6: 2}[h]
    with jax.default_matmul_precision("highest"):
        want, pull_ref = jax.vjp(kda_recurrence, *args)
        got, pull = jax.vjp(lambda *a: scan(*a, chunk=16), *args)
        grads, grads_ref = pull(do), pull_ref(do)
    assert rel(got, want) < 2e-6
    for name, a, b in zip(LEAVES, grads, grads_ref):
        assert rel(a, b) < 5e-6, name


def _grids(fn, *args):
    """``{kernel's name: its grid}`` over the ``pallas_call``s of ``fn``'s
    jaxpr, nested ones too."""
    seen = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                seen[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


def test_the_rule_and_the_grid_at_the_cells_shape() -> None:
    """``kimi-ep32-solo-steady``'s call, ``[4, 8192]`` of 32 heads of 128:
    four heads a step (PERF.md section 6, PR 48), a grid of ``(b, h / 4,
    nc)`` = 2 048 steps a call both ways; the cell's own check (one row of
    2048) takes its heads from the head axis too. Wider heads take fewer
    a step: VMEM."""
    assert kda._LADDER == (4, 2, 1)
    assert kda._heads_a_step(32, 128, 128, 128) == 4
    assert kda._heads_a_step(32, 128, 256, 256) == 4
    assert kda._heads_a_step(32, 128, 512, 512) == 2
    assert kda._heads_a_step(6, 128, 128, 128) == 2
    assert [kda._heads_a_step(h, 16, 16, 16) for h in (1, 2, 3, 4, 5)] == [
        1, 2, 1, 4, 1]

    def shapes(b, s):
        wide = jax.ShapeDtypeStruct((b, s, 32, 128), jnp.bfloat16)
        return (wide, wide, wide,
                jax.ShapeDtypeStruct((b, s, 32, 128), jnp.float32),
                jax.ShapeDtypeStruct((b, s, 32), jnp.float32))

    for b, s in ((4, 8192), (1, 2048)):
        grids = _grids(
            lambda *a: jax.vjp(kda_scan, *a[:-1])[1](a[-1]),
            *shapes(b, s), jax.ShapeDtypeStruct((b, s, 32, 128),
                                                jnp.bfloat16))
        assert grids == {"kda_fwd": (b, 8, s // 128),
                         "kda_bwd": (b, 8, s // 128)}


def test_a_call_site_traces_no_kernel_body_again() -> None:
    """The builders stand under an inner ``jax.jit``: the second layer of
    a shape finds the first's traced body (a body of four heads is four
    times the Python), and the kernels keep their names in the scope
    path the benchmark's reader files them by."""
    args, do = inputs(9, 1, 32, 2, 16, 16)
    calls = []
    plain = kda._kda_fwd_kernel

    def counted(*a, **kw):
        calls.append(1)
        return plain(*a, **kw)

    def two_layers(q, k, v, g, beta):
        v = kda_scan(q, k, v, g, beta)
        return kda_scan(q, k, v, g, beta)

    kda._kda_fwd_kernel = counted
    jax.clear_caches()
    try:
        text = str(jax.make_jaxpr(two_layers)(*args))
    finally:
        kda._kda_fwd_kernel = plain
        jax.clear_caches()
    assert text.count("name=kda_fwd") >= 1 and len(calls) == 1


# -- the kernels at the cell's widths, for a described v5e --------------------


def test_both_kernels_compile_for_the_v5e_at_the_cells_widths(one_chip):
    """[1, 1024] of 32 heads of 128 at the cell's chunk and the cell's
    four heads a grid step: Mosaic takes the rolls, the tile reshapes,
    the transposes, the HIGHEST-precision cumulative sums and the VMEM
    four heads' temporaries ask for (``_VMEM_LIMIT``), and nothing ``[B,
    S, H, K, V]`` is planned (the states are ``S / C`` of them)."""
    from jax.experimental.compilation_cache import compilation_cache

    b, s, h, d = 1, 1024, 32, 128

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide, f32 = sd((b, s, h, d), jnp.bfloat16), jnp.float32

    def both(q, k, v, g, beta, do):
        o, pull = jax.vjp(
            lambda *a: kda._kda(*a, kda._CHUNK, False), q, k, v, g, beta)
        return o, pull(do)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(both).lower(
            wide, wide, wide, sd((b, s, h, d), f32), sd((b, s, h), f32), wide
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert kda._heads_a_step(h, kda._CHUNK, d, d) == 4
    for kernel in ("kda_fwd", "kda_bwd"):
        assert f"{kernel}/pallas_call" in text, kernel
    per_position_states = b * s * h * d * d * 4
    assert compiled.memory_analysis().temp_size_in_bytes < \
        per_position_states / 16
