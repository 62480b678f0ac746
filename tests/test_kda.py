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
from benchmark.reference.olmo_hybrid_f32 import gdn_recurrence
from one_program import pallas_calls, value_and_pullback
from torchft_tpu.ops import kda
from torchft_tpu.ops.kda import _choose_chunk, gdn_scan, kda_scan
from torchft_tpu.utils.metrics import TRACED


LEAVES = ("dq", "dk", "dv", "dg", "dbeta")
# The file runs ONE head a grid step (``conftest.one_head_a_step``): the
# interpreter runs the heads of a step side by side, so a body of four (or,
# for the scalar decay, six) heads is that many times the program to trace
# and compile, and a head's mathematics — what most of these tests are about
# — does not know what else its step holds. That it does not is held bit for
# bit by ``test_heads_that_share_a_step_change_no_bit`` and
# ``test_scalar_decay_heads_that_share_a_step_change_no_bit``, which set
# their own rungs; the tests about the rule itself, the pinned jaxpr and the
# two compiles for the v5e run on the module's own rungs, kept here.
pytestmark = pytest.mark.usefixtures("one_head_a_step")
LADDER, GDN_LADDER = kda._LADDER, kda._GDN_LADDER


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
        want, grads_ref = value_and_pullback(kda_recurrence, args, do)
        got, grads = value_and_pullback(
            lambda *a: scan(*a, chunk=chunk), args, do)
    assert rel(got, want) < 2e-6
    for name, a, b in zip(LEAVES, grads, grads_ref):
        assert rel(a, b) < 5e-6, name


def test_key_and_value_widths_may_differ() -> None:
    args, do = inputs(3, 1, 48, 3, 16, 32)
    with jax.default_matmul_precision("highest"):
        want, grads_ref = value_and_pullback(kda_recurrence, args, do)
        got, grads = value_and_pullback(
            lambda *a: scan(*a, chunk=16), args, do)
    assert got.shape == (1, 48, 3, 32) and rel(got, want) < 2e-6
    for name, a, b in zip(LEAVES, grads, grads_ref):
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
        want, grads_ref = value_and_pullback(kda_recurrence, args, do)
        got, grads = value_and_pullback(
            lambda *a: scan(*a, chunk=chunk), args, do)
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
    o, grads = value_and_pullback(
        lambda *a: kda._kda(*a, chunk, kda._interpret()), args, do)
    return (o,) + grads


@pytest.fixture
def ladder(monkeypatch):
    """Sets the rungs ``ops/kda.py`` may choose from. jit keeps a traced
    body a shape, so whoever moves the ladder clears jax's caches."""
    def to(*rungs):
        monkeypatch.setattr(kda, "_LADDER", rungs)
        jax.clear_caches()
    yield to
    jax.clear_caches()


_ONE_HEAD = {}       # the one-head-a-step results a test below compares with


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
    bit. Crosses, a case: a rung of ``_LADDER`` (2 or 4 heads a step) and
    two groups of heads where ``h`` is twice the rung; at a chunk of 16
    two chunk boundaries and a ragged end (40 of 48) or, with two batch
    rows, three whole chunks; at the cell's chunk of 128 one boundary (256:
    the least without padding) or a ragged end over two rows (200 of 256).
    Each shape is compiled at one head a step once, for both of its
    rungs."""
    args, do = inputs(s + h, b, s, h, 16, 32, 0.3)
    assert kda._heads_a_step(h, chunk, 16, 32) == 1     # the file's rung
    if (b, s, h, chunk) not in _ONE_HEAD:       # a shape's two rungs: once
        _ONE_HEAD[b, s, h, chunk] = both_ways(args, do, chunk)
    want = _ONE_HEAD[b, s, h, chunk]
    ladder(heads)
    assert kda._heads_a_step(h, chunk, 16, 32) == heads
    got = both_ways(args, do, chunk)
    for name, a, w in zip(("o",) + LEAVES, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w), name)


@pytest.mark.parametrize("h", [3, 5, 6])
def test_a_head_count_takes_the_largest_rung_that_divides_it(ladder, h):
    """3 and 5 heads: no rung but 1; 6: two a step, not four. Each is
    the recurrence. Crosses: the module's own rungs, and two chunk
    boundaries (48 positions at a chunk of 16)."""
    ladder(*LADDER)
    args, do = inputs(h, 1, 48, h, 16, 16)
    assert kda._heads_a_step(h, 16, 16, 16) == {3: 1, 5: 1, 6: 2}[h]
    with jax.default_matmul_precision("highest"):
        want, grads_ref = value_and_pullback(kda_recurrence, args, do)
        got, grads = value_and_pullback(
            lambda *a: scan(*a, chunk=16), args, do)
    assert rel(got, want) < 2e-6
    for name, a, b in zip(LEAVES, grads, grads_ref):
        assert rel(a, b) < 5e-6, name


def _grids(fn, *args):
    """``{kernel's name: its grid}`` over the ``pallas_call``s of ``fn``'s
    jaxpr, nested ones too."""
    return {name: tuple(eqn.params["grid_mapping"].grid)
            for name, eqn in pallas_calls(fn, *args).items()}


def test_the_rule_and_the_grid_at_the_cells_shape(ladder) -> None:
    """``kimi-ep32-solo-steady``'s call, ``[4, 8192]`` of 32 heads of 128:
    four heads a step (PERF.md section 6, PR 48), a grid of ``(b, h / 4,
    nc)`` = 2 048 steps a call both ways; the cell's own check (one row of
    2048) takes its heads from the head axis too. Wider heads take fewer
    a step: VMEM."""
    assert LADDER == (4, 2, 1)
    ladder(*LADDER)
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


# -- one decay a head: the scalar-decay kernels (Gated DeltaNet) -------------


def gdn_inputs(seed, b, s, h, kd, vd, decay=1.0):
    """As :func:`inputs`, with ONE log-decay a head a position and steps
    over (0, 2)."""
    (q, key, v, _, _), do = inputs(seed, b, s, h, kd, vd)
    k = jax.random.split(jax.random.key(1000 + seed), 2)
    g = -decay * jax.nn.softplus(jax.random.normal(k[0], (b, s, h)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(k[1], (b, s, h)))
    return (q, key, v, g, beta), do


def gdn(q, k, v, g, beta, chunk=None):
    if chunk is None:
        return gdn_scan(q, k, v, g, beta)
    return kda._gdn(q, k, v, g, beta, chunk, kda._interpret())


def broadcast(q, k, v, g, beta, chunk):
    """``kda_scan``'s kernels fed the scalar decay a channel."""
    return kda._kda(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta,
                    chunk, kda._interpret())


@pytest.mark.parametrize("b,s,h,kd,vd,chunk,decay", [
    (2, 40, 6, 16, 32, 8, 1.0),       # a ragged end, two batch rows
    (1, 100, 6, 24, 48, 32, 0.1),     # slow decays, a ragged end
    (1, 256, 6, 96, 192, 128, 0.3),   # the cell's widths and chunk
    (1, 192, 6, 64, 128, 64, 0.3),    # 64 / 128
    (1, 48, 30, 12, 24, 16, 0.05),    # the cell's head count
    (1, 128, 3, 96, 192, None, 0.02),  # the public function's own choice
])
def test_the_scalar_decay_scan_is_the_recurrence_and_the_broadcast(
        b, s, h, kd, vd, chunk, decay):
    """Each case crosses at least one chunk boundary at its chunk (40 / 8,
    100 / 32, 256 / 128, 192 / 64, 48 / 16, 128 at the rule's own choice),
    two of them with a ragged end; the widths are the cases' subject (12 /
    24 up to the cell's 96 / 192, key and value unequal), and so is the
    cell's head count of 30. One head a grid step (the file's rung): the
    steps that heads share are the next test but two's."""
    args, do = gdn_inputs(s, b, s, h, kd, vd, decay)
    with jax.default_matmul_precision("highest"):
        want, grads_ref = value_and_pullback(gdn_recurrence, args, do)
        got, grads = value_and_pullback(
            lambda *a: gdn(*a, chunk=chunk), args, do)
        other, grads_other = value_and_pullback(
            lambda *a: broadcast(*a, chunk or _choose_chunk(s)), args, do)
    assert got.shape == (b, s, h, vd) and rel(got, want) < 2e-6
    assert rel(got, other) < 3e-6
    assert float(jnp.max(args[4])) > 1.0     # the negative-eigenvalue branch
    for name, a, ref, via in zip(LEAVES, grads, grads_ref, grads_other):
        assert a.shape == ref.shape, name
        assert rel(a, ref) < 5e-6, name
        assert rel(a, via) < 5e-6, name


def test_each_scan_refuses_the_others_decay() -> None:
    """A decay of rank three is ``gdn_scan``'s, one of rank four
    ``kda_scan``'s: each refuses the other's, and operands that do not
    fit, with a message."""
    args, _ = gdn_inputs(5, 1, 32, 2, 8, 16)
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="kda_scan"):
        kda_scan(*args)
    three = [jnp.concatenate([z, z[:, :, :1]], axis=2) for z in (v, g, beta)]
    for bad in ((q, k, v, g[:, :8], beta), (q, k, v, g, beta[..., :1]),
                (q, k[..., :4], v, g, beta), (q, k, v[:, :8], g, beta),
                (q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta),
                # two key heads do not divide three value heads; the decay
                # and the step stand at the VALUE heads; k at q's
                (q, k, *three), (q[:, :, :1], k, v, g, beta),
                (q[:, :, :1], k[:, :, :1], v, g[..., :1], beta)):
        with pytest.raises(ValueError, match="gdn_scan.*do not fit"):
            gdn_scan(*bad)


@pytest.mark.parametrize("contract", [((1,), (0,)), kda._NT, ((0,), (0,))],
                         ids=["ab", "abT", "aTb"])
def test_the_three_pass_matmul_is_an_f32_matmul_to_2e_minus_5(contract):
    """``_gdot`` as the chip runs it (the interpreter's kernels multiply
    in f32): ``hi·hi + hi·lo + lo·hi`` of f32 operands split in two bf16
    halves against the f32 product — 2^-16 of ``|a|·|b|`` where ONE bf16
    pass, the channel-wise kernels' ``_dot``, is off by 2^-8."""
    a, b = (jax.random.normal(jax.random.key(i), (128, 128), jnp.float32)
            for i in (1, 2))
    want = jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST)
    scale = jax.lax.dot_general(jnp.abs(a), jnp.abs(b), (contract, ((), ())),
                                precision=jax.lax.Precision.HIGHEST)
    three = float(jnp.max(
        jnp.abs(kda._gdot(False, a, b, contract) - want) / scale))
    bf = jnp.bfloat16
    one = float(jnp.max(jnp.abs(
        kda._dot(a.astype(bf), b.astype(bf), contract) - want) / scale))
    assert three < 2e-5 < 1e-3 < one * 4, (three, one)
    # operands that are exact in bf16 lose nothing
    exact = kda._gdot(False, kda._f32(a.astype(bf)), kda._f32(b.astype(bf)),
                      contract)
    assert float(jnp.max(jnp.abs(exact - jax.lax.dot_general(
        kda._f32(a.astype(bf)), kda._f32(b.astype(bf)), (contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST)) / scale)) < 1e-6


def test_the_scalar_decay_scan_in_bf16_and_a_decay_that_underflows() -> None:
    (q, k, v, g, beta), do = gdn_inputs(11, 1, 64, 3, 24, 48)
    bf = jnp.bfloat16
    got, pull = jax.vjp(
        lambda *a: gdn(*a, chunk=16), q.astype(bf), k.astype(bf),
        v.astype(bf), g, beta)
    grads = pull(do.astype(bf))
    assert got.dtype == bf
    assert [x.dtype for x in grads] == [bf, bf, bf, jnp.float32, jnp.float32]
    rounded = tuple(x.astype(bf).astype(jnp.float32) for x in (q, k, v))
    assert rel(got.astype(jnp.float32),
               gdn_recurrence(*rounded, g, beta)) < 5e-3
    # a chunk that forgets by e^-12800: 0, never inf or nan
    hard = jnp.full_like(g, -200.0)
    out, pull = jax.vjp(lambda *a: gdn(*a, chunk=64), q, k, v, hard, beta)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in (out,) + pull(do))
    with jax.default_matmul_precision("highest"):
        assert rel(out, gdn_recurrence(q, k, v, hard, beta)) < 2e-6


@pytest.mark.parametrize("h,kd,vd,want", [
    (30, 96, 192, 4),     # the cell: 4 x 96 = 3 tiles, 4 x 192 = 6; 7.5 groups
    (32, 96, 192, 4),
    (30, 128, 128, 6), (32, 128, 128, 4), (7, 128, 128, 1), (10, 128, 128, 5),
    (30, 64, 128, 6), (9, 64, 128, 2),    # two heads of 64 are a tile
    (30, 128, 1024, 1),   # a step too large for the limit: a smaller group
])
def test_the_scalar_decay_grid_takes_any_head_count(monkeypatch, h, kd, vd,
                                                     want) -> None:
    """A group is whole lane tiles on the TPU (of 96 / 192 only four heads
    are) and need not divide the heads: the rung that leaves the fewest
    heads of the last group outside the arrays, the largest of those.
    (The module's own rungs; nothing is traced, so no cache is cleared.)"""
    monkeypatch.setattr(kda, "_GDN_LADDER", GDN_LADDER)
    assert kda._gdn_heads_a_step(h, 128, kd, vd, False) == want


def test_the_scalar_decay_grid_refuses_widths_no_group_tiles(
        monkeypatch) -> None:
    monkeypatch.setattr(kda, "_GDN_LADDER", GDN_LADDER)
    with pytest.raises(ValueError, match="gdn_scan: no group.*100 key"):
        kda._gdn_heads_a_step(30, 128, 100, 192, False)
    # the interpreter takes any width, and the group that wastes least
    assert kda._gdn_heads_a_step(30, 16, 12, 24, True) == 6
    assert kda._gdn_heads_a_step(7, 16, 12, 24, True) == 1


@pytest.fixture
def gdn_ladder(monkeypatch):
    def to(*rungs):
        monkeypatch.setattr(kda, "_GDN_LADDER", rungs)
        jax.clear_caches()
    yield to
    jax.clear_caches()


@pytest.mark.parametrize("heads", [2, 3, 4, 5, 6])
def test_scalar_decay_heads_that_share_a_step_change_no_bit(
        gdn_ladder, heads) -> None:
    """Four and five do not divide six: the last group's missing heads
    lie outside the arrays (an edge block), as two of the cell's 32 head
    places do, and change nothing in the six that are there."""
    args, do = gdn_inputs(21, 2, 40, 6, 16, 32)

    def all_six(chunk=16):
        o, grads = value_and_pullback(
            lambda *a: gdn(*a, chunk=chunk), args, do)
        return (o,) + grads

    if "gdn" not in _ONE_HEAD:         # the file's one head a step, once
        _ONE_HEAD["gdn"] = all_six()
    want = _ONE_HEAD["gdn"]
    gdn_ladder(heads)
    for name, a, b in zip(("o",) + LEAVES, all_six(), want):
        np.testing.assert_array_equal(a, b, err_msg=name)


# -- more value heads than key heads: q and k read where they lie -------------


def _copied(z, r):
    return jnp.repeat(z, r, axis=2)


def _key_head_sum(z, r):
    """``[B, S, H_k·r, K]`` -> the f32 sum over a key head's ``r`` value
    heads."""
    b, s, h, kd = z.shape
    return z.reshape(b, s, h // r, r, kd).sum(axis=3)


@pytest.mark.parametrize("hk,hv,rung", [
    (2, 4, 4),       # two key heads a step, each read by two value heads
    (1, 4, 4),       # one key head, four value heads: r = 4
    (3, 6, 4),       # two steps; the second holds a key head outside q
    (2, 2, 2),       # r = 1: the program of equal heads, two a step
], ids=lambda n: str(n))
def test_value_head_h_reads_key_head_h_over_r_where_it_lies(
        gdn_ladder, hk, hv, rung) -> None:
    """``gdn_scan`` handed q and k at the KEY heads against itself on
    ``jnp.repeat``-ed copies, at the same rung: ``o``, ``dv``, ``dg``,
    ``dβ`` bit for bit (a value head's chain is the same instructions on
    the same numbers); ``dq`` and ``dk`` are the sum of the copied call's
    over a key head's value heads, added in f32 inside the kernel; and all
    six leaves are the recurrence's. Crosses: key and value widths that
    differ (16 / 32), 40 positions at a chunk of 16 (two boundaries and a
    ragged end), two batch rows, and with six value heads at four a step a
    last group whose second key head lies outside the arrays."""
    gdn_ladder(rung)
    r = hv // hk
    (q, k, v, g, beta), do = gdn_inputs(31, 2, 40, hv, 16, 32)
    q, k = q[:, :, ::r], k[:, :, ::r]
    assert kda._gdn_heads_a_step(hv, 16, 16, 32, True, r) == rung
    before = TRACED.snapshot().get("gdn_value_group_copies", 0)
    with jax.default_matmul_precision("highest"):
        got, grads = value_and_pullback(
            lambda *a: gdn(*a, chunk=16), (q, k, v, g, beta), do)
        copied = (_copied(q, r), _copied(k, r), v, g, beta)
        same, grads_same = value_and_pullback(
            lambda *a: gdn(*a, chunk=16), copied, do)
        want, grads_ref = value_and_pullback(gdn_recurrence, copied, do)
    assert TRACED.snapshot().get("gdn_value_group_copies", 0) == before
    np.testing.assert_array_equal(got, same, err_msg="o")
    assert rel(got, want) < 2e-6
    for name, a, b, ref in zip(LEAVES, grads, grads_same, grads_ref):
        if name in ("dq", "dk"):
            assert a.shape == q.shape, name
            b, ref = _key_head_sum(b, r), _key_head_sum(ref, r)
            assert rel(a, b) < 1e-6, name
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
        assert rel(a, ref) < 5e-6, name


def _gdn_calls(fn, *args):
    """``{kernel's name: (its grid, the lanes of its q block, the lanes
    of q)}`` over the ``pallas_call``s of ``fn``'s jaxpr."""
    def of(eqn):
        mapping = eqn.params["grid_mapping"]
        return (tuple(mapping.grid),
                mapping.block_mappings[0].block_shape[-1].block_size,
                eqn.invars[0].aval.shape[-1])
    return {name: of(eqn) for name, eqn in pallas_calls(fn, *args).items()}


def _gdn_shapes(b, s, hk, hv, kd, vd):
    keys = jax.ShapeDtypeStruct((b, s, hk, kd), jnp.bfloat16)
    values = jax.ShapeDtypeStruct((b, s, hv, vd), jnp.bfloat16)
    scalar = jax.ShapeDtypeStruct((b, s, hv), jnp.float32)
    return keys, keys, values, scalar, scalar, values


def _both_gdn_kernels(*a):
    return jax.vjp(gdn_scan, *a[:-1])[1](a[-1])


def test_the_head_group_rule_with_value_heads_a_key_head(
        monkeypatch, gdn_ladder) -> None:
    """``qwen3next-ep16-solo-steady``'s call, ``[4, 8192]`` of 16 key and
    32 value heads of 128, as the TPU takes it: four VALUE heads a step
    are two key heads — 256 lanes of q and of k beside 512 of v —, the
    grid ``(4, 8, 64)`` it had on copied operands, and q, k (and ``dq``,
    ``dk``) ``H_k·K`` = 2 048 wide: nothing ``H_v·K`` wide goes in. A rung
    is whole key heads whose lanes are whole tiles; where none is, the op
    copies q and k itself, counts it and runs at equal heads."""
    gdn_ladder(*GDN_LADDER)
    monkeypatch.setattr(kda, "_interpret", lambda: False)
    assert kda._gdn_heads_a_step(32, 128, 128, 128, False, 2) == 4
    assert kda._gdn_rungs(128, 128, 128, False, 2) == [6, 4, 2]
    assert kda._gdn_rungs(128, 128, 128, False, 4) == [4]
    assert kda._gdn_rungs(128, 64, 128, False, 2) == [4]    # 2 x 64 a tile
    assert kda._gdn_rungs(128, 64, 128, False, 3) == [6]
    assert kda._gdn_rungs(128, 128, 128, False, 1) == list(GDN_LADDER)

    def copies():
        return TRACED.snapshot().get("gdn_value_group_copies", 0)

    before = copies()
    calls = _gdn_calls(_both_gdn_kernels, *_gdn_shapes(4, 8192, 16, 32, 128,
                                                       128))
    assert calls == {"gdn_fwd": ((4, 8, 64), 256, 2048),
                     "gdn_bwd": ((4, 8, 64), 256, 2048)}
    assert copies() == before
    # no rung is a multiple of 7; of 96-wide key heads read by two value
    # heads each only 4 key heads = 8 value heads are whole tiles
    for hk, hv, kd, vd, at in ((1, 7, 128, 128, 1), (15, 30, 96, 192, 4)):
        assert kda._gdn_rungs(128, kd, vd, False, hv // hk) == []
        before = copies()
        calls = _gdn_calls(_both_gdn_kernels,
                           *_gdn_shapes(1, 256, hk, hv, kd, vd))
        assert copies() == before + 1
        steps = (1, -(-hv // at), 2)
        assert calls == {"gdn_fwd": (steps, at * kd, hv * kd),
                         "gdn_bwd": (steps, at * kd, hv * kd)}


def test_a_group_no_rung_fits_is_copied_and_counted(gdn_ladder) -> None:
    """Seven value heads on one key head: no rung is a multiple of seven,
    in the interpreter either. The op copies q and k, says so, and is
    the recurrence; ``dq`` and ``dk`` come back at the key head."""
    gdn_ladder(*GDN_LADDER)
    (q, k, v, g, beta), do = gdn_inputs(33, 1, 24, 7, 8, 16)
    q, k = q[:, :, :1], k[:, :, :1]
    before = TRACED.snapshot().get("gdn_value_group_copies", 0)
    with jax.default_matmul_precision("highest"):
        got, grads = value_and_pullback(gdn_scan, (q, k, v, g, beta), do)
        want, grads_ref = value_and_pullback(
            lambda q, k, *rest: gdn_recurrence(
                _copied(q, 7), _copied(k, 7), *rest), (q, k, v, g, beta), do)
    assert TRACED.snapshot().get("gdn_value_group_copies", 0) == before + 1
    assert rel(got, want) < 2e-6
    for name, a, ref in zip(LEAVES, grads, grads_ref):
        assert a.shape == ref.shape and rel(a, ref) < 5e-6, name


# sha256 of the jaxpr (source positions cut out) of the gradient of
# ``kda_scan`` at Kimi's call ([4, 8192] of 32 heads of 128, bf16 q / k /
# v, f32 g a channel and β): both kernels, their grids and every
# instruction. Pinned at the commit before the scalar-decay path entered
# the file (6762fd9): a change that moves it moves what
# ``kimi-ep32-solo-steady`` runs; regenerate on purpose only.
_KIMI_CALL_JAXPR = \
    "0ccd965ce1f92ce6410b312c1761048ab2d2269b6d5745972acd5e4fb89fdec7"


def test_kimis_call_traces_to_the_kernels_it_traced_to(ladder) -> None:
    import hashlib
    import re

    ladder(*LADDER)

    q = jax.ShapeDtypeStruct((4, 8192, 32, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((4, 8192, 32, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((4, 8192, 32), jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(kda_scan(q, k, v, g, beta).astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        q, q, q, g, beta))
    text = re.sub(r"/[^ ]*?\.py:\d+", "", text)
    assert "name=kda_fwd" in text and "name=kda_bwd" in text
    assert "name=gdn_" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _KIMI_CALL_JAXPR


# The same of ``gdn_scan`` at Olmo Hybrid's call ([1, 8192] of 30 heads of
# 96 key and 192 value channels, as many key as value heads): as the CPU
# traces it (the interpreter: f32 matmuls, six heads a step) and as the TPU
# does (three-pass matmuls, four heads a step). Taken at the commit before
# a value head could read another head's q and k (65e11e1, PR 64): with as
# many key as value heads the kernels are the program they were — what
# ``olmohybrid-vp8-solo-steady`` and both cells' scan comparisons run.
_OLMO_HYBRID_CALL_JAXPR = {
    True: "22c61242f72f5fc8782098bb154d63a93eda46c0637fc1be24bfb65669518bb4",
    False: "dc93918ace149788e4c57696eb15617da609909def44acad9964bc5468d2159b",
}


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["as_the_cpu_traces_it", "as_the_tpu_does"])
def test_olmo_hybrids_call_traces_to_the_kernels_it_traced_to(
        monkeypatch, gdn_ladder, interpret) -> None:
    import hashlib
    import re

    gdn_ladder(*GDN_LADDER)
    monkeypatch.setattr(kda, "_interpret", lambda: interpret)
    keys = jax.ShapeDtypeStruct((1, 8192, 30, 96), jnp.bfloat16)
    values = jax.ShapeDtypeStruct((1, 8192, 30, 192), jnp.bfloat16)
    scalar = jax.ShapeDtypeStruct((1, 8192, 30), jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(gdn_scan(q, k, v, g, beta).astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        keys, keys, values, scalar, scalar))
    text = re.sub(r"/[^ ]*?\.py:\d+", "", text)
    assert "name=gdn_fwd" in text and "name=gdn_bwd" in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _OLMO_HYBRID_CALL_JAXPR[interpret]


# -- the kernels at the cell's widths, for a described v5e --------------------


def test_both_kernels_compile_for_the_v5e_at_the_cells_widths(one_chip,
                                                              ladder):
    """[1, 1024] of 32 heads of 128 at the cell's chunk and the cell's
    four heads a grid step: Mosaic takes the rolls, the tile reshapes,
    the transposes, the HIGHEST-precision cumulative sums and the VMEM
    four heads' temporaries ask for (``_VMEM_LIMIT``), and nothing ``[B,
    S, H, K, V]`` is planned (the states are ``S / C`` of them)."""
    from jax.experimental.compilation_cache import compilation_cache

    ladder(*LADDER)
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


def test_the_scalar_decay_kernels_compile_for_the_v5e_at_the_cells_widths(
        one_chip, gdn_ladder):
    """[1, 1024] of 30 heads of 96 key and 192 value channels at the
    cell's chunk and four heads a grid step: Mosaic takes the lane slices
    that start between tiles, the matmuls 96 and 192 wide, the edge block
    of the eighth group, the ``[1, 1]`` decays, the transposes and the
    VMEM four heads' temporaries ask for; no head is padded in HBM (no
    operand 30 x 128 or 30 x 256 wide), ``g`` stands nowhere a channel
    (no f32 ``[B, S, H·K]`` operand), and nothing ``[B, S, H, K, V]`` is
    planned."""
    from jax.experimental.compilation_cache import compilation_cache

    gdn_ladder(*GDN_LADDER)
    b, s, h, kd, vd = 1, 1024, 30, 96, 192

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    keys, values = (sd((b, s, h, d), jnp.bfloat16) for d in (kd, vd))
    scalar = sd((b, s, h), jnp.float32)

    def both(q, k, v, g, beta, do):
        o, pull = jax.vjp(
            lambda *a: kda._gdn(*a, kda._CHUNK, False), q, k, v, g, beta)
        return o, pull(do)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(both).lower(
            keys, keys, values, scalar, scalar, values).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert kda._gdn_heads_a_step(h, kda._CHUNK, kd, vd, False) == 4
    for kernel in ("gdn_fwd", "gdn_bwd"):
        assert f"{kernel}/pallas_call" in text, kernel
    for wide in (h * kd, h * 128, h * 256):
        assert f"f32[{b},{s},{wide}]" not in text
    assert f"bf16[{b},{s},{h * 128}]" not in text
    assert f"bf16[{b},{s},{h * 256}]" not in text
    per_position_states = b * s * h * kd * vd * 4
    assert compiled.memory_analysis().temp_size_in_bytes < \
        per_position_states / 8


def test_the_grouped_kernels_compile_for_the_v5e_at_qwen3_nexts_widths(
        one_chip, gdn_ladder):
    """[1, 1024] of 16 key and 32 value heads of 128 at the cell's chunk:
    Mosaic takes a step of four value heads on two key heads (256 lanes
    of q and k beside 512 of v), and ``dq`` and ``dk`` come back at the
    key heads."""
    from jax.experimental.compilation_cache import compilation_cache

    gdn_ladder(*GDN_LADDER)
    b, s, hk, h, d = 1, 1024, 16, 32, 128

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    keys, values = (sd((b, s, n, d), jnp.bfloat16) for n in (hk, h))
    scalar = sd((b, s, h), jnp.float32)

    def both(q, k, v, g, beta, do):
        o, pull = jax.vjp(
            lambda *a: kda._gdn(*a, kda._CHUNK, False), q, k, v, g, beta)
        return o, pull(do)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered = jax.jit(both).lower(
            keys, keys, values, scalar, scalar, values)
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert kda._gdn_heads_a_step(h, kda._CHUNK, d, d, False, h // hk) == 4
    for kernel in ("gdn_fwd", "gdn_bwd"):
        assert f"{kernel}/pallas_call" in text, kernel
    dq, dk = compiled.out_info[1][:2]
    assert dq.shape == dk.shape == (b, s, hk, d)
    assert compiled.memory_analysis().temp_size_in_bytes < \
        b * s * h * d * d * 4 / 8
