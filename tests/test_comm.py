"""Tests for the CommContext layer (spec: ref process_group_test.py —
the `_test_pg` collective sweep at :63-111, reconfigure behavior :216-250,
error latching :379-403)."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu.comm import (
    DummyCommContext,
    ErrorSwallowingCommContext,
    ReduceOp,
    StoreServer,
    TcpCommContext,
)


@pytest.fixture()
def store():
    server = StoreServer()
    yield server
    server.shutdown()


def _run_ranks(store, world_size, fn, prefix="q0", timeout=20.0):
    """Run fn(ctx, rank) on `world_size` TcpCommContexts on threads."""
    ctxs = [TcpCommContext(timeout=10.0) for _ in range(world_size)]
    results = [None] * world_size

    def _worker(rank):
        ctx = ctxs[rank]
        ctx.configure(f"{store.addr}/{prefix}", rank, world_size)
        results[rank] = fn(ctx, rank)

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        futs = [pool.submit(_worker, r) for r in range(world_size)]
        for f in futs:
            f.result(timeout=timeout)
    for ctx in ctxs:
        ctx.shutdown()
    return results


def test_idle_lane_lets_go_of_its_last_op(store) -> None:
    # A lane thread blocked on its queue must not pin the op it ran last:
    # the op's future carries the caller's continuations, which reach the
    # caller's buffers (for DDP, that step's gradients on the device).
    import gc
    import weakref

    class Payload:
        pass

    def _fn(ctx, rank):
        payload = Payload()
        alive = weakref.ref(payload)
        fut = ctx.allreduce([np.ones(4, np.float32)]).future()
        fut.add_done_callback(lambda _f, _p=payload: None)
        fut.result(timeout=10)
        del fut, payload
        # the lane is idle again once this rank has its result; give its
        # loop the moment it needs to come back around to its queue
        time.sleep(0.2)
        gc.collect()
        return alive() is None

    assert _run_ranks(store, 2, _fn) == [True, True]


@pytest.mark.parametrize("world_size", [1, 2, 4])
def test_allreduce_sum(store, world_size) -> None:
    def _fn(ctx, rank):
        a = np.full((3, 4), float(rank + 1), dtype=np.float32)
        b = np.arange(5, dtype=np.float64) * (rank + 1)
        work = ctx.allreduce([a, b], op=ReduceOp.SUM)
        return work.future().result(timeout=10)

    results = _run_ranks(store, world_size, _fn)
    expected_a = np.full((3, 4), sum(range(1, world_size + 1)), np.float32)
    expected_b = np.arange(5, dtype=np.float64) * sum(range(1, world_size + 1))
    for res in results:
        np.testing.assert_allclose(res[0], expected_a)
        np.testing.assert_allclose(res[1], expected_b)


def test_allreduce_avg_and_max(store) -> None:
    def _fn(ctx, rank):
        avg = ctx.allreduce(
            [np.full(4, float(rank), np.float32)], op=ReduceOp.AVG
        ).future().result(timeout=10)
        mx = ctx.allreduce(
            [np.array([rank, -rank], np.int64)], op=ReduceOp.MAX
        ).future().result(timeout=10)
        return avg, mx

    for avg, mx in _run_ranks(store, 3, _fn):
        np.testing.assert_allclose(avg[0], np.full(4, 1.0, np.float32))
        np.testing.assert_array_equal(mx[0], np.array([2, 0]))


def test_broadcast(store) -> None:
    def _fn(ctx, rank):
        data = np.full(6, float(rank * 100 + 7), np.float32)
        return ctx.broadcast([data], root=1).future().result(timeout=10)

    for res in _run_ranks(store, 3, _fn):
        np.testing.assert_allclose(res[0], np.full(6, 107.0, np.float32))


def test_allgather(store) -> None:
    def _fn(ctx, rank):
        # different shapes per rank exercises the metadata path
        data = np.arange(rank + 1, dtype=np.int32)
        return ctx.allgather([data]).future().result(timeout=10)

    for res in _run_ranks(store, 3, _fn):
        assert len(res) == 3
        for r in range(3):
            np.testing.assert_array_equal(res[r][0], np.arange(r + 1))


def test_multiple_sequential_ops(store) -> None:
    def _fn(ctx, rank):
        outs = []
        for i in range(5):
            w = ctx.allreduce([np.full(2, float(i + rank), np.float32)])
            outs.append(w)
        return [w.future().result(timeout=10)[0][0] for w in outs]

    res = _run_ranks(store, 2, _fn)
    assert res[0] == [2 * i + 1 for i in range(5)]
    assert res[0] == res[1]


def test_reconfigure_new_quorum(store) -> None:
    # Same contexts reconfigured under a new prefix with fewer ranks
    # (the per-quorum reconfiguration path, ref manager.py:470-477).
    ctx0 = TcpCommContext(timeout=10.0)
    ctx1 = TcpCommContext(timeout=10.0)
    with ThreadPoolExecutor(max_workers=2) as pool:
        f0 = pool.submit(ctx0.configure, f"{store.addr}/q1", 0, 2)
        f1 = pool.submit(ctx1.configure, f"{store.addr}/q1", 1, 2)
        f0.result(timeout=10)
        f1.result(timeout=10)
        r = ctx0.allreduce([np.ones(2, np.float32)]).future()
        r2 = ctx1.allreduce([np.ones(2, np.float32)]).future()
        np.testing.assert_allclose(r.result(10)[0], np.full(2, 2.0))
        r2.result(10)

    # rank 1 dies; survivor reconfigures to world_size=1
    ctx1.shutdown()
    ctx0.configure(f"{store.addr}/q2", 0, 1)
    out = ctx0.allreduce([np.ones(3, np.float32)]).future().result(timeout=10)
    np.testing.assert_allclose(out[0], np.ones(3))
    ctx0.shutdown()


def test_peer_death_fails_op_and_latches(store) -> None:
    ctx0 = TcpCommContext(timeout=5.0)
    ctx1 = TcpCommContext(timeout=5.0)
    with ThreadPoolExecutor(max_workers=2) as pool:
        f0 = pool.submit(ctx0.configure, f"{store.addr}/qx", 0, 2)
        f1 = pool.submit(ctx1.configure, f"{store.addr}/qx", 1, 2)
        f0.result(timeout=10)
        f1.result(timeout=10)

    ctx1.shutdown()  # peer vanishes
    work = ctx0.allreduce([np.ones(4, np.float32)])
    with pytest.raises((ConnectionError, OSError)):
        work.future().result(timeout=10)
    assert ctx0.errored() is not None
    # subsequent ops fail fast
    with pytest.raises((ConnectionError, OSError)):
        ctx0.allreduce([np.ones(4)]).future().result(timeout=10)
    # reconfigure clears the latch
    ctx0.configure(f"{store.addr}/qy", 0, 1)
    assert ctx0.errored() is None
    ctx0.shutdown()


def test_configure_timeout_when_peer_missing(store) -> None:
    ctx = TcpCommContext(timeout=0.3)
    with pytest.raises(TimeoutError):
        ctx.configure(f"{store.addr}/lonely", 0, 2)
    ctx.shutdown()


def test_dummy_context() -> None:
    ctx = DummyCommContext()
    ctx.configure("ignored", 0, 1)
    arrays = [np.arange(4, dtype=np.float32)]
    out = ctx.allreduce(arrays).future().result(timeout=1)
    np.testing.assert_array_equal(out[0], arrays[0])
    assert ctx.size() == 1
    assert ctx.configure_count == 1


def test_error_swallowing_wrapper(store) -> None:
    inner0 = TcpCommContext(timeout=5.0)
    inner1 = TcpCommContext(timeout=5.0)
    wrapped = ErrorSwallowingCommContext(inner0)
    with ThreadPoolExecutor(max_workers=2) as pool:
        f0 = pool.submit(wrapped.configure, f"{store.addr}/es", 0, 2)
        f1 = pool.submit(inner1.configure, f"{store.addr}/es", 1, 2)
        f0.result(timeout=10)
        f1.result(timeout=10)

    # healthy op passes through
    w = wrapped.allreduce([np.ones(2, np.float32)])
    w2 = inner1.allreduce([np.ones(2, np.float32)])
    np.testing.assert_allclose(w.future().result(10)[0], np.full(2, 2.0))
    w2.future().result(10)
    assert wrapped.errored() is None

    # peer dies: wrapped op completes with identity instead of raising,
    # and the error is latched (ref process_group.py:408-501)
    inner1.shutdown()
    arrays = [np.full(2, 5.0, np.float32)]
    out = wrapped.allreduce(arrays).future().result(timeout=10)
    np.testing.assert_array_equal(out[0], arrays[0])
    assert wrapped.errored() is not None

    # later ops short-circuit to identity until reconfigure
    out = wrapped.allreduce([np.full(3, 2.0)]).future().result(timeout=1)
    np.testing.assert_array_equal(out[0], np.full(3, 2.0))
    wrapped.shutdown()


def test_large_buffer_allreduce(store) -> None:
    # ~32 MB per rank exercises chunked socket IO.
    def _fn(ctx, rank):
        data = np.full(8 << 20, float(rank + 1), dtype=np.float32)
        return ctx.allreduce([data]).future().result(timeout=30)

    results = _run_ranks(store, 2, _fn, timeout=60.0)
    np.testing.assert_allclose(results[0][0][:10], np.full(10, 3.0))
    np.testing.assert_allclose(results[1][0][-10:], np.full(10, 3.0))


# ------------------------------------------------------------- ring variant


def _run_ring(store, world_size, fn, prefix="ring", timeout=30.0):
    ctxs = [TcpCommContext(timeout=10.0, algorithm="ring")
            for _ in range(world_size)]
    results = [None] * world_size

    def _worker(rank):
        ctxs[rank].configure(f"{store.addr}/{prefix}", rank, world_size)
        results[rank] = fn(ctxs[rank], rank)

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        futs = [pool.submit(_worker, r) for r in range(world_size)]
        for f in futs:
            f.result(timeout=timeout)
    for ctx in ctxs:
        ctx.shutdown()
    return results


@pytest.mark.parametrize("world_size", [2, 3, 4])
def test_ring_allreduce_matches_star(store, world_size) -> None:
    def _fn(ctx, rank):
        a = np.arange(10, dtype=np.float32) * (rank + 1)
        b = np.full((3, 5), float(rank), dtype=np.float64)
        return ctx.allreduce([a, b]).future().result(timeout=15)

    results = _run_ring(store, world_size, _fn)
    total = sum(range(1, world_size + 1))
    for res in results:
        np.testing.assert_allclose(res[0], np.arange(10) * total)
        np.testing.assert_allclose(
            res[1], np.full((3, 5), sum(range(world_size)))
        )


def test_ring_allreduce_avg_and_uneven_sizes(store) -> None:
    def _fn(ctx, rank):
        # 7 elements across 3 ranks: uneven chunking
        avg = ctx.allreduce(
            [np.full(7, float(rank), np.float32)], op=ReduceOp.AVG
        ).future().result(timeout=15)
        return avg

    for res in _run_ring(store, 3, _fn):
        np.testing.assert_allclose(res[0], np.full(7, 1.0))


def test_ring_broadcast_and_allgather(store) -> None:
    def _fn(ctx, rank):
        bc = ctx.broadcast(
            [np.full(4, float(rank * 10 + 3), np.float32)], root=2
        ).future().result(timeout=15)
        ag = ctx.allgather(
            [np.arange(rank + 1, dtype=np.int32)]
        ).future().result(timeout=15)
        return bc, ag

    for bc, ag in _run_ring(store, 3, _fn):
        np.testing.assert_allclose(bc[0], np.full(4, 23.0))
        assert len(ag) == 3
        for r in range(3):
            np.testing.assert_array_equal(ag[r][0], np.arange(r + 1))


def test_ring_sequential_ops_and_reconfigure(store) -> None:
    def _fn(ctx, rank):
        outs = []
        for i in range(4):
            w = ctx.allreduce([np.full(5, float(i + rank), np.float32)])
            outs.append(w)
        return [w.future().result(timeout=15)[0][0] for w in outs]

    res = _run_ring(store, 3, _fn, prefix="ringseq")
    assert res[0] == res[1] == res[2]

    # auto mode picks ring for >= 3 ranks
    ctx = TcpCommContext(timeout=5.0, algorithm="auto")
    ctx.configure(f"{store.addr}/auto1", 0, 1)
    assert not ctx._use_ring
    ctx.shutdown()


@pytest.mark.parametrize("world_size,expect_ring", [(2, False), (3, True)])
def test_auto_algorithm_selection(store, world_size, expect_ring) -> None:
    ctxs = [TcpCommContext(timeout=10.0, algorithm="auto")
            for _ in range(world_size)]

    def _fn(rank):
        ctxs[rank].configure(f"{store.addr}/autosel", rank, world_size)
        return ctxs[rank].allreduce(
            [np.full(3, float(rank + 1), np.float32)]
        ).future().result(timeout=15)

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        results = [f.result(timeout=30)
                   for f in [pool.submit(_fn, r) for r in range(world_size)]]
    total = sum(range(1, world_size + 1))
    for res in results:
        np.testing.assert_allclose(res[0], np.full(3, total))
    for ctx in ctxs:
        assert ctx._use_ring == expect_ring
        ctx.shutdown()


def test_channels_overlap_latency(store) -> None:
    # 4 ops over 4 lanes are on the wire TOGETHER (the backward/comm-
    # overlap property, VERDICT item 3): every op, as its lane starts it,
    # waits until all 4 of its rank have been started. A transport that
    # ran them one after another would never fill the barrier; the 10 s
    # is the deadline of a hung run, not a bound on a time (the control
    # below shows one lane does run them in turn).
    n_ops = 4

    def _fn(ctx, rank):
        together = threading.Barrier(n_ops)
        assert len(ctx._lanes) == n_ops
        for lane in ctx._lanes:
            def _started_together(p, run=lane._execute):
                together.wait(timeout=10)
                return run(p)
            lane._execute = _started_together
        works = [
            ctx.allreduce([np.full(8, float(rank + 1), np.float32)])
            for _ in range(n_ops)
        ]
        for w in works:
            np.testing.assert_allclose(
                w.future().result(timeout=20)[0], np.full(8, 3.0))
        return together.broken

    assert _run_ranks(store, 2, _fn, timeout=30.0) == [False, False]


def test_channels_single_lane_serializes(store) -> None:
    # Control for the overlap test: channels=1 must take >= n_ops * delay.
    n_ops, delay = 3, 0.1

    def _worker(ctx, rank, results):
        ctx._op_delay = delay
        ctx.configure(f"{store.addr}/ser", rank, 2)
        t0 = time.perf_counter()
        works = [
            ctx.allreduce([np.full(4, 1.0, np.float32)])
            for _ in range(n_ops)
        ]
        for w in works:
            w.future().result(timeout=10)
        results[rank] = time.perf_counter() - t0

    ctxs = [TcpCommContext(timeout=10.0, channels=1) for _ in range(2)]
    results = [None, None]
    with ThreadPoolExecutor(max_workers=2) as pool:
        futs = [
            pool.submit(_worker, ctxs[r], r, results) for r in range(2)
        ]
        for f in futs:
            f.result(timeout=20)
    for ctx in ctxs:
        ctx.shutdown()
    for elapsed in results:
        assert elapsed >= n_ops * delay * 0.95


# ------------------------------------------------------- gradient compression


def _run_compressed(store, world_size, compression, algorithm, prefix):
    rng = np.random.default_rng(7)
    payloads = [
        rng.standard_normal(257).astype(np.float32) * (rank + 1)
        for rank in range(world_size)
    ]
    exact = np.sum(payloads, axis=0)

    def _fn(ctx, rank):
        work = ctx.allreduce([payloads[rank]], op=ReduceOp.SUM)
        return work.future().result(timeout=15)[0]

    ctxs = [
        TcpCommContext(
            timeout=10.0, algorithm=algorithm, compression=compression
        )
        for _ in range(world_size)
    ]
    results = [None] * world_size

    def _worker(rank):
        ctxs[rank].configure(f"{store.addr}/{prefix}", rank, world_size)
        results[rank] = _fn(ctxs[rank], rank)

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        futs = [pool.submit(_worker, r) for r in range(world_size)]
        for f in futs:
            f.result(timeout=30)
    for ctx in ctxs:
        ctx.shutdown()
    return results, exact


@pytest.mark.parametrize("algorithm,world_size", [("star", 2), ("ring", 4)])
@pytest.mark.parametrize("compression,rel_bound", [
    ("bf16", 2e-2),   # bf16 has 8 mantissa bits -> ~0.4% per value; the
                      # ring reduce accumulates a few roundings
    ("fp16", 2e-3),
    ("int8", 8e-2),   # absmax/254 absolute error per element
])
def test_compressed_allreduce_numerics(
    store, algorithm, world_size, compression, rel_bound
) -> None:
    results, exact = _run_compressed(
        store, world_size, compression, algorithm,
        f"c_{compression}_{algorithm}",
    )
    scale = np.max(np.abs(exact))
    for out in results:
        err = np.max(np.abs(out - exact)) / scale
        assert err < rel_bound, f"{compression}/{algorithm}: err {err}"
    # bitwise identity across ranks: encoded bytes are fanned out /
    # forwarded verbatim, so every rank decodes the same values
    for out in results[1:]:
        np.testing.assert_array_equal(out, results[0])


def test_compression_passthrough_ints(store) -> None:
    # integer arrays must never be quantized/downcast
    def _fn(ctx, rank):
        work = ctx.allreduce(
            [np.full(5, rank + 1, np.int64)], op=ReduceOp.SUM
        )
        return work.future().result(timeout=10)[0]

    ctxs = [
        TcpCommContext(timeout=10.0, algorithm="star", compression="int8")
        for _ in range(2)
    ]
    results = [None, None]

    def _worker(rank):
        ctxs[rank].configure(f"{store.addr}/ci", rank, 2)
        results[rank] = _fn(ctxs[rank], rank)

    with ThreadPoolExecutor(max_workers=2) as pool:
        for f in [pool.submit(_worker, r) for r in range(2)]:
            f.result(timeout=20)
    for ctx in ctxs:
        ctx.shutdown()
    for out in results:
        np.testing.assert_array_equal(out, np.full(5, 3, np.int64))


def test_codec_wire_sizes() -> None:
    from torchft_tpu.comm.transport import _CODECS

    v = np.zeros(1000, np.float32)
    assert _CODECS["none"]().wire_nbytes(v) == 4000
    assert _CODECS["bf16"]().wire_nbytes(v) == 2000
    assert _CODECS["int8"]().wire_nbytes(v) == 1004
    # encoded byte streams actually shrink
    assert len(_CODECS["bf16"]().encode_views([v])) == 2000
    assert len(_CODECS["int8"]().encode_views([v])) == 1004


def test_int8_nonfinite_poisons_not_corrupts() -> None:
    # Inf/NaN gradients must decode as NaN (catchable downstream), never
    # as plausible clipped int8 values.
    from torchft_tpu.comm.transport import _Int8Codec

    def roundtrip(codec, a):
        out = np.zeros_like(a)
        codec.decode_into(
            codec.encode_views([a]), [out], lambda v, inc: np.copyto(v, inc)
        )
        return out

    codec = _Int8Codec()
    bad = np.array([1.0, np.inf, 2.0, np.nan], np.float32)
    out = roundtrip(codec, bad)
    assert np.all(np.isnan(out)), out
    # finite arrays still roundtrip within quantization error
    good = np.array([1.0, -2.0, 0.5], np.float32)
    np.testing.assert_allclose(roundtrip(codec, good), good, atol=2.0 / 127)
