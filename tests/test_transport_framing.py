"""Framing parity for the zero-copy transport data path.

The scatter-gather writer (`_array_frame_iovecs` + `_sendmsg_all`) and
the pooled reader (`_RecvBufs` + `_recv_arrays`) replaced the
materialize-and-sendall / recv-and-copy pair; the star allreduce moved to
the codec raw-stream frame decoded in place. These tests pin the two
invariants the rewrite must preserve:

* wire BYTES of the generic frame are identical to `_pack_arrays`
  (old and new builds of the framework interoperate frame-for-frame),
* reduced VALUES are bitwise identical across ranks for every codec, and
  bitwise equal to the sequential rank-order reduction for the identity
  codec (the trajectory-consistency invariant in the codec docstring).
"""

import socket
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu.comm import ReduceOp, StoreServer, TcpCommContext
from torchft_tpu.comm.transport import (
    _CODECS,
    _RecvBufs,
    _array_frame_iovecs,
    _iov_join,
    _iov_nbytes,
    _pack_arrays,
    _recv_arrays,
    _send_arrays,
    _sendmsg_all,
    _unpack_arrays,
)


def _bf16():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def _sample_arrays():
    rng = np.random.default_rng(3)
    return [
        rng.standard_normal((3, 4)).astype(np.float32),
        np.arange(7, dtype=np.int64),
        np.float32(2.5).reshape(()),              # 0-d
        np.zeros((0, 5), dtype=np.float64),       # empty
        rng.standard_normal(9).astype(np.float64).astype(_bf16()),  # ext dtype
        np.frombuffer(b"\x01\x02\x03", dtype=np.uint8),  # read-only base
    ]


def test_iovec_frame_bytes_match_pack_arrays() -> None:
    arrays = _sample_arrays()
    assert _iov_join(_array_frame_iovecs(arrays)) == _pack_arrays(arrays)
    assert _iov_nbytes(_array_frame_iovecs(arrays)) == len(
        _pack_arrays(arrays)
    )
    # empty frame (broadcast non-root contribution)
    assert _iov_join(_array_frame_iovecs([])) == _pack_arrays([])


def test_sendmsg_recv_roundtrip_bitwise() -> None:
    arrays = _sample_arrays()
    expected = _unpack_arrays(_pack_arrays(arrays))
    s_tx, s_rx = socket.socketpair()
    try:
        sender = threading.Thread(target=_send_arrays, args=(s_tx, arrays))
        sender.start()
        got = _recv_arrays(s_rx, _RecvBufs())
        sender.join(timeout=10)
    finally:
        s_tx.close()
        s_rx.close()
    assert len(got) == len(expected)
    for g, e in zip(got, expected):
        assert g.dtype == e.dtype and g.shape == e.shape
        assert g.tobytes() == e.tobytes()
        assert g.flags.owndata or g.base is None  # owned, pool-independent


def test_sendmsg_all_partial_send_chunks() -> None:
    # Many small buffers exceed one sendmsg's iovec budget and the socket
    # buffer; the loop must still deliver every byte in order.
    payload = [bytes([i % 251]) * 700 for i in range(1400)]
    want = b"".join(payload)
    s_tx, s_rx = socket.socketpair()
    try:
        s_tx.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        sender = threading.Thread(target=_sendmsg_all, args=(s_tx, payload))
        sender.start()
        got = bytearray()
        while len(got) < len(want):
            chunk = s_rx.recv(1 << 16)
            assert chunk
            got.extend(chunk)
        sender.join(timeout=10)
    finally:
        s_tx.close()
        s_rx.close()
    assert bytes(got) == want


@pytest.mark.parametrize("codec_name", sorted(_CODECS))
def test_encode_iovecs_matches_encode_views(codec_name) -> None:
    codec = _CODECS[codec_name]()
    rng = np.random.default_rng(11)
    views = [
        rng.standard_normal(37).astype(np.float32),
        rng.standard_normal(5).astype(np.float64),
        np.arange(6, dtype=np.int32),
    ]
    joined = _iov_join(codec.encode_iovecs(views))
    assert joined == codec.encode_views(views)
    assert len(joined) == sum(codec.wire_nbytes(v) for v in views)


@pytest.fixture()
def store():
    server = StoreServer()
    yield server
    server.shutdown()


def _run_world(store, world, algorithm, compression, prefix, fn):
    ctxs = [
        TcpCommContext(
            timeout=10.0, algorithm=algorithm, compression=compression
        )
        for _ in range(world)
    ]
    results = [None] * world

    def _worker(rank):
        ctxs[rank].configure(f"{store.addr}/{prefix}", rank, world)
        results[rank] = fn(ctxs[rank], rank)

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(_worker, r) for r in range(world)]:
            f.result(timeout=30)
    for ctx in ctxs:
        ctx.shutdown()
    return results


@pytest.mark.parametrize("algorithm,world", [("star", 3), ("ring", 3)])
@pytest.mark.parametrize("codec_name", sorted(_CODECS))
def test_allreduce_bitwise_identical_across_ranks(
    store, algorithm, world, codec_name
) -> None:
    rng = np.random.default_rng(5)
    payloads = [
        rng.standard_normal(131).astype(np.float32) * (r + 1)
        for r in range(world)
    ]

    def _fn(ctx, rank):
        return ctx.allreduce(
            [payloads[rank].copy()], op=ReduceOp.SUM
        ).future().result(timeout=15)[0]

    results = _run_world(
        store, world, algorithm, codec_name, f"bw_{algorithm}_{codec_name}",
        _fn,
    )
    for out in results[1:]:
        assert out.tobytes() == results[0].tobytes(), (
            f"{algorithm}/{codec_name}: ranks diverged bitwise"
        )
    if codec_name == "none" and algorithm == "star":
        # Identity codec on the star: result must equal the sequential
        # rank-order accumulation bit for bit (the old path's semantics).
        acc = payloads[0].copy()
        for r in range(1, world):
            np.add(acc, payloads[r], out=acc)
        assert results[0].tobytes() == acc.tobytes()


def test_allreduce_reduces_in_place_into_donated_buffer(store) -> None:
    # The donation contract: a contiguous writable input is never copied —
    # the future resolves to the SAME array, reduced.
    staged = [np.full(64, float(r + 1), np.float32) for r in range(2)]

    def _fn(ctx, rank):
        out = ctx.allreduce([staged[rank]]).future().result(timeout=10)[0]
        return out is staged[rank], out

    results = _run_world(store, 2, "star", "none", "inplace", _fn)
    for aliased, out in results:
        assert aliased
        np.testing.assert_array_equal(out, np.full(64, 3.0, np.float32))


def test_allreduce_copies_readonly_input(store) -> None:
    # Read-only inputs (jax.device_get views) must be copied at submit,
    # not crash the in-place reduce.
    def _fn(ctx, rank):
        a = np.full(16, float(rank + 1), np.float32)
        a.setflags(write=False)
        out = ctx.allreduce([a]).future().result(timeout=10)[0]
        assert a[0] == rank + 1  # input untouched
        return out

    for out in _run_world(store, 2, "star", "none", "ro", _fn):
        np.testing.assert_array_equal(out, np.full(16, 3.0, np.float32))


def test_bucket_plan_staging_arena_reuse() -> None:
    from torchft_tpu.ddp import _BucketPlan

    leaves = [
        np.arange(6, dtype=np.float32).reshape(2, 3),
        np.arange(4, dtype=np.float32) * 2.0,
        np.arange(3, dtype=np.int64),
    ]
    plan = _BucketPlan(leaves, bucket_bytes=16)  # force multiple buckets
    staging = plan.alloc_staging()
    assert len(staging) == len(plan.buckets)
    for _round in range(2):  # second round reuses the same buffers
        packed = [
            plan.pack_bucket_into(
                bucket, [leaves[i] for i in bucket], staging[k]
            )
            for k, bucket in enumerate(plan.buckets)
        ]
        for k, got in enumerate(packed):
            assert got is staging[k]
            ref = _BucketPlan.pack_bucket(
                [leaves[i] for i in plan.buckets[k]]
            )
            np.testing.assert_array_equal(got, ref)
        out = plan.unpack(packed)
        for leaf, orig in zip(out, leaves):
            np.testing.assert_array_equal(leaf, orig)
    # dtype drift must fail loudly, not silently cast into the arena
    with pytest.raises(TypeError):
        plan.pack_bucket_into(
            plan.buckets[0],
            [np.zeros(plan.sizes[i], np.float64) for i in plan.buckets[0]],
            staging[0],
        )


@pytest.mark.parametrize("world", [3, 4])
def test_ring_member_death_fails_every_survivor_at_once(world) -> None:
    # The victim's ring neighbours see its sockets close; a survivor that
    # is NOT its neighbour only ever talks to healthy ranks. It must fail
    # as soon as they do (they shut their own sockets down on the latch),
    # not sit in a hop until the timeout: with one op in flight there is
    # no later op whose header would trip the sequence check.
    import time

    timeout = 12.0
    store = StoreServer()
    ctxs = [TcpCommContext(timeout=timeout, algorithm="ring")
            for _ in range(world)]
    victim = world - 1
    configured = threading.Barrier(world)
    failed_after = [None] * world
    died_at = [None]

    def _worker(rank):
        ctxs[rank].configure(f"{store.addr}/death", rank, world)
        configured.wait()
        if rank == victim:
            time.sleep(0.5)  # the others are inside their first hop
            died_at[0] = time.perf_counter()
            ctxs[rank].shutdown()
            return
        fut = ctxs[rank].allreduce(
            [np.ones(4 << 20, np.float32)]  # 16 MiB: hops of megabytes
        ).future()
        with pytest.raises(Exception):
            fut.result(timeout=2 * timeout)
        failed_after[rank] = time.perf_counter()

    threads = [threading.Thread(target=_worker, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=3 * timeout)
        assert not t.is_alive()
    for ctx in ctxs:
        ctx.shutdown()
    store.shutdown()
    for rank in range(world):
        if rank != victim:
            assert ctxs[rank].errored() is not None
            assert failed_after[rank] - died_at[0] < timeout / 3, (
                f"rank {rank} sat {failed_after[rank] - died_at[0]:.1f} s "
                "after the death"
            )
