"""Chunk-striped allreduce: parity, donation, and error feedback.

The striping invariant the transport must preserve (transport.py module
docstring): for a FIXED chunk grid (``chunk_bytes``), distributing the
chunks across many lanes produces results bitwise identical to running
the whole grid on a single lane — striping changes where bytes travel,
never what is computed. Pinned here for every codec, both topologies,
and chunk sizes that do and do not divide the payload.

The ring's own cut (``chunk_bytes=None``, identity codec): sized from the
op — every rank derives it from shapes alone, a one-array op gives each
lane one contiguous view, results are bitwise equal on all ranks and
equal to the explicit grid of the same slices; an explicit grid
reproduces the bytes it always gave (golden digests), for every codec.

Error feedback (ddp.py): the per-bucket residual arena makes the lossy
codecs' quantization error a delayed correction instead of a bias —
int8+EF tracks the fp32 trajectory on a toy quadratic while raw int8
parks at a quantization-bias fixed point — and residuals reset on every
transport incarnation change.
"""

import threading
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np
import pytest

import hashlib

from torchft_tpu.comm import ReduceOp, StoreServer, TcpCommContext
from torchft_tpu.comm import transport
from torchft_tpu.comm.context import Work
from torchft_tpu.comm.transport import (
    _CODECS,
    _chunk_grid,
    _chunk_grid_owned,
    _ring_lanes,
)
from torchft_tpu.ddp import DistributedDataParallel
from torchft_tpu.futures import future_chain


# ------------------------------------------------------------- chunk grid


def test_chunk_grid_shapes_and_coverage() -> None:
    a = np.arange(131, dtype=np.float32)
    b = np.arange(7, dtype=np.int64)
    empty = np.zeros(0, dtype=np.float64)
    # 64 f32 elems per 256-byte chunk: 131 -> 64 + 64 + 3
    chunks = _chunk_grid([a, b, empty], chunk_bytes=256)
    assert [c.size for c in chunks] == [64, 64, 3, 7]
    # chunks are VIEWS of the inputs (the zero-copy precondition)
    chunks[0][0] = -1.0
    assert a[0] == -1.0
    # chunk_bytes=0: one chunk per non-empty view
    whole = _chunk_grid([a, b, empty], chunk_bytes=0)
    assert [c.size for c in whole] == [131, 7]
    # grid is deterministic from layout alone
    again = _chunk_grid([np.empty_like(a), np.empty_like(b)], 256)
    assert [c.size for c in again] == [64, 64, 3, 7]


# ------------------------------------------------------- bitwise parity


@pytest.fixture()
def store():
    server = StoreServer()
    yield server
    server.shutdown()


def _run_world(store, world, prefix, fn, **ctx_kw):
    ctxs = [TcpCommContext(timeout=15.0, **ctx_kw) for _ in range(world)]
    results = [None] * world

    def _worker(rank):
        ctxs[rank].configure(f"{store.addr}/{prefix}", rank, world)
        results[rank] = fn(ctxs[rank], rank)

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(_worker, r) for r in range(world)]:
            f.result(timeout=60)
    for ctx in ctxs:
        ctx.shutdown()
    return results


def _payloads(world, n_elems=131):
    rng = np.random.default_rng(5)
    base = [
        rng.standard_normal(n_elems).astype(np.float32),
        rng.standard_normal(40).astype(np.float64),
        np.arange(9, dtype=np.int64),
    ]
    return [[(a * (r + 2)).astype(a.dtype) for a in base] for r in range(world)]


@pytest.mark.parametrize("algorithm,world", [("star", 3), ("ring", 3)])
@pytest.mark.parametrize("codec_name", sorted(_CODECS))
@pytest.mark.parametrize("chunk_bytes", [256, 524])  # 524 = 131 f32 bytes
def test_striped_bitwise_identical_to_single_lane(
    store, algorithm, world, codec_name, chunk_bytes
) -> None:
    # chunk_bytes=256 does not divide the 131-elem f32 view (64+64+3) and
    # splits the f64 view unevenly; 524 divides the f32 view exactly once.
    payloads = _payloads(world)

    def _fn(ctx, rank):
        return [
            a.copy() for a in ctx.allreduce(
                [a.copy() for a in payloads[rank]], op=ReduceOp.SUM
            ).future().result(timeout=30)
        ]

    kw = dict(algorithm=algorithm, compression=codec_name,
              chunk_bytes=chunk_bytes)
    striped = _run_world(
        store, world, f"st_{algorithm}_{codec_name}_{chunk_bytes}", _fn,
        channels=4, **kw,
    )
    single = _run_world(
        store, world, f"sl_{algorithm}_{codec_name}_{chunk_bytes}", _fn,
        channels=1, **kw,
    )
    # cross-rank identity within each run
    for run in (striped, single):
        for out in run[1:]:
            for got, ref in zip(out, run[0]):
                assert got.tobytes() == ref.tobytes(), (
                    f"{algorithm}/{codec_name}: ranks diverged bitwise"
                )
    # striped vs single-lane identity at the same grid
    for got, ref in zip(striped[0], single[0]):
        assert got.tobytes() == ref.tobytes(), (
            f"{algorithm}/{codec_name}/chunk={chunk_bytes}: striping "
            "changed the reduced values"
        )


def test_striped_star_matches_sequential_accumulation(store) -> None:
    # Identity codec on the striped star must still equal the sequential
    # rank-order accumulation bit for bit, even when chunks land on
    # different lanes (the root reduces peers in rank order PER CHUNK).
    world = 3
    rng = np.random.default_rng(11)
    payloads = [
        rng.standard_normal(1031).astype(np.float32) * (r + 1)
        for r in range(world)
    ]

    def _fn(ctx, rank):
        return ctx.allreduce(
            [payloads[rank].copy()], op=ReduceOp.SUM
        ).future().result(timeout=30)[0].copy()

    results = _run_world(
        store, world, "seqacc", _fn,
        algorithm="star", channels=4, chunk_bytes=512,
    )
    acc = payloads[0].copy()
    for r in range(1, world):
        np.add(acc, payloads[r], out=acc)
    for out in results:
        assert out.tobytes() == acc.tobytes()


def test_striped_allreduce_reduces_in_place_and_avg(store) -> None:
    # Donation contract survives striping: the future resolves to the
    # SAME arrays, with every chunk view reduced in place across lanes.
    staged = [np.full(4096, float(r + 1), np.float32) for r in range(2)]

    def _fn(ctx, rank):
        out = ctx.allreduce(
            [staged[rank]], op=ReduceOp.AVG
        ).future().result(timeout=30)[0]
        return out is staged[rank], out

    results = _run_world(
        store, 2, "inplace_striped", _fn,
        algorithm="star", channels=4, chunk_bytes=1024,
    )
    for aliased, out in results:
        assert aliased
        np.testing.assert_array_equal(out, np.full(4096, 1.5, np.float32))


# ------------------------------------------------- the ring's derived cut

# ddp._BucketPlan over cerebras-gpt-111m at 32 MiB (the kill cell's step),
# in elements, and the same step at 1/64 of the size
_CELL_PLAN = [8260608, 8263680] + [7080960] * 7 + [
    4718592, 38633472, 1574400, 38633472,
]
_CELL_PLAN_64TH = [n // 64 for n in _CELL_PLAN]
_MIB = 1 << 20


def _shrink_hops(monkeypatch, factor: int) -> None:
    """The rule at 1/factor of its size: small payloads then take the cuts
    that real ones take (the rule only ever sees bytes over hop size)."""
    monkeypatch.setattr(
        transport, "_RING_HOP_BYTES", transport._RING_HOP_BYTES // factor
    )


@pytest.mark.parametrize("nbytes,world,lanes,want", [
    (1, 4, 4, 1), (4, 4, 4, 1), (_MIB, 4, 4, 1),
    (32 * _MIB, 4, 4, 1),            # a DDP bucket: one lane, 8 MiB hops
    (33_054_720, 4, 4, 1), (18_874_368, 4, 4, 1),
    (48 * _MIB - 4, 4, 4, 1), (48 * _MIB + 4, 4, 4, 2),
    (154_533_888, 4, 4, 4),          # wte / lm_head: every lane a slice
    (154_533_888, 4, 2, 2), (154_533_888, 4, 1, 1),
    (154_533_888, 3, 4, 4), (33_054_720, 3, 4, 1), (28_323_840, 3, 4, 1),
    (600 * 10**6, 8, 4, 4), (33_054_720, 8, 4, 1),
])
def test_ring_lanes_rule(nbytes, world, lanes, want) -> None:
    assert _ring_lanes(nbytes, world, lanes) == want


def _cut_case(name):
    f32, f64, i64 = np.float32, np.float64, np.int64
    return {
        "one_elem": [(1, f32)],
        "fewer_than_members": [(3, f32)],
        "odd": [(131, f32), (40, f64), (9, i64)],
        "with_empty": [(0, f32), (1031, f32), (0, i64), (7, f64)],
        "bucket": [(8 << 20, f32)],
        "big_leaf": [(38633472, f32)],
        "cell_step_one_op": [(n, f32) for n in _CELL_PLAN],
        "mixed_big": [(5_000_001, f32), (3_000_003, f64), (11, i64),
                      (9_000_001, f32)],
    }[name]


@pytest.mark.parametrize("world,lanes", [(2, 4), (3, 4), (4, 4), (4, 2),
                                         (4, 1)])
@pytest.mark.parametrize("case", [
    "one_elem", "fewer_than_members", "odd", "with_empty", "bucket",
    "big_leaf", "cell_step_one_op", "mixed_big",
])
def test_ring_cut_from_shapes_alone(case, world, lanes) -> None:
    layout = _cut_case(case)
    owners = [i % world for i in range(len(layout))]

    def cut(make):
        flats = [make(n, dt) for n, dt in layout]
        return flats, _chunk_grid_owned(
            flats, owners, 1 << 20, ring=(world, lanes)
        )

    # two ranks, different contents, same shapes: the same cut
    flats, (chunks, ch_owners, shares) = cut(
        lambda n, dt: np.zeros(n, dt)
    )
    _, (chunks2, ch_owners2, shares2) = cut(
        lambda n, dt: np.ones(n, dt)
    )
    assert [c.size for c in chunks] == [c.size for c in chunks2]
    assert (ch_owners, shares) == (ch_owners2, shares2)
    # the chunks tile every non-empty view exactly, in view order, as
    # views of it, and inherit its owner
    it = iter(zip(chunks, ch_owners))
    for f, o in zip(flats, owners):
        at = 0
        while at < f.size:
            ch, ch_o = next(it)
            assert np.shares_memory(ch, f) and ch.dtype == f.dtype
            assert ch.ctypes.data == f.ctypes.data + at * f.itemsize
            assert ch_o == o and ch.size > 0
            at += ch.size
        assert at == f.size
    assert next(it, None) is None
    # shares: 0..k-1 in order, k what the rule says for the op's bytes,
    # near-equal in bytes, and a view is cut only where a share ends
    total = sum(f.nbytes for f in flats)
    k = _ring_lanes(total, world, lanes)
    assert shares == sorted(shares) and set(shares) <= set(range(k))
    assert len(chunks) <= sum(1 for f in flats if f.size) + k - 1
    if total >= 1024 * k:
        assert sorted(set(shares)) == list(range(k))
        per_share = [
            sum(c.nbytes for c, s in zip(chunks, shares) if s == i)
            for i in range(k)
        ]
        assert max(per_share) - min(per_share) <= 8 * k + 16
    if len(layout) == 1 and flats[0].size >= k:
        # a one-array op: ONE contiguous view a lane
        assert len(chunks) == k


def _positive_payloads(world, layout, seed=31):
    """No cancellation: a re-associated f32 sum then stays within a few
    ulp of any other order."""
    rng = np.random.default_rng(seed)
    base = [
        (1.0 + rng.random(n, dtype=np.float32)).astype(dt)
        if np.dtype(dt).kind == "f"
        else np.arange(n, dtype=dt)
        for n, dt in layout
    ]
    return [
        [(a * (r + 1)).astype(a.dtype) for a in base] for r in range(world)
    ]


def _avg_all(payloads):
    def _fn(ctx, rank):
        works = [
            ctx.allreduce([a], op=ReduceOp.AVG) for a in
            [a.copy() for a in payloads[rank]]
        ]
        return [w.future().result(timeout=60)[0] for w in works]

    return _fn


@pytest.mark.parametrize("world", [2, 3, 4])
@pytest.mark.parametrize("case", [
    "one_elem", "fewer_than_members", "odd", "forty_million", "cell_step",
])
def test_ring_default_all_ranks_bitwise_equal_and_near_mean(
    store, monkeypatch, case, world
) -> None:
    f32 = np.float32
    if case == "forty_million":
        layout = [(40_000_000, f32)]  # real size: four slices at world 4
    elif case == "cell_step":
        # the kill cell's 13 buckets in proportion, one op each, all in
        # flight at once; the rule at 1/64 cuts them as it cuts the cell
        layout = [(n, f32) for n in _CELL_PLAN_64TH]
        _shrink_hops(monkeypatch, 64)
        assert [
            _ring_lanes(4 * n, 4, 4) for n in _CELL_PLAN_64TH
        ] == [1] * 10 + [4, 1, 4]
    else:
        layout = [(n, dt) for n, dt in _cut_case(case)
                  if np.dtype(dt).kind == "f"]
    payloads = _positive_payloads(world, layout)
    results = _run_world(
        store, world, f"rd_{case}_{world}", _avg_all(payloads),
        algorithm="ring",
    )
    for out in results[1:]:
        for got, ref in zip(out, results[0]):
            assert got.tobytes() == ref.tobytes(), "ranks diverged bitwise"
    for i, got in enumerate(results[0]):
        want = np.mean(
            np.stack([payloads[r][i] for r in range(world)]), axis=0,
            dtype=got.dtype,
        )
        np.testing.assert_array_max_ulp(got, want, maxulp=4)


@pytest.mark.parametrize("case", ["whole_on_one_lane", "sliced"])
def test_derived_cut_equals_explicit_grid_of_same_slices(
    store, monkeypatch, case
) -> None:
    # The rule decides WHERE an op is cut and which lane carries what;
    # given the cut, the values are those of the explicit grid with the
    # same slices on any lanes (was: stripe=False against stripe=True).
    world = 3
    n = 12_000
    payloads = _positive_payloads(world, [(n, np.float32)], seed=7)
    if case == "whole_on_one_lane":
        explicit = dict(chunk_bytes=0, channels=1)
    else:
        # hop target 1000 B at world 3: round(48000 / 3000) = 16 -> 4
        # lanes, 3000 elements a slice
        monkeypatch.setattr(transport, "_RING_HOP_BYTES", 1000)
        assert _ring_lanes(4 * n, world, 4) == 4
        explicit = dict(chunk_bytes=4 * n // 4, channels=2)

    def _fn(ctx, rank):
        return ctx.allreduce(
            [payloads[rank][0].copy()]
        ).future().result(timeout=30)[0]

    derived = _run_world(store, world, f"dc_d_{case}", _fn,
                         algorithm="ring", channels=4)
    pinned = _run_world(store, world, f"dc_e_{case}", _fn,
                        algorithm="ring", **explicit)
    for r in range(world):
        assert derived[r].tobytes() == pinned[0].tobytes()


@pytest.mark.parametrize("world", [3, 4])
def test_ring_default_reduce_scatter_owners_decode_allreduce_bytes(
    store, monkeypatch, world
) -> None:
    # several arrays in ONE op (the sharded reducer's shape), cut across
    # lanes by the rule: an owner's arrays hold what allreduce gives
    _shrink_hops(monkeypatch, 1024)
    layout = [(9_001, np.float32), (3, np.float32), (20_000, np.float64),
              (4_097, np.float32), (12_345, np.float32)]
    owners = [i % world for i in range(len(layout))]
    payloads = _positive_payloads(world, layout, seed=11)
    assert _ring_lanes(sum(a.nbytes for a in payloads[0]), world, 4) > 1

    def _ar(ctx, rank):
        return ctx.allreduce(
            [a.copy() for a in payloads[rank]], op=ReduceOp.AVG
        ).future().result(timeout=30)

    def _rs(ctx, rank):
        return ctx.reduce_scatter(
            [a.copy() for a in payloads[rank]], op=ReduceOp.AVG,
            owners=owners,
        ).future().result(timeout=30)

    full = _run_world(store, world, f"rso_a{world}", _ar, algorithm="ring")
    scat = _run_world(store, world, f"rso_s{world}", _rs, algorithm="ring")
    for rank in range(world):
        for i, o in enumerate(owners):
            if o == rank:
                assert scat[rank][i].tobytes() == full[0][i].tobytes()


# sha256[:16] over the reduced arrays of _payloads(world) under SUM, as the
# tree BEFORE the derived cut computed them (PR 27's parent, 20ee77e):
# an explicit chunk_bytes is the codecs' granularity and the star's
# pipeline depth, and keeps giving the bytes it gave.
_PARENT_DIGESTS = {
    ("star", 3, "bf16", 256): "13a8e57c55b5918e",
    ("star", 3, "bf16", 524): "13a8e57c55b5918e",
    ("star", 3, "fp16", 256): "2bdb39c48d906995",
    ("star", 3, "fp16", 524): "2bdb39c48d906995",
    ("star", 3, "int8", 256): "9ddb4531c1082ab2",
    ("star", 3, "int8", 524): "364fc84bbb29ebeb",
    ("star", 3, "none", 256): "43e36f4683cf1391",
    ("star", 3, "none", 524): "43e36f4683cf1391",
    ("ring", 3, "bf16", 256): "64edcb006f466f92",
    ("ring", 3, "bf16", 524): "64edcb006f466f92",
    ("ring", 3, "fp16", 256): "8e1d9a66a28a0c9d",
    ("ring", 3, "fp16", 524): "8e1d9a66a28a0c9d",
    ("ring", 3, "int8", 256): "7879a63e90b2e97e",
    ("ring", 3, "int8", 524): "e2577dc47251bf94",
    ("ring", 3, "none", 256): "a8bc02f8b169987c",
    ("ring", 3, "none", 524): "190eb2cfafb6bcf5",
    ("ring", 4, "bf16", 256): "4e86dffd619cc726",
    ("ring", 4, "bf16", 524): "4e86dffd619cc726",
    ("ring", 4, "fp16", 256): "aaf422157598af5b",
    ("ring", 4, "fp16", 524): "aaf422157598af5b",
    ("ring", 4, "int8", 256): "9192962a1997eb22",
    ("ring", 4, "int8", 524): "e1a8448f7f34fb06",
    ("ring", 4, "none", 256): "9a938b31fb5f44de",
    ("ring", 4, "none", 524): "b88b056d3c7f1511",
}


@pytest.mark.parametrize(
    "algorithm,world,codec_name,chunk_bytes", sorted(_PARENT_DIGESTS)
)
def test_explicit_grid_reproduces_parent_bytes(
    store, algorithm, world, codec_name, chunk_bytes
) -> None:
    payloads = _payloads(world)

    def _fn(ctx, rank):
        return ctx.allreduce(
            [a.copy() for a in payloads[rank]], op=ReduceOp.SUM
        ).future().result(timeout=30)

    results = _run_world(
        store, world, f"pd_{algorithm}{world}{codec_name}{chunk_bytes}",
        _fn, algorithm=algorithm, compression=codec_name,
        chunk_bytes=chunk_bytes, channels=4,
    )
    for out in results:
        h = hashlib.sha256()
        for a in out:
            h.update(a.tobytes())
        assert h.hexdigest()[:16] == _PARENT_DIGESTS[
            (algorithm, world, codec_name, chunk_bytes)
        ]


def test_default_grid_kept_for_lossy_codecs_and_star() -> None:
    # chunk_bytes=None is the ring's licence to cut by size, nothing
    # else's: the lossy codecs and the star keep the 1 MiB grid (int8
    # scales per chunk; the star's pipeline depth)
    a = np.zeros(3 * (1 << 18) + 5, np.float32)  # 3 MiB + 20 B
    for codec in sorted(_CODECS):
        ctx = TcpCommContext(compression=codec)
        assert ctx._grid_bytes == 1 << 20
        per_chunk = {"none": 0, "bf16": 0, "fp16": 0, "int8": 4}[codec]
        scale = {"none": 4, "bf16": 2, "fp16": 2, "int8": 1}[codec]
        assert ctx.wire_nbytes(a) == a.size * scale + 4 * per_chunk
    assert TcpCommContext(chunk_bytes=0)._grid_bytes == 0
    with pytest.raises(TypeError):
        TcpCommContext(stripe=False)  # settled by PR 27's table: gone


@pytest.mark.parametrize("n_elems,chunks,hop_bytes", [
    (8 << 20, 1, 8 << 20),          # a 32 MiB bucket: one lane, whole
    (38633472, 4, 9658368),         # 154.5 MB: every lane carries a slice
])
def test_ring_default_counts(store, n_elems, chunks, hop_bytes) -> None:
    # Counts only (no time is asserted anywhere): at world 4 with the
    # defaults a hop is ONE view — one iovec, one receive target, one
    # np.add / np.copyto — of the size the rule says.
    world = 4
    payloads = [np.full(n_elems, float(r + 1), np.float32)
                for r in range(world)]
    snaps = [None] * world

    def _fn(ctx, rank):
        before = ctx.metrics.snapshot()
        out = ctx.allreduce(
            [payloads[rank]], op=ReduceOp.SUM
        ).future().result(timeout=120)[0]
        after = ctx.metrics.snapshot()
        snaps[rank] = (before, after)
        return float(out[0]), float(out[-1])

    results = _run_world(store, world, f"cnt_{n_elems}", _fn)  # defaults
    assert results == [(10.0, 10.0)] * world
    hops = 2 * (world - 1)
    for before, after in snaps:
        def delta(key):
            return after.get(key, 0.0) - before.get(key, 0.0)

        assert delta("comm_chunks") == chunks
        assert delta("comm_ring_hops") == hops * chunks  # hops a lane: 6
        assert delta("comm_ring_views") == hops * chunks  # views a hop: 1
        assert after["comm_hop_bytes"] == hop_bytes
        lanes_used = [
            i for i in range(4) if f"comm_l{i}_wire_reduce_p50_ms" in after
        ]
        assert len(lanes_used) == chunks


def test_striped_multi_op_pipelining(store) -> None:
    # Several striped ops in flight (the DDP bucket pattern) must not
    # cross-talk: per-lane streams stay ordered by submission index.
    world = 2
    rng = np.random.default_rng(3)
    bufs = [
        [rng.standard_normal(777).astype(np.float32) * (r + 1 + k)
         for k in range(6)]
        for r in range(world)
    ]

    def _fn(ctx, rank):
        works = [ctx.allreduce([b.copy()]) for b in bufs[rank]]
        return [w.future().result(timeout=30)[0].copy() for w in works]

    results = _run_world(
        store, world, "multi", _fn,
        algorithm="star", channels=3, chunk_bytes=512,
    )
    for k in range(6):
        want = bufs[0][k] + bufs[1][k]
        for r in range(world):
            np.testing.assert_array_equal(results[r][k], want)


# ------------------------------------------------------ wire_roundtrip


@pytest.mark.parametrize("codec_name", sorted(_CODECS))
def test_wire_roundtrip_matches_codec_for_star_peer(codec_name) -> None:
    ctx = TcpCommContext(compression=codec_name, chunk_bytes=128)
    # star peer is the one role whose contribution crosses the wire
    # through the codec (white-box: roundtrip is rank/topology aware)
    ctx._rank, ctx._world_size, ctx._use_ring = 1, 2, False
    rng = np.random.default_rng(9)
    src = rng.standard_normal(100).astype(np.float32)
    out = np.empty_like(src)
    ctx.wire_roundtrip(src, out)
    codec = _CODECS[codec_name]()
    # reference: per-chunk encode/decode over the same grid
    ref = np.empty_like(src)
    for s, o in zip(_chunk_grid([src], 128), _chunk_grid([ref], 128)):
        data = b"".join(
            bytes(np.ascontiguousarray(b).reshape(-1).view(np.uint8))
            if isinstance(b, np.ndarray) else bytes(b)
            for b in codec.encode_iovecs([s])
        )
        codec.decode_into(data, [o], lambda v, inc: np.copyto(v, inc))
    np.testing.assert_array_equal(out, ref)
    if codec_name == "none":
        np.testing.assert_array_equal(out, src)


def test_wire_roundtrip_identity_for_star_root_and_ring() -> None:
    # The star root's contribution is the in-place accumulator (never
    # encoded); ring contributions ride uncompressed partial sums — both
    # must see an IDENTITY roundtrip or EF would compensate error the
    # wire never made.
    rng = np.random.default_rng(13)
    src = rng.standard_normal(64).astype(np.float32)
    for rank, use_ring in ((0, False), (1, True)):
        ctx = TcpCommContext(compression="int8", chunk_bytes=64)
        ctx._rank, ctx._world_size, ctx._use_ring = rank, 3, use_ring
        out = np.empty_like(src)
        ctx.wire_roundtrip(src, out)
        np.testing.assert_array_equal(out, src)


# ------------------------------------------------------- error feedback


class _WireStubManager:
    """Manager facade over a raw TcpCommContext: quorum is a no-op, AVG
    scaling divides by the wire world (what Manager._normalize does), and
    the wire_* introspection passes through — everything DDP's
    average_gradients needs, with none of the control plane."""

    def __init__(self, ctx: TcpCommContext, world: int) -> None:
        self._ctx = ctx
        self._world = world

    def wait_quorum(self) -> None:
        pass

    def is_solo_wire(self) -> bool:
        return self._world == 1

    def is_participating(self) -> bool:
        return True

    def report_error(self, e) -> None:
        raise e

    def wire_is_lossy(self) -> bool:
        return self._ctx.wire_is_lossy()

    def wire_compensable(self) -> bool:
        return self._ctx.wire_compensable()

    def wire_generation(self) -> int:
        return self._ctx.wire_generation()

    def wire_roundtrip(self, src, out) -> None:
        self._ctx.wire_roundtrip(src, out)

    def allreduce_arrays(self, arrays, op=ReduceOp.SUM) -> Work:
        work = self._ctx.allreduce(list(arrays), ReduceOp.SUM)
        scale = np.float32(1.0 / self._world)

        def _avg(f: Future):
            reduced = f.result()
            for a in reduced:
                if a.dtype in (np.float32, np.float64):
                    np.multiply(a, a.dtype.type(scale), out=a)
            return reduced

        return Work(future_chain(work.future(), _avg))


def _descend(store, prefix, codec, error_feedback, steps, targets,
             chunk_bytes=64, tail=50):
    """2-replica GD on f(x) = mean_r 0.5*||x - t_r||^2 through the real
    transport + DDP (one bucket). Returns rank 0's Polyak tail average
    (mean of the last ``tail`` iterates): EF's transmitted error is a
    delayed correction, so its limit cycle time-averages out, while raw
    quantization bias survives any amount of averaging."""
    world = len(targets)
    ctxs = [
        TcpCommContext(
            timeout=15.0, algorithm="star", channels=2,
            compression=codec, chunk_bytes=chunk_bytes,
        )
        for _ in range(world)
    ]
    finals = [None] * world

    def _worker(rank):
        ctx = ctxs[rank]
        ctx.configure(f"{store.addr}/{prefix}", rank, world)
        manager = _WireStubManager(ctx, world)
        ddp = DistributedDataParallel(manager, error_feedback=error_feedback)
        x = np.zeros_like(targets[rank])
        acc = np.zeros(x.shape, np.float64)
        for t in range(steps):
            grad = {"x": x - targets[rank]}
            avg = ddp.average_gradients(grad)
            x = x - 0.2 * np.asarray(avg["x"])
            if t >= steps - tail:
                acc += x
        finals[rank] = (acc / tail).astype(np.float32)

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(_worker, r) for r in range(world)]:
            f.result(timeout=120)
    for ctx in ctxs:
        ctx.shutdown()
    return finals[0]


def test_int8_error_feedback_converges_where_raw_drifts(store) -> None:
    # Heterogeneous per-chunk magnitudes (a few 100x elements dominate
    # each chunk's absmax) — the regime where raw int8's bias is worst:
    # small-magnitude coordinates see a coarse quantization grid set by
    # their chunk's outliers. EF compensates exactly that.
    rng = np.random.default_rng(17)
    targets = []
    for _ in range(2):
        t = rng.standard_normal(48).astype(np.float32)
        t[:4] *= 100.0
        targets.append(t)
    optimum = (targets[0] + targets[1]) / 2.0
    steps = 200

    x_fp32 = _descend(store, "ef_fp32", "none", "auto", steps, targets)
    x_raw = _descend(store, "ef_raw", "int8", False, steps, targets)
    x_ef = _descend(store, "ef_on", "int8", "auto", steps, targets)

    err_fp32 = float(np.max(np.abs(x_fp32 - optimum)))
    err_raw = float(np.max(np.abs(x_raw - optimum)))
    err_ef = float(np.max(np.abs(x_ef - optimum)))

    # fp32 converges essentially exactly at this step count
    assert err_fp32 < 1e-4
    # EF tracks the fp32 optimum to ~1e-3 (measured 0.0023 with wide
    # margin); raw int8 parks at a bias fixed point two orders worse
    # (measured 0.317).
    assert err_ef < 2e-2, f"int8+EF did not converge (err={err_ef})"
    assert err_raw > 1e-1, (
        f"raw int8 unexpectedly converged (err={err_raw})"
    )
    assert err_raw > 10 * err_ef, (
        f"raw int8 unexpectedly matched EF (raw={err_raw}, ef={err_ef})"
    )


def test_error_feedback_residuals_reset_on_reconfigure(store) -> None:
    # One real context reconfigured between steps: the residual arena
    # must zero itself when wire_generation changes (membership change —
    # stale residuals would inject error owed to the previous cohort).
    world = 2
    rng = np.random.default_rng(23)
    grads = [rng.standard_normal(32).astype(np.float32) * (r + 1)
             for r in range(world)]
    ctxs = [
        TcpCommContext(timeout=15.0, algorithm="star", channels=2,
                       compression="int8", chunk_bytes=64)
        for _ in range(world)
    ]
    ddps = [None] * world
    barrier = threading.Barrier(world, timeout=30)

    def _worker(rank):
        ctx = ctxs[rank]
        manager = _WireStubManager(ctx, world)
        ddp = DistributedDataParallel(manager, error_feedback="auto")
        ddps[rank] = ddp
        for round_no in range(2):
            barrier.wait()
            ctx.configure(f"{store.addr}/efgen{round_no}", rank, world)
            barrier.wait()
            ddp.average_gradients({"g": grads[rank].copy()})
            if round_no == 0:
                if rank != 0:
                    # star PEER: arena allocated, residual is the int8
                    # quantization error of the compensated gradient —
                    # non-zero for real data
                    res = ddp._residuals[0]
                    assert res is not None
                    assert float(np.abs(res).max()) > 0
                    gen = ddp._ef_generation
                else:
                    # star ROOT: contribution never encoded, so the gate
                    # (wire_compensable) keeps the arena OFF entirely
                    assert ddp._residuals is None
                barrier.wait()  # hold both ranks until the check is done
            elif rank != 0:
                assert ddp._ef_generation == ctx.wire_generation()
                assert ddp._ef_generation != gen

    threads = [threading.Thread(target=_worker, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    for ctx in ctxs:
        ctx.shutdown()


def test_error_feedback_survives_nonfinite_gradient(store) -> None:
    # An Inf/NaN gradient poisons its int8 wire image (NaN-scale
    # poisoning) and the step is discarded — but the residual buffer
    # persists across steps. It must be scrubbed back to finite, or the
    # spike would re-inject NaN into every later step until a membership
    # change.
    world = 2
    targets = [np.full(32, 1.0 + r, np.float32) for r in range(world)]
    ctxs = [
        TcpCommContext(timeout=15.0, algorithm="star", channels=2,
                       compression="int8", chunk_bytes=64)
        for _ in range(world)
    ]
    finals = [None] * world

    def _worker(rank):
        ctx = ctxs[rank]
        ctx.configure(f"{store.addr}/efnan", rank, world)
        ddp = DistributedDataParallel(_WireStubManager(ctx, world),
                                      error_feedback="auto")
        x = np.zeros_like(targets[rank])
        for t in range(12):
            grad = x - targets[rank]
            if t == 3 and rank == 1:
                grad = grad.copy()
                grad[0] = np.inf  # transient spike on the PEER rank
            avg = ddp.average_gradients({"x": grad})
            if t != 3:  # the poisoned step's average is NaN by design
                x = x - 0.2 * np.asarray(avg["x"])
            if ddp._residuals is not None and t >= 3:
                assert np.all(np.isfinite(ddp._residuals[0])), (
                    f"rank {rank}: residual stayed non-finite after the "
                    f"spike (step {t})"
                )
        finals[rank] = x

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(_worker, r) for r in range(world)]:
            f.result(timeout=60)
    for ctx in ctxs:
        ctx.shutdown()
    # training recovered after the spike: iterates stayed finite and
    # moved toward the optimum
    for x in finals:
        assert np.all(np.isfinite(x))
        assert abs(float(x[1]) - 1.5) < 0.2


def test_error_feedback_auto_off_for_lossless_wire(store) -> None:
    # Identity codec: auto-EF must not allocate residuals or perturb the
    # values (the roundtrip would be a pure copy anyway).
    world = 2
    grads = [np.full(16, float(r + 1), np.float32) for r in range(world)]
    ctxs = [
        TcpCommContext(timeout=15.0, algorithm="star", channels=2)
        for _ in range(world)
    ]
    outs = [None] * world

    def _worker(rank):
        ctx = ctxs[rank]
        ctx.configure(f"{store.addr}/efoff", rank, world)
        ddp = DistributedDataParallel(_WireStubManager(ctx, world),
                                      error_feedback="auto")
        outs[rank] = ddp.average_gradients({"g": grads[rank]})
        assert ddp._residuals is None

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(_worker, r) for r in range(world)]:
            f.result(timeout=30)
    for ctx in ctxs:
        ctx.shutdown()
    for out in outs:
        np.testing.assert_allclose(np.asarray(out["g"]),
                                   np.full(16, 1.5, np.float32))
