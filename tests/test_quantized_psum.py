"""Quantized collectives on the hardware-native psum path (EQuARX).

The conftest forces an 8-device virtual CPU platform, so the quantized
exchange (comm/xla_backend.py _build_quantized_psum /
_build_quantized_psum_scatter) runs its real shard_map all_to_all /
all_gather collectives here.

The load-bearing suites:

* **Convergence oracle** — psum's reduction order is XLA's to choose,
  so (unlike star/ring) this path can NEVER enter a bitwise A/B. What
  is pinned instead: (a) the device phase-1 encode is bit-identical to
  the host codec at matching chunk grids (``device_codec_roundtrip`` vs
  ``codec_roundtrip`` — so the EF arena's host-computed residual
  describes exactly what the quantized wire lost), and (b) int8+EF over
  the quantized psum TRACKS the fp32 trajectory on the PR 2 toy
  quadratic while raw int8 parks at a bias fixed point.

* **Compile-count discipline** — one compile per (world, codec,
  layout), zero retraces across a kill→reform, exactly like the PR 6
  mesh cache (the counters are the e2e oracle on a sandbox where
  wall-clock A/Bs null).

* **Bytes-on-wire honesty** — ``comm_encoded_bytes``/``comm_raw_bytes``
  cumulative counters and codec-aware ``wire_nbytes`` on the psum path:
  int8 at the 1MB grid is <= 0.3x raw (the graded ratio).
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu.comm.context import (
    DummyCommContext,
    ErrorSwallowingCommContext,
    ReduceOp,
)
from torchft_tpu.comm.transport import (
    _CODECS,
    TcpCommContext,
    codec_roundtrip,
    codec_wire_nbytes,
    host_unsupported_reason,
)
from torchft_tpu.comm.xla_backend import (
    MeshManager,
    XlaCommContext,
    device_codec_roundtrip,
)

CHUNK = 1 << 12  # small grid: multiple chunks + per-chunk int8 scales


@pytest.fixture(scope="module")
def mesh_mgr():
    # One pool for the whole module: executables cache across tests,
    # like one training process surviving many quorum epochs.
    return MeshManager()


def _run_cohort(ctxs, tag, world, body, timeout=120.0):
    results = [None] * world

    def _worker(rank):
        ctxs[rank].configure(f"xla://{tag}", rank, world)
        results[rank] = body(ctxs[rank], rank)

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(_worker, r) for r in range(world)]:
            f.result(timeout=timeout)
    return results


def _qpsum_ctxs(mesh_mgr, world, codec, chunk_bytes=CHUNK, timeout=30.0):
    return [
        XlaCommContext(timeout=timeout, algorithm="psum",
                       compression=codec, chunk_bytes=chunk_bytes,
                       mesh_manager=mesh_mgr)
        for _ in range(world)
    ]


def _inputs(world, seed, size=5000):
    rng = np.random.default_rng(seed)
    return [
        (rng.standard_normal(size) * (r + 1)).astype(np.float32)
        for r in range(world)
    ]


# ------------------------------------------------------ capability query


def test_capability_surface_one_definition() -> None:
    # xla: every codec runs on psum for sum/avg; lossy psum refuses
    # max/min PRESCRIPTIVELY; star/ring keep carrying every op.
    for codec in ("none", "bf16", "fp16", "int8"):
        assert XlaCommContext.supports("psum", codec)
        assert XlaCommContext.supports("psum", codec, ReduceOp.AVG)
        assert XlaCommContext.supports("star", codec, ReduceOp.MAX)
    assert XlaCommContext.supports("psum", "none", ReduceOp.MAX)
    for op in (ReduceOp.MAX, ReduceOp.MIN):
        assert not XlaCommContext.supports("psum", "int8", op)
        reason = XlaCommContext.unsupported_reason("psum", "int8", op)
        assert "only ACCUMULATES" in reason and "star/ring" in reason
    assert "unknown algorithm" in XlaCommContext.unsupported_reason(
        "tree", "none"
    )
    # host: psum does not exist on sockets — one shared definition for
    # TcpCommContext and the subprocess proxy
    from torchft_tpu.comm.subproc import SubprocessCommContext

    for cls in (TcpCommContext, SubprocessCommContext):
        assert not cls.supports("psum", "none")
        assert "xla" in cls.unsupported_reason("psum", "none")
        assert cls.supports("ring", "int8", ReduceOp.MAX)
    assert host_unsupported_reason("psum", "none") == (
        TcpCommContext.unsupported_reason("psum", "none")
    )
    # constructing the now-legal combo works; the host combo raises the
    # same prescriptive text the query returns
    XlaCommContext(algorithm="psum", compression="int8")
    with pytest.raises(ValueError, match="no psum"):
        TcpCommContext(algorithm="psum")
    # wrappers follow the wrapped backend, not the identity default
    wrapped = ErrorSwallowingCommContext(TcpCommContext(timeout=1.0))
    assert not wrapped.supports("psum", "none")
    assert wrapped.supports("star", "int8")
    assert DummyCommContext().supports("psum", "int8", ReduceOp.MAX)
    # the managed surface routes through Manager.comm_supports /
    # comm_unsupported_reason (WireStubManager mirrors that surface)
    from torchft_tpu.comm.context import ManagedCommContext
    from torchft_tpu.comm.wire_stub import WireStubManager

    mcc = ManagedCommContext(WireStubManager(
        XlaCommContext(algorithm="psum", compression="int8"), 2
    ))
    assert mcc.supports("psum", "int8")
    assert not mcc.supports("psum", "int8", ReduceOp.MAX)


def test_quantized_psum_max_raises_prescriptive(mesh_mgr) -> None:
    world = 2
    ctxs = _qpsum_ctxs(mesh_mgr, world, "int8")
    inputs = _inputs(world, seed=5, size=256)

    def body(ctx, rank):
        w = ctx.allreduce([inputs[rank].copy()], ReduceOp.MAX)
        with pytest.raises(ValueError, match="only ACCUMULATES"):
            w.future().result(timeout=30)
        return True

    assert all(_run_cohort(ctxs, "qmax", world, body))
    for c in ctxs:
        c.shutdown()


# ------------------------------------------- numeric + bytes-on-wire


@pytest.mark.parametrize("codec,ratio_max,err_div", [
    ("int8", 0.30, 100.0),   # 1B payload + 4B/chunk scales vs 4B elems
    ("bf16", 0.51, 100.0),   # 2B payload, no scales
])
def test_quantized_psum_numeric_counters_trajectory(
    mesh_mgr, codec, ratio_max, err_div
) -> None:
    # Numeric oracle (XLA owns the order): the quantized reduction must
    # land within the codec's quantization-error envelope of the exact
    # f64 sum, every rank must decode IDENTICAL bytes (trajectory
    # consistency — the all-gather ships encoded bytes, decode is
    # deterministic), and the encoded-bytes counters must report the
    # codec's ratio, not raw.
    world = 4
    inputs = _inputs(world, seed=11)
    exact = np.sum(inputs, axis=0, dtype=np.float64)
    absmax = max(float(np.abs(a).max()) for a in inputs)
    bound = (world + 1) * absmax / err_div
    for op in (ReduceOp.SUM, ReduceOp.AVG):
        ctxs = _qpsum_ctxs(mesh_mgr, world, codec)

        def body(ctx, rank):
            w = ctx.allreduce([inputs[rank].copy()], op)
            return w.future().result(timeout=60)[0]

        results = _run_cohort(ctxs, f"qn_{codec}_{op}", world, body)
        expected = exact / world if op == ReduceOp.AVG else exact
        assert float(np.abs(results[0] - expected).max()) < bound
        ref = results[0].tobytes()
        assert all(r.tobytes() == ref for r in results), (
            "ranks decoded divergent bytes — trajectory consistency "
            "broken"
        )
        for ctx in ctxs:
            snap = ctx.metrics.snapshot()
            raw = snap.get("comm_raw_bytes")
            enc = snap.get("comm_encoded_bytes")
            assert raw and enc and np.isfinite(raw) and np.isfinite(enc)
            assert enc / raw <= ratio_max, (codec, enc, raw)
            # wire_nbytes (the gauge definition) agrees with the
            # counter increment per op
            assert enc == ctx.wire_nbytes(inputs[0])
        for c in ctxs:
            c.shutdown()


def test_quantized_psum_mixed_payload_int_rides_raw(mesh_mgr) -> None:
    # Non-f32 device dtypes ride an uncompressed native psum branch in
    # the SAME executable (the host codecs' _is_compressible gate):
    # integer sums must come back exact.
    world = 2
    rng = np.random.default_rng(7)
    floats = [
        (rng.standard_normal(300) * (r + 1)).astype(np.float32)
        for r in range(world)
    ]
    ints = [
        rng.integers(-50, 50, 100).astype(np.int32) for r in range(world)
    ]
    ctxs = _qpsum_ctxs(mesh_mgr, world, "int8")

    def body(ctx, rank):
        w = ctx.allreduce([floats[rank].copy(), ints[rank].copy()])
        return w.future().result(timeout=60)

    results = _run_cohort(ctxs, "qmix", world, body)
    assert np.array_equal(results[0][1], ints[0] + ints[1])
    exact = (floats[0] + floats[1]).astype(np.float64)
    absmax = max(float(np.abs(a).max()) for a in floats)
    assert float(np.abs(results[0][0] - exact).max()) < 3 * absmax / 100
    for c in ctxs:
        c.shutdown()


def test_quantized_psum_zero_size_payload(mesh_mgr) -> None:
    # Every other path supports size-0 arrays (an empty leaf in a grad
    # tree); the quantized exchange must too — the empty view ships
    # nothing and the non-empty neighbors reduce normally.
    world = 2
    rng = np.random.default_rng(29)
    floats = [
        (rng.standard_normal(100) * (r + 1)).astype(np.float32)
        for r in range(world)
    ]
    ctxs = _qpsum_ctxs(mesh_mgr, world, "int8")

    def body(ctx, rank):
        w = ctx.allreduce([
            np.zeros(0, np.float32), floats[rank].copy(),
        ])
        return w.future().result(timeout=60)

    results = _run_cohort(ctxs, "qzero", world, body)
    assert results[0][0].size == 0
    exact = (floats[0] + floats[1]).astype(np.float64)
    absmax = max(float(np.abs(a).max()) for a in floats)
    assert float(np.abs(results[0][1] - exact).max()) < 3 * absmax / 100
    for c in ctxs:
        c.shutdown()


def test_wire_nbytes_codec_aware_on_psum_path() -> None:
    # Satellite: the native path used to be stuck reporting raw bytes
    # (it could not carry a codec at all). A quantized-psum context must
    # report the same encoded size as the host plane at the same grid —
    # outer_wire_bytes/compression gauges stay honest.
    src = np.zeros(6000, np.float32)
    qp = XlaCommContext(algorithm="psum", compression="int8",
                        chunk_bytes=CHUNK)
    host = TcpCommContext(algorithm="star", compression="int8",
                          chunk_bytes=CHUNK)
    assert qp.wire_nbytes(src) == host.wire_nbytes(src)
    assert qp.wire_nbytes(src) == codec_wire_nbytes(
        _CODECS["int8"](), CHUNK, src
    )
    assert qp.wire_nbytes(src) < src.nbytes * 0.3
    raw = XlaCommContext(algorithm="psum", compression="none")
    assert raw.wire_nbytes(src) == src.nbytes


# ------------------------------------------------- convergence oracle


def test_residual_parity_host_vs_device(mesh_mgr) -> None:
    # THE convergence-oracle precondition: the device phase-1 encode is
    # bit-identical to the host codec at matching chunk grids, so the
    # EF arena's wire_roundtrip (host numpy) images exactly what the
    # quantized exchange transmits.
    rng = np.random.default_rng(3)
    src = rng.standard_normal(6000).astype(np.float32)
    src[17] = 250.0  # per-chunk outlier: scales differ across chunks
    for codec in ("int8", "bf16"):
        host = np.empty_like(src)
        codec_roundtrip(_CODECS[codec](), CHUNK, src, host)
        dev = device_codec_roundtrip(codec, CHUNK, src)
        assert host.tobytes() == dev.tobytes(), codec
    # nonfinite poisons the chunk's scale alike on both sides (NaN
    # decode, never silent clipping)
    bad = src.copy()
    bad[5] = np.inf
    host = np.empty_like(bad)
    codec_roundtrip(_CODECS["int8"](), CHUNK, bad, host)
    dev = device_codec_roundtrip("int8", CHUNK, bad)
    assert np.isnan(dev[: CHUNK // 4]).all()
    assert host.tobytes() == dev.tobytes()
    # role surface: on the quantized psum path EVERY rank's
    # contribution crosses the exchange encoded -> all compensable, and
    # wire_roundtrip serves the host image (not identity)
    for rank in (0, 1):
        ctx = XlaCommContext(algorithm="psum", compression="int8",
                             chunk_bytes=CHUNK)
        ctx._rank, ctx._world_size = rank, 2
        assert ctx.wire_compensable()
        out = np.empty_like(src)
        ctx.wire_roundtrip(src, out)
        ref = np.empty_like(src)
        codec_roundtrip(_CODECS["int8"](), CHUNK, src, ref)
        assert out.tobytes() == ref.tobytes()
    lossless = XlaCommContext(algorithm="psum", compression="none")
    lossless._rank, lossless._world_size = 1, 2
    assert not lossless.wire_compensable()


def _descend(mesh_mgr, tag, codec, error_feedback, steps, targets,
             chunk_bytes=64, tail=40):
    """2-replica GD on f(x) = mean_r 0.5*||x - t_r||^2 through the
    QUANTIZED PSUM wire + DDP (the PR 2 toy-quadratic oracle,
    tests/test_transport_striping.py). Returns rank 0's Polyak tail
    average: EF's transmitted error is a delayed correction whose limit
    cycle time-averages out; raw quantization bias survives any
    averaging."""
    from torchft_tpu.ddp import DistributedDataParallel
    from torchft_tpu.comm.wire_stub import WireStubManager

    world = len(targets)
    ctxs = _qpsum_ctxs(mesh_mgr, world, codec, chunk_bytes=chunk_bytes)

    def body(ctx, rank):
        manager = WireStubManager(ctx, world)
        ddp = DistributedDataParallel(manager,
                                      error_feedback=error_feedback)
        x = np.zeros_like(targets[rank])
        acc = np.zeros(x.shape, np.float64)
        for t in range(steps):
            avg = ddp.average_gradients({"x": x - targets[rank]})
            x = x - 0.2 * np.asarray(avg["x"])
            if t >= steps - tail:
                acc += x
        return (acc / tail).astype(np.float32)

    try:
        return _run_cohort(ctxs, tag, world, body, timeout=300)[0]
    finally:
        for c in ctxs:
            c.shutdown()


def test_int8_ef_converges_over_quantized_psum_where_raw_parks(
    mesh_mgr,
) -> None:
    # Heterogeneous per-chunk magnitudes (a few 100x elements dominate
    # each chunk's absmax) — the regime where raw int8 bias is worst.
    # int8+EF over the QUANTIZED NATIVE path must track the fp32-psum
    # trajectory to ~1e-3 of the problem scale; raw int8 parks at a
    # bias fixed point an order of magnitude worse.
    rng = np.random.default_rng(17)
    targets = []
    for _ in range(2):
        t = rng.standard_normal(48).astype(np.float32)
        t[:4] *= 100.0
        targets.append(t)
    optimum = (targets[0] + targets[1]) / 2.0
    scale = float(np.abs(optimum).max())
    steps = 200

    x_fp32 = _descend(mesh_mgr, "qef_fp32", "none", "auto", steps,
                      targets)
    x_raw = _descend(mesh_mgr, "qef_raw", "int8", False, steps, targets)
    x_ef = _descend(mesh_mgr, "qef_on", "int8", "auto", steps, targets)

    err_fp32 = float(np.max(np.abs(x_fp32 - optimum)))
    err_raw = float(np.max(np.abs(x_raw - optimum)))
    err_ef = float(np.max(np.abs(x_ef - optimum)))

    # fp32 psum converges essentially exactly at this step count
    assert err_fp32 < 1e-4
    # EF tracks fp32 to ~1e-3 RELATIVE to the problem scale (the
    # acceptance bar; measured ~2e-5 relative / ~2e-3 absolute with
    # scale ~113) ...
    assert float(np.max(np.abs(x_ef - x_fp32))) < 1e-3 * scale, (
        f"int8+EF did not track fp32 (ef={err_ef}, fp32={err_fp32})"
    )
    assert err_ef < 2e-2, f"int8+EF did not converge (err={err_ef})"
    # ... while raw int8 parks at a bias fixed point an order worse
    assert err_raw > 1e-1, (
        f"raw int8 unexpectedly converged (err={err_raw})"
    )
    assert err_raw > 10 * err_ef, (
        f"raw int8 unexpectedly matched EF (raw={err_raw}, ef={err_ef})"
    )


# ------------------------------------------- compile-count discipline


def test_quantized_psum_compile_cache_kill_reform() -> None:
    # THE acceptance pin: exactly 1 compile per (world, codec, layout)
    # across a kill -> shrink -> reform cycle, ZERO retraces — a death
    # costs a cache lookup at the step boundary, never a recompile.
    mm = MeshManager()
    inputs4 = _inputs(4, seed=42)
    inputs3 = _inputs(3, seed=43)

    def round_of(ctxs, tag, inputs):
        world = len(ctxs)

        def body(ctx, rank):
            w = ctx.allreduce([inputs[rank].copy()])
            return w.future().result(timeout=60)[0]

        return _run_cohort(ctxs, tag, world, body)

    ctxs = _qpsum_ctxs(mm, 4, "int8")
    round_of(ctxs, "qchurn/e1", inputs4)
    assert mm.compile_count == 1 and mm.trace_count == 1

    # steady state at the same world size: pure cache hits
    hits0 = mm.hit_count
    round_of(ctxs, "qchurn/e1b", inputs4)
    assert mm.compile_count == 1 and mm.trace_count == 1
    assert mm.hit_count > hits0

    # replica 3 dies; survivors reform at world 3: ONE new compile
    ctxs[3].shutdown()
    survivors = ctxs[:3]
    round_of(survivors, "qchurn/e2", inputs3)
    assert mm.compile_count == 2 and mm.trace_count == 2

    # the replica comes back: world 4 was seen — ZERO new compiles
    ctxs = _qpsum_ctxs(mm, 4, "int8")
    hits1 = mm.hit_count
    round_of(ctxs, "qchurn/e3", inputs4)
    assert mm.compile_count == 2 and mm.trace_count == 2
    assert mm.hit_count > hits1
    for c in ctxs:
        c.shutdown()

    # a different codec at the same world is a DIFFERENT executable
    # (one compile per (world, codec)), not a retrace of the first
    ctxs = _qpsum_ctxs(mm, 4, "bf16")
    round_of(ctxs, "qchurn/e4", inputs4)
    assert mm.compile_count == 3 and mm.trace_count == 3
    for c in ctxs:
        c.shutdown()


# ------------------------------------------- sharded update integration


def test_sharded_update_over_quantized_psum_scatter(mesh_mgr) -> None:
    # ZERO call-site changes: ShardedOptimizerWrapper's reduce_scatter
    # lands on the quantized psum_scatter executable purely by comm
    # configuration. Oracle: the sharded arm over the quantized wire
    # stays within the int8 quantization envelope of the replicated
    # fp32 arm, and all ranks' allgathered params agree bitwise.
    import optax

    import jax
    import jax.numpy as jnp
    from torchft_tpu.optim import ShardedOptimizerWrapper
    from torchft_tpu.comm.wire_stub import WireStubManager

    world = 2
    rng = np.random.default_rng(0)
    params0 = {
        f"w{i}": rng.standard_normal(257 + i).astype(np.float32)
        for i in range(4)
    }
    grads0 = {
        k: (rng.standard_normal(v.shape[0]) * 0.5).astype(np.float32)
        for k, v in params0.items()
    }

    def run(codec, sharded, tag):
        ctxs = _qpsum_ctxs(mesh_mgr, world, codec)

        def body(ctx, rank):
            mgr = WireStubManager(ctx, world)
            opt = ShardedOptimizerWrapper(mgr, optax.sgd(0.1),
                                          sharded=sharded)
            params = jax.tree_util.tree_map(jnp.asarray, params0)
            state = opt.init(params)
            grads = jax.tree_util.tree_map(jnp.asarray, grads0)
            params, state, ok = opt.step(params, state, grads)
            assert ok, "sharded step discarded"
            return {k: np.asarray(v) for k, v in params.items()}

        try:
            return _run_cohort(ctxs, tag, world, body)
        finally:
            for c in ctxs:
                c.shutdown()

    quant = run("int8", True, "qshard_q")
    full = run("none", False, "qshard_f")
    # ranks agree bitwise after the params allgather (raw bytes)
    for k in params0:
        assert quant[0][k].tobytes() == quant[1][k].tobytes()
        # identical grads on both ranks -> average == grad; the only
        # difference vs the replicated fp32 arm is the int8 wire
        envelope = 0.1 * 2 * float(np.abs(grads0[k]).max()) / 100
        assert float(np.abs(quant[0][k] - full[0][k]).max()) <= envelope
