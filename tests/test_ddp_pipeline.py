"""Streamed step pipeline: bitwise identity vs the lock-step path,
arena-generation overlap/aliasing, EF-offload ordering, Pure DDP parity,
and the FutureGroup barrier (docs/architecture.md "Step pipeline").

The load-bearing invariant: the streamed/out-of-order pipeline is a pure
SCHEDULING change — same math, same buffers, same per-lane submission
order — so its results must be bitwise identical to the PR 2 lock-step
path for every codec, both topologies, EF on and off, at every step of a
multi-step run (residual evolution included)."""

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from unittest.mock import MagicMock

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from torchft_tpu.comm import ReduceOp, StoreServer, TcpCommContext
from torchft_tpu.comm.context import CompletedWork, Work
from torchft_tpu.ddp import DistributedDataParallel, PureDistributedDataParallel
from torchft_tpu.futures import FutureGroup, completed_future, future_chain
from torchft_tpu.optim import OptimizerWrapper
from torchft_tpu.utils.metrics import Metrics


@pytest.fixture()
def store():
    server = StoreServer()
    yield server
    server.shutdown()


class _WireStubManager:
    """Manager facade over a raw TcpCommContext (the test_transport_striping
    stub, plus a real Metrics sink so the pipeline stage timers can be
    asserted): quorum is a no-op, AVG scaling divides by the wire world,
    wire_* introspection passes through."""

    def __init__(self, ctx: TcpCommContext, world: int) -> None:
        self._ctx = ctx
        self._world = world
        self.metrics = Metrics()

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


def _grad_tree(rank: int):
    """Multi-dtype, multi-leaf tree that splits into >= 4 buckets at
    bucket_bytes=512 (three 128-elem f32 leaves = 512B each -> three f32
    buckets, plus an f64 and an int bucket)."""
    rng = np.random.default_rng(100 + rank)
    return {
        "w1": rng.standard_normal(128).astype(np.float32),
        "w2": rng.standard_normal(128).astype(np.float32),
        "w3": rng.standard_normal(128).astype(np.float32),
        "b": rng.standard_normal(40).astype(np.float64),
        "i": np.arange(9, dtype=np.int64) * (rank + 1),
    }


def _run_mode(store, prefix, algorithm, world, codec, ef, streamed,
              steps=3):
    """Run `steps` averages through a real transport world; returns the
    per-step averaged trees (host copies) for every rank."""
    ctxs = [
        TcpCommContext(timeout=15.0, algorithm=algorithm, channels=3,
                       compression=codec, chunk_bytes=256)
        for _ in range(world)
    ]
    outs = [None] * world

    def _worker(rank):
        ctx = ctxs[rank]
        ctx.configure(f"{store.addr}/{prefix}", rank, world)
        ddp = DistributedDataParallel(
            _WireStubManager(ctx, world), bucket_bytes=512,
            error_feedback=ef, streamed=streamed,
        )
        base = _grad_tree(rank)
        per_step = []
        for t in range(steps):
            grads = {
                k: (v * (t + 1)).astype(v.dtype) for k, v in base.items()
            }
            avg = ddp.average_gradients(grads)
            per_step.append(
                {k: np.asarray(avg[k]).copy() for k in sorted(avg)}
            )
        outs[rank] = per_step

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(_worker, r) for r in range(world)]:
            f.result(timeout=120)
    for ctx in ctxs:
        ctx.shutdown()
    return outs


@pytest.mark.parametrize("algorithm,world", [("star", 2), ("ring", 3)])
@pytest.mark.parametrize("codec", ["none", "bf16", "int8"])
def test_streamed_bitwise_identical_to_lockstep(
    store, algorithm, world, codec
) -> None:
    # EF "auto" engages exactly where it should (star peers under a lossy
    # codec; identity/ring keep it off) — the identity must hold with the
    # residual arena evolving across steps in both modes.
    streamed = _run_mode(
        store, f"sp_{algorithm}_{codec}", algorithm, world, codec,
        "auto", streamed=True,
    )
    lockstep = _run_mode(
        store, f"ls_{algorithm}_{codec}", algorithm, world, codec,
        "auto", streamed=False,
    )
    for rank in range(world):
        for t, (got, ref) in enumerate(zip(streamed[rank], lockstep[rank])):
            for key in ref:
                assert got[key].tobytes() == ref[key].tobytes(), (
                    f"{algorithm}/{codec}: streamed diverged from "
                    f"lock-step at step {t}, rank {rank}, leaf {key!r}"
                )
    # cross-rank identity within the streamed run (trajectory consistency)
    for rank in range(1, world):
        for t in range(len(streamed[0])):
            for key in streamed[0][t]:
                assert (
                    streamed[rank][t][key].tobytes()
                    == streamed[0][t][key].tobytes()
                )


def test_streamed_identical_to_lockstep_ef_disabled(store) -> None:
    # error_feedback=False (raw quantization) is its own code path on
    # both sides; it must also match bitwise.
    streamed = _run_mode(
        store, "sp_rawq", "star", 2, "int8", False, streamed=True
    )
    lockstep = _run_mode(
        store, "ls_rawq", "star", 2, "int8", False, streamed=False
    )
    for rank in range(2):
        for got, ref in zip(streamed[rank], lockstep[rank]):
            for key in ref:
                assert got[key].tobytes() == ref[key].tobytes()


def test_pipeline_stage_timers_and_op_wire_metric(store) -> None:
    # Per-bucket stage timers land in the manager's metrics sink (d2h/
    # ef/wire/h2d + the two overlap gauges), and the transport observes
    # the op-level comm_op_wire.
    world = 2
    ctxs = [
        TcpCommContext(timeout=15.0, algorithm="star", channels=3,
                       compression="int8", chunk_bytes=256)
        for _ in range(world)
    ]
    snaps = [None] * world
    ctx_snaps = [None] * world

    def _worker(rank):
        ctx = ctxs[rank]
        ctx.configure(f"{store.addr}/stage_timers", rank, world)
        stub = _WireStubManager(ctx, world)
        ddp = DistributedDataParallel(stub, bucket_bytes=512)
        base = _grad_tree(rank)
        for _ in range(2):
            ddp.average_gradients(base)
        snaps[rank] = stub.metrics.snapshot()
        ctx_snaps[rank] = ctx.metrics.snapshot()

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(_worker, r) for r in range(world)]:
            f.result(timeout=60)
    for ctx in ctxs:
        ctx.shutdown()

    # rank 1 is a star PEER: compensable -> the ef stage actually ran
    snap = snaps[1]
    for stage in ("ddp_d2h", "ddp_ef", "ddp_h2d",
                  "ddp_wire_total", "ddp_wire_exposed"):
        assert f"{stage}_avg_ms" in snap, (stage, sorted(snap))
        assert np.isfinite(snap[f"{stage}_avg_ms"])
    # the star root never encodes its own contribution: no ef stage
    assert "ddp_ef_avg_ms" not in snaps[0]
    # op-level wire timing from the transport (striped ops only report
    # per-sub-op wire_reduce otherwise)
    assert "comm_op_wire_avg_ms" in ctx_snaps[0]


# -------------------------------------------------- arena generations


def _mock_manager():
    m = MagicMock()
    m.is_solo_wire.return_value = False
    m.is_participating.return_value = True
    m.wire_compensable.return_value = False
    return m


def _donated_delayed_allreduce(delay):
    """Work that resolves to the DONATED arrays after `delay` — exactly
    the transport's contract, so arena aliasing bugs surface as values
    from the wrong call."""

    def _ar(arrays, **kw):
        fut: Future = Future()
        fut.set_running_or_notify_cancel()
        arrs = list(arrays)

        def _complete():
            time.sleep(delay)
            fut.set_result(arrs)

        threading.Thread(target=_complete, daemon=True).start()
        return Work(fut)

    return _ar


def test_arena_generations_allow_overlapping_averages() -> None:
    # Two arenas: a second average may pack while the first is on the
    # wire; both must resolve to their OWN values (the donated staging
    # buffers are per-generation, and results are jnp.array copies).
    manager = _mock_manager()
    manager.allreduce_arrays.side_effect = _donated_delayed_allreduce(0.25)
    ddp = DistributedDataParallel(manager, bucket_bytes=64,
                                  staging_arenas=2)
    grads_a = {"w": jnp.arange(32, dtype=jnp.float32)}
    grads_b = {"w": jnp.arange(32, dtype=jnp.float32) * 100.0}
    fut_a = ddp.average_gradients_async(grads_a)
    fut_b = ddp.average_gradients_async(grads_b)  # must NOT raise
    out_a = fut_a.result(timeout=10)
    out_b = fut_b.result(timeout=10)
    np.testing.assert_array_equal(np.asarray(out_a["w"]),
                                  np.arange(32, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(out_b["w"]),
                                  np.arange(32, dtype=np.float32) * 100.0)


def test_arena_results_survive_next_pack() -> None:
    # The jnp.array-not-asarray contract: a resolved average's leaves
    # must not alias the staging arena — the NEXT call's pack into the
    # same generation must not change them.
    manager = _mock_manager()
    manager.allreduce_arrays.side_effect = _donated_delayed_allreduce(0.05)
    ddp = DistributedDataParallel(manager, bucket_bytes=64,
                                  staging_arenas=1)
    out_a = ddp.average_gradients({"w": jnp.full(32, 7.0, jnp.float32)})
    snapshot = np.asarray(out_a["w"]).copy()
    # reuses (and overwrites) the same generation-0 staging buffer
    ddp.average_gradients({"w": jnp.full(32, -3.0, jnp.float32)})
    np.testing.assert_array_equal(np.asarray(out_a["w"]), snapshot)


def test_all_arenas_in_flight_is_a_hard_error() -> None:
    manager = _mock_manager()
    manager.allreduce_arrays.side_effect = _donated_delayed_allreduce(0.4)
    ddp = DistributedDataParallel(manager, bucket_bytes=64,
                                  staging_arenas=2)
    grads = {"w": jnp.ones(32, jnp.float32)}
    futs = [ddp.average_gradients_async(grads) for _ in range(2)]
    with pytest.raises(RuntimeError, match="in flight"):
        ddp.average_gradients_async(grads)
    for f in futs:
        f.result(timeout=10)
    # after the in-flight averages resolve, acquisition works again
    ddp.average_gradients_async(grads).result(timeout=10)


def test_single_arena_restores_one_outstanding_guard() -> None:
    manager = _mock_manager()
    manager.allreduce_arrays.side_effect = _donated_delayed_allreduce(0.3)
    ddp = DistributedDataParallel(manager, bucket_bytes=64,
                                  staging_arenas=1)
    grads = {"w": jnp.ones(16, jnp.float32)}
    fut = ddp.average_gradients_async(grads)
    with pytest.raises(RuntimeError, match="in flight"):
        ddp.average_gradients_async(grads)
    fut.result(timeout=10)


def test_midloop_failure_keeps_arena_guard() -> None:
    # A submit-loop failure after bucket 0 is already on the wire must
    # NOT leave the arena looking free: a retrying caller would pack
    # into staging the lane threads are still reducing into — corrupted
    # buffers with no error anywhere (code-review finding). The guard
    # future must hold until the in-flight bucket settles, then clear.
    manager = _mock_manager()
    delayed = _donated_delayed_allreduce(0.3)
    calls = []

    def _flaky(arrays, **kw):
        calls.append(None)
        if len(calls) == 2:
            raise RuntimeError("submit blew up")
        return delayed(arrays, **kw)

    manager.allreduce_arrays.side_effect = _flaky
    ddp = DistributedDataParallel(manager, bucket_bytes=64,
                                  staging_arenas=1)
    grads = {
        "a": jnp.ones(32, jnp.float32),
        "b": jnp.ones(32, jnp.bfloat16),  # second (failing) bucket
    }
    with pytest.raises(RuntimeError, match="submit blew up"):
        ddp.average_gradients_async(grads)
    # bucket 0 is still riding the (delayed) wire: the arena must be
    # guarded even though the call above raised
    with pytest.raises(RuntimeError, match="in flight"):
        ddp.average_gradients_async(grads)
    time.sleep(0.5)  # let bucket 0 settle -> the guard future resolves
    manager.allreduce_arrays.side_effect = delayed
    out = ddp.average_gradients_async(grads).result(timeout=10)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.ones(32, np.float32))


def test_staging_arenas_validation() -> None:
    with pytest.raises(ValueError, match="staging_arenas"):
        DistributedDataParallel(_mock_manager(), staging_arenas=0)


# ------------------------------------------------------- Pure DDP parity


def test_pure_ddp_latches_quorum_failure() -> None:
    manager = _mock_manager()
    manager.wait_quorum.side_effect = TimeoutError("quorum timed out")
    ddp = PureDistributedDataParallel(manager)
    grads = {"w": jnp.ones(4)}
    out = ddp.average_gradients(grads)
    # latched (so should_commit votes False), never raised, grads
    # returned untouched, transport never touched
    manager.report_error.assert_called_once()
    assert isinstance(manager.report_error.call_args[0][0], TimeoutError)
    assert out is grads
    manager.allreduce_arrays.assert_not_called()


def test_pure_ddp_solo_wire_fast_path() -> None:
    manager = _mock_manager()
    manager.is_solo_wire.return_value = True
    ddp = PureDistributedDataParallel(manager)
    grads = {"w": jnp.full(4, 3.0)}
    out = ddp.average_gradients(grads)
    assert out is grads
    manager.allreduce_arrays.assert_not_called()
    manager.wait_quorum.assert_called_once()


def test_pure_ddp_still_averages_with_peers() -> None:
    manager = _mock_manager()
    manager.allreduce_arrays.side_effect = lambda arrays, **kw: (
        CompletedWork([np.array(a, copy=True) for a in arrays])
    )
    ddp = PureDistributedDataParallel(manager)
    grads = {"w": jnp.full((2,), 3.0), "b": jnp.ones(1)}
    out = ddp.average_gradients(grads)
    np.testing.assert_allclose(np.asarray(out["w"]), np.full(2, 3.0))
    assert manager.allreduce_arrays.call_count == 2  # one per leaf
    manager.wait_quorum.assert_called_once()


# ------------------------------------------- optimizer future-grads hook


def test_optimizer_step_accepts_grads_future() -> None:
    # The cross-step overlap surface: a loop hands the UNRESOLVED
    # average_gradients_async future straight to step().
    manager = MagicMock()
    manager.did_heal.return_value = False

    def _commit_async(**kw):
        fut = completed_future(True)
        fut.local_should_commit = True
        return fut

    manager.should_commit_async.side_effect = _commit_async
    opt = OptimizerWrapper(manager, optax.sgd(0.1))
    params = {"w": jnp.ones(3)}
    state = opt.init(params)
    grads_fut = completed_future({"w": jnp.full(3, 2.0)})
    new_params, _, committed = opt.step(params, state, grads_fut)
    assert committed
    np.testing.assert_allclose(
        np.asarray(new_params["w"]), np.full(3, 0.8), rtol=1e-6
    )


# ----------------------------------------------------------- FutureGroup


def test_future_group_resolves_after_all_members() -> None:
    group = FutureGroup()
    members = [Future() for _ in range(3)]
    for m in members:
        m.set_running_or_notify_cancel()
        group.add(m)
    out = group.seal(lambda: "done")
    members[2].set_result(None)  # out of order
    members[0].set_result(None)
    assert not out.done()
    members[1].set_result(None)
    assert out.result(timeout=5) == "done"


def test_future_group_empty_seal_resolves_immediately() -> None:
    group = FutureGroup()
    assert group.seal(lambda: 42).result(timeout=1) == 42


def test_future_group_member_error_fails_after_all_settle() -> None:
    group = FutureGroup()
    a, b = Future(), Future()
    for m in (a, b):
        m.set_running_or_notify_cancel()
        group.add(m)
    out = group.seal(lambda: "never")
    a.set_exception(ValueError("boom"))
    # one member failed, but the group must stay open until b settles
    # (the arena-quiescence guarantee)
    assert not out.done()
    b.set_result(None)
    with pytest.raises(ValueError, match="boom"):
        out.result(timeout=5)


def test_future_group_add_after_seal_rejected() -> None:
    group = FutureGroup()
    group.seal(lambda: None)
    f = Future()
    f.set_running_or_notify_cancel()
    with pytest.raises(RuntimeError, match="after seal"):
        group.add(f)


def test_future_group_accepts_completed_members() -> None:
    group = FutureGroup()
    group.add(completed_future(1))
    group.add(completed_future(2))
    assert group.seal(lambda: "ok").result(timeout=1) == "ok"
