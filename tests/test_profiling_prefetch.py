"""StepProfiler (XLA trace windows) and PrefetchIterator (H2D pipeline)."""

import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchft_tpu.data import PrefetchIterator
from torchft_tpu.utils.profiling import StepProfiler


def test_step_profiler_disabled_is_noop(monkeypatch) -> None:
    monkeypatch.delenv("TORCHFT_TPU_PROFILE_DIR", raising=False)
    p = StepProfiler()
    assert not p.enabled
    for _ in range(10):
        p.step()
    p.close()


def test_step_profiler_traces_window(tmp_path) -> None:
    log_dir = str(tmp_path / "trace")
    p = StepProfiler(log_dir=log_dir, start=2, num_steps=2)
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    for _ in range(6):
        jax.block_until_ready(f(x))
        p.step()
    p.close()
    # a plugins/profile/<ts>/ tree with at least one trace artifact
    found = []
    for root, _, files in os.walk(log_dir):
        found.extend(files)
    assert found, f"no trace files under {log_dir}"


def test_step_profiler_early_loop_exit_closes_trace(tmp_path) -> None:
    log_dir = str(tmp_path / "early")
    p = StepProfiler(log_dir=log_dir, start=0, num_steps=100)
    p.step()  # starts the trace; loop "ends" before the window does
    p.close()
    assert any(files for _, _, files in os.walk(log_dir))


# ------------------------------------------------------------- prefetch


def test_prefetch_yields_all_batches_in_order() -> None:
    batches = [{"x": np.full((4,), i)} for i in range(10)]
    it = PrefetchIterator(iter(batches), depth=2)
    out = list(it)
    assert len(out) == 10
    for i, b in enumerate(out):
        assert isinstance(b["x"], jax.Array)  # device-placed
        np.testing.assert_array_equal(np.asarray(b["x"]), np.full((4,), i))


def test_prefetch_overlaps_source_latency() -> None:
    # with depth=2 the worker runs ahead while the consumer "computes":
    # the consumer's step on batch i IS waiting until the source has been
    # asked for batch i+1. An iterator that read the source only when
    # asked would never get there (10 s: the deadline for that, no bound
    # on a time)
    asked_for = [threading.Event() for _ in range(6)]

    def source():
        for i, asked in enumerate(asked_for):
            asked.set()
            yield np.full((2,), i)

    seen = []
    for i, b in enumerate(PrefetchIterator(source(), depth=2)):
        if i + 1 < len(asked_for):
            assert asked_for[i + 1].wait(timeout=10), (
                f"batch {i + 1} was not read while batch {i} was in use")
        seen.append(b)
    assert len(seen) == 6


def test_prefetch_propagates_source_error() -> None:
    def bad_source():
        yield np.zeros((2,))
        raise RuntimeError("dataset exploded")

    it = PrefetchIterator(bad_source())
    next(it)
    with pytest.raises(RuntimeError, match="dataset exploded"):
        next(it)


def test_prefetch_close_unblocks_worker() -> None:
    it = PrefetchIterator((np.zeros((2,)) for _ in range(1000)), depth=1)
    next(it)
    it.close()  # must not hang


def test_prefetch_exhausted_iterator_stays_stopped() -> None:
    it = PrefetchIterator(iter([np.zeros((2,))]))
    assert len(list(it)) == 1
    with pytest.raises(StopIteration):
        next(it)  # must not hang


def test_prefetch_error_then_next_raises_stop() -> None:
    def bad():
        raise RuntimeError("boom")
        yield  # pragma: no cover

    it = PrefetchIterator(bad())
    with pytest.raises(RuntimeError):
        next(it)
    with pytest.raises(StopIteration):
        next(it)  # terminal state latched, no hang
