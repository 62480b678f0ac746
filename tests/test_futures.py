"""Tests for torchft_tpu.futures (spec: ref futures_test.py semantics)."""

import threading
import time
from concurrent.futures import Future

import pytest

from torchft_tpu.futures import (
    completed_future,
    failed_future,
    future_chain,
    future_timeout,
    future_wait,
)


def test_future_timeout_success() -> None:
    fut: Future = Future()
    wrapped = future_timeout(fut, 5.0)
    fut.set_result(42)
    assert wrapped.result(timeout=1.0) == 42


def test_future_timeout_expiry() -> None:
    fut: Future = Future()
    wrapped = future_timeout(fut, 0.05)
    with pytest.raises(TimeoutError):
        wrapped.result(timeout=2.0)
    # original future untouched
    assert not fut.done()


def test_future_timeout_exception_propagates() -> None:
    fut: Future = Future()
    wrapped = future_timeout(fut, 5.0)
    fut.set_exception(RuntimeError("boom"))
    with pytest.raises(RuntimeError, match="boom"):
        wrapped.result(timeout=1.0)


def test_future_timeout_late_completion_ignored() -> None:
    fut: Future = Future()
    wrapped = future_timeout(fut, 0.05)
    time.sleep(0.2)
    fut.set_result("late")  # must not raise even though wrapper timed out
    with pytest.raises(TimeoutError):
        wrapped.result(timeout=1.0)


def test_finished_timeout_releases_what_its_future_reaches() -> None:
    # A finished op's deadline entry sits in the timer heap until the
    # deadline passes; it must not keep the op's future — and through its
    # continuations a whole step's device buffers — alive that long. (At
    # 125m this held ~1 GB of gradients per classic step for `timeout`
    # seconds: RESOURCE_EXHAUSTED on the chip after a few steps.)
    import gc
    import weakref

    class Payload:
        pass

    fut: Future = Future()
    wrapped = future_timeout(fut, 600.0)
    payload = Payload()
    wrapped.add_done_callback(lambda _f, _p=payload: None)
    alive = weakref.ref(payload)
    fut.set_result(1)
    assert wrapped.result(timeout=1.0) == 1
    del fut, wrapped, payload
    gc.collect()
    assert alive() is None


def test_future_wait() -> None:
    fut: Future = Future()

    def _complete() -> None:
        time.sleep(0.05)
        fut.set_result("ok")

    threading.Thread(target=_complete, daemon=True).start()
    assert future_wait(fut, 2.0) == "ok"


def test_future_wait_timeout() -> None:
    fut: Future = Future()
    with pytest.raises(TimeoutError):
        future_wait(fut, 0.05)


def test_future_chain_value_and_error() -> None:
    fut: Future = Future()
    chained = future_chain(fut, lambda f: f.result() + 1)
    fut.set_result(1)
    assert chained.result(timeout=1.0) == 2

    bad: Future = Future()
    chained2 = future_chain(bad, lambda f: f.result())
    bad.set_exception(ValueError("nope"))
    with pytest.raises(ValueError):
        chained2.result(timeout=1.0)


def test_chain_observes_error_and_recovers() -> None:
    bad: Future = Future()
    recovered = future_chain(
        bad, lambda f: "fallback" if f.exception() else f.result()
    )
    bad.set_exception(ValueError("nope"))
    assert recovered.result(timeout=1.0) == "fallback"


def test_completed_and_failed() -> None:
    assert completed_future(7).result() == 7
    with pytest.raises(KeyError):
        failed_future(KeyError("k")).result()


def test_many_timers_stress() -> None:
    futs = [Future() for _ in range(200)]
    wrapped = [future_timeout(f, 0.2) for f in futs]
    for f in futs[::2]:
        f.set_result(1)
    done = sum(1 for w in wrapped[::2] if w.result(timeout=1.0) == 1)
    assert done == 100
    timed_out = 0
    for w in wrapped[1::2]:
        try:
            w.result(timeout=2.0)
        except TimeoutError:
            timed_out += 1
    assert timed_out == 100
