"""Future timeout management.

TPU-native analog of the reference's future utilities
(/root/reference/torchft/futures.py:1-165): a singleton background timer
thread that can wrap any ``concurrent.futures.Future`` in a deadline, plus
blocking waits and continuation chaining.

Unlike the reference (which rides torch.futures + an asyncio event loop),
this implementation is built directly on ``concurrent.futures.Future`` and a
single deadline-heap thread — there is no torch in this framework, and the
jax async-dispatch model means device work never lives inside these futures;
they carry host-side control-plane and DCN-transport results only.
"""

from __future__ import annotations

import concurrent.futures
import heapq
import itertools
import threading
from concurrent.futures import Future
from datetime import timedelta
from typing import Callable, Optional, TypeVar

T = TypeVar("T")
S = TypeVar("S")

__all__ = [
    "future_timeout",
    "future_wait",
    "future_chain",
    "future_all",
    "FutureGroup",
    "StealableTask",
    "completed_future",
    "failed_future",
    "TimerHandle",
]


class TimerHandle:
    """Cancellable handle to a pending deadline (ref futures.py:12-29).
    The handle owns the callback and lets go of it when cancelled: the
    timer heap keeps a cancelled entry until its deadline, and whatever
    the callback closes over — a whole step's futures, and through their
    continuations the gradients on the device — must not wait that long
    to be freed."""

    def __init__(self, fn: "Optional[Callable[[], None]]" = None) -> None:
        self._lock = threading.Lock()
        self._cancelled = False
        self._fn = fn

    def cancel(self) -> None:
        with self._lock:
            self._cancelled = True
            self._fn = None

    @property
    def cancelled(self) -> bool:
        with self._lock:
            return self._cancelled

    def take(self) -> "Optional[Callable[[], None]]":
        """The callback, once: None if cancelled or already taken."""
        with self._lock:
            fn, self._fn = self._fn, None
            return fn


class _TimerManager:
    """Singleton deadline thread: min-heap of (deadline, seq, handle).

    Replaces the reference's asyncio ``call_later`` loop
    (ref futures.py:32-117) with a plain condition-variable heap, which is
    easier to reason about under free-threading and has no event-loop
    startup cost on the hot path.
    """

    def __init__(self) -> None:
        self._lock = threading.Condition()
        self._heap: list = []
        self._seq = itertools.count()
        self._thread: Optional[threading.Thread] = None

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="torchft_tpu_timers", daemon=True
            )
            self._thread.start()

    def call_at(self, deadline: float, fn: Callable[[], None]) -> TimerHandle:
        handle = TimerHandle(fn)
        with self._lock:
            heapq.heappush(self._heap, (deadline, next(self._seq), handle))
            self._ensure_thread()
            self._lock.notify()
        return handle

    def _run(self) -> None:
        import time

        while True:
            with self._lock:
                while not self._heap:
                    self._lock.wait()
                deadline, _, handle = self._heap[0]
                now = time.monotonic()
                if deadline > now:
                    self._lock.wait(timeout=deadline - now)
                    continue
                heapq.heappop(self._heap)
            fn = handle.take()
            if fn is not None:
                try:
                    fn()
                except Exception:  # timer callbacks must never kill the thread
                    pass


_TIMER_MANAGER = _TimerManager()


def _as_seconds(timeout: "float | timedelta") -> float:
    if isinstance(timeout, timedelta):
        return timeout.total_seconds()
    return float(timeout)


def future_timeout(fut: "Future[T]", timeout: "float | timedelta") -> "Future[T]":
    """Return a new future that mirrors ``fut`` but fails with
    ``TimeoutError`` if ``fut`` is not done within ``timeout``
    (ref futures.py:120-135).

    The original future is left untouched (it may still complete later);
    only the returned wrapper observes the deadline.
    """
    import time

    out: Future = Future()
    out.set_running_or_notify_cancel()
    seconds = _as_seconds(timeout)
    handle = _TIMER_MANAGER.call_at(
        time.monotonic() + seconds,
        lambda: _try_set_exception(
            out, TimeoutError(f"future timed out after {seconds}s")
        ),
    )

    def _done(f: "Future[T]") -> None:
        handle.cancel()
        _transfer(f, out)

    fut.add_done_callback(_done)
    return out


def future_wait(fut: "Future[T]", timeout: "float | timedelta") -> T:
    """Block on ``fut`` up to ``timeout``; raise ``TimeoutError`` on expiry
    (ref futures.py:138-165)."""
    try:
        return fut.result(timeout=_as_seconds(timeout))
    except concurrent.futures.TimeoutError:
        if fut.done():
            # The future COMPLETED with a TimeoutError of its own (on
            # 3.11+ the classes are one) — that is the real error, not a
            # wait expiry; rewriting it would sever the cause chain.
            raise
        # On < 3.11, concurrent.futures.TimeoutError is NOT the builtin
        # TimeoutError this API (and future_timeout) promises — normalize
        # so callers can catch one class on every supported Python.
        raise TimeoutError(
            f"future timed out after {_as_seconds(timeout)}s"
        ) from None


def future_chain(fut: "Future[T]", fn: "Callable[[Future[T]], S]") -> "Future[S]":
    """``then``-style continuation: returns a future holding ``fn(fut)``
    once ``fut`` completes; ``fn`` receives the *completed* future so it can
    inspect errors (mirrors torch.futures.Future.then used at ref
    manager.py:277-291)."""
    out: Future = Future()
    out.set_running_or_notify_cancel()

    def _done(f: "Future[T]") -> None:
        try:
            out.set_result(fn(f))
        except Exception as e:
            _try_set_exception(out, e)

    fut.add_done_callback(_done)
    return out


def future_all(futs: "list[Future]") -> "Future[list[Future]]":
    """Completes with the input futures once ALL of them are done —
    successfully or not (the caller inspects each; errors are typically
    already latched by wrap_future). Non-blocking barrier for fan-out ops
    like DDP's per-bucket allreduces, which can finish out of order when
    the transport runs multiple lanes."""
    out: Future = Future()
    out.set_running_or_notify_cancel()
    if not futs:
        out.set_result([])
        return out
    remaining = [len(futs)]
    lock = threading.Lock()

    def _done(_f: Future) -> None:
        with lock:
            remaining[0] -= 1
            if remaining[0] != 0:
                return
        out.set_result(list(futs))

    for f in futs:
        f.add_done_callback(_done)
    return out


class FutureGroup:
    """Dynamic completion barrier for streamed fan-out pipelines.

    ``future_all`` needs the whole future list up front; a streamed
    producer (DDP's per-bucket pipeline) creates members incrementally —
    a wire future per bucket, a worker future per unpack/error-feedback
    task — while earlier members are already completing on other
    threads. ``add()`` registers members as they are born, ``seal(fn)``
    arms the group and returns a future that resolves to ``fn()`` once
    every member has completed (out of order, on whichever thread
    finishes last — keep ``fn`` cheap).

    Error semantics: the first member (or ``fn``) exception fails the
    group future, but only AFTER every member has settled — so resources
    the group guards (e.g. a staging arena generation) are guaranteed
    quiescent by the time the group future is done, success or not.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pending = 0
        self._sealed = False
        self._fn: "Optional[Callable[[], object]]" = None
        self._error: Optional[BaseException] = None
        self._out: Future = Future()
        self._out.set_running_or_notify_cancel()

    def add(self, fut: Future) -> None:
        """Register a member. Must happen before :meth:`seal`; members may
        already be completed (their callback fires inline)."""
        with self._lock:
            if self._sealed:
                raise RuntimeError("FutureGroup.add after seal")
            self._pending += 1
        fut.add_done_callback(self._member_done)

    @property
    def outstanding(self) -> int:
        """Members registered but not yet settled. Observability for
        streamed producers: the outer-sync scheduler reads this right
        before its round-end drain to report how many fragments were
        still riding the wire when the round ran out of inner steps to
        hide them behind (the overlap evidence)."""
        with self._lock:
            return self._pending

    def _member_done(self, f: Future) -> None:
        exc = f.exception()
        with self._lock:
            if exc is not None and self._error is None:
                self._error = exc
            self._pending -= 1
            finish = self._sealed and self._pending == 0
        if finish:
            self._resolve()

    def seal(self, fn: "Callable[[], S]") -> "Future[S]":
        """Arm the group: no more members may be added; the returned
        future resolves to ``fn()`` once every member has completed (or
        fails with the first member error)."""
        with self._lock:
            if self._sealed:
                raise RuntimeError("FutureGroup sealed twice")
            self._sealed = True
            self._fn = fn
            finish = self._pending == 0
        if finish:
            self._resolve()
        return self._out

    def _resolve(self) -> None:
        if self._error is not None:
            _try_set_exception(self._out, self._error)  # type: ignore[arg-type]
            return
        try:
            self._out.set_result(self._fn())  # type: ignore[misc]
        except Exception as e:  # noqa: BLE001
            _try_set_exception(self._out, e)


class StealableTask:
    """A deferred computation exactly one thread may execute, with any
    number of waiters.

    This is the lazy-staging heal plane's priority-bump primitive: the
    donor's background stager walks leaf tasks in order calling
    :meth:`run`, while an HTTP handler thread that needs leaf *i* NOW
    calls :meth:`result` on that leaf directly — whichever side claims
    the task first executes it inline, the other just observes
    ``future``. No queue reshuffling, no executor priorities: the bump
    is the requester stealing the work onto its own thread.

    The callable is dropped after execution so a task whose closure
    pins large buffers (a staged device array) releases them once the
    result exists.
    """

    def __init__(self, fn: "Callable[[], T]") -> None:
        self._fn: "Optional[Callable[[], T]]" = fn
        self._lock = threading.Lock()
        self._claimed = False
        self.future: "Future[T]" = Future()
        self.future.set_running_or_notify_cancel()

    def run(self) -> None:
        """Execute the task if unclaimed (no-op otherwise); resolves
        ``future`` either way (immediately, or by the claiming thread
        when it finishes)."""
        with self._lock:
            if self._claimed:
                return
            self._claimed = True
            fn = self._fn
            self._fn = None
        try:
            self.future.set_result(fn())  # type: ignore[misc]
        except BaseException as e:  # noqa: BLE001 — deliver to waiters
            _try_set_exception(self.future, e)  # type: ignore[arg-type]

    @property
    def done(self) -> bool:
        return self.future.done()

    def result(self, timeout: Optional[float] = None) -> T:
        """Priority path: claim-and-run inline when still pending, else
        wait for the thread that already claimed it."""
        self.run()
        return self.future.result(timeout)


def completed_future(value: T) -> "Future[T]":
    f: Future = Future()
    f.set_result(value)
    return f


def failed_future(exc: Exception) -> "Future[T]":
    f: Future = Future()
    f.set_exception(exc)
    return f


def _try_set_exception(fut: Future, exc: Exception) -> None:
    try:
        fut.set_exception(exc)
    except Exception:
        pass  # already completed


def _transfer(src: Future, dst: Future) -> None:
    exc = src.exception()
    if exc is not None:
        _try_set_exception(dst, exc)
    else:
        try:
            dst.set_result(src.result())
        except Exception:
            pass  # dst already timed out
