"""The program's own timeline: host spans on the profiler's clock, and
XLA device traces for a step window.

The reference ships no profiler hook (SURVEY.md §5); on TPU the natural
tool is jax.profiler — its traces capture XLA op timelines, HBM traffic,
and ICI collectives, viewable in TensorBoard/Perfetto. Three shapes:

- ``span(metrics, name, **args)``: THE span primitive. One block, timed
  once, lands in two places: the ``Metrics`` sink (rolling timing
  ``name``) and, while a ``jax.profiler`` trace is active, a
  ``TraceAnnotation`` named ``tft.<name>`` on the host plane, beside
  the device lines, carrying ``replica=<replica_id>`` (read from the
  sink's ``replica_id`` label, which the Manager sets) plus whatever
  the emitter passes (``step=``, ``bucket=``...). Outside a trace the
  annotation is a no-op of under a microsecond; there is no switch.
- ``throughput_span``: ``span`` + a bandwidth gauge and a byte counter.
- ``StepProgram`` / ``scope_tables``: the device side of the same
  timeline. A step program is jitted under a stable name
  (``tft_train_step``...) and its body sits in ``jax.named_scope``s; the
  TPU trace names an operation only by its HLO instruction
  (``fusion.1916``), so the program hands whoever reads a trace the
  instruction → scope-path table of what it compiled.
- ``step_program`` / ``program_stats``: a step program is built once a
  process. Its identity is what it was built from (config, optimizer,
  loss...), not the object that asked for it, so a replica group
  rebuilt beside its peers runs the executables the process holds.
- ``StepProfiler``: profile steps [start, stop) of a loop, driven by env
  vars so ANY trainer (bench.py, the examples) can be profiled without
  code changes: TORCHFT_TPU_PROFILE_DIR=/tmp/trace
  TORCHFT_TPU_PROFILE_START=10 TORCHFT_TPU_PROFILE_STEPS=5.

Profiling is strictly zero-cost when TORCHFT_TPU_PROFILE_DIR is unset:
``step()`` is two integer compares.
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, Callable, Dict, Hashable, Optional, Sequence, Tuple

__all__ = ["SPAN_PREFIX", "StepProfiler", "StepProgram", "program_stats",
           "scope_tables", "span", "step_args", "step_program",
           "throughput_span"]

# Every span of the library is ``tft.<name>`` on the profiler timeline:
# one prefix a trace reducer selects on.
SPAN_PREFIX = "tft."

_ANNOTATION: Any = None  # jax.profiler.TraceAnnotation, False without jax


def _annotation_cls() -> Any:
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            import jax

            _ANNOTATION = jax.profiler.TraceAnnotation
        except Exception:  # pragma: no cover — jax-less environment
            # (numpy-only transport tools): spans still feed the sink
            _ANNOTATION = False
    return _ANNOTATION


class span:
    """Time the enclosed block into ``metrics`` under ``name`` AND
    annotate the profiler timeline as ``tft.<name>`` with ``args``.

    ``metrics=None`` keeps the annotation only. ``elapsed`` holds the
    block's wall seconds after exit. A class, not a generator: the
    steady-state step enters half a dozen of these."""

    __slots__ = ("_metrics", "_name", "_annotation", "_start", "elapsed")

    def __init__(self, metrics, name: str, **args: Any) -> None:
        self._metrics = metrics
        self._name = name
        self.elapsed = 0.0
        cls = _annotation_cls()
        if cls:
            if metrics is not None:
                # getattr: test doubles of the sink only need observe()
                label = getattr(metrics, "label_value", None)
                replica = label("replica_id") if label else None
                if replica is not None:
                    args["replica"] = replica
            self._annotation = cls(SPAN_PREFIX + name, **args)
        else:  # pragma: no cover — jax-less environment
            self._annotation = None

    def __enter__(self) -> "span":
        self._start = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        self.elapsed = time.perf_counter() - self._start
        if self._metrics is not None:
            self._metrics.observe(self._name, self.elapsed)


@contextmanager
def throughput_span(metrics, name: str, nbytes: "int | list", **args: Any):
    """``span`` + a derived ``{name}_bytes_per_s`` gauge + a cumulative
    ``{name}_bytes`` counter.

    The heal plane wraps its wire phase in this so the same block feeds
    the profiler timeline, the ``{name}`` timing window, AND a
    bandwidth gauge the bench artifacts report directly. The gauge is
    last-write-wins (the most recent span's rate); the counter
    integrates bytes across the whole run so an incremental poller
    (scripts/fleet_top.py) can compute TRUE average bandwidth between
    two polls as Δ``{name}_bytes``/Δt instead of sampling whichever
    span happened to finish last. ``nbytes`` may be a mutable
    single-element list when the byte count is only known at exit (a
    fetch whose manifest arrives inside the span)."""
    timed = span(metrics, name, **args)
    try:
        with timed:
            yield
    finally:
        if metrics is not None:
            n = nbytes[0] if isinstance(nbytes, list) else nbytes
            if n:
                metrics.incr(f"{name}_bytes", n)
                if timed.elapsed > 0:
                    metrics.gauge(f"{name}_bytes_per_s", n / timed.elapsed)


# instruction name → op_name of one line of ``Compiled.as_text()``:
#   %fusion.12 = bf16[...] fusion(...), ..., metadata={op_name="jit(f)/attn/dot_general" ...}
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"'
)

# The newest program that ran under each name (``tft_train_step``,
# ``tft_grad_step``, ``tft_opt_update``): what ``scope_tables`` reads. A
# strong reference, because the reader comes after the loop that owned
# the program has gone; one entry a name, so nothing accumulates.
_PROGRAMS: Dict[str, "StepProgram"] = {}


class StepProgram:
    """A jitted step function under a stable name.

    Built through ``step_program``, it is the process's ONE program for
    what it was built from: every caller with equal arguments holds this
    object, so jax's caches (keyed on the function object) serve a new
    state with seen shapes on a seen device without a trace, a lowering
    or a backend compile. It outlives its first caller.

    Calls go straight to the jitted function; the first call also notes
    the arguments' shapes, dtypes and placements (no array is kept), so
    that ``scope_table`` can later lower and compile the same program
    again — served by jax's compilation cache where one is placed — and
    read, from the compiled text, which ``jax.named_scope`` path each HLO
    instruction came from. Every other attribute (``lower``, ``trace``,
    ``clear_cache``...) is the jitted function's own."""

    def __init__(self, jitted: Any) -> None:
        self._jitted = jitted
        self._avals: Any = None
        self.name: str = jitted.__name__

    def __call__(self, *args: Any) -> Any:
        if self._avals is None:
            import jax

            self._avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None)
                ),
                args,
            )
            _PROGRAMS[self.name] = self
        return self._jitted(*args)

    def __getattr__(self, attr: str) -> Any:
        return getattr(self._jitted, attr)

    def scope_table(self) -> Dict[str, str]:
        """``{HLO instruction name: op_name}`` of the compiled program:
        ``op_name`` is the scope path jax recorded, e.g.
        ``jit(tft_train_step)/transpose(jvp(attn))/dot_general``. Empty
        before the first call."""
        if self._avals is None:
            return {}
        text = self._jitted.lower(*self._avals).compile().as_text()
        table: Dict[str, str] = {}
        for line in text.splitlines():
            m = _HLO_LINE.match(line)
            if m:
                table[m.group(1)] = m.group(2)
        return table


# Step programs by what they were built from. Strong references, and
# that is the point: the executables outlive the group that first ran
# them, so its replacement in this process finds them. Program text only
# (a program holds no state or gradient buffers); bounded, least recently
# asked for out.
_STORE_SIZE = 16
_STORE: "OrderedDict[Hashable, StepProgram]" = OrderedDict()
_STORE_LOCK = threading.Lock()
_STORE_STATS = {"built": 0, "reused": 0}


def step_program(fn: Callable, key: Tuple[Any, ...],
                 donate_argnums: Sequence[int] = ()
                 ) -> Tuple[StepProgram, bool]:
    """The process's ``StepProgram`` for ``fn``: ``(program, reused)``.

    ``key`` is everything ``fn`` closes over that can change what it
    computes; with ``fn.__name__`` and ``donate_argnums`` it is the
    program's identity. An equal key seen before returns the program
    built then (``reused`` True; ``fn`` is dropped) — same function
    object, so jax re-traces nothing for shapes and devices it has
    served. Configs hash by value, functions and optax transformations
    by identity: a caller that wants a private program passes a private
    ``tx`` or ``loss``. A key that cannot be hashed gets a fresh program
    every time. THE place a step program is jitted."""
    import jax

    donate_argnums = tuple(donate_argnums)
    key = (fn.__name__, donate_argnums) + tuple(key)
    try:
        hash(key)
    except TypeError:
        key = None
    with _STORE_LOCK:
        program = _STORE.get(key) if key is not None else None
        if program is not None:
            _STORE.move_to_end(key)
            _STORE_STATS["reused"] += 1
            return program, True
        program = StepProgram(jax.jit(fn, donate_argnums=donate_argnums))
        _STORE_STATS["built"] += 1
        if key is not None:
            _STORE[key] = program
            if len(_STORE) > _STORE_SIZE:
                _STORE.popitem(last=False)
        return program, False


def program_stats() -> Dict[str, int]:
    """``{"built", "reused", "held"}``: step programs jitted in this
    process, requests served with one built earlier, and programs the
    store holds now."""
    with _STORE_LOCK:
        return dict(_STORE_STATS, held=len(_STORE))


def scope_tables() -> Dict[str, Dict[str, str]]:
    """``{XLA module name: {instruction: op_name}}`` for the newest step
    program that ran under each name in this process. The module name is
    the one a trace's ``XLA Modules`` line shows (``jit_tft_train_step``).
    Compiles (from the cache): call it after the measured window."""
    return {
        "jit_" + name: program.scope_table()
        for name, program in list(_PROGRAMS.items())
    }


def step_args(name: str) -> Any:
    """What the newest step program that ran under ``name``
    (``"tft_train_step"``) was first called with: its arguments as a
    pytree of ``jax.ShapeDtypeStruct``. ``None`` if none ran."""
    program = _PROGRAMS.get(name)
    return None if program is None else program._avals


class StepProfiler:
    """Trace a window of training steps, configured by env or args.

    Call ``step()`` once per loop iteration. The trace starts when the
    step counter reaches ``start`` and stops after ``num_steps`` more;
    ``close()`` stops a still-open trace if the loop ends early.

    Also a context manager: ``with StepProfiler() as prof:`` guarantees
    the trace is closed when the block exits (success or exception) —
    trainers should prefer this over relying on ``__del__``, which only
    runs at GC/interpreter-exit time and can silently drop an open
    trace's tail. The env-var contract is unchanged.
    """

    def __init__(self, log_dir: Optional[str] = None,
                 start: Optional[int] = None,
                 num_steps: Optional[int] = None):
        self.log_dir = (
            log_dir
            if log_dir is not None
            else os.environ.get("TORCHFT_TPU_PROFILE_DIR")
        )
        self.start = (
            start
            if start is not None
            else int(os.environ.get("TORCHFT_TPU_PROFILE_START", "3"))
        )
        self.num_steps = (
            num_steps
            if num_steps is not None
            else int(os.environ.get("TORCHFT_TPU_PROFILE_STEPS", "5"))
        )
        self._step = 0
        self._active = False
        self._done = self.log_dir is None  # disabled: step() is a no-op

    @property
    def enabled(self) -> bool:
        return self.log_dir is not None

    def step(self) -> None:
        """Advance the step counter; start/stop the trace at the window
        edges."""
        if self._done:
            return
        import jax

        if not self._active and self._step == self.start:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
        elif self._active and self._step >= self.start + self.num_steps:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
        self._step += 1

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
        self._done = True

    def __enter__(self) -> "StepProfiler":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def __del__(self):  # pragma: no cover — best-effort
        try:
            self.close()
        except Exception:
            pass
