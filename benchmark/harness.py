"""What every job shares: the run's context (arguments, the cell's files,
devices, clock, tracing) and the arithmetic from step records to the
end-to-end numbers. Jobs live in ``jobs/<job>.py`` and are found by the
name a traffic file gives; this file knows none of them.

A job is ``run(ctx) -> record``. The keys ``run.py`` reads: ``checks``
(``{name: {"ok": bool, ...}}``; ``correct`` is their conjunction),
``attempted``, ``failed``, ``end_to_end`` (``{metric: value}``, the job's
own) and optionally ``notes``. The keys the per-layer readers read:
``records`` (one dict a group-step: gid, incarnation, t0, t1, committed,
step, participants, path, healed, loss), ``sinks`` (one entry a group that
lived in the window: its ``manager`` and ``optimizer`` snapshots at the
end, those at the window's ``start``, and ``replacement``), ``kills``,
``bare`` (step seconds and tokens/s of the plain step; traced run only),
``ft_tokens_per_s_per_chip``, ``window_over_blocks``, ``flops_per_token``,
``boot_s``, ``first_step_s``, ``compiles_in_window``; ``run.py`` adds
``trace`` (the reduction) and ``device_kind``."""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import queue
import shutil
import statistics
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


@dataclasses.dataclass
class Context:
    workload: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    family: Any
    devices: Sequence[Any]
    t_start: float                 # process start, on time.perf_counter
    boot_s: float                  # process start -> TPU claimed
    counter: Any                   # group.CompileCounter
    trace_span: Optional[List[float]] = None  # perf_counter at start/stop
    trace_file: Optional[str] = None

    def start_trace(self) -> None:
        import jax

        path = os.path.join(TRACE_DIR, self.workload)
        shutil.rmtree(path, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # bm.* annotations are the host spans
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(path, profiler_options=opts)
        self.trace_span = [time.perf_counter()]

    def stop_trace(self) -> None:
        import jax

        assert self.trace_span is not None and len(self.trace_span) == 1
        self.trace_span.append(time.perf_counter())
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            TRACE_DIR, self.workload, "plugins", "profile", "*", "*.xplane.pb"
        )))
        if not found:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        self.trace_file = found[-1]


def peak_hbm_bytes(devices: Sequence[Any]) -> int:
    """Peak HBM on the fullest of ``devices``: the peak of the buffers in
    use plus the peak the runtime reserved for running programs. On this
    TPU runtime ``peak_bytes_in_use`` counts arrays only; a program's
    temporaries (activations: 12 GB of the 111m step's 14) are
    ``bytes_reserved`` (my chip run, PR 22). The sum of the two peaks
    bounds the true peak from above and meets it in a loop whose state is
    live while its step runs."""
    def peak(d: Any) -> int:
        stats = d.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)) + int(
            stats.get("peak_bytes_reserved", 0)
        )

    return max(peak(d) for d in devices)


def commit_aligned_rate(records: Sequence[Dict[str, Any]], t0: float,
                        t1: float, tokens_per_group_step: int) -> Dict[str, Any]:
    """Tokens per second between two commits, so that no part of a step is
    counted or lost at either edge: from the last step committed before
    ``t0`` (the warm-up's last) to the last committed before ``t1``. A
    step's commit time is when the last of its groups had committed it;
    each group that took part counts its own batch. With steps of seconds
    and a window of under a minute, counting whole steps inside fixed
    edges would swing by one step in fifteen from run to run."""
    by_step: Dict[int, List[float]] = {}
    for r in records:
        if r["committed"]:
            by_step.setdefault(r["step"], []).append(r["t1"])
    commits = sorted((max(ts), len(ts)) for ts in by_step.values())
    before = [t for t, _n in commits if t <= t0]
    inside = [(t, n) for t, n in commits if t0 < t <= t1]
    if not before or not inside:
        return {"tokens_per_s": 0.0, "steps": len(inside), "span_s": 0.0}
    span = inside[-1][0] - before[-1]
    tokens = tokens_per_group_step * sum(n for _t, n in inside)
    return {"tokens_per_s": tokens / span, "steps": len(inside),
            "span_s": span}


class CompletionClock:
    """The host-clock time at which each watched device array became
    ready, in the order watched: a thread of the benchmark's own that
    waits on one array after the other. The fused loop's host runs up to
    nine steps ahead of the device and drains its fence eight at a time,
    so the loop's own records say when a step was dispatched, not when it
    ran; this says when it ran, and changes nothing in the loop."""

    def __init__(self) -> None:
        self.times: List[float] = []
        self._queue: "queue.SimpleQueue[Any]" = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._wait, daemon=True)
        self._thread.start()

    def watch(self, array: Any) -> None:
        self._queue.put(array)

    def _wait(self) -> None:
        while True:
            array = self._queue.get()
            if array is None:
                return
            array.block_until_ready()
            self.times.append(time.perf_counter())

    def close(self) -> List[float]:
        """Waits for everything watched, ends the thread, returns the
        times."""
        self._queue.put(None)
        self._thread.join()
        return self.times


def block_median_rate(times: Sequence[float], tokens_per_step: int,
                      blocks: int) -> Dict[str, Any]:
    """Tokens per second as the median over ``blocks`` equal runs of
    consecutive committed steps. ``times[0]`` is when the first step could
    start and ``times[k]`` when the k-th committed step had run, so every
    block is taken between two completions and none loses part of a step.
    A block holds several steps, so whatever recurs every few steps (a
    fence drain, a periodic flush) is in every block and in the median; a
    single stall of the machine or the program falls into one or two
    blocks and is not. ``whole`` is the plain rate over all steps, which
    does include it, and ``slowest`` the slowest block's."""
    n = len(times) - 1
    if n < 1 or times[-1] <= times[0]:
        return {"tokens_per_s": 0.0, "whole": 0.0, "slowest": 0.0, "blocks": 0}
    k = max(1, min(int(blocks), n))
    edges = [j * n // k for j in range(k + 1)]
    rates = [(b - a) * tokens_per_step / (times[b] - times[a])
             for a, b in zip(edges, edges[1:])]
    return {"tokens_per_s": statistics.median(rates),
            "whole": n * tokens_per_step / (times[-1] - times[0]),
            "slowest": min(rates), "blocks": k}


def losses_finite(records: Sequence[Dict[str, Any]]) -> bool:
    """Every committed step's loss, read back in one transfer."""
    import jax

    losses = jax.device_get(
        [r["loss"] for r in records if r["committed"] and r["loss"] is not None]
    )
    return all(math.isfinite(float(x)) for x in losses)


def median(xs: Sequence[float]) -> Optional[float]:
    xs = list(xs)
    return statistics.median(xs) if xs else None


def bare_step_loop(train_step: Any, state: Dict[str, Any], source: Any,
                   device: Any, first_batch: int, steps: int) -> Dict[str, Any]:
    """``steps`` plain steps of the donated train step, each timed to
    ``jax.block_until_ready``: the bare jitted step on the cell's own
    shapes. Returns the state (donated through), the step seconds, the
    losses, and tokens/s at the median step."""
    import jax

    params, opt = state["params"], state["opt"]
    times, losses = [], []
    for i in range(first_batch, first_batch + steps):
        batch = source.device_batch(i, device)
        jax.block_until_ready(batch)
        t = time.perf_counter()
        params, opt, loss = train_step(params, opt, *batch)
        jax.block_until_ready((params, opt, loss))
        times.append(time.perf_counter() - t)
        losses.append(loss)
    return {"state": {"params": params, "opt": opt}, "step_s": times,
            "losses": [float(x) for x in jax.device_get(losses)],
            "tokens_per_s": source.tokens_per_batch / median(times)}


def free(tree: Any) -> None:
    """Release the HBM of every array in ``tree`` at once, not when the
    garbage collector gets to it: the next state needs the room."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        leaf.delete()
