"""Flight recorder: a bounded ring buffer of structured lifecycle events.

The metrics sink (utils/metrics.py) answers "how long do things take";
this answers "WHAT HAPPENED WHEN": a discarded step, a quorum shrink, a
heal, an outer-round abort each leave one structured event instead of
interleaved log lines across processes. The Manager owns one recorder
per process (``manager.events``) and shares it with the transport, the
checkpoint server, and the wrappers the same way it shares its Metrics
sink — so one ring holds the whole story of a replica's lifecycle, and
the checkpoint HTTP server exposes it at ``GET /telemetry/events``.

Event vocabulary (producers in parentheses):

    quorum_start / quorum_complete   (manager.py: the async quorum RPC)
    step_commit / step_discard       (manager.py: the commit barrier)
    heal_start / heal_done           (manager.py: heal assignment →
                                      healed state applied)
    member_dead                      (manager.py: a replica left the
                                      wire between two quorums)
    recovery_episode                 (manager.py: the commit that ends a
                                      commit-to-commit interval in which
                                      a step was discarded, an error
                                      latched, the wire membership
                                      changed or this replica healed —
                                      ``episode`` shrink / grow / rejoin
                                      / error, or ``shrink+grow`` where
                                      one quorum dropped a member and
                                      admitted another (the interval's
                                      timings are then observed under
                                      both kinds), ``gap_ms`` and the phases
                                      that partition it: quorum_wait,
                                      wire_wait, heal, barrier, other,
                                      and for a rejoin init, first_step)
    error_latched                    (manager.py / comm/transport.py /
                                      comm/xla_backend.py: first latch
                                      of an error episode)
    round_abort                      (local_sgd.py: outer round rolled
                                      back; ddp.py: submit loop failed
                                      mid-flight)
    mesh_reconfigure / mesh_compile  (comm/xla_backend.py: device mesh
                                      rebuilt for a new world size / an
                                      executable actually compiled)
    hier_exchange                    (comm/transport.py /
                                      comm/xla_backend.py: a hierarchical
                                      exchange plan installed for a
                                      cohort — domains, egress ranks,
                                      assignment fingerprint)
    shard_grid_rebuild               (ddp.py: the sharded-update leaf
                                      grid rebuilt for a new wire world
                                      size — old/new worlds attached)
    reshard                          (optim.py/local_sgd.py: sharded
                                      optimizer state redistributed at a
                                      quorum boundary — old/new worlds,
                                      moved/wire/lower-bound byte counts
                                      and any reinitialized leaves
                                      attached)
    redist_plan                      (comm/redistribute.py /
                                      checkpointing.py: a redistribution
                                      transfer plan executed — spec
                                      fingerprints, cache hit/miss,
                                      fetch/unsourced counts, moved vs
                                      lower-bound bytes)
    fused_step                       (fused.py: one fused
                                      single-executable step dispatched —
                                      mesh shape, codec, dispatch /
                                      executable counts, compile-cache
                                      state)
    microbatch_send / microbatch_recv
                                     (pipeline.py: one activation/grad
                                      frame crossed a stage boundary —
                                      step, microbatch, lane, frame
                                      kind, stages, bytes, replay flag;
                                      the recv stream alone replays the
                                      whole 1F1B schedule)
    stage_rebalance                  (pipeline.py: layer ranges moved
                                      between stages via the redist
                                      planner — moved vs lower-bound
                                      bytes, spec fingerprints, plan
                                      cache state)
    deploy_publish                   (serve.py: a committed weight
                                      version staged on the train-side
                                      publisher pair — version, units,
                                      bytes)
    deploy_start / deploy_done       (serve.py: one adoption — a
                                      planner-compiled train→serve
                                      transition fetched, version-gated
                                      and flipped live; moved vs
                                      lower-bound bytes, spec
                                      fingerprints attached)
    serve_flip                       (serve.py: a serving replica's
                                      atomic version flip — it now
                                      answers from the new version)
    serve_reroute                    (serve.py: the cohort router moved
                                      a request off a dead member onto
                                      another live holder)
    serve_join                       (serve.py: a killed serving replica
                                      rejoined — shard healed FROM SERVE
                                      PEERS, moved bytes and donor
                                      members attached)

Every event is stamped with a process-monotonic sequence number, wall +
monotonic clocks, the bound replica_id/rank, and (when the emitter knows
them) the step and quorum epoch. ``t_mono`` is ``time.monotonic()``,
which on Linux reads the same clock (CLOCK_MONOTONIC) as
``time.perf_counter()`` — the clock of the Metrics timings, the ``tft.*``
spans' durations and a ``recovery_episode``'s ``t_open`` — so an event's
``t_mono`` can be laid against any of them. ``since(seq)`` reads are seq-cursored so
pollers (scripts/fleet_top.py) are incremental; overwritten events are
reported as a ``dropped`` count, never silently.

Overhead contract:

- ``emit`` is O(append): one lock acquire, one dict build, one ring-slot
  store. No I/O, no sorting, no growth.
- The DISABLED path must be allocation-free, so hot call sites use the
  guard pattern ``ev = <recorder or None>; if ev: ev.emit(...)`` —
  ``__bool__`` is ``enabled`` and building the kwargs never happens when
  the guard fails. (``emit`` also checks ``enabled`` itself for callers
  that don't guard.)

``to_chrome_trace`` merges any set of per-replica ``dump()`` payloads
(or ``/telemetry/events`` bodies) into ONE Perfetto/Chrome
``trace_event`` JSON — one process track per replica, one thread per
rank, paired start/done events rendered as duration slices — so the
fault-tolerance timeline lands next to jax.profiler's device traces
instead of in a separate universe.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "EVENT_KINDS",
    "EventRecorder",
    "to_chrome_trace",
    "validate_chrome_trace",
]

EVENT_KINDS = (
    "quorum_start",
    "quorum_complete",
    "step_commit",
    "step_discard",
    "heal_start",
    "heal_done",
    "round_abort",
    "error_latched",
    "member_dead",
    "recovery_episode",
    "mesh_reconfigure",
    "mesh_compile",
    "hier_exchange",
    "shard_grid_rebuild",
    "reshard",
    "redist_plan",
    "fused_step",
    "microbatch_send",
    "microbatch_recv",
    "stage_rebalance",
    "lease_break",
    "job_preempted",
    "deploy_publish",
    "deploy_start",
    "deploy_done",
    "serve_flip",
    "serve_reroute",
    "serve_join",
)

_DEFAULT_CAPACITY = 4096

# Paired kinds rendered as Chrome duration slices ("ph": "X"): the start
# kind opens, the end kind closes. Everything else is an instant.
_SPAN_PAIRS = {
    "quorum_start": "quorum_complete",
    "heal_start": "heal_done",
    "deploy_start": "deploy_done",
}
_SPAN_ENDS = {v: k for k, v in _SPAN_PAIRS.items()}
_SPAN_NAMES = {
    "quorum_start": "quorum",
    "heal_start": "heal",
    "deploy_start": "deploy",
}


class EventRecorder:
    """Bounded, lock-cheap ring of lifecycle events.

    ``capacity``: ring size (oldest events are overwritten; reads report
    how many were dropped past a cursor). ``enabled``: None reads the
    ``TORCHFT_TPU_EVENTS`` env var ("0" disables; default enabled) —
    the recorder is cheap enough to stay on, the switch exists for
    overhead A/Bs and paranoid jobs. ``replica_id``/``rank`` are stamped
    onto every event (rebindable via :meth:`bind` once known)."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY,
                 enabled: Optional[bool] = None,
                 replica_id: str = "", rank: int = 0) -> None:
        if enabled is None:
            enabled = os.environ.get("TORCHFT_TPU_EVENTS", "1") != "0"
        capacity = int(capacity)
        if capacity < 1:
            enabled = False
            capacity = 1
        self._enabled = bool(enabled)
        self._cap = capacity
        self._buf: List[Optional[Dict[str, Any]]] = [None] * capacity
        self._seq = 0
        self._lock = threading.Lock()
        self.replica_id = str(replica_id)
        self.rank = int(rank)

    # -- write side ---------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def __bool__(self) -> bool:
        """The hot-path guard: ``if recorder: recorder.emit(...)`` keeps
        the disabled path allocation-free (no kwargs dict is ever
        built)."""
        return self._enabled

    @property
    def next_seq(self) -> int:
        """Total events ever emitted (== the next event's seq)."""
        with self._lock:
            return self._seq

    def bind(self, replica_id: str, rank: int) -> None:
        """(Re)bind the identity stamped onto subsequent events."""
        self.replica_id = str(replica_id)
        self.rank = int(rank)

    def emit(self, kind: str, step: Optional[int] = None,
             epoch: Optional[int] = None, **fields: Any) -> int:
        """Record one event; returns its seq (-1 when disabled).

        ``fields`` must be JSON-safe (strings/numbers/None) — events ride
        ``/telemetry/events`` verbatim. O(append): one lock, one dict,
        one slot store."""
        if not self._enabled:
            return -1
        rec: Dict[str, Any] = {
            "kind": kind,
            "t_wall": time.time(),
            "t_mono": time.monotonic(),
            "replica_id": self.replica_id,
            "rank": self.rank,
            "step": step,
            "epoch": epoch,
        }
        if fields:
            rec.update(fields)
        with self._lock:
            seq = self._seq
            rec["seq"] = seq
            self._buf[seq % self._cap] = rec
            self._seq = seq + 1
        return seq

    # -- read side ----------------------------------------------------------

    def since(self, seq: int = 0) -> "Tuple[List[Dict[str, Any]], int, int]":
        """Events with ``event.seq >= seq``, oldest first.

        Returns ``(events, next_seq, dropped)``: pass ``next_seq`` back
        as the next poll's cursor; ``dropped`` counts events past the
        cursor that the ring already overwrote (poll faster or raise
        capacity)."""
        seq = max(0, int(seq))
        with self._lock:
            end = self._seq
            first_avail = max(0, end - self._cap)
            start = max(seq, first_avail)
            out = [self._buf[i % self._cap] for i in range(start, end)]
        dropped = max(0, min(first_avail, end) - seq) if seq < end else 0
        return out, end, dropped

    def dump(self) -> Dict[str, Any]:
        """Full snapshot in the shape ``/telemetry/events`` serves (and
        ``to_chrome_trace`` consumes)."""
        events, nxt, dropped = self.since(0)
        return {
            "replica_id": self.replica_id,
            "rank": self.rank,
            "enabled": self._enabled,
            "capacity": self._cap,
            "next": nxt,
            "dropped": dropped,
            "events": events,
        }


# ------------------------------------------------------------ chrome export


def _track_ids(dumps: Sequence[Dict[str, Any]]) -> Dict[str, int]:
    """Stable pid assignment: one Chrome 'process' per replica_id, in
    first-seen order (deterministic for a fixed dump list)."""
    pids: Dict[str, int] = {}
    for d in dumps:
        rid = str(d.get("replica_id", ""))
        if rid not in pids:
            pids[rid] = len(pids) + 1
    return pids


def to_chrome_trace(dumps: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge per-replica event dumps into one Chrome ``trace_event`` JSON.

    ``dumps``: any mix of ``EventRecorder.dump()`` payloads and
    ``/telemetry/events`` response bodies (same shape). Output: a dict
    with ``traceEvents`` ready for ``json.dump`` → chrome://tracing /
    https://ui.perfetto.dev. One process (pid) per replica, one thread
    (tid) per rank; ``quorum_start→quorum_complete`` and
    ``heal_start→heal_done`` become duration slices, everything else an
    instant. Timestamps are wall-clock microseconds, so dumps from
    different processes on a synchronized fleet land on one timeline
    (and next to jax.profiler spans, which also use epoch time)."""
    pids = _track_ids(dumps)
    trace_events: List[Dict[str, Any]] = []
    for rid, pid in pids.items():
        trace_events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"replica {rid or '?'}"},
        })
    for d in dumps:
        rid = str(d.get("replica_id", ""))
        pid = pids[rid]
        rank = int(d.get("rank", 0) or 0)
        tid = rank + 1  # Chrome treats tid 0 oddly; keep ranks 1-based
        trace_events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": f"rank {rank}"},
        })
        # pending span starts by kind, per track (events arrive seq-ordered)
        open_spans: Dict[str, Dict[str, Any]] = {}
        events = sorted(
            d.get("events", []), key=lambda e: e.get("seq", 0)
        )
        for ev in events:
            kind = ev.get("kind", "?")
            ts = float(ev.get("t_wall", 0.0)) * 1e6
            args = {
                k: v for k, v in ev.items()
                if k not in ("kind", "t_wall", "replica_id", "rank")
                and v is not None
            }
            if kind in _SPAN_PAIRS:
                # span start: held until its end arrives; a start whose
                # end never came (crash mid-quorum) degrades to an
                # instant below
                prev = open_spans.pop(kind, None)
                if prev is not None:
                    trace_events.append(prev["instant"])
                open_spans[kind] = {
                    "ts": ts, "args": args,
                    "instant": _instant(kind, ts, pid, tid, args),
                }
                continue
            if kind in _SPAN_ENDS:
                start_kind = _SPAN_ENDS[kind]
                start = open_spans.pop(start_kind, None)
                if start is not None:
                    merged = dict(start["args"])
                    merged.update(args)
                    trace_events.append({
                        "name": _SPAN_NAMES[start_kind], "ph": "X",
                        "cat": "torchft_tpu",
                        "ts": start["ts"],
                        "dur": max(0.0, ts - start["ts"]),
                        "pid": pid, "tid": tid, "args": merged,
                    })
                    continue
                # end without a start (ring dropped it): plain instant
            trace_events.append(_instant(kind, ts, pid, tid, args))
        for pending in open_spans.values():  # unclosed starts
            trace_events.append(pending["instant"])
    trace_events.sort(key=lambda e: (e["ph"] == "M" and -1, e.get("ts", 0)))
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def _instant(kind: str, ts: float, pid: int, tid: int,
             args: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "name": kind, "ph": "i", "s": "t", "cat": "torchft_tpu",
        "ts": ts, "pid": pid, "tid": tid, "args": args,
    }


def validate_chrome_trace(trace: Any) -> List[str]:
    """Structural check of a ``to_chrome_trace`` result (the bench smoke
    gate): returns a list of problems, empty when the object is a valid
    Chrome trace_event JSON container."""
    problems: List[str] = []
    if not isinstance(trace, dict):
        return [f"trace is {type(trace).__name__}, not a dict"]
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        return ["traceEvents missing or not a list"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"traceEvents[{i}] not a dict")
            continue
        for key in ("name", "ph", "pid"):
            if key not in ev:
                problems.append(f"traceEvents[{i}] missing {key!r}")
        ph = ev.get("ph")
        if ph not in ("M", "i", "X", "B", "E"):
            problems.append(f"traceEvents[{i}] bad ph {ph!r}")
        if ph in ("i", "X") and not isinstance(
            ev.get("ts"), (int, float)
        ):
            problems.append(f"traceEvents[{i}] missing numeric ts")
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            problems.append(f"traceEvents[{i}] X event missing dur")
    return problems
