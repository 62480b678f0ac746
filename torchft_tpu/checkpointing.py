"""Live checkpoint transport for healing replicas.

TPU-native rendering of the reference's checkpoint plane
(/root/reference/torchft/checkpointing.py:34-270): an up-to-date replica
serves its in-memory state dict over HTTP; a healing replica fetches it at
the step boundary. Serving is lock-gated so the training loop can never
mutate state mid-send — `send_checkpoint` stages the state and opens the
gate for a specific step; `should_commit` closes it again
(ref manager.py:591).

Zero-copy streaming pipeline (the heal plane's analog of the gradient
transport's PR 1-2 rebuild; byte primitives shared via comm/wire.py):

- Donor: staging is LAZY-PER-LEAF. ``send_checkpoint`` builds the
  manifest from metadata only (shapes/dtypes/shard indices — no D2H) and
  opens the gate immediately; a background stager drains leaves in order
  while an HTTP handler that needs leaf *i* NOW claims and stages it
  inline (``futures.StealableTask`` — the priority bump is the requester
  stealing the work onto its own thread). The healer's first fetch
  therefore streams while later leaves are still leaving the device.
  ``disallow_checkpoint`` finishes residual staging synchronously before
  dropping the gate, so the trainer can never donate a device buffer a
  pending stage still needs.
- Donor serve path: leaf/slice tensor bytes go out as chunked writes of
  a ``memoryview`` over the staged array (uint8 reinterpret — no
  ``tobytes`` copy, no pickle for tensor payloads, no full-body
  materialization). ``serve_copy_stats`` counts the rare fallbacks.
- Healer: ``fetch_leaf`` bounds reads to the advertised Content-Length
  (cross-checked against dtype/shape) and ``readinto``s straight into a
  preallocated array; large regions stripe across MULTIPLE donors and
  parallel keep-alive connections on a deterministic grid whose exact
  cover is verified geometrically; per-leaf H2D overlaps with in-flight
  network receives on a bounded worker.
- Heal stays BITWISE by default (trajectory oracles depend on it). The
  opt-in ``heal_wire_dtype="bf16"`` lever downcasts float leaves on the
  wire only (same astype roundtrip as the gradient transport's bf16
  codec) for bandwidth-starved links.

Telemetry plane: the same HTTP server doubles as the per-manager
observability endpoint — ``GET /telemetry/metrics`` (the Manager's
Metrics snapshot, framed with replica/rank/step/epoch) and
``GET /telemetry/events?since=<seq>`` (the flight recorder's
seq-cursored lifecycle ring, utils/events.py). Telemetry is NOT gated
on the checkpoint serving gate; scripts/fleet_top.py polls it fleet-wide
(docs/operations.md §8).

Trust model: the legacy full-stream endpoint still deserializes PICKLE
from whatever address quorum metadata names — run on a trusted cluster
network only. The DEFAULT healer paths (chunked, sharded) use pickle
ONLY for the manifest and non-tensor object leaves; tensor data rides
raw bytes + dtype/shape headers with no code-execution surface.
"""

from __future__ import annotations

import http.client
import io
import logging
import os
import pickle
import socket
import struct
import threading
import time
import urllib.error
import urllib.request
from abc import ABC, abstractmethod
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import cached_property
from datetime import timedelta
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Generic, List, Optional, Sequence, TypeVar

import numpy as np

from torchft_tpu.comm.redistribute import (
    RedistPlanner,
    ShardSpec,
    execute_fetches,
)
from torchft_tpu.comm.wire import (
    as_bytes_view,
    bf16_wire_dtype,
    readinto_exact,
    split_stripes,
    tensor_wire_view,
)
from torchft_tpu.futures import FutureGroup, StealableTask, future_chain
from torchft_tpu.utils.crc32c import crc32c
from torchft_tpu.utils.profiling import span, throughput_span
from torchft_tpu.utils.serialization import pytree_from_stream, pytree_to_stream

logger = logging.getLogger(__name__)

T = TypeVar("T")

__all__ = [
    "ChecksumError",
    "CheckpointTransport",
    "CheckpointServer",
    "RedistFetcher",
    "fetch_manifest",
    "fetch_leaf",
    "fetch_opt_shard",
    "format_slice_spec",
    "recv_checkpoint_sharded",
    "redistribute_exchange",
    "serve_copy_stats",
    "serve_redist_payload",
    "wire_crc_stats",
]

# Chunk size for streaming a staged leaf's byte view into the socket:
# large enough that syscall count is negligible, small enough that a
# dying healer is detected within a chunk.
_SEND_CHUNK = 1 << 20

# Wire-downcast applies to the same dtypes the gradient codecs compress.
_WIRE_COMPRESSIBLE = (np.dtype(np.float32), np.dtype(np.float64))

_WIRE_DTYPES = {"bf16": bf16_wire_dtype}

# CRC32C integrity frames on the raw tensor wire (utils/crc32c.py): each
# tensor body carries a 4-byte little-endian trailer the receiver
# verifies before the bytes are trusted — a flipped bit that previously
# landed silently now raises a prescriptive retryable error and the
# striped/failover machinery refetches the SAME bounds from a healthy
# peer. Default ON (the frame costs 4 bytes + one linear pass);
# TORCHFT_TPU_WIRE_CRC=0 is the escape hatch for mixed-version fleets.
_WIRE_CRC = os.environ.get("TORCHFT_TPU_WIRE_CRC", "1") != "0"


class ChecksumError(ConnectionError):
    """A tensor body failed its CRC32C wire frame — the payload was
    corrupted in flight (or by a torn donor buffer). Subclasses
    ConnectionError so every failover site already treats it as
    "this copy is bad, refetch from a peer"."""


# Test seam (like CheckpointServer._stage_hook): a callable mapping an
# outgoing chunk to what actually hits the socket, applied AFTER the
# frame checksum accumulated the true bytes — the only way to simulate
# corruption-in-flight, which by definition happens downstream of the
# donor's CRC.
_WIRE_FAULT_HOOK = None

_crc_stats_lock = threading.Lock()
_crc_stats = {"frames_checked": 0, "checksum_errors": 0}


def wire_crc_stats(reset: bool = False) -> Dict[str, int]:
    """Snapshot (optionally reset) the receiver-side CRC frame counters
    (test hook, like :func:`serve_copy_stats`)."""
    with _crc_stats_lock:
        out = dict(_crc_stats)
        if reset:
            for k in _crc_stats:
                _crc_stats[k] = 0
    return out


def _count_crc(ok: bool) -> None:
    with _crc_stats_lock:
        _crc_stats["frames_checked"] += 1
        if not ok:
            _crc_stats["checksum_errors"] += 1


# ------------------------------------------------------------- copy counting
# Test hook (ISSUE 4 acceptance): the donor must perform ZERO full-array
# copies when serving a C-contiguous non-ml_dtypes leaf. tensor_wire_view
# reports its copies; the handler accumulates them here.

_copy_stats_lock = threading.Lock()
_copy_stats = {"zero_copy_serves": 0, "full_array_copies": 0}


def serve_copy_stats(reset: bool = False) -> Dict[str, int]:
    """Snapshot (optionally reset) the donor serve-path copy counters."""
    with _copy_stats_lock:
        out = dict(_copy_stats)
        if reset:
            for k in _copy_stats:
                _copy_stats[k] = 0
    return out


def _count_serve(copies: int) -> None:
    with _copy_stats_lock:
        if copies == 0:
            _copy_stats["zero_copy_serves"] += 1
        else:
            _copy_stats["full_array_copies"] += copies


def _wire_encode(arr: np.ndarray, wire_dtype: "Optional[np.dtype]"):
    """One tensor's wire bytes: ``(byte view, wire dtype or None)``.
    The single implementation behind BOTH the /leaf and /rawleaves
    serve paths — the opt-in downcast inherently allocates (and is not
    counted as a serve-path copy); the default path is the counted
    zero-copy view."""
    if wire_dtype is not None and arr.dtype in _WIRE_COMPRESSIBLE:
        view, _ = tensor_wire_view(arr.astype(wire_dtype))
        return view, wire_dtype
    view, copies = tensor_wire_view(arr)
    _count_serve(copies)
    return view, None


# ------------------------------------------------------- bounded worker pools
# Process-wide bounded pools (the PR 3 DDP pattern): staging D2H on the
# donor and H2D assembly on the healer each get a small dedicated pool so
# many server instances (tests, multi-model apps) cannot accumulate
# threads, and a heavy H2D can never queue behind another heal's staging.

_POOL_LOCK = threading.Lock()
_POOLS: "Dict[str, ThreadPoolExecutor]" = {}


def _heal_executor(kind: str) -> ThreadPoolExecutor:
    with _POOL_LOCK:
        ex = _POOLS.get(kind)
        if ex is None:
            ex = ThreadPoolExecutor(
                max_workers=2, thread_name_prefix=f"torchft_tpu_heal_{kind}"
            )
            _POOLS[kind] = ex
        return ex


class _ShardedLeaf:
    """Host copy of one sharded jax.Array, stored SHARD-WISE: per-shard
    numpy pieces keyed by their global bounds, never assembled unless a
    request actually spans pieces. This is the multi-host-correct donor
    structure (each host only ever holds its addressable shards) and
    skips the full-array assembly device_get would perform."""

    def __init__(self, x) -> None:  # x: jax.Array
        self.shape = tuple(x.shape)
        self.dtype = np.dtype(x.dtype)
        self.nbytes = int(
            np.prod(self.shape, dtype=np.int64) * self.dtype.itemsize
        )
        pieces: dict = {}
        for shard in x.addressable_shards:
            bounds = _normalize_index(shard.index, self.shape)
            if bounds not in pieces:
                pieces[bounds] = np.asarray(shard.data)
        self.pieces = pieces

    def read(self, slices: "Optional[tuple]" = None) -> np.ndarray:
        """Materialize the requested region (default: the full array).
        Exact shard-bounds requests — the common case when healer and
        donor share a sharding layout — return the piece directly."""
        if slices is None:
            bounds = tuple((0, d) for d in self.shape)
        else:
            bounds = _normalize_index(slices, self.shape)
        hit = self.pieces.get(bounds)
        if hit is not None:
            return hit
        out = np.empty(
            tuple(b - a for a, b in bounds), dtype=self.dtype
        )
        covered = 0
        for pb, arr in self.pieces.items():
            # overlap of piece bounds with request bounds, both global
            inter = [
                (max(a1, a2), min(b1, b2))
                for (a1, b1), (a2, b2) in zip(pb, bounds)
            ]
            if any(a >= b for a, b in inter):
                continue
            src = tuple(
                slice(a - pa, b - pa)
                for (a, b), (pa, _) in zip(inter, pb)
            )
            dst = tuple(
                slice(a - ra, b - ra)
                for (a, b), (ra, _) in zip(inter, bounds)
            )
            out[dst] = arr[src]
            covered += int(
                np.prod([b - a for a, b in inter], dtype=np.int64)
            )
        expect = int(
            np.prod([b - a for a, b in bounds], dtype=np.int64)
        )
        if covered != expect:
            raise ValueError(
                f"requested region {bounds} not fully covered by this "
                "donor's addressable shards (multi-host: fetch the rest "
                "from the shard-owning host)"
            )
        return out


@dataclass(frozen=True)
class _Staged:
    """One staged checkpoint: per-leaf StealableTask slots (resolving to
    the staged host object — np.ndarray, _ShardedLeaf, or a non-tensor
    object), a metadata-only manifest, and an ``all_staged`` future that
    resolves once every slot has. Immutable host copies are born as the
    slots run; the bundle itself is safe to stream from outside the
    serving gate."""

    step: int
    slots: List[StealableTask]
    entries: List[dict]
    manifest_bytes: bytes
    treedef: Any = field(repr=False, default=None)
    all_staged: "Future" = field(repr=False, default=None)  # type: ignore[assignment]

    def leaf(self, i: int, timeout: "Optional[float]" = None) -> Any:
        """Staged host object for leaf ``i`` — claims and stages it
        INLINE when the background stager has not reached it yet (the
        request-priority bump)."""
        return self.slots[i].result(timeout)

    @property
    def num_leaves(self) -> int:
        return len(self.slots)

    @property
    def leaves(self) -> List[Any]:
        """Staged host objects, staging any slot that has not run yet
        (tests / introspection; request paths use :meth:`leaf`)."""
        return [s.result() for s in self.slots]

    def finish_staging(self, timeout: "Optional[float]" = None) -> None:
        """Drain every slot on the calling thread (claimed ones are
        joined, each waited up to ``timeout``). Called by
        ``disallow_checkpoint`` so a stage task does not normally
        outlive the gate into territory where the trainer donates
        device buffers. Staging errors — including a join timeout, the
        escape hatch that keeps ``should_commit`` bounded — are logged,
        not raised: if a straggler stage later touches a donated array,
        jax raises (deleted-buffer access), the slot's future fails,
        and the healer gets a retryable 503 — never silently corrupt
        bytes."""
        for slot in self.slots:
            try:
                slot.result(timeout)
            except Exception as e:  # noqa: BLE001
                logger.warning("checkpoint leaf staging failed: %s", e)

    @cached_property
    def state(self) -> Any:
        """Fully-materialized pytree (legacy full-stream path / tests).
        Cached: N healing peers on the legacy path share ONE assembly
        (stage-once-serve-many); cached_property writes the instance
        __dict__ directly, which frozen dataclasses permit."""
        import jax

        return jax.tree_util.tree_unflatten(
            self.treedef,
            [_materialize_leaf(s.result()) for s in self.slots],
        )


def _materialize_leaf(leaf: Any) -> Any:
    return leaf.read() if isinstance(leaf, _ShardedLeaf) else leaf


def _entry_wire_nbytes(entry: dict,
                       wire_dtype: "Optional[np.dtype]") -> int:
    """Wire bytes of one manifest ndarray entry — from METADATA only, so
    both sides can size a raw multi-leaf stream before any staging."""
    dtype = _dtype_from_str(entry["dtype"])
    if wire_dtype is not None and dtype in _WIRE_COMPRESSIBLE:
        count = int(np.prod(entry["shape"], dtype=np.int64))
        return count * wire_dtype.itemsize
    return int(entry["nbytes"])


def _build_staged(step: int, state: Any,
                  peers: "Optional[List[str]]" = None,
                  shard_filter: "Optional[Any]" = None,
                  lazy: bool = False,
                  metrics: "Optional[Any]" = None,
                  stage_hook: "Optional[Any]" = None) -> _Staged:
    """Stage ``state`` for serving.

    The manifest (paths, dtypes, shapes, shard-piece bounds) is built
    from METADATA ONLY — ``shard.index`` and array shapes need no device
    transfer — so this returns without a single D2H when ``lazy=True``.
    Mutation safety varies by leaf kind: jax.Arrays are immutable, so
    holding the reference and copying later is sound (the donation
    hazard is handled by ``disallow_checkpoint`` draining slots before
    the gate closes); np.ndarray leaves are mutable host state and are
    snapshot EAGERLY; other objects are held by reference exactly as the
    eager path always did.

    ``peers``: other hosts' checkpoint server addresses for this replica
    group, advertised in the manifest so a healer whose shards span donor
    hosts can fan out. ``shard_filter(path, bounds) -> bool`` drops pieces
    at staging time — the single-process simulation of a real multi-host
    donor, where ``addressable_shards`` only ever yields the local ones.
    """
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    slots: List[StealableTask] = []
    entries = []
    group = FutureGroup()
    for i, (keypath, leaf) in enumerate(flat):
        path = jax.tree_util.keystr(keypath)
        if isinstance(leaf, jax.Array):
            shape = tuple(leaf.shape)
            dtype = np.dtype(leaf.dtype)
            piece_bounds = sorted({
                _normalize_index(sh.index, shape)
                for sh in leaf.addressable_shards
            })
            if shard_filter is not None:
                piece_bounds = [
                    b for b in piece_bounds if shard_filter(path, b)
                ]

            def _stage(x=leaf, p=path, pb=tuple(piece_bounds), idx=i):
                if stage_hook is not None:
                    stage_hook(idx, p)
                with span(metrics, "heal_stage", leaf=idx, bytes=x.nbytes):
                    staged = _ShardedLeaf(x)
                    staged.pieces = {
                        b: arr for b, arr in staged.pieces.items()
                        if b in set(pb)
                    }
                return staged

            slots.append(StealableTask(_stage))
            entries.append(
                {
                    "path": path,
                    "kind": "ndarray",
                    "dtype": str(dtype),
                    "shape": shape,
                    "nbytes": int(
                        np.prod(shape, dtype=np.int64) * dtype.itemsize
                    ),
                    # global bounds of the pieces THIS host holds: the
                    # healer routes region fetches with these
                    "pieces": piece_bounds,
                }
            )
        elif isinstance(leaf, np.ndarray):
            with span(metrics, "heal_stage", leaf=i, bytes=leaf.nbytes):
                # detach from live training NOW (host arrays are
                # mutable) — this memcpy is staging work like any D2H
                snap = np.array(leaf, copy=True)
            slots.append(StealableTask(lambda s=snap: s))
            entries.append(
                {
                    "path": path,
                    "kind": "ndarray",
                    "dtype": str(snap.dtype),
                    "shape": tuple(snap.shape),
                    "nbytes": int(snap.nbytes),
                    "pieces": [tuple((0, d) for d in snap.shape)],
                }
            )
        else:
            slots.append(StealableTask(lambda o=leaf: o))
            entries.append({"path": path, "kind": "object"})
    for s in slots:
        group.add(s.future)
    manifest = {
        "step": step,
        "leaves": entries,
        "treedef": treedef,
        "peers": list(peers or []),
    }
    staged = _Staged(
        step=step,
        slots=slots,
        entries=entries,
        manifest_bytes=pickle.dumps(manifest, protocol=5),
        treedef=treedef,
        all_staged=group.seal(lambda: None),
    )
    if not lazy:
        staged.finish_staging()
    return staged


class CheckpointTransport(ABC, Generic[T]):
    """Pluggable transport moving live checkpoints donor→healer
    (ref checkpointing.py:34-88)."""

    @abstractmethod
    def metadata(self) -> str:
        """Metadata string advertised via the manager's CheckpointMetadata
        RPC (e.g. the donor's serving URL)."""

    @abstractmethod
    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: T,
        timeout: "float | timedelta",
    ) -> None:
        """Stage `state_dict` for the given recovering ranks at `step`."""

    def disallow_checkpoint(self) -> None:  # noqa: B027 — optional hook
        """Close the serving gate (training may mutate state again)."""

    @abstractmethod
    def recv_checkpoint(
        self, src_rank: int, metadata: str, step: int,
        timeout: "float | timedelta",
    ) -> T:
        """Fetch the checkpoint staged by the donor for `step`."""

    def shutdown(self, wait: bool = True) -> None:  # noqa: B027
        """Tear down any serving resources."""


def _parse_slice_spec(spec: str, shape: tuple) -> tuple:
    """Parse "0:4,:,2:8" into a tuple of slices (one per dim, '' = full)."""
    parts = spec.split(",")
    if len(parts) != len(shape):
        raise ValueError(
            f"slice spec has {len(parts)} dims, array has {len(shape)}"
        )
    out = []
    for p, dim in zip(parts, shape):
        p = p.strip()
        if p in ("", ":"):
            out.append(slice(None))
            continue
        start_s, _, stop_s = p.partition(":")
        start = int(start_s) if start_s else 0
        stop = int(stop_s) if stop_s else dim
        if not (0 <= start <= stop <= dim):
            raise ValueError(f"slice {p} out of bounds for dim {dim}")
        out.append(slice(start, stop))
    return tuple(out)


def format_slice_spec(slices: Sequence[slice]) -> str:
    """Inverse of _parse_slice_spec (for building leaf shard URLs)."""
    for s in slices:
        if s.step not in (None, 1):
            raise ValueError(
                f"strided slices are not supported by the checkpoint "
                f"plane (got step={s.step}); shard specs must be "
                "contiguous start:stop ranges"
            )
    return ",".join(
        f"{'' if s.start in (None, 0) else s.start}:"
        f"{'' if s.stop is None else s.stop}"
        for s in slices
    )


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "torchft_tpu_ckpt"

    def log_message(self, format: str, *args) -> None:  # noqa: A002
        logger.debug("checkpoint http: " + format, *args)

    def _await_staged(self, step: int) -> "Optional[_Staged]":
        """Gate: block until the donor has staged a checkpoint. A healer's
        fetch can land before the donor's send_checkpoint staged the state
        (both sides act on the same quorum response concurrently), so the
        gate must WAIT, not fail (ref checkpointing.py:139-170 holds a
        lock while disallowed for the same reason). Returns the staged
        bundle (its host copies materialize as slots run; the bundle is
        safe to stream outside the gate), or None after having sent an
        error response."""
        server: "CheckpointServer" = self.server.ckpt_server  # type: ignore[attr-defined]
        with server._cond:
            with span(server._metrics, "heal_gate", step=step):
                opened = server._cond.wait_for(
                    lambda: not server._disallowed, timeout=server._timeout
                )
            if not opened:
                self.send_error(
                    503,
                    f"timed out waiting for checkpoint gate for step {step}",
                )
                return None
            staged = server._staged
            if staged is None or staged.step != step:
                have = None if staged is None else staged.step
                self.send_error(
                    400,
                    f"checkpoint for step {step} not available "
                    f"(staged={have})",
                )
                return None
            return staged

    def _write_body(self, view, crc: bool) -> float:
        """Chunked writes of one tensor's wire bytes and, under ``crc``,
        its CRC32C trailer, accumulated chunk by chunk on the way out.
        Returns the seconds the checksum took: it runs inline on this
        handler's thread, between two writes."""
        c, crc_s = 0, 0.0
        for off in range(0, view.nbytes, _SEND_CHUNK):
            chunk = view[off: off + _SEND_CHUNK]
            if crc:
                t0 = time.perf_counter()
                c = crc32c(chunk, c)
                crc_s += time.perf_counter() - t0
            if _WIRE_FAULT_HOOK is not None:
                chunk = _WIRE_FAULT_HOOK(chunk)
            self.wfile.write(chunk)
        if crc:
            self.wfile.write(struct.pack("<I", c))
        return crc_s

    def _send_tensor(self, arr: np.ndarray, dtype: np.dtype,
                     wire_dtype: "Optional[np.dtype]",
                     crc: bool = False, *, leaf: int) -> None:
        """Stream one tensor region: headers + chunked writes of a byte
        view over the (staged) array — no tobytes, no body
        materialization. ``dtype`` is the staged dtype; ``wire_dtype``
        (when set and the leaf is wire-compressible) downcasts on the
        way out, which inherently allocates — it is the opt-in lossy
        lever, never the default. ``crc`` appends the 4-byte CRC32C
        trailer (requested via ``?crc=1``; Content-Length includes
        it). ``leaf`` names the response on the donor's timeline."""
        metrics = self.server.ckpt_server._metrics  # type: ignore[attr-defined]
        view, wired = _wire_encode(arr, wire_dtype)
        with span(metrics, "heal_serve", leaf=leaf, bytes=view.nbytes):
            self.send_response(200)
            self.send_header("X-Kind", "ndarray")
            self.send_header("X-Dtype", str(dtype))
            if wired is not None:
                self.send_header("X-Wire-Dtype", str(wired))
            self.send_header(
                "X-Shape", ",".join(str(d) for d in arr.shape)
            )
            self.send_header(
                "Content-Length", str(view.nbytes + (4 if crc else 0))
            )
            self.end_headers()
            self._body_streaming = True
            crc_s = self._write_body(view, crc)
            self._body_streaming = False
        if crc and metrics is not None:
            metrics.incr("heal_serve_crc_s", crc_s)

    def _send_json(self, obj: dict) -> None:
        import json

        body = json.dumps(obj).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _do_telemetry(self, parts, url) -> None:
        """GET /telemetry/metrics and GET /telemetry/events?since=<seq>.

        Telemetry is NOT gated on the checkpoint serving gate: a fleet
        poller must get an answer from a replica that is mid-step (gate
        closed) or has never staged a checkpoint at all. Responses are
        framed with the Manager-provided identity probe (replica_id,
        rank, step, quorum epoch) so a poller needs no side channel to
        attribute them."""
        from urllib.parse import parse_qs

        server: "CheckpointServer" = self.server.ckpt_server  # type: ignore[attr-defined]
        base: dict = {}
        info_fn = server._telemetry_info
        if callable(info_fn):
            try:
                base = dict(info_fn())
            except Exception as e:  # noqa: BLE001 — framing only; the
                base = {"telemetry_info_error": repr(e)[:200]}  # payload
                # below still answers
        if len(parts) == 2 and parts[1] == "metrics":
            metrics = server._metrics
            base["t_wall"] = time.time()
            base["metrics"] = (
                metrics.snapshot() if metrics is not None else {}
            )
            self._send_json(base)
            return
        if len(parts) == 2 and parts[1] == "events":
            q = parse_qs(url.query)
            try:
                since = int(q.get("since", ["0"])[0])
            except ValueError:
                self.send_error(400, "bad since cursor (want an integer)")
                return
            events = server._events
            if events is not None:
                evs, nxt, dropped = events.since(since)
                base.setdefault("replica_id", events.replica_id)
                base.setdefault("rank", events.rank)
                base.update(
                    events=evs, next=nxt, dropped=dropped,
                    enabled=events.enabled,
                )
            else:
                base.update(events=[], next=0, dropped=0, enabled=False)
            base["t_wall"] = time.time()
            self._send_json(base)
            return
        self.send_error(
            404,
            "unknown telemetry path (have /telemetry/metrics and "
            "/telemetry/events?since=<seq>)",
        )

    def do_GET(self) -> None:  # noqa: N802
        from urllib.parse import parse_qs, urlparse

        url = urlparse(self.path)
        parts = [p for p in url.path.split("/") if p]
        if parts and parts[0] == "telemetry":
            try:
                self._do_telemetry(parts, url)
            except (BrokenPipeError, ConnectionResetError):
                logger.debug("telemetry poller disconnected")
            return
        if not parts or parts[0] != "checkpoint":
            self.send_error(404, "unknown path")
            return
        try:
            step = int(parts[1])
        except (IndexError, ValueError):
            self.send_error(400, "bad step")
            return
        staged = self._await_staged(step)
        if staged is None:
            return
        server: "CheckpointServer" = self.server.ckpt_server  # type: ignore[attr-defined]

        try:
            if len(parts) == 2:  # /checkpoint/{step} — full pickle stream
                # Materialize BEFORE headers: a multi-host donor whose
                # shards don't fully cover a leaf raises here, and that
                # must surface as an error status, not a torn body.
                try:
                    full_state = staged.state
                except Exception as e:  # noqa: BLE001 — staging/coverage
                    self.send_error(503, str(e))
                    return
                self.send_response(200)
                self.send_header(
                    "Content-Type", "application/octet-stream"
                )
                # Chunked-free streaming: close delimits the body.
                self.send_header("Connection", "close")
                self.end_headers()
                self._body_streaming = True
                # all-host copy (assembled once, cached on the stage)
                pytree_to_stream(full_state, self.wfile, convert=False)
                self._body_streaming = False
                self.close_connection = True
                return

            if parts[2] == "manifest":  # /checkpoint/{step}/manifest
                body = staged.manifest_bytes
                self.send_response(200)
                self.send_header(
                    "Content-Type", "application/octet-stream"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return

            # (the pre-streaming pickled /leaves/{lo}-{hi} endpoint is
            # gone: rawleaves + /leaf cover every receiver, and tensor
            # pickle now exists ONLY on the legacy full-stream path)

            if parts[2] == "rawleaves" and len(parts) == 4:
                # /checkpoint/{step}/rawleaves/{lo}-{hi}[?wire=bf16]:
                # the leaves' tensor bytes BACK-TO-BACK, no framing —
                # every length is derivable from the manifest the healer
                # already holds, so ONE request moves a whole leaf range
                # with zero pickle and zero per-leaf round trips. The
                # Content-Length is computed from METADATA, so headers go
                # out immediately and each leaf is staged just-in-time
                # while earlier leaves are already on the wire (the
                # stage/wire pipeline). A staging failure mid-stream
                # surfaces as a short body, which the healer's bounded
                # read turns into a retryable error.
                lo_s, _, hi_s = parts[3].partition("-")
                lo, hi = int(lo_s), int(hi_s)
                if not (0 <= lo < hi <= staged.num_leaves):
                    self.send_error(404, f"bad leaf range {lo}-{hi}")
                    return
                q = parse_qs(url.query)
                wire = q.get("wire", [None])[0]
                crc = q.get("crc", ["0"])[0] == "1"
                if wire is not None and wire not in _WIRE_DTYPES:
                    self.send_error(400, f"unknown wire dtype {wire!r}")
                    return
                wire_dtype = (
                    _WIRE_DTYPES[wire]() if wire is not None else None
                )
                sizes = []
                for entry in staged.entries[lo:hi]:
                    if entry["kind"] != "ndarray":
                        self.send_error(
                            400,
                            f"leaf range {lo}-{hi} contains non-tensor "
                            "leaves — fetch those via /leaf/{i}",
                        )
                        return
                    sizes.append(_entry_wire_nbytes(entry, wire_dtype))
                # per-leaf CRC trailers ride INSIDE the body (after each
                # leaf's bytes) because the leaves stage just-in-time —
                # their checksums cannot exist at header time, and the
                # Content-Length must stay metadata-derivable: + 4/leaf.
                clen = sum(sizes) + (4 * (hi - lo) if crc else 0)
                self.send_response(200)
                self.send_header("X-Kind", "rawleaves")
                self.send_header("X-Count", str(hi - lo))
                self.send_header("Content-Length", str(clen))
                self.end_headers()
                self._body_streaming = True
                server_timeout = server._timeout
                metrics, crc_s = server._metrics, 0.0
                for i in range(lo, hi):
                    leaf = staged.leaf(i, server_timeout)  # JIT stage
                    arr = (
                        leaf.read()
                        if isinstance(leaf, _ShardedLeaf) else leaf
                    )
                    view, _ = _wire_encode(arr, wire_dtype)
                    with span(metrics, "heal_serve", leaf=i,
                              bytes=view.nbytes):
                        crc_s += self._write_body(view, crc)
                self._body_streaming = False
                if crc and metrics is not None:
                    metrics.incr("heal_serve_crc_s", crc_s)
                return

            if parts[2] == "leaf" and len(parts) == 4:
                # /checkpoint/{step}/leaf/{i}[?slice=0:4,:...][&wire=bf16]
                # All slicing/staging happens BEFORE headers are sent: a
                # failure after send_response(200) could only corrupt the
                # stream, not signal an error.
                idx = int(parts[3])
                if not (0 <= idx < staged.num_leaves):
                    self.send_error(404, f"no leaf {idx}")
                    return
                # priority bump: stages leaf idx inline if the background
                # stager has not reached it yet
                leaf = staged.leaf(idx, server._timeout)
                if not isinstance(leaf, (np.ndarray, _ShardedLeaf)):
                    body = pickle.dumps(leaf, protocol=5)
                    self.send_response(200)
                    self.send_header("X-Kind", "object")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                q = parse_qs(url.query)
                spec = q.get("slice", [None])[0]
                wire = q.get("wire", [None])[0]
                crc = q.get("crc", ["0"])[0] == "1"
                if wire is not None and wire not in _WIRE_DTYPES:
                    self.send_error(
                        400,
                        f"unknown wire dtype {wire!r} "
                        f"(supported: {sorted(_WIRE_DTYPES)})",
                    )
                    return
                wire_dtype = (
                    _WIRE_DTYPES[wire]() if wire is not None else None
                )
                # Server-side shard slicing: only the healer's shard
                # bytes cross the wire (SURVEY.md §7 hard part 3). For a
                # shard-wise staged leaf, a matching-bounds request is
                # served from the piece directly, no copies.
                dtype = np.dtype(leaf.dtype)
                if isinstance(leaf, _ShardedLeaf):
                    slices = (
                        _parse_slice_spec(spec, leaf.shape)
                        if spec is not None else None
                    )
                    arr = leaf.read(slices)
                elif spec is not None:
                    arr = leaf[_parse_slice_spec(spec, leaf.shape)]
                else:
                    arr = leaf
                self._send_tensor(arr, dtype, wire_dtype, crc=crc, leaf=idx)
                return

            self.send_error(404, "unknown path")
        except (ValueError, IndexError) as e:
            self.send_error(400, str(e))
        except (BrokenPipeError, ConnectionResetError):
            logger.warning("checkpoint receiver disconnected mid-stream")
        except Exception as e:  # noqa: BLE001 — e.g. a leaf whose lazy
            # staging failed (donated device buffer). Before headers:
            # surface a 503 the healer can retry on. MID-BODY: never
            # write an error response into the advertised byte stream
            # (the healer would decode it as tensor payload) — close the
            # connection abruptly so the bounded read sees a SHORT body
            # and raises its prescriptive retryable error.
            logger.exception("checkpoint serve failed: %s", e)
            if getattr(self, "_body_streaming", False):
                self.close_connection = True
                try:
                    self.connection.close()
                except OSError:
                    pass
            else:
                try:
                    self.send_error(503, str(e)[:300])
                except (OSError, ValueError):
                    pass


class CheckpointServer(CheckpointTransport[T]):
    """Daemon-thread HTTP server streaming the staged state dict
    (ref checkpointing.py:110-270)."""

    def __init__(self, timeout: "float | timedelta" = 60.0,
                 num_chunks: int = 0,
                 template_fn: "Optional[Any]" = None,
                 lazy_stage: bool = True,
                 heal_wire_dtype: "Optional[str]" = None,
                 stripe_bytes: int = 4 << 20) -> None:
        """``num_chunks``: when >= 1, recv_checkpoint fetches the donor's
        leaves raw over that many keep-alive connections (1 = a single
        streaming connection) instead of the legacy one-shot pickle
        stream, which ``num_chunks=0`` keeps (ref checkpointing.py
        num_chunks).

        ``template_fn``: zero-arg callable returning the healer's CURRENT
        state dict (same pytree structure the donor serves). When set,
        recv_checkpoint performs a SHARDING-AWARE fetch: for every leaf
        whose template counterpart is a sharded jax.Array, only the local
        shard slices are requested (sliced donor-side, so just shard bytes
        cross DCN) and the healed leaf is assembled directly onto the
        healer's devices with its existing sharding — the HSDP heal path
        (SURVEY.md §7 hard part 3).

        ``lazy_stage``: stage leaves in the background/on-demand (the
        streaming pipeline). False restores eager full-tree staging
        inside send_checkpoint — the legacy A/B arm.

        ``heal_wire_dtype``: opt-in lossy wire precision for this
        healer's fetches ("bf16"); float leaves are downcast donor-side
        and upcast on receive. Default None keeps heals bitwise.

        ``stripe_bytes``: regions at least this large stripe across
        multiple donors/connections (<=0 disables striping)."""
        if isinstance(timeout, timedelta):
            timeout = timeout.total_seconds()
        self._timeout = float(timeout)
        self._num_chunks = int(num_chunks)
        self._template_fn = template_fn
        self._lazy_stage = bool(lazy_stage)
        if heal_wire_dtype is not None and heal_wire_dtype not in _WIRE_DTYPES:
            raise ValueError(
                f"heal_wire_dtype={heal_wire_dtype!r} unsupported "
                f"(choose from {sorted(_WIRE_DTYPES)} or None)"
            )
        self._heal_wire_dtype = heal_wire_dtype
        self._stripe_bytes = int(stripe_bytes)
        self._metrics = None
        self._events = None          # flight recorder (set_events)
        self._telemetry_info = None  # identity/state probe (set_telemetry)
        self._cond = threading.Condition()
        self._disallowed = True
        self._staged: Optional[_Staged] = None
        self._peers: List[str] = []
        self._shard_filter = None  # test seam: simulate multi-host staging
        self._stage_hook = None    # test seam: observe/delay leaf staging

        self._server = ThreadingHTTPServer(("0.0.0.0", 0), _Handler)
        self._server.daemon_threads = True
        self._server.request_queue_size = 1024  # ref http.py:1-7
        self._server.ckpt_server = self  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="torchft_tpu_ckpt_server",
            daemon=True,
        )
        self._thread.start()

        from torchft_tpu.utils.net import advertised_host

        self._addr = (
            f"http://{advertised_host()}:{self._server.server_address[1]}"
        )

    # -- CheckpointTransport ------------------------------------------------

    def metadata(self) -> str:
        return self._addr

    def set_metrics(self, metrics) -> None:
        """Share a Metrics sink (the Manager's) so heal stage/wire/H2D
        spans and gauges land next to the step-pipeline timers. The
        same sink is what GET /telemetry/metrics serves."""
        self._metrics = metrics

    def set_events(self, events) -> None:
        """Share a flight recorder (utils/events.EventRecorder — the
        Manager's) so GET /telemetry/events can serve the process's
        lifecycle ring. The server only READS it; emitters stay the
        manager/transport/wrapper layers."""
        self._events = events

    def set_telemetry(self, info_fn) -> None:
        """Register a zero-arg callable returning the identity/state
        dict (replica_id, rank, step, epoch, ...) that frames every
        /telemetry response (Manager._telemetry_info)."""
        self._telemetry_info = info_fn

    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: T,
        timeout: "float | timedelta",
    ) -> None:
        # Build the manifest and per-leaf stage slots NOW (metadata only
        # — no D2H), open the gate, then drain staging in the background:
        # the healer's first fetch streams while later leaves are still
        # leaving the device. np.ndarray host state is snapshot eagerly
        # (mutable); jax.Arrays are immutable so the per-leaf D2H can
        # happen lazily, priority-bumped by incoming requests.
        del dst_ranks  # HTTP transport serves whoever fetches
        staged = _build_staged(
            step, state_dict, peers=self._peers,
            shard_filter=self._shard_filter,
            lazy=self._lazy_stage,
            metrics=self._metrics,
            stage_hook=self._stage_hook,
        )
        with self._cond:
            self._staged = staged
            self._disallowed = False
            self._cond.notify_all()
        if self._lazy_stage:
            def _drain(slots=staged.slots):
                for slot in slots:
                    slot.run()

            _heal_executor("stage").submit(_drain)

    def set_peers(self, peers: List[str]) -> None:
        """Register the other hosts' checkpoint server addresses for this
        replica group. Advertised in every staged manifest so a healer
        whose shard layout spans donor hosts can fetch each region from
        the host that owns it (the multi-host fan-out path)."""
        self._peers = [p for p in peers if p != self._addr]

    def disallow_checkpoint(self) -> None:
        with self._cond:
            staged = self._staged
            if self._disallowed:
                return
            self._disallowed = True
            self._staged = None
        # Outside the lock: drain residual lazy staging BEFORE returning
        # control to the trainer — after this point the training step may
        # donate device buffers, which would invalidate arrays a pending
        # stage still needs. Normally free: the background stager has
        # already drained during the step's wire time.
        if staged is not None:
            staged.finish_staging(self._timeout)

    @property
    def fetch_workers(self) -> int:
        """Connections ``recv_checkpoint`` fetches over at once."""
        if self._template_fn is not None:
            return max(2, self._num_chunks)
        return max(1, self._num_chunks)

    def recv_checkpoint(
        self, src_rank: int, metadata: str, step: int,
        timeout: "float | timedelta",
    ) -> T:
        del src_rank
        if isinstance(timeout, timedelta):
            timeout = timeout.total_seconds()
        t0 = time.perf_counter()
        if self._template_fn is not None:
            out = recv_checkpoint_sharded(
                metadata, step, self._template_fn(), float(timeout),
                parallel=self.fetch_workers,
                metrics=self._metrics,
                wire_dtype=self._heal_wire_dtype,
                stripe_bytes=self._stripe_bytes,
            )
        elif self._num_chunks >= 1:
            out = _recv_chunked(
                metadata, step, self._num_chunks, float(timeout),
                metrics=self._metrics,
                wire_dtype=self._heal_wire_dtype,
            )
        else:
            url = f"{metadata}/checkpoint/{step}"
            logger.info("fetching checkpoint from %s", url)
            with urllib.request.urlopen(url, timeout=timeout) as resp:
                out = pytree_from_stream(resp)
        if self._metrics is not None:
            # the fetch alone; ``heal_wall_ms`` is the Manager's, from
            # the heal's assignment to the state applied
            self._metrics.gauge(
                "heal_fetch_ms", (time.perf_counter() - t0) * 1000.0
            )
        return out

    def shutdown(self, wait: bool = True) -> None:
        self._server.shutdown()
        self._server.server_close()
        if wait:
            self._thread.join(timeout=5.0)

    # -- convenience for tests (ref manager_test.py:184-193 pre-seeding) ----

    def allow_checkpoint(self, step: int, state_dict: T) -> None:
        self.send_checkpoint([], step, state_dict, self._timeout)

    def address(self) -> str:
        return self._addr


# ---------------------------------------------------------------- client side
# Leaf-addressable fetch API. recv_checkpoint(num_chunks>1) uses it for
# parallel transfer; the HSDP healer uses fetch_leaf with a slice spec to
# stream only its own shard of each parameter (SURVEY.md §7 hard part 3).


def _dtype_from_str(name: str) -> np.dtype:
    """np.dtype from its str(), including ml_dtypes extension types
    (bfloat16, float8_*) that numpy only resolves once registered."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


class _DonorConn:
    """Thin keep-alive HTTP client for the heal plane.

    urllib opens one TCP connection per request; a chunked/striped heal
    issues hundreds of leaf requests, so each worker thread holds one of
    these per donor host and reuses the socket (the server speaks
    HTTP/1.1 with Content-Length on every raw endpoint). A stale
    keep-alive socket (donor idle-closed it between steps) is retried
    ONCE on a fresh connection; real donor death surfaces as the second
    failure."""

    def __init__(self, metadata: str, timeout: float) -> None:
        from urllib.parse import urlparse

        u = urlparse(metadata)
        if u.hostname is None:
            raise ValueError(f"bad donor address {metadata!r}")
        self._host, self._port = u.hostname, u.port or 80
        self._timeout = timeout
        self._conn: "Optional[http.client.HTTPConnection]" = None

    def _connect(self) -> http.client.HTTPConnection:
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self._host, self._port, timeout=self._timeout
            )
        return self._conn

    def close(self) -> None:
        if self._conn is not None:
            try:
                self._conn.close()
            except OSError:  # pragma: no cover — best-effort
                pass
            self._conn = None

    def get(self, path: str) -> http.client.HTTPResponse:
        """GET returning the live response (caller MUST consume exactly
        the advertised body for the connection to stay reusable). Non-200
        raises urllib.error.HTTPError for parity with the urlopen-based
        callers/tests."""
        for attempt in (0, 1):
            conn = self._connect()
            try:
                conn.request("GET", path)
                resp = conn.getresponse()
                break
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
        if resp.status != 200:
            body = resp.read()
            self.close()  # error bodies may lack lengths; start fresh
            raise urllib.error.HTTPError(
                f"http://{self._host}:{self._port}{path}",
                resp.status,
                body.decode(errors="replace")[:500],
                resp.headers,
                io.BytesIO(body),
            )
        return resp


class _ConnPool:
    """Keep-alive donor connections shared across fetch workers, keyed
    by host: acquire per request, release only after the body was
    consumed exactly (a conn with stale bytes must be CLOSED, not
    released — the next request on it would parse tensor bytes as a
    status line), close_all when the heal ends (a leaked conn pins a
    blocked donor handler thread until GC). The single implementation
    behind both the sharded and chunked receivers."""

    def __init__(self, timeout: float) -> None:
        self._timeout = timeout
        self._lock = threading.Lock()
        self._idle: "Dict[str, List[_DonorConn]]" = {}
        self._all: "List[_DonorConn]" = []

    def acquire(self, host: str) -> _DonorConn:
        with self._lock:
            idle = self._idle.setdefault(host, [])
            if idle:
                return idle.pop()
        c = _DonorConn(host, self._timeout)
        with self._lock:
            self._all.append(c)
        return c

    def release(self, host: str, conn: _DonorConn) -> None:
        with self._lock:
            self._idle.setdefault(host, []).append(conn)

    def close_all(self) -> None:
        with self._lock:
            for c in self._all:
                c.close()


def fetch_manifest(metadata: str, step: int, timeout: float = 60.0,
                   conn: "Optional[_DonorConn]" = None) -> dict:
    """Fetch the donor's leaf manifest: {step, leaves: [{path, kind, dtype,
    shape, nbytes, pieces}...], treedef, peers}. Pass ``conn`` to ride an
    existing keep-alive donor connection (the urllib opener chain costs
    several ms per call — measurable against a small manifest)."""
    if conn is not None:
        resp = conn.get(f"/checkpoint/{step}/manifest")
        clen = int(resp.headers["Content-Length"])
        body = resp.read(clen)
        if len(body) != clen:
            raise ConnectionError(
                f"manifest truncated at {len(body)}/{clen} bytes"
            )
        return pickle.loads(body)
    url = f"{metadata}/checkpoint/{step}/manifest"
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return pickle.load(resp)


def _wire_seconds(metrics) -> "Tuple[float, float, float]":
    """Seconds the sink's fetch workers have spent, summed over every
    fetch so far: before a response's headers came (the donor's gate,
    staging and handler), reading bodies, and checking them. Counters,
    because a heal makes more fetches than a timing's window keeps."""
    return (metrics.count("heal_wire_wait_s"),
            metrics.count("heal_wire_read_s"),
            metrics.count("heal_wire_crc_s"))


def _gauge_fetch(metrics, t0: float, wire0: "Tuple[float, float, float]",
                 nbytes: int, leaves: int) -> None:
    """What a finished fetch that began at ``t0`` leaves in the sink:
    its wire bytes, leaves and whole-fetch bandwidth, and of its
    workers' in-flight seconds since ``wire0`` the share they waited on
    the donor."""
    wall = time.perf_counter() - t0
    metrics.gauge("heal_bytes", nbytes)
    metrics.gauge("heal_leaves", leaves)
    if nbytes and wall > 0:
        metrics.gauge("heal_bytes_per_s", nbytes / wall)
    wait, read, crc = (
        b - a for a, b in zip(wire0, _wire_seconds(metrics))
    )
    if wait + read + crc > 0:
        metrics.gauge("heal_donor_wait_share", wait / (wait + read + crc))


def _read_wire_tensor(resp, dtype: np.dtype, shape: tuple,
                      wire_np: np.dtype, what: str,
                      out: "Optional[np.ndarray]" = None,
                      check_crc: bool = False,
                      metrics: "Optional[Any]" = None) -> np.ndarray:
    """Land one tensor body from ``resp``: readinto a preallocated (or
    fresh) array in the staged dtype, via a wire-dtype temporary + upcast
    when the opt-in lossy encoding is active. The single implementation
    behind BOTH fetch_leaf and the rawleaves range reader.

    ``check_crc``: the body carries a 4-byte CRC32C trailer (the donor
    was asked with ``?crc=1``); it is read and verified against the WIRE
    bytes before they are trusted — a mismatch raises
    :class:`ChecksumError` (a ConnectionError: every failover site
    already retries it from a peer) and increments the receiver-side
    frame counters. ``metrics``: the heal's sink, for the spans
    ``heal_wire_read`` and ``heal_wire_crc`` and their summed seconds."""
    if wire_np == dtype:
        wire_arr = out if out is not None else np.empty(shape, dtype)
        result = wire_arr
    else:
        wire_arr = np.empty(shape, wire_np)
        result = None  # upcast AFTER the frame check: corrupt bytes
        # must never be written into a caller's buffer
    with span(metrics, "heal_wire_read") as reading:
        readinto_exact(resp, as_bytes_view(wire_arr), what=what)
    if metrics is not None:
        metrics.incr("heal_wire_read_s", reading.elapsed)
    if check_crc:
        trailer = bytearray(4)
        readinto_exact(
            resp, memoryview(trailer), what=f"{what} crc frame"
        )
        want = struct.unpack("<I", trailer)[0]
        with span(metrics, "heal_wire_crc") as checking:
            got = crc32c(as_bytes_view(wire_arr))
        if metrics is not None:
            metrics.incr("heal_wire_crc_s", checking.elapsed)
        _count_crc(got == want)
        if got != want:
            raise ChecksumError(
                f"{what}: CRC32C mismatch (wire frame {want:#010x}, "
                f"computed {got:#010x}) — payload corrupted in flight; "
                "refetch from a peer"
            )
    if result is not None:
        return result
    if out is not None:
        out[...] = wire_arr.astype(dtype)
        return out
    return wire_arr.astype(dtype)


def _leaf_path(step: int, index: int,
               slices: "Optional[Sequence[slice]]",
               wire_dtype: "Optional[str]",
               crc: bool = False) -> str:
    path = f"/checkpoint/{step}/leaf/{index}"
    params = []
    if slices is not None:
        params.append("slice=" + format_slice_spec(slices))
    if wire_dtype is not None:
        params.append(f"wire={wire_dtype}")
    if crc:
        params.append("crc=1")
    return path + ("?" + "&".join(params) if params else "")


def fetch_leaf(
    metadata: str,
    step: int,
    index: int,
    slices: Optional[Sequence[slice]] = None,
    timeout: float = 60.0,
    out: "Optional[np.ndarray]" = None,
    wire_dtype: "Optional[str]" = None,
    conn: "Optional[_DonorConn]" = None,
    crc: "Optional[bool]" = None,
    metrics: "Optional[Any]" = None,
) -> Any:
    """Fetch one leaf (optionally a server-sliced shard of it) by index.

    Reads are BOUNDED by the advertised Content-Length, which is itself
    cross-checked against the dtype/shape headers — a mismatch raises a
    prescriptive error instead of a downstream frombuffer shape crash.
    ``out``: preallocated C-contiguous destination (dtype/shape must
    match); the body is ``readinto`` it with no intermediate bytes.
    ``wire_dtype``: request the opt-in lossy wire encoding ("bf16");
    the result is upcast back to the staged dtype. ``conn``: reuse a
    keep-alive donor connection (callers doing many fetches).
    ``crc``: request + verify the CRC32C integrity frame (default: the
    process-wide ``TORCHFT_TPU_WIRE_CRC`` policy; objects are exempt —
    the frame covers raw tensor bytes). ``metrics``: the heal's sink —
    the fetch then tiles into spans ``heal_wire_wait`` (request →
    response headers: the donor's gate, staging and handler),
    ``heal_wire_read`` and ``heal_wire_crc``, each also summed in
    seconds under ``<span>_s``."""
    if crc is None:
        crc = _WIRE_CRC
    own_conn = conn is None
    if own_conn:
        conn = _DonorConn(metadata, timeout)
    try:
        with span(metrics, "heal_wire_wait") as waiting:
            resp = conn.get(
                _leaf_path(step, index, slices, wire_dtype, crc=crc)
            )
        if metrics is not None:
            metrics.incr("heal_wire_wait_s", waiting.elapsed)
        kind = resp.headers.get("X-Kind", "ndarray")
        clen_hdr = resp.headers.get("Content-Length")
        if clen_hdr is None:
            raise ConnectionError(
                "donor sent no Content-Length for leaf "
                f"{index} — refusing an unbounded read"
            )
        clen = int(clen_hdr)
        if kind == "object":
            body = resp.read(clen)
            if len(body) != clen:
                raise ConnectionError(
                    f"object leaf {index} body truncated at "
                    f"{len(body)}/{clen} bytes — donor died mid-stream; "
                    "refetch from a live peer"
                )
            return pickle.loads(body)
        dtype = _dtype_from_str(resp.headers["X-Dtype"])
        shape = tuple(
            int(d) for d in resp.headers["X-Shape"].split(",") if d
        )
        wire_hdr = resp.headers.get("X-Wire-Dtype")
        wire_dt = _dtype_from_str(wire_hdr) if wire_hdr else dtype
        expect = int(np.prod(shape, dtype=np.int64)) * wire_dt.itemsize
        expect += 4 if crc else 0  # the CRC32C trailer rides the body
        if clen != expect:
            raise ConnectionError(
                f"leaf {index}: advertised Content-Length {clen} != "
                f"{expect} implied by dtype={wire_dt} shape={shape} — "
                "donor/healer version skew or corrupt stream; refusing "
                "to decode"
            )
        if out is not None:
            if tuple(out.shape) != shape or out.dtype != dtype:
                raise ValueError(
                    f"out buffer {out.dtype}{tuple(out.shape)} does not "
                    f"match leaf {dtype}{shape}"
                )
            if not out.flags.c_contiguous:
                raise ValueError(
                    "out buffer must be C-contiguous for recv-into"
                )
        return _read_wire_tensor(
            resp, dtype, shape, wire_dt, f"leaf {index} body", out=out,
            check_crc=crc, metrics=metrics,
        )
    finally:
        if own_conn:
            conn.close()


def _normalize_index(index, shape) -> "tuple[tuple[int, int], ...]":
    """Shard index (tuple of slices from a jax sharding) as hashable
    (start, stop) pairs with concrete bounds for every dim (slice objects
    themselves are unhashable before Python 3.12)."""
    out = []
    for s, dim in zip(index, shape):
        start = 0 if s.start is None else int(s.start)
        stop = dim if s.stop is None else int(s.stop)
        out.append((start, stop))
    return tuple(out)


def _bounds_to_slices(bounds) -> "tuple[slice, ...]":
    return tuple(slice(a, b) for a, b in bounds)


def _intersect(a, b):
    """Intersection of two bounds tuples, or None if empty."""
    out = tuple(
        (max(a1, a2), min(b1, b2)) for (a1, b1), (a2, b2) in zip(a, b)
    )
    if any(lo >= hi for lo, hi in out):
        return None
    return out


def _covers_exactly(bounds, covers) -> bool:
    """True iff the union of ``covers`` contains every point of
    ``bounds``. Exact for any layout (including overlapping pieces):
    coordinate-compress each dim, then require every elementary cell to
    lie inside some cover. Cell counts are tiny — O(pieces) cuts/dim."""
    import itertools

    cuts = []
    for d, (lo, hi) in enumerate(bounds):
        pts = {lo, hi}
        for c in covers:
            a, b = c[d]
            pts.add(min(max(a, lo), hi))
            pts.add(min(max(b, lo), hi))
        cuts.append(sorted(pts))
    cells_per_dim = [list(zip(c[:-1], c[1:])) for c in cuts]
    for cell in itertools.product(*cells_per_dim):
        if not any(
            all(
                ca <= c_lo and c_hi <= cb
                for (c_lo, c_hi), (ca, cb) in zip(cell, cov)
            )
            for cov in covers
        ):
            return False
    return True


def _route_region(bounds, piece_maps):
    """Plan fetches for one needed region across donor hosts.

    ``piece_maps``: {host_addr: [piece bounds...]} for this leaf. Returns
    a list of (host, fetch_bounds) whose union covers ``bounds`` — a
    single entry when one host covers the whole region (the matching-
    layout fast path), per-piece intersections otherwise. Raises if the
    hosts together cannot cover the region."""
    for host, pieces in piece_maps.items():
        for p in pieces:
            if _intersect(bounds, p) == bounds:
                return [(host, bounds)]
    plan = []
    seen = set()
    for host, pieces in piece_maps.items():
        for p in pieces:
            inter = _intersect(bounds, p)
            if inter is None or inter in seen:
                continue
            seen.add(inter)
            if plan and _covers_exactly(inter, [b for _, b in plan]):
                # another host's pieces already supply every byte of this
                # intersection — don't fetch it twice
                continue
            plan.append((host, inter))
    if not _covers_exactly(bounds, [b for _, b in plan]):
        raise ValueError(
            f"region {bounds} not covered by any donor host "
            f"(hosts: {list(piece_maps)}) — resharded beyond the donor "
            "group's union of shards"
        )
    return plan


def _covering_hosts(bounds, piece_maps, dead=()) -> List[str]:
    """Hosts whose shard pieces fully contain ``bounds`` (stripe/retry
    candidates), dead hosts excluded."""
    return [
        host
        for host, pieces in piece_maps.items()
        if host not in dead
        and any(_intersect(bounds, p) == bounds for p in pieces)
    ]


def _stripe_region(bounds, nbytes: int, stripe_bytes: int,
                   parallel: int) -> "Optional[List[tuple]]":
    """Deterministic stripe grid for one region: contiguous dim-0 bands
    of roughly ``stripe_bytes`` each (so each stripe lands in a
    contiguous slab of the preallocated region buffer). Returns None
    when the region is too small / unsplittable. The resulting set is
    exact-cover verified geometrically, like the gradient transport's
    chunk grid."""
    if stripe_bytes <= 0 or nbytes < 2 * stripe_bytes:
        return None
    rows = bounds[0][1] - bounds[0][0]
    if rows < 2:
        return None
    want = min(
        max(2, nbytes // stripe_bytes), max(2, parallel), rows
    )
    base = bounds[0][0]
    stripes = [
        ((base + a, base + b),) + tuple(bounds[1:])
        for a, b in split_stripes(rows, want)
    ]
    if not _covers_exactly(bounds, stripes):  # pragma: no cover — grid
        # construction is exact by construction; this guards refactors
        raise ValueError(
            f"stripe grid does not exactly cover region {bounds}"
        )
    return stripes


def recv_checkpoint_sharded(
    metadata: str,
    step: int,
    template: Any,
    timeout: float = 60.0,
    parallel: int = 4,
    metrics: "Optional[Any]" = None,
    wire_dtype: "Optional[str]" = None,
    stripe_bytes: int = 4 << 20,
) -> Any:
    """Sharding-aware heal fetch: for each leaf whose ``template``
    counterpart is a jax.Array, fetch only the slices this process's
    devices hold (donor slices server-side) and assemble the result with
    the template's sharding via make_array_from_callback. Other leaves are
    fetched whole. The donor and healer must run the same model — leaf
    paths are cross-checked against the donor's manifest.

    Streaming pipeline: every region lands via ``readinto`` in a
    preallocated host buffer cut from the template's dtype/shape (no
    intermediate bytes + frombuffer copy); regions >= ``stripe_bytes``
    stripe across every donor host that holds them AND multiple parallel
    keep-alive connections; each leaf's H2D (device assembly) is
    submitted to a bounded worker the moment its last region lands, so
    device uploads overlap with in-flight network receives.

    Multi-host fan-out: when a needed region is not fully held by the
    primary donor host, the manifest's ``peers`` addresses are consulted
    (their manifests fetched once) and each region — split per piece when
    it spans hosts — is fetched from a host that owns it. A donor that
    dies MID-STREAM fails only its in-flight fetches: each is retried
    against the surviving hosts that cover the same bounds, and the heal
    either completes whole or raises — no partial state is ever
    returned.

    ``timeout`` bounds each individual wait (socket ops, per-leaf
    result joins) — the transport-wide idle-deadline convention, NOT an
    end-to-end wall clock; a heal that keeps making progress is never
    killed mid-recovery."""
    import jax

    t0 = time.perf_counter()
    wire0 = _wire_seconds(metrics) if metrics is not None else None
    manifest = fetch_manifest(metadata, step, timeout=timeout)
    entries = manifest["leaves"]
    t_flat, t_def = jax.tree_util.tree_flatten_with_path(template)
    if len(t_flat) != len(entries):
        raise ValueError(
            f"template has {len(t_flat)} leaves, donor checkpoint has "
            f"{len(entries)} — model structure mismatch"
        )
    for (kp, _), entry in zip(t_flat, entries):
        path = jax.tree_util.keystr(kp)
        if path != entry["path"]:
            raise ValueError(
                f"leaf path mismatch: template {path!r} vs donor "
                f"{entry['path']!r}"
            )

    # Per-host piece maps, lazily extended with peer manifests only if
    # some region is not covered by the primary host.
    manifests = {metadata: manifest}
    peers_lock = threading.Lock()  # guards manifests + peers_left
    peers_left = [p for p in manifest.get("peers", []) if p != metadata]

    def _piece_maps(leaf_idx: int, shape) -> dict:
        full = tuple((0, d) for d in shape)
        out = {}
        # snapshot under the lock: a fetch worker's donor-death failover
        # inserts peer manifests concurrently (_pull_locked), and a dict
        # mutated mid-iteration raises in THIS thread
        with peers_lock:
            items = list(manifests.items())
        for host, m in items:
            entry = m["leaves"][leaf_idx]
            out[host] = [
                tuple(tuple(b) for b in p)
                for p in entry.get("pieces", [full])
            ]
        return out

    def _pull_peer_manifests() -> None:
        # pull all peer manifests (once, in parallel — a serial walk
        # would stall recovery by a full RTT per donor host); also
        # called from fetch workers on a donor death, so alternates
        # exist even when planning never needed the peers. The lock is
        # held THROUGH the pull: a second worker racing in here must
        # not observe "peers already claimed" while the manifests dict
        # is still empty — it would conclude no peer covers its region.
        with peers_lock:
            if not peers_left:
                return
            pending = list(peers_left)
            _pull_locked(pending)
            peers_left.clear()

    def _pull_locked(pending) -> None:
        def _pull(peer):
            try:
                return peer, fetch_manifest(peer, step, timeout=timeout)
            except Exception as e:  # noqa: BLE001 — a dead peer only
                # narrows coverage; the final route raises if coverage
                # stays short
                logger.warning(
                    "peer manifest fetch failed %s: %s", peer, e
                )
                return peer, None
        with ThreadPoolExecutor(
            max_workers=max(1, min(len(pending), parallel))
        ) as pool:
            for peer, m in pool.map(_pull, pending):
                if m is not None:
                    manifests[peer] = m

    def _plan_region(leaf_idx, shape, bounds):
        try:
            return _route_region(bounds, _piece_maps(leaf_idx, shape))
        except ValueError:
            if peers_left:
                _pull_peer_manifests()
            return _route_region(bounds, _piece_maps(leaf_idx, shape))

    # Plan all fetches first (unique shard slices per leaf, routed to the
    # owning host), then stream them through the fetch pool with per-leaf
    # completion groups driving the H2D worker.
    plans = []  # (leaf_index, entry, tleaf, {bounds: [(host, sub)...]})
    for i, ((kp, tleaf), entry) in enumerate(zip(t_flat, entries)):
        if entry["kind"] == "ndarray" and isinstance(tleaf, jax.Array):
            shape = tuple(entry["shape"])
            if tuple(tleaf.shape) != shape:
                raise ValueError(
                    f"shape mismatch at {entry['path']}: template "
                    f"{tuple(tleaf.shape)} vs donor {shape}"
                )
            if str(np.dtype(tleaf.dtype)) != entry["dtype"]:
                # mirror the shape check: a donor/healer dtype skew must
                # fail loudly, not heal with a silent precision change
                raise ValueError(
                    f"dtype mismatch at {entry['path']}: template "
                    f"{np.dtype(tleaf.dtype)} vs donor {entry['dtype']}"
                )
            idx_map = tleaf.sharding.addressable_devices_indices_map(shape)
            unique = {
                _normalize_index(ix, shape): None
                for ix in idx_map.values()
            }
            routed = {
                b: _plan_region(i, shape, b) for b in unique
            }
            plans.append((i, entry, tleaf, routed))
        else:
            plans.append((i, entry, tleaf, None))

    # ---- streamed fetch + overlapped H2D --------------------------------
    dead_hosts: set = set()
    dead_lock = threading.Lock()
    total_bytes = [0]
    bytes_lock = threading.Lock()  # += is not atomic across workers

    conn_pool = _ConnPool(timeout)

    _NET_ERRORS = (
        urllib.error.URLError, http.client.HTTPException,
        ConnectionError, socket.timeout, TimeoutError, OSError,
    )

    wire_np = _WIRE_DTYPES[wire_dtype]() if wire_dtype is not None else None

    def _planned_nbytes(i, fetch_bounds) -> int:
        # the wire bytes the plan expects of this fetch (0: an object)
        entry = entries[i]
        if entry["kind"] != "ndarray":
            return 0
        whole = _entry_wire_nbytes(entry, wire_np)
        if fetch_bounds is None:
            return whole
        count = int(np.prod(entry["shape"], dtype=np.int64))
        part = int(np.prod([b - a for a, b in fetch_bounds], dtype=np.int64))
        return whole // count * part if count else 0

    def _fetch_once(host, i, fetch_bounds, out):
        nb = [0]
        with throughput_span(metrics, "heal_wire", nb, leaf=i, host=host,
                             bytes=_planned_nbytes(i, fetch_bounds)):
            conn = conn_pool.acquire(host)
            try:
                got = fetch_leaf(
                    host, step, i,
                    slices=(
                        _bounds_to_slices(fetch_bounds)
                        if fetch_bounds is not None else None
                    ),
                    timeout=timeout, out=out, wire_dtype=wire_dtype,
                    conn=conn, metrics=metrics,
                )
            except BaseException:
                conn.close()  # possibly mid-body: stale, not reusable
                raise
            conn_pool.release(host, conn)
            if isinstance(got, np.ndarray):
                # count WIRE bytes: under the opt-in lossy encoding the
                # socket moved the downcast payload, not the upcast copy
                wire_nb = got.nbytes
                if (wire_dtype is not None
                        and got.dtype in _WIRE_COMPRESSIBLE):
                    wire_nb = (
                        got.size * _WIRE_DTYPES[wire_dtype]().itemsize
                    )
                nb[0] = wire_nb
                with bytes_lock:
                    total_bytes[0] += wire_nb
        return got

    def _fetch_job(host, i, fetch_bounds, out, alternates):
        """One wire fetch with donor-death failover: on a network error
        the host is marked dead and the SAME bounds are refetched from
        each surviving host that covers them."""
        try:
            return _fetch_once(host, i, fetch_bounds, out)
        except urllib.error.HTTPError:
            raise  # donor answered: a protocol error, not a death
        except _NET_ERRORS as first:
            if isinstance(first, ChecksumError) and metrics is not None:
                # corrupt payload, not a dead donor — but the
                # prescription is the same: this copy is bad, refetch
                # the SAME bounds from a peer (the host is excluded
                # below like any dead donor for this heal)
                metrics.incr("heal_checksum_errors")
            with dead_lock:
                dead_hosts.add(host)
            # a donor death is exactly when the peer manifests become
            # load-bearing — pull them before computing alternates
            try:
                _pull_peer_manifests()
            except Exception:  # noqa: BLE001 — alternates just narrow
                pass
            for alt in alternates():
                logger.warning(
                    "donor %s died mid-stream; refetching leaf %d "
                    "%s from %s", host, i, fetch_bounds, alt,
                )
                try:
                    return _fetch_once(alt, i, fetch_bounds, out)
                except _NET_ERRORS as again:
                    if (isinstance(again, ChecksumError)
                            and metrics is not None):
                        metrics.incr("heal_checksum_errors")
                    with dead_lock:
                        dead_hosts.add(alt)
            raise ConnectionError(
                f"leaf {i} bounds {fetch_bounds}: donor {host} died and "
                "no surviving peer covers the region"
            ) from first

    h2d_ex = _heal_executor("h2d")
    fetch_pool = ThreadPoolExecutor(
        max_workers=max(1, parallel),
        thread_name_prefix="torchft_tpu_heal_fetch",
    )
    leaf_results: "List[Optional[Future]]" = [None] * len(plans)
    try:
        for i, entry, tleaf, routed in plans:
            group = FutureGroup()
            if routed is None:
                # whole-leaf fetch (object or non-jax template leaf);
                # ndarray leaves still land via readinto into a
                # preallocated buffer
                out_buf = None
                if entry["kind"] == "ndarray":
                    out_buf = np.empty(
                        tuple(entry["shape"]),
                        _dtype_from_str(entry["dtype"]),
                    )

                def _alts(i=i, shape=tuple(entry.get("shape", ()))):
                    maps = _piece_maps(i, shape) if shape else {
                        h: [] for h in manifests
                    }
                    with dead_lock:
                        dead = set(dead_hosts)
                    if shape:
                        full = tuple((0, d) for d in shape)
                        return [
                            h for h in _covering_hosts(full, maps, dead)
                            if h != metadata
                        ]
                    return [
                        h for h in manifests
                        if h not in dead and h != metadata
                    ]

                leaf_results[i] = fetch_pool.submit(
                    _fetch_job, metadata, i, None, out_buf, _alts
                )
                continue

            shape = tuple(entry["shape"])
            dtype = _dtype_from_str(entry["dtype"])
            maps = _piece_maps(i, shape)
            region_bufs: dict = {}
            for bounds, sub in routed.items():
                buf = np.empty(
                    tuple(b - a for a, b in bounds), dtype
                )
                region_bufs[bounds] = buf
                region_nbytes = int(buf.nbytes)

                if len(sub) == 1 and sub[0][1] == bounds:
                    host = sub[0][0]
                    stripes = _stripe_region(
                        bounds, region_nbytes, stripe_bytes, parallel
                    )
                    if stripes is not None:
                        # multi-donor, multi-connection striped fetch:
                        # stripe s goes to covering host s % n (every
                        # covering host shares the load; single-host
                        # donors still win connection parallelism).
                        # Hosts already marked dead by an earlier leaf's
                        # failover don't get fresh stripes.
                        with dead_lock:
                            dead_now = set(dead_hosts)
                        hosts = _covering_hosts(
                            bounds, maps, dead_now
                        ) or [host]
                        base0 = bounds[0][0]
                        for s_idx, sb in enumerate(stripes):
                            dst = buf[
                                sb[0][0] - base0: sb[0][1] - base0
                            ]
                            def _salts(sb=sb, i=i, shape=shape):
                                with dead_lock:
                                    dead = set(dead_hosts)
                                return _covering_hosts(
                                    sb, _piece_maps(i, shape), dead
                                )
                            group.add(fetch_pool.submit(
                                _fetch_job,
                                hosts[s_idx % len(hosts)],
                                i, sb, dst, _salts,
                            ))
                    else:
                        def _ralts(bounds=bounds, i=i, shape=shape):
                            with dead_lock:
                                dead = set(dead_hosts)
                            return _covering_hosts(
                                bounds, _piece_maps(i, shape), dead
                            )
                        group.add(fetch_pool.submit(
                            _fetch_job, host, i, bounds, buf, _ralts
                        ))
                else:
                    # region spans hosts: fetch each piece (no out
                    # buffer — piece destinations may be mid-dim and
                    # non-contiguous), copy into the region buffer
                    for host, piece_b in sub:
                        dst = tuple(
                            slice(a - ra, b - ra)
                            for (a, b), (ra, _) in zip(piece_b, bounds)
                        )

                        def _piece_fetch(host=host, i=i,
                                         piece_b=piece_b, dst=dst,
                                         buf=buf, shape=shape):
                            def _palts():
                                with dead_lock:
                                    dead = set(dead_hosts)
                                return _covering_hosts(
                                    piece_b, _piece_maps(i, shape), dead
                                )
                            arr = _fetch_job(
                                host, i, piece_b, None, _palts
                            )
                            buf[dst] = arr

                        group.add(fetch_pool.submit(_piece_fetch))

            def _assemble(tleaf=tleaf, shape=shape,
                          region_bufs=region_bufs, i=i):
                with span(metrics, "heal_h2d", leaf=i, bytes=sum(
                        int(b.nbytes) for b in region_bufs.values())):
                    shards = {
                        b: np.asarray(a) for b, a in region_bufs.items()
                    }

                    def _cb(index, _shards=shards, _shape=shape):
                        return _shards[_normalize_index(index, _shape)]

                    return jax.make_array_from_callback(
                        shape, tleaf.sharding, _cb
                    )

            sealed = group.seal(lambda: None)
            # H2D overlaps in-flight receives: the moment this leaf's
            # last region lands, its device assembly rides the bounded
            # worker while the fetch pool keeps streaming later leaves.
            leaf_results[i] = future_chain(
                sealed,
                lambda f, a=_assemble: (f.result(), h2d_ex.submit(a))[1],
            )

        leaves = []
        for i, entry, tleaf, routed in plans:
            # No wall clock on the fetch join: every underlying job is
            # already bounded by per-socket idle deadlines and a finite
            # retry set, so this settles exactly when they do — a huge
            # leaf that keeps making wire progress is never killed (the
            # idle-deadline contract above). The H2D result keeps
            # ``timeout`` as a device-hang backstop.
            got = leaf_results[i].result()
            if routed is None:
                # the fetch job future carries the fetched object/array
                leaves.append(got)
            else:
                leaves.append(got.result(timeout))
    finally:
        fetch_pool.shutdown(wait=True, cancel_futures=True)
        conn_pool.close_all()

    if metrics is not None:
        _gauge_fetch(metrics, t0, wire0, total_bytes[0], len(leaves))
    return jax.tree_util.tree_unflatten(t_def, leaves)


# The heal path's shared plan cache: donor spec pairs repeat across
# heals of a stable fleet layout (the same "seen spec pair costs zero
# builds" discipline the wrapper-owned planners get).
_OPT_SHARD_PLANNER = RedistPlanner()


def fetch_opt_shard(
    donors: "Sequence[str]",
    step: int,
    needed: "Sequence[int]",
    state_slots: int,
    slots_path_re: str = r".*\['slots'\]\[(\d+)\]\[(\d+)\]$",
    timeout: float = 60.0,
    parallel: int = 4,
    metrics: "Optional[Any]" = None,
    planner: "Optional[RedistPlanner]" = None,
    events: "Optional[Any]" = None,
) -> "Dict[int, List[np.ndarray]]":
    """Shard-spec-aware optimizer-state fetch for a healer joining at a
    *different* world size — a client of the redistribution engine
    (comm/redistribute.py): the donor manifests ARE the source shard
    spec, ``needed`` is the destination, and the compiled plan is
    provably minimal (each missing leaf fetched exactly once, striped
    across its covering donors).

    Each donor's checkpoint carries only ITS 1/N shard of the per-leaf
    optimizer states, in a FIXED tree structure where non-held leaves
    are zero-length placeholder arrays
    (``ShardedOptimizerWrapper.opt_state_dict``). A donor's MANIFEST is
    therefore its shard spec: leaf ``i`` is held exactly when every one
    of its ``state_slots`` slot entries (manifest paths matching
    ``slots_path_re`` with groups ``(leaf, slot)``) advertises
    ``nbytes > 0``. The (src spec → needed) plan is cached per spec
    pair (module-shared planner unless ``planner`` is supplied) with
    ``redist_plan_builds``/``redist_plan_cache_hits`` counters, and the
    fetched bytes are pinned against the plan's lower bound
    (``redist_moved_bytes``/``redist_lower_bound_bytes``).

    Donor-death failover rides the engine: a donor that dies mid-fetch
    (network error, not an HTTP protocol error) is excluded and each of
    its assigned leaves refetched from the surviving donors that cover
    it; the fetch completes whole or raises — no partial shard is ever
    returned.

    Returns ``{leaf_index: [slot arrays...]}`` for every index in
    ``needed`` (feed ``ShardedOptimizerWrapper._unflatten_state`` /
    ``load_opt_state_dict``-shaped adoption)."""
    import re as _re

    needed = sorted(set(int(i) for i in needed))
    if not needed:
        return {}
    pat = _re.compile(slots_path_re)

    # donor -> {leaf: {slot: manifest_index}}, only for fully-held
    # leaves; per-leaf byte sizes ride along for the plan's accounting.
    coverage: "Dict[str, Dict[int, Dict[int, int]]]" = {}
    leaf_bytes: "Dict[int, int]" = {}
    for donor in donors:
        try:
            manifest = fetch_manifest(donor, step, timeout=timeout)
        except Exception as e:  # noqa: BLE001 — a dead donor only
            # narrows coverage; the plan below raises if it stays short
            logger.warning("opt-shard manifest fetch failed %s: %s",
                           donor, e)
            continue
        slots: "Dict[int, Dict[int, int]]" = {}
        sizes: "Dict[int, int]" = {}
        for mi, entry in enumerate(manifest["leaves"]):
            m = pat.match(entry.get("path", ""))
            if m is None or entry.get("kind") != "ndarray":
                continue
            if int(entry.get("nbytes", 0)) <= 0:
                continue
            leaf, slot = int(m.group(1)), int(m.group(2))
            slots.setdefault(leaf, {})[slot] = mi
            sizes[leaf] = sizes.get(leaf, 0) + int(entry["nbytes"])
        coverage[donor] = {
            leaf: by_slot for leaf, by_slot in slots.items()
            if len(by_slot) == state_slots
        }
        for leaf in coverage[donor]:
            leaf_bytes[leaf] = max(leaf_bytes.get(leaf, 0), sizes[leaf])

    # Specs over the leaf grid: holders are donor POSITIONS (stable
    # within a call and across calls with the same donor list — the
    # cache key), the healer is one receiver past them.
    n_units = max(
        [*needed, *(l for c in coverage.values() for l in c)]
    ) + 1
    src = ShardSpec(n_units, {
        di: list(coverage[d])
        for di, d in enumerate(donors) if coverage.get(d)
    })
    receiver = len(donors)
    dst = ShardSpec(n_units, {receiver: needed})
    unit_bytes = [leaf_bytes.get(u, 0) for u in range(n_units)]
    planner = planner if planner is not None else _OPT_SHARD_PLANNER
    hits0 = planner.hits
    plan = planner.plan(src, dst, unit_bytes, metrics=metrics)
    missing = list(plan.receiver_unsourced(receiver))
    if missing:
        raise ConnectionError(
            f"no donor covers optimizer-state leaves {missing[:8]}"
            f"{'...' if len(missing) > 8 else ''} at step {step} — "
            "shard specs do not union to the needed shard (donors died "
            "or checkpoints predate the sharded wrapper)"
        )

    conn_pool = _ConnPool(timeout)

    def _fetch_unit(holder: int, leaf: int) -> "List[np.ndarray]":
        donor = donors[holder]
        by_slot = coverage[donor][leaf]
        nb = [0]
        with throughput_span(metrics, "heal_wire", nb, leaf=leaf,
                             host=donor, bytes=leaf_bytes[leaf]):
            arrays = _pool_fetch_leaves(
                conn_pool, donor, step,
                [by_slot[slot] for slot in range(state_slots)],
                timeout, what=f"opt-shard leaf {leaf}", metrics=metrics,
            )
            nb[0] = sum(int(a.nbytes) for a in arrays)
        return arrays

    try:
        out, total_bytes = execute_fetches(
            plan, receiver, _fetch_unit, parallel=parallel
        )
    finally:
        conn_pool.close_all()
    lower = plan.lower_bound_bytes.get(receiver, 0)
    if metrics is not None:
        metrics.gauge("heal_opt_bytes", float(total_bytes))
        metrics.incr("heal_opt_bytes_total", float(total_bytes))
        metrics.incr("redist_moved_bytes", float(total_bytes))
        metrics.incr("redist_lower_bound_bytes", float(lower))
    if events:
        events.emit(
            "redist_plan", source="opt_shard_heal",
            src_spec=src.fingerprint(), dst_spec=dst.fingerprint(),
            n_units=n_units, cache_hit=planner.hits > hits0,
            fetches=len(plan.receiver_fetches(receiver)),
            unsourced=0,
            moved_bytes=int(total_bytes), lower_bound_bytes=int(lower),
        )
    return out


# ------------------------------------------------- redistribution transport
# The byte-movement hooks comm/redistribute.py injects (layering: comm/
# may not import this module): publishing rides an EPHEMERAL
# CheckpointServer — lazy per-leaf staging means over-publication costs
# metadata only — and fetching rides the same keep-alive _DonorConn /
# fetch_leaf raw plane every heal uses. Exchanges happen at membership
# changes (rare), so a fresh server per exchange beats a persistent one
# fighting the Manager's heal-serving gate for the staging slot.

_REDIST_STEP = 0
_REDIST_PATH_RE = r".*\['units'\]\['(\d+)'\]\[(\d+)\]$"


def _pool_fetch_leaves(
    pool: _ConnPool, host: str, step: int, indices: "Sequence[int]",
    timeout: float, what: str = "unit",
    metrics: "Optional[Any]" = None,
) -> "List[np.ndarray]":
    """THE keep-alive manifest-indexed fetch: acquire a pooled donor
    connection, fetch each leaf index in order, release only after the
    bodies were consumed exactly (close — never release — on error: a
    conn with stale bytes would parse tensor bytes as a status line),
    with the death vocabulary the redistribution engine's failover
    keys on — ``urllib.error.HTTPError`` passes through (the holder
    ANSWERED: protocol error / version skew, escalate), everything
    transport-shaped normalizes to ``ConnectionError``/``OSError``
    family. Shared by ``fetch_opt_shard`` and :class:`RedistFetcher`
    so the two redistribution clients cannot drift in failover
    behavior."""
    try:
        conn = pool.acquire(host)
        try:
            arrays = [
                np.asarray(fetch_leaf(
                    host, step, int(mi), timeout=timeout, conn=conn,
                    metrics=metrics,
                ))
                for mi in indices
            ]
        except BaseException:
            conn.close()  # possibly mid-body: not reusable
            raise
        pool.release(host, conn)
        return arrays
    except urllib.error.HTTPError:
        raise  # the holder answered: protocol error, not a death
    except (http.client.HTTPException, socket.timeout) as e:
        # normalize to the engine's death vocabulary (URLError and
        # ConnectionError are already OSError family)
        raise ConnectionError(
            f"holder {host} died fetching {what}: {e}"
        ) from e


def serve_redist_payload(
    units: "Dict[int, Sequence[Any]]", timeout: float = 60.0,
    step: int = _REDIST_STEP,
) -> "tuple[str, Any]":
    """Publish a holder's redistribution payload: one ephemeral
    checkpoint server staging ``{"units": {str(u): [arrays...]}}`` at
    the redist step (``step``: ephemeral exchanges keep the fixed
    default; the serve plane passes the model version so adoption
    fetches are version-gated). Arrays may be DEVICE arrays — the
    server's lazy per-leaf staging defers any device-to-host copy until
    a receiver actually fetches that unit (host ndarrays are snapshot
    eagerly, which is what makes the close-side drain safe). Returns
    ``(address, close)``; ``close()`` drains residual staging and
    tears the server down. The ``serve_fn`` hook of
    ``comm.redistribute.exchange``."""
    srv = CheckpointServer(timeout=timeout)
    tree = {
        "units": {
            str(int(u)): list(arrays)
            for u, arrays in units.items()
        }
    }
    srv.allow_checkpoint(int(step), tree)

    def _close() -> None:
        try:
            srv.disallow_checkpoint()
        finally:
            srv.shutdown(wait=False)

    return srv.metadata(), _close


class RedistFetcher:
    """Pull side of the redistribution plane: per-address manifest
    cache + keep-alive connection pool over the ``fetch_leaf`` raw
    plane. ``fetch(address, unit)`` returns the unit's arrays in slot
    order; holder death surfaces as ``ConnectionError``/``OSError`` so
    the engine's failover can reroute. The ``fetch_factory`` hook of
    ``comm.redistribute.exchange``.

    ``step``: the checkpoint step the holders staged their payload at.
    Ephemeral reshard exchanges use the fixed ``_REDIST_STEP``; the
    serve plane's deploy adoptions pass the MODEL VERSION here, which
    makes every fetch version-gated for free — a holder still staging
    (or already past) that version answers 400/503, never stale
    bytes."""

    def __init__(self, timeout: float = 60.0,
                 step: int = _REDIST_STEP) -> None:
        import re as _re

        self._timeout = float(timeout)
        self._step = int(step)
        self._pool = _ConnPool(self._timeout)
        self._pat = _re.compile(_REDIST_PATH_RE)
        self._slots: "Dict[str, Dict[int, List[int]]]" = {}
        self._lock = threading.Lock()

    def _unit_slots(self, addr: str) -> "Dict[int, List[int]]":
        with self._lock:
            cached = self._slots.get(addr)
        if cached is not None:
            return cached
        manifest = fetch_manifest(
            addr, self._step, timeout=self._timeout
        )
        by_unit: "Dict[int, Dict[int, int]]" = {}
        for mi, entry in enumerate(manifest["leaves"]):
            m = self._pat.match(entry.get("path", ""))
            if m is None or entry.get("kind") != "ndarray":
                continue
            by_unit.setdefault(int(m.group(1)), {})[int(m.group(2))] = mi
        slots = {
            u: [by_slot[s] for s in sorted(by_slot)]
            for u, by_slot in by_unit.items()
        }
        with self._lock:
            self._slots[addr] = slots
        return slots

    def fetch(self, addr: str, unit: int) -> "List[np.ndarray]":
        try:
            slots = self._unit_slots(addr)
        except urllib.error.HTTPError:
            raise  # protocol error, not a death
        except (http.client.HTTPException, socket.timeout) as e:
            raise ConnectionError(
                f"redist holder {addr} died serving its manifest: {e}"
            ) from e
        if int(unit) not in slots:
            raise ConnectionError(
                f"holder {addr} advertises no unit {unit} — its "
                "published spec and the plan diverged"
            )
        return _pool_fetch_leaves(
            self._pool, addr, self._step, slots[int(unit)],
            self._timeout, what=f"unit {unit}",
        )

    def close(self) -> None:
        self._pool.close_all()


def redistribute_exchange(
    mgr: Any,
    my_rank: int,
    world: int,
    dst_spec: ShardSpec,
    holdings: "Dict[int, Sequence[Any]]",
    planner: RedistPlanner,
    timeout: float = 60.0,
    parallel: int = 4,
    source: str = "reshard",
):
    """``comm.redistribute.exchange`` bound to the raw-bytes heal plane
    — THE cohort redistribution call the sharded optimizer wrapper and
    DiLoCo's ``sharded_outer`` heal retarget onto. Returns the
    engine's ``ExchangeResult`` or ``None`` (wire latched / transfer
    failed whole — caller keeps its old grid and the next healthy
    quorum retries)."""
    from torchft_tpu.comm.redistribute import exchange

    return exchange(
        mgr, my_rank, world, dst_spec, holdings, planner,
        serve_fn=lambda units: serve_redist_payload(units, timeout),
        fetch_factory=lambda: RedistFetcher(timeout),
        parallel=parallel, source=source,
    )


def split_leaf_payload(
    arrays: "Sequence[Any]", model_shards: int
) -> "List[List[np.ndarray]]":
    """Split one redistribution unit's slot arrays into ``model_shards``
    sub-unit payloads — the 2-D mesh's holdings shape. Each slot array
    is raveled and cut into ``model_shards`` contiguous pieces (piece
    ``m`` of every slot → sub-unit ``m``), so sub-unit ``leaf * M + m``
    carries exactly the bytes device column ``m`` owns. Slots whose
    flat length does not divide evenly put the remainder on the LAST
    shard (deterministic, mirrored by :func:`join_leaf_payload`)."""
    m = max(1, int(model_shards))
    out: "List[List[np.ndarray]]" = [[] for _ in range(m)]
    for a in arrays:
        flat = np.ascontiguousarray(a).ravel()
        step = len(flat) // m
        for s in range(m):
            lo = s * step
            hi = (s + 1) * step if s < m - 1 else len(flat)
            out[s].append(flat[lo:hi])
    return out


def join_leaf_payload(
    pieces_by_shard: "Sequence[Sequence[Any]]",
    template_shapes: "Sequence[Tuple[int, ...]]",
) -> "List[np.ndarray]":
    """Inverse of :func:`split_leaf_payload`: reassemble a unit's slot
    arrays from its ``model_shards`` sub-unit payloads, restoring the
    shapes of ``template_shapes`` (one per slot). Raises ``ValueError``
    when the received bytes cannot fill a template — the caller treats
    that unit as missing and reinitializes (the reshard adoption
    contract)."""
    n_slots = len(template_shapes)
    for shard in pieces_by_shard:
        if len(shard) != n_slots:
            raise ValueError(
                f"sub-unit carries {len(shard)} slots, expected {n_slots}"
            )
    out: "List[np.ndarray]" = []
    for i, shape in enumerate(template_shapes):
        flat = np.concatenate([
            np.ascontiguousarray(shard[i]).ravel()
            for shard in pieces_by_shard
        ]) if pieces_by_shard else np.empty((0,))
        want = int(np.prod(shape)) if shape else 1
        if flat.size != want:
            raise ValueError(
                f"slot {i}: reassembled {flat.size} elements, template "
                f"shape {tuple(shape)} needs {want}"
            )
        out.append(flat.reshape(shape))
    return out


def _recv_chunked(
    metadata: str, step: int, num_chunks: int, timeout: float,
    metrics: "Optional[Any]" = None,
    wire_dtype: "Optional[str]" = None,
) -> Any:
    """Parallel transfer over ``num_chunks`` keep-alive connections:
    tensor leaves ride the RAW multi-leaf stream (``rawleaves`` ranges:
    back-to-back tensor bytes readinto preallocated arrays — no pickle
    for tensor data, closing that trust surface, and no per-leaf round
    trips; the donor stages each leaf just-in-time while earlier leaves
    are on the wire), reassembled with the donor's treedef. Pickle
    remains for the manifest and non-tensor object leaves."""
    import jax

    t0 = time.perf_counter()
    wire0 = _wire_seconds(metrics) if metrics is not None else None
    conn_pool = _ConnPool(timeout)

    first_conn = conn_pool.acquire(metadata)
    manifest = fetch_manifest(
        metadata, step, timeout=timeout, conn=first_conn
    )
    conn_pool.release(metadata, first_conn)
    entries = manifest["leaves"]
    n = len(entries)
    num_chunks = max(1, num_chunks)
    outs: List[Any] = [None] * n
    total = [0]
    total_lock = threading.Lock()  # += is not atomic across workers

    # contiguous index ranges balanced by BYTES (a byte-balanced split
    # keeps every connection busy for roughly the whole transfer; leaf
    # counts alone can put 90% of the state on one connection)
    tensor_idx = [
        i for i, e in enumerate(entries) if e["kind"] == "ndarray"
    ]
    object_idx = [
        i for i, e in enumerate(entries) if e["kind"] != "ndarray"
    ]
    ranges: List[tuple] = []
    wire_np = _WIRE_DTYPES[wire_dtype]() if wire_dtype is not None else None
    if tensor_idx:
        budget = sum(
            _entry_wire_nbytes(entries[i], wire_np) for i in tensor_idx
        ) / float(num_chunks)
        run_start, run_bytes = None, 0
        prev = None
        for i in tensor_idx:
            if run_start is None:
                run_start, run_bytes = i, 0
            elif i != prev + 1 or (
                run_bytes >= budget and len(ranges) < num_chunks - 1
            ):
                ranges.append((run_start, prev + 1))
                run_start, run_bytes = i, 0
            run_bytes += _entry_wire_nbytes(entries[i], wire_np)
            prev = i
        ranges.append((run_start, prev + 1))
    logger.info(
        "fetching checkpoint step %d: %d leaves over %d connections "
        "(%d raw ranges)", step, n, num_chunks, len(ranges),
    )

    def _fetch_range(r: tuple) -> None:
        lo, hi = r
        nb = [0]
        with throughput_span(
            metrics, "heal_wire", nb, leaf=f"{lo}-{hi}", host=metadata,
            bytes=sum(_entry_wire_nbytes(e, wire_np)
                      for e in entries[lo:hi])
            + (4 * (hi - lo) if _WIRE_CRC else 0),  # the frames ride it
        ):
            _fetch_range_inner(lo, hi, nb)

    def _fetch_range_inner(lo: int, hi: int, nb: list) -> None:
        use_crc = _WIRE_CRC
        params = []
        if wire_dtype is not None:
            params.append(f"wire={wire_dtype}")
        if use_crc:
            params.append("crc=1")
        path = f"/checkpoint/{step}/rawleaves/{lo}-{hi}"
        if params:
            path += "?" + "&".join(params)
        conn = conn_pool.acquire(metadata)
        try:
            with span(metrics, "heal_wire_wait") as waiting:
                resp = conn.get(path)
            if metrics is not None:
                metrics.incr("heal_wire_wait_s", waiting.elapsed)
            clen = int(resp.headers["Content-Length"])
            got = 0
            for i in range(lo, hi):
                entry = entries[i]
                dtype = _dtype_from_str(entry["dtype"])
                shape = tuple(entry["shape"])
                leaf_np = (
                    wire_np
                    if wire_np is not None
                    and dtype in _WIRE_COMPRESSIBLE
                    else dtype
                )
                outs[i] = _read_wire_tensor(
                    resp, dtype, shape, leaf_np, f"leaf {i} body",
                    check_crc=use_crc, metrics=metrics,
                )
                # count WIRE bytes (the downcast payload under the
                # opt-in lossy encoding, not the upcast copy; the
                # 4-byte CRC frame rides the body for length
                # accounting but is not payload)
                wire_nb = _entry_wire_nbytes(entry, wire_np) + (
                    4 if use_crc else 0
                )
                got += wire_nb
                with total_lock:
                    total[0] += wire_nb
                nb[0] += wire_nb
            if got != clen:
                raise ConnectionError(
                    f"rawleaves {lo}-{hi}: advertised Content-Length "
                    f"{clen} != {got} implied by the manifest — "
                    "donor/healer version skew; refusing to desync "
                    "the stream"
                )
        except BaseException:
            # possibly mid-body or with unread trailing bytes: stale,
            # must not be reused by a concurrent worker
            conn.close()
            raise
        conn_pool.release(metadata, conn)

    def _fetch_object(i: int) -> None:
        conn = conn_pool.acquire(metadata)
        try:
            outs[i] = fetch_leaf(
                metadata, step, i, timeout=timeout, conn=conn,
                metrics=metrics,
            )
        except BaseException:
            conn.close()
            raise
        conn_pool.release(metadata, conn)

    try:
        with ThreadPoolExecutor(max_workers=num_chunks) as pool:
            futs = [pool.submit(_fetch_range, r) for r in ranges]
            futs += [pool.submit(_fetch_object, i) for i in object_idx]
            for f in futs:
                f.result()
    finally:
        # keep-alive conns die with the heal, not with GC: a leaked conn
        # pins a blocked donor handler thread until the socket collects
        conn_pool.close_all()
    if metrics is not None:
        _gauge_fetch(metrics, t0, wire0, total[0], n)
    return jax.tree_util.tree_unflatten(manifest["treedef"], outs)
