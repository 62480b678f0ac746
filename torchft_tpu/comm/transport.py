"""TCP transport for cross-replica collectives (DCN plane).

The reference's data plane is c10d Gloo/NCCL rebuilt per quorum
(ref process_group.py:250-336). On TPU, cross-replica-group traffic rides
the data-center network between hosts, so the equivalent is a host-side
socket transport that is rebuilt per quorum from the rendezvous store:

    configure(store_addr, rank, world_size):
        endpoints rendezvous through the store; two wire topologies —
        "star" (rank 0 reduces and fans out; lowest latency for tiny
        payloads) and "ring" (bandwidth-optimal reduce-scatter +
        all-gather), selected per context ("auto" picks ring at >= 3).

Collectives are distributed over ``channels`` independent lanes — each
lane owns its own socket set and worker thread, so several ops (e.g. DDP
gradient buckets) are in flight on the wire at once and overlap with the
backward pass that produces later buckets (the role of the reference's
mid-backward comm hooks, ref ddp.py:49-71). Assignment is deterministic
(submission index modulo lane count), so identical op sequences land on
identical lanes on every rank and each lane's stream stays ordered.

Reconfigure/shutdown closes sockets, which fails in-flight ops with
ConnectionError — the abort analog for wedged transports (XLA collectives
cannot be aborted; host sockets can, SURVEY.md §7 hard-part #2). The first
error a context latches does the same to its own sockets (shutdown, then
close): a ring member only ever hears from its two neighbours, so a death
has to be passed on by them at once — a survivor two hops from the victim
otherwise sits in its hop until the timeout (``_latch_error``).

Zero-copy data path: sends are scatter-gather (``sendmsg`` iovecs: one
small metadata buffer plus the array bodies themselves — the full payload
is never materialized into a fresh bytes object), receives land in
step-persistent per-lane buffer pools via ``recv_into`` (two rotating
payload slots, so a ring hop can forward the previous frame while the
next one streams in), and ALLREDUCE payloads are decoded straight into
the caller's arrays through the codec ``decode_into`` interface — the
reduction is in place. The caller DONATES the arrays it submits: the
returned future resolves to arrays that may alias the inputs (reduced in
place); after a transport error their contents are unspecified, which is
fine because an errored step never commits (manager error latching).

How a gradient op is cut and mapped to lanes (``_submit`` →
``_chunk_grid_owned``; ALLREDUCE and REDUCE_SCATTER alike). Every rank
computes the same cut and the same chunk->lane map from shapes, world
size and lane count alone, so each lane's frame stream stays ordered
exactly as in the one-op-one-lane model; each involved lane runs an
independent sub-op over its chunk views and a shared op state resolves
the caller's future when the last lane finishes. Two cuts:

* **A ``chunk_bytes`` grid** — contiguous <= ``chunk_bytes`` slices of
  each flat view, in view order, chunk c on lane ``(base + c) %
  channels`` (``base`` = the op's round-robin index). The grid is part of
  what is computed: it is the lossy codecs' encode granularity (int8
  carries one scale a chunk) and the star's pipeline depth (per-chunk
  length-prefixed frames, upload and replies interleaved by the
  select-driven ``_duplex_exchange`` so chunk k+1 ships while the root
  still reduces chunk k). An explicit ``chunk_bytes`` is always
  honoured; with none given, the lossy codecs and the star keep 1 MiB.
  Because the star root drains peers in rank order PER CHUNK and the ring
  treats each chunk view as an independent payload, the reduced values
  are bitwise identical whichever lanes the chunks ride
  (tests/test_transport_striping.py pins this for every codec, and pins
  the bytes themselves against golden digests).

* **The ring's own cut** (identity codec, no ``chunk_bytes`` given — the
  default, and what a DDP bucket rides). A ring sub-op makes 2(n-1)
  hops, each moving rank-part p of EVERY chunk view the lane holds; a
  hop's fixed costs (a select round, the header, a lock-step handshake
  with both neighbours, GIL hand-offs around every syscall and every
  per-view ``np.add``) are paid per hop and per view, not per byte. On
  a fixed 1 MiB grid dealt over four lanes a 32 MiB bucket was 4 lanes
  x 6 hops of 8 separate 256 KB views, and the step's rate was set by
  views, not by bytes or cores (PERF.md, PR 27: 342 -> 760 MB/s on the
  same bytes). So the op is cut from its own size: its bytes are dealt
  in view order into ``_ring_lanes`` near-equal contiguous shares — as
  many lanes as bring a hop nearest ``_RING_HOP_BYTES`` (8 MiB), at
  least one, at most all — and a view is cut only where a share ends
  inside it. A bucket (<= 32 MiB at world 4) rides ONE lane whole: a hop
  is one iovec, one ``recv_into`` target, one ``np.add`` or
  ``np.copyto``, and the other buckets in flight fill the other lanes.
  An array much larger than a bucket (an embedding table; LocalSGD /
  DiLoCo's whole-model arrays) is cut into at most ``channels``
  contiguous slices, so every socket carries a stream and no single lane
  becomes the step's tail. The lanes' receive pools grow to the largest
  hop (two slots a lane). Moving the part boundaries re-associates the
  same f32 sum (which rank's partial an element starts from), so results
  may differ in the last bit from a ``chunk_bytes`` grid's; all ranks
  still decode the same bytes and stay bitwise equal to each other.
"""

from __future__ import annotations

import logging
import queue
import socket
import struct
import threading
import time
from concurrent.futures import Future
from datetime import timedelta
from typing import Dict, List, Optional, Sequence

import numpy as np

from torchft_tpu.comm.context import CommContext, ReduceOp, Work
from torchft_tpu.comm.store import create_store_client
from torchft_tpu.comm.wire import (
    HAS_SENDMSG as _HAS_SENDMSG,
    IOV_MAX as _IOV_MAX,
    as_bytes_view as _as_bytes_view,
    bf16_wire_dtype as _bf16_dtype,
    iov_join as _iov_join,
    iov_nbytes as _iov_nbytes,
    recv_exact as _recv_exact,
    recv_into_exact as _recv_into_exact,
    sendmsg_all as _sendmsg_all,
)
from torchft_tpu.utils.metrics import Metrics
from torchft_tpu.utils.profiling import span

logger = logging.getLogger(__name__)

__all__ = [
    "TcpCommContext",
    "codec_decode_frame",
    "codec_encode_frame",
    "codec_roundtrip",
    "codec_wire_nbytes",
    "host_unsupported_reason",
    "make_wire_codec",
]

_OP_ALLREDUCE = 1
_OP_ALLGATHER = 2
_OP_BROADCAST = 3
_OP_REDUCE_SCATTER = 4

# Opcodes that ride the chunked gradient data path (and therefore
# land in the comm_* phase timers): allreduce plus its scatter variant.
_GRAD_OPCODES = (_OP_ALLREDUCE, _OP_REDUCE_SCATTER)

_REDUCE_FNS = {
    ReduceOp.SUM: lambda a, b: np.add(a, b, out=a),
    ReduceOp.MAX: lambda a, b: np.maximum(a, b, out=a),
    ReduceOp.MIN: lambda a, b: np.minimum(a, b, out=a),
}

# The byte-plane primitives (iovec sends, exact receives, uint8
# reinterpret views) live in comm/wire.py, SHARED with the heal plane —
# one implementation for both data paths. The private aliases above keep
# this module's historical names for its own call sites and tests.


def _duplex_exchange(tx_sock: socket.socket, tx_bufs: Sequence,
                     rx_sock: socket.socket, rx_targets,
                     timeout: float) -> None:
    """Single-threaded full-duplex exchange: stream ``tx_bufs`` (an iovec
    list) to ``tx_sock`` while filling the memoryviews yielded by the
    ``rx_targets`` generator from ``rx_sock``, interleaved via select.

    This replaces the sender-thread-per-exchange pattern: same
    deadlock-freedom (receives always drain, so the peer's sends always
    progress), none of the thread spawn/GIL-handoff cost — which
    dominated on oversubscribed hosts once striping multiplied the
    number of concurrent exchanges. ``rx_targets`` may yield each next
    buffer lazily (e.g. parse a header to size the payload slot);
    ``tx_sock`` and ``rx_sock`` may be the same socket (star peer)."""
    mvs = [mv for mv in (_as_bytes_view(b) for b in tx_bufs) if len(mv)]
    sender: Optional[threading.Thread] = None
    send_err: List[Optional[Exception]] = [None]
    if not _HAS_SENDMSG:  # pragma: no cover — non-Linux fallback
        # sendall-to-completion before receiving would deadlock once both
        # sides' payloads exceed the socket buffers — keep the old
        # sender-thread shape on platforms without sendmsg.
        def _send_all() -> None:
            try:
                _sendmsg_all(tx_sock, mvs)
            except Exception as e:  # noqa: BLE001
                send_err[0] = e

        sender = threading.Thread(target=_send_all, daemon=True)
        sender.start()
        mvs = []
    rx_mv: Optional[memoryview] = None
    rx_off = 0

    def _advance_rx() -> None:
        nonlocal rx_mv, rx_off
        rx_off = 0
        rx_mv = next(rx_targets, None)
        while rx_mv is not None and len(rx_mv) == 0:
            rx_mv = next(rx_targets, None)

    _advance_rx()
    if not mvs and rx_mv is None:
        if sender is not None:  # pragma: no cover — non-Linux fallback
            sender.join(timeout=timeout)
            if send_err[0] is not None:
                raise send_err[0]
            if sender.is_alive():
                raise TimeoutError("duplex exchange send stalled")
        return
    import select as _select

    # Idle deadline, not wall-clock: extended on every byte of progress,
    # matching the old per-syscall timeout semantics — a slow link that
    # keeps moving data must not fail a large exchange.
    deadline = time.perf_counter() + timeout
    # With a sender thread (non-sendmsg fallback) the select phase has
    # nothing to send — and toggling the tx socket non-blocking under
    # the thread's in-flight sendall would make it crash with
    # BlockingIOError. Leave every socket in timeout mode there.
    socks = {tx_sock, rx_sock} if sender is None else set()
    for s in socks:
        s.setblocking(False)
    try:
        # Interleave only while there is still something to SEND — that
        # is the window where a blocking receive could deadlock (both
        # sides wedged in sends against full buffers). Once tx drains,
        # fall through to plain blocking receives: half the wakeups, and
        # each one can sleep through GIL contention with in-process
        # compute (jax dispatch) instead of re-waking per TCP segment —
        # measured as a 3x allreduce_p50 regression in bench.py when the
        # select loop ran the whole exchange.
        while mvs:
            now = time.perf_counter()
            if now > deadline:
                raise TimeoutError("duplex exchange stalled")
            rlist = [rx_sock] if rx_mv is not None else []
            wlist = [tx_sock]
            r, w, _ = _select.select(
                rlist, wlist, [], min(1.0, deadline - now)
            )
            if w:
                # Drain until the buffer fills — one select round can
                # ship many chunks; re-selecting per sendmsg doubled the
                # syscall count on fast loopback paths.
                while mvs:
                    try:
                        sent = tx_sock.sendmsg(mvs[:_IOV_MAX])
                    except (BlockingIOError, InterruptedError):
                        break
                    if sent == 0:
                        raise ConnectionError(
                            "comm transport connection closed"
                        )
                    deadline = time.perf_counter() + timeout
                    while sent and mvs:
                        if sent >= len(mvs[0]):
                            sent -= len(mvs[0])
                            mvs.pop(0)
                        else:
                            mvs[0] = mvs[0][sent:]
                            sent = 0
            if r:
                while rx_mv is not None:
                    try:
                        n = rx_sock.recv_into(rx_mv[rx_off:])
                    except (BlockingIOError, InterruptedError):
                        break
                    if n == 0:
                        raise ConnectionError(
                            "comm transport connection closed"
                        )
                    deadline = time.perf_counter() + timeout
                    rx_off += n
                    if rx_off == len(rx_mv):
                        _advance_rx()
        # tx drained — finish the remaining receives blocking (the
        # socket timeout bounds each recv, i.e. idle time, not total).
        rx_sock.settimeout(timeout)
        while rx_mv is not None:
            n = rx_sock.recv_into(rx_mv[rx_off:])
            if n == 0:
                raise ConnectionError("comm transport connection closed")
            rx_off += n
            if rx_off == len(rx_mv):
                _advance_rx()
        if sender is not None:  # pragma: no cover — non-Linux fallback
            sender.join(timeout=timeout)
            if send_err[0] is not None:
                raise send_err[0]
            if sender.is_alive():
                raise TimeoutError("duplex exchange send stalled")
    finally:
        for s in socks:
            s.settimeout(timeout)


class _RecvBufs:
    """Per-lane receive buffer pool, step-persistent and sized to the
    largest seen frame. Headers land in a dedicated scratch; payloads
    rotate across TWO slots so the full-duplex ring can forward the
    previous frame (a view into slot A) while the next one is received
    into slot B. Returned memoryviews are valid until the slot's next
    reuse — consumers must decode/copy out before two more payload
    receives."""

    def __init__(self) -> None:
        self._hdr = bytearray(4096)  # covers any metadata piece (dtype
        # tags, <=255-dim shape vectors); payload bodies use the slots
        self._slots = [bytearray(), bytearray()]
        self._i = 0

    def recv_header(self, sock: socket.socket, n: int) -> memoryview:
        if n > len(self._hdr):
            # n comes off the wire (dtype-tag/shape lengths): a corrupt
            # or desynced frame must fail like every other framing error,
            # not trip an assert (stripped under -O) and desync further.
            raise ConnectionError(
                f"oversized frame metadata ({n} bytes) — corrupt or "
                "desynced stream"
            )
        mv = memoryview(self._hdr)[:n]
        _recv_into_exact(sock, mv)
        return mv

    def recv_payload(self, sock: socket.socket, n: int) -> memoryview:
        if n == 0:
            return memoryview(b"")
        mv = self.payload_slot(n)
        _recv_into_exact(sock, mv)
        return mv

    def payload_slot(self, n: int) -> memoryview:
        """Rotate to the next payload slot and return its first ``n``
        bytes WITHOUT receiving — for callers that fill it through the
        select-driven duplex exchange instead of a blocking recv."""
        self._i ^= 1
        if len(self._slots[self._i]) < n:
            self._slots[self._i] = bytearray(n)
        return memoryview(self._slots[self._i])[:n]

    def header_slot(self, n: int) -> memoryview:
        """First ``n`` bytes of the header scratch WITHOUT receiving
        (duplex-exchange variant of recv_header)."""
        if n > len(self._hdr):
            raise ConnectionError(
                f"oversized frame metadata ({n} bytes) — corrupt or "
                "desynced stream"
            )
        return memoryview(self._hdr)[:n]


def _array_frame_iovecs(arrays: Sequence[np.ndarray]) -> List:
    """Iovec list whose concatenation is byte-identical to
    ``_pack_arrays(arrays)`` — metadata in small interleaved bytes
    buffers, bodies as the arrays themselves (zero copy)."""
    iov: List = []
    meta = bytearray(struct.pack("<I", len(arrays)))
    for a in arrays:
        a = np.ascontiguousarray(a)
        dt = _dtype_tag(a.dtype)
        meta += struct.pack("<H", len(dt))
        meta += dt
        meta += struct.pack("<B", a.ndim)
        if a.ndim:
            meta += struct.pack(f"<{a.ndim}q", *a.shape)
        meta += struct.pack("<Q", a.nbytes)
        iov.append(bytes(meta))
        meta = bytearray()
        iov.append(a)
    if meta:
        iov.append(bytes(meta))
    return iov


def _send_arrays(sock: socket.socket, arrays: Sequence[np.ndarray]) -> None:
    # Single framing definition: see _pack_arrays. Scatter-gather send —
    # the payload is never materialized (was sock.sendall(_pack_arrays())).
    _sendmsg_all(sock, _array_frame_iovecs(arrays))


def _dtype_tag(d: np.dtype) -> bytes:
    """Wire tag that round-trips extension dtypes: ml_dtypes types
    (bfloat16, float8_*) stringify to an anonymous '<V2', so use the
    registered name for them instead."""
    if d.str.lstrip("<>|=").startswith("V"):
        return d.name.encode()
    return d.str.encode()


def _dtype_from_tag(tag: str) -> np.dtype:
    try:
        d = np.dtype(tag)
        if not d.str.lstrip("<>|=").startswith("V"):
            return d
    except TypeError:
        pass
    import ml_dtypes

    return np.dtype(getattr(ml_dtypes, tag))


def _pack_arrays(arrays: Sequence[np.ndarray]) -> bytes:
    """In-memory version of _send_arrays' framing."""
    parts = [struct.pack("<I", len(arrays))]
    for a in arrays:
        a = np.ascontiguousarray(a)
        dt = _dtype_tag(a.dtype)
        parts.append(struct.pack("<H", len(dt)))
        parts.append(dt)
        parts.append(struct.pack("<B", a.ndim))
        if a.ndim:
            parts.append(struct.pack(f"<{a.ndim}q", *a.shape))
        parts.append(struct.pack("<Q", a.nbytes))
        parts.append(a.tobytes())
    return b"".join(parts)


def _unpack_arrays(data) -> List[np.ndarray]:
    """Decode _pack_arrays' framing from any buffer (bytes or a reused
    memoryview); the returned arrays own their memory."""
    data = memoryview(data)
    offset = 0

    def take(n: int) -> memoryview:
        nonlocal offset
        out = data[offset: offset + n]
        if len(out) != n:
            raise ConnectionError("truncated array frame")
        offset += n
        return out

    (count,) = struct.unpack("<I", take(4))
    out: List[np.ndarray] = []
    for _ in range(count):
        (dlen,) = struct.unpack("<H", take(2))
        dtype = _dtype_from_tag(bytes(take(dlen)).decode())
        (ndim,) = struct.unpack("<B", take(1))
        shape = struct.unpack(f"<{ndim}q", take(8 * ndim)) if ndim else ()
        (nbytes,) = struct.unpack("<Q", take(8))
        out.append(
            np.frombuffer(take(nbytes), dtype=dtype).reshape(shape).copy()
        )
    return out


def _recv_arrays(
    sock: socket.socket, bufs: Optional[_RecvBufs] = None
) -> List[np.ndarray]:
    # Streaming reader for _pack_arrays' framing: each body lands in the
    # lane's pooled buffer (no per-frame allocation) and is decoded ONCE
    # into an owned output array — huge payloads are never double-buffered
    # into a bytes object on receive.
    bufs = bufs if bufs is not None else _RecvBufs()
    (n,) = struct.unpack("<I", bufs.recv_header(sock, 4))
    out: List[np.ndarray] = []
    for _ in range(n):
        (dlen,) = struct.unpack("<H", bufs.recv_header(sock, 2))
        dtype = _dtype_from_tag(bytes(bufs.recv_header(sock, dlen)).decode())
        (ndim,) = struct.unpack("<B", bufs.recv_header(sock, 1))
        shape = (
            struct.unpack(f"<{ndim}q", bufs.recv_header(sock, 8 * ndim))
            if ndim else ()
        )
        (nbytes,) = struct.unpack("<Q", bufs.recv_header(sock, 8))
        body = bufs.recv_payload(sock, nbytes)
        out.append(np.frombuffer(body, dtype=dtype).reshape(shape).copy())
    return out


class _OpState:
    """Completion state shared by one striped op's per-lane sub-ops: the
    LAST lane to finish resolves the caller's future with the donated
    arrays (reduced in place across all lanes' disjoint chunk views).

    Continuation contract: callbacks attached to the op future
    (``Work.add_done_callback``) run inline on that last lane's thread —
    they must be O(enqueue) cheap, or they stall every later op queued
    on the lane. The streamed DDP pipeline honors this by enqueueing
    per-bucket unpack work to its own bounded worker.

    ``t_submit``/``metrics``: the op's end-to-end wire time (submit →
    last-lane completion) is observed as ``comm_op_wire`` — per-SUB-op
    ``comm_wire_reduce`` understates a striped op (each lane reports
    only its share), and overlap accounting needs the op-level number."""

    __slots__ = ("arrays", "fut", "_remaining", "_lock", "metrics",
                 "t_submit")

    def __init__(self, arrays: List[np.ndarray], fut: Future,
                 n_subops: int, metrics: "Optional[Metrics]" = None) -> None:
        self.arrays = arrays
        self.fut = fut
        self._remaining = n_subops
        self._lock = threading.Lock()
        self.metrics = metrics
        self.t_submit = time.perf_counter()

    def subop_done(self) -> bool:
        with self._lock:
            self._remaining -= 1
            done = self._remaining == 0
        if done and self.metrics is not None:
            self.metrics.observe(
                "comm_op_wire", time.perf_counter() - self.t_submit
            )
        return done


class _PendingOp:
    __slots__ = ("opcode", "arrays", "op", "root", "fut", "t_submit",
                 "chunks", "state", "owners", "seq", "nbytes")

    def __init__(self, opcode: int, arrays: List[np.ndarray], op: str,
                 root: int, fut: Future,
                 chunks: "Optional[List[np.ndarray]]" = None,
                 state: "Optional[_OpState]" = None,
                 owners: "Optional[List[int]]" = None,
                 seq: "Optional[int]" = None, nbytes: int = 0) -> None:
        self.opcode = opcode
        self.arrays = arrays
        self.op = op
        self.root = root
        self.fut = fut
        self.chunks = chunks  # this lane's chunk views (striped allreduce)
        self.state = state    # shared across the op's sub-ops
        # REDUCE_SCATTER only: destination rank per chunk (aligned with
        # ``chunks``) — the rank whose update shard the chunk feeds.
        self.owners = owners
        # Gradient ops only: the op's number in its context
        # (TcpCommContext.next_grad_op) and the raw bytes THIS sub-op
        # carries (the whole op's where it is one).
        self.seq = seq
        self.nbytes = nbytes
        self.t_submit = time.perf_counter()


# Codec granularity where none is given: the lossy codecs quantize per grid
# chunk (int8 carries one scale a chunk), and the star pipelines chunk k+1's
# upload under chunk k's reduction. The identity-codec ring needs neither
# and derives its cut from the op (:func:`_ring_lanes`).
_DEFAULT_GRID_BYTES = 1 << 20

# What a ring hop should carry: few enough bytes that 2(n-1) hops pipeline
# against the other ops in flight, enough that a hop's fixed costs (a
# select round, a header, a lock-step handshake with both neighbours, a
# GIL hand-off either side of every syscall) are paid once per megabytes
# and not once per 256 KB view (PERF.md, PR 27: the chip host's table).
_RING_HOP_BYTES = 8 << 20


def _ring_lanes(nbytes: int, world: int, lanes: int) -> int:
    """How many lanes a ring op of ``nbytes`` rides: as many as bring a
    hop (1/world of a lane's share) nearest ``_RING_HOP_BYTES``, at least
    one, at most all. A DDP bucket (<= 32 MiB at world 4) rides one lane
    whole — the other buckets in flight fill the other lanes; an array
    much larger than a bucket is cut so every socket carries a stream and
    no lane becomes the step's tail. Shapes, world size and lane count
    only: every rank computes the same number."""
    return max(1, min(lanes, round(nbytes / (_RING_HOP_BYTES * world))))


def _chunk_grid(flats: Sequence[np.ndarray],
                chunk_bytes: int) -> List[np.ndarray]:
    """Deterministic chunk grid over the op's flat views: each view is
    split, in view order, into contiguous slices of at most
    ``chunk_bytes`` (at least one element). chunk_bytes <= 0 keeps each
    view whole (one chunk per view). Empty views contribute no chunks.
    Built from shapes/dtypes only, so every rank computes the identical
    grid — the precondition for the chunk->lane map to agree."""
    return _chunk_grid_owned(flats, None, chunk_bytes)[0]


def _chunk_grid_owned(
    flats: Sequence[np.ndarray], owners: "Optional[Sequence[int]]",
    chunk_bytes: int, ring: "Optional[tuple[int, int]]" = None,
) -> "tuple[List[np.ndarray], Optional[List[int]], Optional[List[int]]]":
    """:func:`_chunk_grid` plus a parallel per-chunk owner list: chunk
    views of ``flats[i]`` inherit ``owners[i]`` (the REDUCE_SCATTER
    destination). ``owners=None`` returns ``None`` in its place — the
    allreduce grid. One step rule for both opcodes, so a reduce_scatter
    over the same views computes the identical grid (and identical int8
    per-chunk scales) as an allreduce would.

    ``ring=(world, lanes)`` derives the cut from the op instead of from
    ``chunk_bytes`` (the identity-codec ring's default): the op's bytes
    are dealt, in view order, into :func:`_ring_lanes` near-equal
    contiguous shares, a view being cut only where a share ends inside
    it. The third list names each chunk's share (0-based; its lane is
    ``(base + share) % lanes``); it is None for a ``chunk_bytes`` grid,
    whose chunks are dealt round-robin. A one-array op thus gives each
    of its lanes ONE contiguous view, and a ring hop one rank-part of it."""
    chunks: List[np.ndarray] = []
    chunk_owners: "Optional[List[int]]" = None if owners is None else []
    shares: "Optional[List[int]]" = None if ring is None else []
    if ring is not None:
        total = sum(f.nbytes for f in flats)
        quota = -(-total // _ring_lanes(total, *ring))  # bytes a share
    offset = 0  # of this view in the op's bytes
    for vi, f in enumerate(flats):
        if f.size == 0:
            continue
        if ring is not None:
            # cut where a share boundary falls inside the view, at the
            # first element that starts at or after it
            isz = f.dtype.itemsize
            starts = sorted({0} | {
                -(-(b - offset) // isz)
                for b in range(quota, total, quota)
                if offset < b < offset + f.nbytes
            } - {f.size})
            view_chunks = np.split(f, starts[1:])
            shares.extend((offset + s * isz) // quota for s in starts)
            offset += f.nbytes
        elif chunk_bytes <= 0:
            view_chunks = [f]
        else:
            step = max(1, chunk_bytes // f.dtype.itemsize)
            view_chunks = [f[s: s + step] for s in range(0, f.size, step)]
        chunks.extend(view_chunks)
        if chunk_owners is not None:
            chunk_owners.extend([int(owners[vi])] * len(view_chunks))
    return chunks, chunk_owners, shares


# --------------------------------------------------------------- compression
# Wire codecs for ALLREDUCE payloads (gradients). DCN bandwidth is the
# north-star bottleneck under chaos; bf16 halves the bytes per gradient
# element, int8 quarters them (per-array absmax scale). Reduction still
# accumulates in the caller's dtype (f32), and fan-out/all-gather phases
# forward the SAME encoded bytes to every rank, so all replicas decode
# identical values — the bitwise trajectory-consistency invariant holds.
# allgather/broadcast carry state (checkpoint-adjacent), never compressed.


def _is_compressible(a: np.ndarray) -> bool:
    return a.dtype in (np.float32, np.float64)


class _NoCodec:
    name = "none"

    # flat-view interface (star payload / ring chunk): encode_iovecs for
    # the scatter-gather send side, decode_into for the in-place receive
    # side, wire_nbytes for size validation.
    def wire_nbytes(self, v: np.ndarray) -> int:
        return v.nbytes

    def encode_iovecs(self, views: Sequence[np.ndarray]) -> List:
        """Encoded wire payload as an iovec list for scatter-gather send.
        Concatenation is byte-identical to :meth:`encode_views`; the
        identity codec returns the views themselves (zero copy)."""
        return list(views)

    def encode_views(self, views: Sequence[np.ndarray]) -> bytes:
        return _iov_join(self.encode_iovecs(views))

    def decode_into(self, data: bytes, views: Sequence[np.ndarray],
                    combine) -> None:
        offset = 0
        for v in views:
            nb = v.nbytes
            incoming = np.frombuffer(data[offset: offset + nb], dtype=v.dtype)
            combine(v, incoming)
            offset += nb


class _AstypeCodec(_NoCodec):
    """Lossy float downcast on the wire (bf16 / fp16); non-float arrays
    pass through untouched."""

    def __init__(self, name: str, wire_dtype) -> None:
        self.name = name
        self._wd = np.dtype(wire_dtype)

    def wire_nbytes(self, v: np.ndarray) -> int:
        if _is_compressible(v):
            return v.size * self._wd.itemsize
        return v.nbytes

    def encode_iovecs(self, views):
        # The downcast inherently allocates; non-float views pass through
        # uncopied.
        return [
            v.astype(self._wd) if _is_compressible(v) else v for v in views
        ]

    def decode_into(self, data, views, combine):
        offset = 0
        for v in views:
            if _is_compressible(v):
                nb = v.size * self._wd.itemsize
                incoming = np.frombuffer(
                    data[offset: offset + nb], dtype=self._wd
                ).astype(v.dtype)
            else:
                nb = v.nbytes
                incoming = np.frombuffer(
                    data[offset: offset + nb], dtype=v.dtype
                )
            combine(v, incoming)
            offset += nb


class _Int8Codec(_NoCodec):
    """Per-array (per-chunk on the ring) absmax int8 quantization: wire =
    [scale f32][int8 payload]. Max abs error per element is scale/2 =
    absmax/254."""

    name = "int8"

    @staticmethod
    def _quantize(a: np.ndarray) -> "tuple[np.float32, np.ndarray]":
        absmax = float(np.max(np.abs(a))) if a.size else 0.0
        if not np.isfinite(absmax):
            # Poison the whole array with a NaN scale rather than
            # silently clipping Inf/NaN into plausible int8 values — the
            # decode yields NaN everywhere, so downstream grad-norm/NaN
            # checks fire exactly as they would uncompressed. Wire size
            # stays deterministic (ring peers expect exact lengths).
            return np.float32("nan"), np.zeros(a.shape, np.int8)
        scale = np.float32(absmax / 127.0 if absmax > 0 else 1.0)
        q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
        return scale, q

    def wire_nbytes(self, v: np.ndarray) -> int:
        if _is_compressible(v):
            return 4 + v.size
        return v.nbytes

    def encode_iovecs(self, views):
        parts: List = []
        for v in views:
            if _is_compressible(v):
                scale, q = self._quantize(v)
                parts.append(np.float32(scale).tobytes())
                parts.append(q)
            else:
                parts.append(v)
        return parts

    def decode_into(self, data, views, combine):
        offset = 0
        for v in views:
            if _is_compressible(v):
                scale = np.frombuffer(
                    data[offset: offset + 4], dtype=np.float32
                )[0]
                q = np.frombuffer(
                    data[offset + 4: offset + 4 + v.size], dtype=np.int8
                )
                incoming = q.astype(v.dtype) * v.dtype.type(scale)
                offset += 4 + v.size
            else:
                incoming = np.frombuffer(
                    data[offset: offset + v.nbytes], dtype=v.dtype
                )
                offset += v.nbytes
            combine(v, incoming)


_CODECS = {
    "none": _NoCodec,
    "bf16": lambda: _AstypeCodec("bf16", _bf16_dtype()),
    "fp16": lambda: _AstypeCodec("fp16", np.float16),
    "int8": _Int8Codec,
}

# Stateless identity codec shared by every ring reduce-scatter phase.
_NO_CODEC = _NoCodec()


def make_wire_codec(name: str):
    """Construct a standalone wire codec by name ("none" / "bf16" /
    "fp16" / "int8") — THE public seam for other transport tiers that
    compress whole frames with the allreduce wire's exact codecs (the
    MPMD pipeline plane's stage-boundary act/grad frames,
    torchft_tpu/pipeline.py). Codecs are stateless, so a fresh instance
    per caller is free; error feedback stays the caller's job (the
    codec only defines the wire's local image, exactly as
    :func:`codec_roundtrip` documents)."""
    try:
        return _CODECS[name]()
    except KeyError:
        raise ValueError(
            f"unknown wire codec {name!r}; have {sorted(_CODECS)}"
        ) from None


def codec_roundtrip(codec, chunk_bytes: int, src: np.ndarray,
                    out: np.ndarray) -> None:
    """Write decode(encode(src)) into ``out``, chunked exactly as one
    allreduce contribution over the grid — THE definition of the wire's
    local image. Shared by TcpCommContext.wire_roundtrip and the
    on-device backend (xla_backend.py), whose error-feedback path runs
    this same numpy codec so host and device EF residuals are computed
    against bit-identical images."""
    copy = lambda v, inc: np.copyto(v, inc)  # noqa: E731
    src_chunks = _chunk_grid([src.reshape(-1)], chunk_bytes)
    out_chunks = _chunk_grid([out.reshape(-1)], chunk_bytes)
    for ch_s, ch_o in zip(src_chunks, out_chunks):
        codec.decode_into(
            _iov_join(codec.encode_iovecs([ch_s])), [ch_o], copy
        )


def codec_encode_frame(codec, flat: np.ndarray) -> bytes:
    """Encode one whole flat array as a single wire-frame payload —
    the point-to-point frame surface (pipeline act/grad hops), where a
    tensor travels un-chunked: one frame, one codec image. The
    allreduce planes keep their chunk-grid encoding
    (:func:`codec_roundtrip`); the two must not be mixed, because the
    int8 codec's per-chunk scale makes the images differ."""
    return _iov_join(codec.encode_iovecs([np.ascontiguousarray(flat)]))


def codec_decode_frame(codec, data: bytes, out: np.ndarray) -> None:
    """Decode one :func:`codec_encode_frame` payload into ``out`` in
    place (plain copy combine). Callers that need the wire's local
    image for error feedback decode their own encoded bytes through
    this — residuals stay bit-identical on both ends of the hop."""
    codec.decode_into(data, [out], lambda v, inc: np.copyto(v, inc))


def host_unsupported_reason(algorithm: str, compression: str,
                            op: str = ReduceOp.SUM,
                            topology: str = "flat") -> "Optional[str]":
    """THE host-plane capability rule (CommContext.unsupported_reason):
    shared by TcpCommContext and its subprocess proxy so the two can
    never drift. The socket transport runs every codec on star/ring/auto
    for every reduce op, on both the flat tier and the hierarchical
    domain tier (``topology="hier"``: the intra tier is always
    full-precision star; ``algorithm`` selects the cross-domain tier's
    wire — star fan-in or the multi-hop ring); ``psum`` is the on-device
    hardware-native path and does not exist on sockets."""
    if algorithm == "psum":
        return (
            "algorithm='psum' is the on-device hardware-native path "
            "(comm_backend='xla', comm/xla_backend.py); the host socket "
            "transport has no psum — use algorithm='star'/'ring'/'auto' "
            "here, or select the xla backend"
        )
    if algorithm not in ("auto", "star", "ring"):
        return f"unknown algorithm {algorithm!r}"
    if compression not in _CODECS:
        return (
            f"unknown compression {compression!r}; have {sorted(_CODECS)}"
        )
    if topology not in ("flat", "hier"):
        return (
            f"unknown topology {topology!r}; have 'flat' (one tier "
            "spanning the wire) and 'hier' (domain tree: reduce-within "
            "-> compress -> exchange-across -> broadcast-within)"
        )
    return None


def codec_wire_nbytes(codec, chunk_bytes: int, a: np.ndarray) -> int:
    """Encoded payload size of ``a`` as one allreduce contribution: the
    codec's per-chunk wire size summed over the same chunk grid a real
    op would use (int8 carries a per-chunk scale header, so the grid
    matters). Pure size arithmetic — nothing is encoded."""
    a = np.asarray(a)
    return sum(
        codec.wire_nbytes(ch)
        for ch in _chunk_grid([a.reshape(-1)], chunk_bytes)
    )




class _Lane:
    """One independent connection set + worker thread. A context owns
    ``channels`` lanes; every lane sees the same deterministic subsequence
    of ops on every rank, so per-lane frame sequencing catches desyncs
    exactly like the single-lane design did."""

    def __init__(self, ctx: "TcpCommContext", lane_id: int) -> None:
        self._ctx = ctx
        self._lane_id = lane_id
        self._queue: "queue.Queue[Optional[_PendingOp]]" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._seq = 0
        self._bufs = _RecvBufs()  # step-persistent rx pool, this lane only
        self._peer_socks: Dict[int, socket.socket] = {}   # star: root only
        self._root_sock: Optional[socket.socket] = None   # star: non-root
        self._next_sock: Optional[socket.socket] = None   # ring
        self._prev_sock: Optional[socket.socket] = None   # ring

    # Context-wide configuration, shared by every lane.

    @property
    def _rank(self) -> int:
        return self._ctx._rank

    @property
    def _world_size(self) -> int:
        return self._ctx._world_size

    @property
    def _timeout(self) -> float:
        return self._ctx._timeout

    @property
    def _use_ring(self) -> bool:
        return self._ctx._use_ring

    @property
    def _codec(self):
        return self._ctx._codec

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run_loop,
            name=f"torchft_tpu_comm_l{self._lane_id}",
            daemon=True,
        )
        self._thread.start()

    def close_sockets(self) -> None:
        """Shut down, then close, every socket of this lane. The shutdown
        is what the other side and this lane's own thread feel at once: a
        bare close() from another thread neither wakes a receive blocked
        on the socket nor sends the FIN while that receive holds it."""
        socks = list(self._peer_socks.values())
        self._peer_socks = {}
        for attr in ("_next_sock", "_prev_sock", "_root_sock"):
            s = getattr(self, attr)
            if s is not None:
                socks.append(s)
                setattr(self, attr, None)
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    # ------------------------------------------------------ transport thread

    def _run_loop(self) -> None:
        # Phase split (per lane AND aggregate, see Metrics.snapshot):
        #   submit_wire   — submission → lane dequeue (queue wait: how long
        #                   the op sat behind earlier ops on this lane)
        #   wire_reduce   — dequeue → wire exchange + reduction complete;
        #                   a span, so the lane also shows on its own line
        #                   of a trace's host plane, on the device's clock.
        #                   It says whose it is by ``op=`` (the op's number
        #                   in this context, which ``tft.ddp_submit`` and
        #                   ``tft.ddp_h2d`` carry beside ``bucket=`` and
        #                   ``step=``) and needs no ``step=`` of its own;
        #                   ``bytes=`` is this sub-op's raw bytes and
        #                   ``queue_us=`` its submit_wire, known before
        #                   the span opens
        #   op_resolve    — the LAST lane of an op resolving its future: a
        #                   span, because the continuations run inline
        #                   here (_OpState docstring) and were the unnamed
        #                   gap between a lane's spans until they had one
        metrics = self._ctx.metrics
        tag = f"comm_l{self._lane_id}"
        while True:
            pending = self._queue.get()
            if pending is None:
                return
            t_deq = time.perf_counter()
            try:
                grad = pending.opcode in _GRAD_OPCODES
                if grad:
                    # Allreduce only: these split bench's allreduce number
                    # along the transport's seams — a heal broadcast or
                    # allgather landing here would pin gradient-path
                    # regressions on checkpoint traffic. Striped ops
                    # observe once per SUB-op: the per-lane wire_reduce is
                    # each lane's share of the op (their max approximates
                    # the op's wire time; end-to-end latency is the
                    # manager's `allreduce` timer).
                    queued = t_deq - pending.t_submit
                    with span(metrics, "comm_wire_reduce",
                              lane=self._lane_id, op=pending.seq,
                              bytes=pending.nbytes,
                              queue_us=int(queued * 1e6)) as timed:
                        result = self._execute(pending)
                    metrics.observe("comm_submit_wire", queued)
                    metrics.observe(f"{tag}_wire_reduce", timed.elapsed)
                else:
                    result = self._execute(pending)
                if pending.state is not None:
                    # Striped sub-op: only the LAST lane resolves the
                    # future (with the full donated array list — every
                    # lane reduced its own disjoint chunk views in place).
                    if pending.state.subop_done():
                        with span(metrics, "comm_op_resolve",
                                  op=pending.seq):
                            try:
                                pending.state.fut.set_result(
                                    pending.state.arrays
                                )
                            except Exception:
                                pass  # a sibling lane already failed the op
                elif grad:
                    with span(metrics, "comm_op_resolve", op=pending.seq):
                        pending.fut.set_result(result)
                else:
                    pending.fut.set_result(result)
            except Exception as e:  # noqa: BLE001 — latch every transport error
                self._ctx._latch_error(e, self)
                logger.warning(
                    "comm op failed (rank %d world %d lane %d): %s",
                    self._rank, self._world_size, self._lane_id, e,
                )
                try:
                    # Striped ops share one future: the first failing lane
                    # fails it; a sibling's later set_result/set_exception
                    # is swallowed by the guards (donation contract —
                    # contents are unspecified after an error anyway).
                    pending.fut.set_exception(e)
                except Exception:
                    pass
            # Let go of the op before blocking on the queue: its future's
            # continuations reach the caller's buffers (DDP: that step's
            # gradients on the device), which an idle lane must not pin.
            pending = result = None

    def _execute(self, p: _PendingOp):
        self._seq += 1
        delay = self._ctx._op_delay
        if delay:
            # Test hook: simulated per-op wire latency (overlap tests).
            import time as _time

            _time.sleep(delay)
        if self._world_size == 1:
            if p.opcode in _GRAD_OPCODES:
                # Solo wire: the op's vote is this rank's own health —
                # the degenerate (but still present) data-plane evidence
                # the Manager's fast path consumes.
                self._ctx._record_vote(self._ctx._vote_health_bit())
            if p.opcode == _OP_ALLGATHER:
                return [p.arrays]
            return p.arrays

        if p.opcode in _GRAD_OPCODES:
            # Chunked data path (see module docstring): this sub-op
            # carries the lane's chunk views of the op's payload; every
            # rank built the same grid, so the per-lane frame sequence
            # matches peer for peer. REDUCE_SCATTER rides the exact same
            # phases with per-chunk destinations (p.owners) — only WHERE
            # reduced bytes are delivered differs, never what is
            # computed, so a rank's owned chunks decode bitwise
            # identical to an allreduce over the same grid.
            if self._use_ring:
                self._ring_allreduce_chunks(p)
            elif self._rank == 0:
                self._star_allreduce_root_chunks(p)
            else:
                assert self._root_sock is not None
                self._star_allreduce_peer_chunks(p, self._root_sock)
            return p.arrays

        if self._use_ring:
            return self._execute_ring(p)
        # Star protocol frame (peer->root): [opcode u8][seq u64][op u8] + arrays.
        if self._rank == 0:
            return self._execute_root(p)
        return self._execute_peer(p)

    def _check_header(self, peer_rank: int, sock: socket.socket,
                      opcode: int) -> int:
        """Validate one peer->root frame header and return its third
        byte — the sender's health-vote bit on the gradient opcodes
        (0 = healthy), always 0 on the others."""
        r_op, r_seq, r_vote = struct.unpack(
            "<BQB", self._bufs.recv_header(sock, 10)
        )
        if r_op != opcode or r_seq != self._seq:
            raise ConnectionError(
                f"collective mismatch from rank {peer_rank}: "
                f"got op={r_op} seq={r_seq}, expected op={opcode} "
                f"seq={self._seq}"
            )
        return r_vote & 1

    # Star ALLREDUCE/REDUCE_SCATTER frames carry the step's commit vote
    # for free: the peer->root header's third byte (previously always 0)
    # is the sender's health bit, and after the last reply chunk the root
    # appends ONE aggregate byte (own | OR(peers)) to every peer — so
    # each voted op tells every rank whether ANY participant is unhealthy
    # without a single extra round trip (the Manager's zero-RPC
    # should_commit evidence). Votes ride ONLY the gradient opcodes.
    #
    # Frames otherwise (both directions): per chunk,
    # [nbytes u64] + the codec's raw encoded stream over that chunk view —
    # shapes are known on both sides (both ops require identical
    # layouts), so the self-describing _pack_arrays framing is skipped and
    # each chunk decodes straight into the caller's arrays via
    # codec.decode_into. Reduction is IN PLACE on the donated chunk views;
    # peers are drained in sorted rank order PER CHUNK, so the
    # accumulation order — hence the float result — is bitwise identical
    # to the sequential r=1..n-1 reduction of the whole payload, for any
    # chunk grid and any chunk->lane distribution. REDUCE_SCATTER shares
    # the upload + reduce phase verbatim; only the fan-out narrows: the
    # root replies each completed ENCODED chunk to its owner alone
    # (instead of every peer), so reply wire traffic drops to ~1/n while
    # the owner's decoded bits stay identical to the allreduce's.

    def _star_allreduce_root_chunks(self, p: _PendingOp) -> None:
        codec = self._codec
        reduce_fn = _REDUCE_FNS.get(
            ReduceOp.SUM if p.op == ReduceOp.AVG else p.op
        )
        if reduce_fn is None:
            raise ValueError(f"unsupported reduce op: {p.op}")
        peers = sorted(self._peer_socks.items())
        peer_socks = dict(peers)
        vote = self._ctx._vote_health_bit()
        for peer_rank, sock in peers:
            vote |= self._check_header(peer_rank, sock, p.opcode)
        copy = lambda v, inc: np.copyto(v, inc)  # noqa: E731
        lossy = type(codec) is not _NoCodec
        owners = p.owners if p.opcode == _OP_REDUCE_SCATTER else None
        for c, ch in enumerate(p.chunks):
            expected = codec.wire_nbytes(ch)
            for peer_rank, sock in peers:
                (nbytes,) = struct.unpack(
                    "<Q", self._bufs.recv_header(sock, 8)
                )
                if nbytes != expected:
                    raise ConnectionError(
                        f"allreduce chunk size mismatch from rank "
                        f"{peer_rank}: {nbytes} != {expected} (divergent "
                        "shapes or chunk_bytes?)"
                    )
                payload = self._bufs.recv_payload(sock, nbytes)
                # Streaming reduce: decoded straight into the accumulator,
                # consumed before the next peer's receive reuses the slot.
                codec.decode_into(payload, [ch], reduce_fn)
            if p.op == ReduceOp.AVG:
                np.divide(ch, self._world_size, out=ch)
            if owners is not None:
                # REDUCE_SCATTER: the completed chunk travels ONCE, to
                # its owner — or nowhere when the root owns it (the
                # lossy self-decode below keeps the root's copy
                # byte-identical to what a peer would have decoded).
                owner = owners[c]
                if owner == 0:
                    if lossy:
                        enc = codec.encode_iovecs([ch])
                        codec.decode_into(_iov_join(enc), [ch], copy)
                    continue
                enc = codec.encode_iovecs([ch])
                _sendmsg_all(peer_socks[owner], [
                    struct.pack("<Q", _iov_nbytes(enc)), *enc,
                ])
                continue
            # Fan out the ENCODED chunk as soon as it completes — peers
            # decode chunk k while chunk k+1 is still streaming in. For a
            # lossy codec the root then re-decodes its own encoded bytes
            # so it sees values byte-identical to every peer (identity
            # codec: the bytes ARE the accumulator's).
            enc = codec.encode_iovecs([ch])
            frame = [struct.pack("<Q", _iov_nbytes(enc)), *enc]
            for _, sock in peers:
                _sendmsg_all(sock, frame)
            if lossy:
                codec.decode_into(_iov_join(enc), [ch], copy)
        # Commit vote, aggregated at the root: one trailing byte per
        # peer after the last reply chunk (REDUCE_SCATTER owners with
        # zero reply chunks still get it — the vote is the op's only
        # root->peer traffic for them).
        vote_frame = [struct.pack("<B", vote)]
        for _, sock in peers:
            _sendmsg_all(sock, vote_frame)
        self._ctx._record_vote(vote)

    def _star_allreduce_peer_chunks(
        self, p: _PendingOp, sock: socket.socket
    ) -> None:
        codec = self._codec
        chunks = p.chunks
        copy = lambda v, inc: np.copyto(v, inc)  # noqa: E731
        # REDUCE_SCATTER replies carry only this rank's owned chunks —
        # same per-chunk frames, filtered to the owner (upload side is
        # identical to allreduce: the root needs every contribution).
        if p.opcode == _OP_REDUCE_SCATTER:
            rx_chunks = [
                ch for ch, o in zip(chunks, p.owners) if o == self._rank
            ]
        else:
            rx_chunks = chunks
        # Software pipeline: encode every chunk up front as iovecs (the
        # identity codec ships the chunk views themselves, zero copy;
        # lossy codecs allocate per chunk, bounded by chunk_bytes), then
        # stream the whole upload while pulling replies off the SAME
        # socket in one select-driven loop — chunk k+1 ships while the
        # root still reduces chunk k, replies drain as they land, and
        # neither direction can deadlock on full socket buffers.
        tx: List = [struct.pack(
            "<BQB", p.opcode, self._seq, self._ctx._vote_health_bit()
        )]
        for ch in chunks:
            enc = codec.encode_iovecs([ch])
            tx.append(struct.pack("<Q", _iov_nbytes(enc)))
            tx.extend(enc)

        def _rx_targets():
            for ch in rx_chunks:
                expected = codec.wire_nbytes(ch)
                len_mv = self._bufs.header_slot(8)
                yield len_mv
                (nbytes,) = struct.unpack("<Q", len_mv)
                if nbytes != expected:
                    raise ConnectionError(
                        f"allreduce reply chunk size mismatch: {nbytes} "
                        f"!= {expected} (divergent shapes or chunk_bytes?)"
                    )
                payload = self._bufs.payload_slot(nbytes)
                yield payload
                # decode runs between fills — before the slot's next
                # reuse, same contract as the blocking path
                codec.decode_into(payload, [ch], copy)
            # trailing aggregate commit vote from the root (see the
            # frame comment above _star_allreduce_root_chunks)
            vote_mv = self._bufs.header_slot(1)
            yield vote_mv
            self._ctx._record_vote(vote_mv[0])

        _duplex_exchange(sock, tx, sock, _rx_targets(), self._timeout)

    def _execute_root(self, p: _PendingOp):
        contributions: Dict[int, List[np.ndarray]] = {0: p.arrays}
        for peer_rank, sock in sorted(self._peer_socks.items()):
            self._check_header(peer_rank, sock, p.opcode)
            contributions[peer_rank] = _recv_arrays(sock, self._bufs)

        if p.opcode == _OP_ALLGATHER:
            gathered = [contributions[r] for r in range(self._world_size)]
            flat: List[np.ndarray] = [
                np.asarray(self._world_size, dtype=np.int64)
            ]
            for per_rank in gathered:
                flat.append(np.asarray(len(per_rank), dtype=np.int64))
                flat.extend(per_rank)
            for _, sock in sorted(self._peer_socks.items()):
                _send_arrays(sock, flat)
            return gathered
        if p.opcode == _OP_BROADCAST:
            src = contributions[p.root]
            for _, sock in sorted(self._peer_socks.items()):
                _send_arrays(sock, src)
            return [a.copy() for a in src]
        raise ValueError(f"unknown opcode {p.opcode}")

    def _execute_peer(self, p: _PendingOp):
        sock = self._root_sock
        assert sock is not None
        if p.opcode == _OP_BROADCAST and self._rank != p.root:
            # Root discards non-root contributions for broadcast; send an
            # empty frame instead of the full payload.
            _sendmsg_all(sock, [
                struct.pack("<BQB", p.opcode, self._seq, 0),
                *_array_frame_iovecs([]),
            ])
        else:
            _sendmsg_all(sock, [
                struct.pack("<BQB", p.opcode, self._seq, 0),
                *_array_frame_iovecs(p.arrays),
            ])
        result = _recv_arrays(sock, self._bufs)
        if p.opcode == _OP_ALLGATHER:
            # Decode the flattened [world, n_0, bufs_0..., n_1, ...] frame.
            idx = 0
            world = int(result[idx])
            idx += 1
            gathered: List[List[np.ndarray]] = []
            for _ in range(world):
                n = int(result[idx])
                idx += 1
                gathered.append(result[idx: idx + n])
                idx += n
            return gathered
        return result

    # ---------------------------------------------------------- ring variant

    # opcode, seq, step, payload bytes, vote: the vote byte is the
    # sender's accumulated unhealthy-OR on the gradient opcodes (each
    # rank forwards own | everything-received-so-far, so after the n-1
    # reduce-scatter hops every rank holds the OR over ALL ranks — the
    # ring analog of the star root's aggregate byte), always 0 on the
    # others.
    _RING_HDR = struct.Struct("<BQHQB")

    def _ring_sendrecv(
        self, opcode: int, step: int, bufs: Sequence, nbytes: int,
        vote: int = 0,
    ) -> "tuple[memoryview, int]":
        """Full-duplex one-step exchange: push to next while pulling from
        prev, interleaved in THIS thread by the select-driven
        _duplex_exchange (deadlock-free like the old sender-thread
        version — receives always drain — without a thread spawn and the
        GIL handoffs per hop, which striping would multiply by lanes x
        chunks). Every frame carries [opcode][seq][step][nbytes] and the
        receiver validates it — a desynced collective sequence fails fast
        instead of silently reducing misaligned bytes (parity with the
        star path's mismatch check).

        ``bufs`` is an iovec list (scatter-gather send, no payload
        materialization). The received payload lands in this lane's rx
        pool and is returned as a memoryview — the pool's 2-slot rotation
        keeps it valid through exactly one more exchange, which is what
        lets the all-gather phase forward it verbatim on the NEXT hop
        while that hop's frame streams into the other slot."""
        next_sock, prev_sock = self._next_sock, self._prev_sock
        assert next_sock is not None and prev_sock is not None
        header = self._RING_HDR.pack(opcode, self._seq, step, nbytes, vote)
        hdr_size = self._RING_HDR.size
        out: List[memoryview] = []
        rvotes: List[int] = []

        def _rx_targets():
            hdr_mv = self._bufs.header_slot(hdr_size)
            yield hdr_mv
            r_op, r_seq, r_step, r_len, r_vote = self._RING_HDR.unpack(
                hdr_mv
            )
            if (r_op, r_seq, r_step) != (opcode, self._seq, step):
                raise ConnectionError(
                    f"ring collective mismatch: got op={r_op} seq={r_seq} "
                    f"step={r_step}, expected op={opcode} seq={self._seq} "
                    f"step={step}"
                )
            rvotes.append(r_vote & 1)
            if r_len == 0:
                out.append(memoryview(b""))
                return
            payload = self._bufs.payload_slot(r_len)
            out.append(payload)
            yield payload

        _duplex_exchange(
            next_sock, [header, *bufs], prev_sock, _rx_targets(),
            self._timeout,
        )
        return out[0], rvotes[0]

    @staticmethod
    def _chunk_bounds(total: int, n: int, c: int) -> "tuple[int, int]":
        """Element bounds of chunk c when splitting `total` into n
        near-equal parts (first total % n chunks get one extra)."""
        base, extra = divmod(total, n)
        start = c * base + min(c, extra)
        return start, start + base + (1 if c < extra else 0)

    def _execute_ring(self, p: _PendingOp):
        n, r = self._world_size, self._rank
        if p.opcode == _OP_BROADCAST:
            # forward whole payload around the ring, root first; frames
            # carry the seq header so desyncs fail fast
            hdr = self._RING_HDR
            if r == p.root:
                iov = _array_frame_iovecs(p.arrays)
                _sendmsg_all(self._next_sock, [
                    hdr.pack(
                        _OP_BROADCAST, self._seq, 0, _iov_nbytes(iov), 0
                    ),
                    *iov,
                ])
                return [np.array(a, copy=True) for a in p.arrays]
            r_op, r_seq, _, r_len, _ = hdr.unpack(
                self._bufs.recv_header(self._prev_sock, hdr.size)
            )
            if (r_op, r_seq) != (_OP_BROADCAST, self._seq):
                raise ConnectionError(
                    f"ring broadcast mismatch: got op={r_op} seq={r_seq}, "
                    f"expected op={_OP_BROADCAST} seq={self._seq}"
                )
            payload = self._bufs.recv_payload(self._prev_sock, r_len)
            if (r + 1) % n != p.root:
                # store-and-forward: the send completes before the pool
                # slot can be reused, so the view is forwarded verbatim
                _sendmsg_all(self._next_sock, [
                    hdr.pack(_OP_BROADCAST, self._seq, 0, r_len, 0),
                    payload,
                ])
            return _unpack_arrays(payload)
        if p.opcode == _OP_ALLGATHER:
            # rotate contributions n-1 times; slot by source rank
            gathered: List[Optional[List[np.ndarray]]] = [None] * n
            gathered[r] = [np.array(a, copy=True) for a in p.arrays]
            carry: List = _array_frame_iovecs(gathered[r])
            carry_len = _iov_nbytes(carry)
            for step in range(n - 1):
                src = (r - step - 1) % n
                data, _ = self._ring_sendrecv(
                    _OP_ALLGATHER, step, carry, carry_len
                )
                gathered[src] = _unpack_arrays(data)
                carry, carry_len = [data], len(data)
            return gathered
        raise ValueError(f"unknown opcode {p.opcode}")

    @staticmethod
    def _part_views(flats: Sequence[np.ndarray], n: int,
                    c: int) -> List[np.ndarray]:
        """Rank-part ``c`` of every grid chunk (the _chunk_bounds split)."""
        views = []
        for f in flats:
            s, e = _Lane._chunk_bounds(f.size, n, c)
            views.append(f[s:e])
        return views

    @staticmethod
    def _expect_len(codec_, views: List[np.ndarray]) -> int:
        return sum(codec_.wire_nbytes(v) for v in views)

    @staticmethod
    def _decode_filtered(codec, data, views: List[np.ndarray],
                         owned: "Optional[List[bool]]", combine) -> None:
        """Decode ``data`` into ``views`` (the all-gather landing),
        skipping views whose ``owned`` flag is False — byte offsets still
        advance, so owned views decode the exact bytes an unfiltered
        decode would have handed them. ``owned=None`` decodes
        everything (the allreduce landing)."""
        if owned is None:
            codec.decode_into(data, views, combine)
            return
        data = memoryview(data)
        offset = 0
        for v, own in zip(views, owned):
            nb = codec.wire_nbytes(v)
            if own:
                codec.decode_into(data[offset: offset + nb], [v], combine)
            offset += nb

    def _ring_reduce_scatter_phase(self, p: _PendingOp,
                                   flats: Sequence[np.ndarray],
                                   reduce_fn, vote: int,
                                   seams: List[float]) -> int:
        """THE reduce-scatter phase, shared verbatim by ALLREDUCE and
        REDUCE_SCATTER (the hoist the ISSUE's satellite asks for): n-1
        hops, each moving ~1/n of the lane's payload; after step s, part
        (r - s) was sent onward and part (r - s - 1) absorbed — rank r
        ends owning part (r + 1) % n of every grid chunk, fully reduced.

        Hops carry PARTIAL SUMS: re-encoding them with a lossy codec at
        every hop would compound quantization error linearly with world
        size, so this phase always runs uncompressed; the configured
        codec applies only to the all-gather phase, where each completed
        part is encoded exactly once by its owner — the same
        single-quantization error bound as the star path.

        ``seams`` accumulates the sub-op's seconds inside
        :meth:`_ring_sendrecv` ([0]: socket send + receive AND the wait
        for the ring neighbour) and inside the reduction ([1]), and
        keeps the clock's reading at the end of the FIRST hop ([2]):
        until then the lane has waited for its neighbours to reach this
        op, after it the ring moves in step."""
        n, r = self._world_size, self._rank
        rs_codec = _NO_CODEC
        for step in range(n - 1):
            send_views = self._part_views(flats, n, (r - step) % n)
            recv_views = self._part_views(flats, n, (r - step - 1) % n)
            t0 = time.perf_counter()
            data, rvote = self._ring_sendrecv(
                p.opcode, step,
                rs_codec.encode_iovecs(send_views),
                self._expect_len(rs_codec, send_views),
                vote=vote,
            )
            t1 = time.perf_counter()
            seams[0] += t1 - t0
            if step == 0:
                seams[2] = t1
            vote |= rvote
            if len(data) != self._expect_len(rs_codec, recv_views):
                raise ConnectionError(
                    "ring allreduce chunk size mismatch (divergent shapes?)"
                )
            t0 = time.perf_counter()
            rs_codec.decode_into(data, recv_views, reduce_fn)
            seams[1] += time.perf_counter() - t0
        return vote

    def _ring_allgather_phase(self, p: _PendingOp,
                              flats: Sequence[np.ndarray],
                              owned: "Optional[List[bool]]",
                              vote: int, seams: List[float]) -> int:
        """All-gather of the completed parts. Each part is encoded ONCE
        by its owner and the received bytes are forwarded VERBATIM, so
        with a lossy codec every rank decodes identical bytes — replicas
        stay bitwise consistent. The part-owner also re-decodes its own
        encoded bytes for the same reason.

        ``owned`` (REDUCE_SCATTER): per-flat flags — frames stay
        byte-identical to the allreduce's rotation (every part of every
        flat must still route through the ring to reach its owner), but
        each rank DECODES only the flats whose update shard it owns; the
        other flats' contents stay unspecified (donation contract). The
        ring's sharded win is therefore decode/O(memory) work and the
        downstream 1/n optimizer update, not wire bytes — the ring
        rotation is already bandwidth-optimal."""
        n, r = self._world_size, self._rank
        codec = self._codec
        copy = lambda v, inc: np.copyto(v, inc)  # noqa: E731
        own_c = (r + 1) % n
        own_views = self._part_views(flats, n, own_c)
        if type(codec) is _NoCodec:
            carry: List = codec.encode_iovecs(own_views)
        else:
            own_bytes = _iov_join(codec.encode_iovecs(own_views))
            t0 = time.perf_counter()
            self._decode_filtered(codec, own_bytes, own_views, owned, copy)
            seams[1] += time.perf_counter() - t0
            carry = [own_bytes]
        carry_len = self._expect_len(codec, own_views)
        for step in range(n - 1):
            recv_views = self._part_views(flats, n, (r - step) % n)
            t0 = time.perf_counter()
            data, rvote = self._ring_sendrecv(
                p.opcode, n - 1 + step, carry, carry_len, vote=vote
            )
            seams[0] += time.perf_counter() - t0
            vote |= rvote
            if len(data) != self._expect_len(codec, recv_views):
                raise ConnectionError(
                    "ring allreduce chunk size mismatch (divergent shapes?)"
                )
            t0 = time.perf_counter()
            self._decode_filtered(codec, data, recv_views, owned, copy)
            seams[1] += time.perf_counter() - t0
            carry, carry_len = [data], len(data)
        return vote

    def _ring_allreduce_chunks(self, p: _PendingOp) -> None:
        """Bandwidth-optimal allreduce (or reduce_scatter) over this
        lane's chunk views: the shared reduce-scatter phase then the
        all-gather phase, 2(n-1) steps. Each grid chunk is an independent
        flat view (split into n rank-parts via _chunk_bounds), so the
        per-element accumulation order depends only on the grid —
        identical whether the chunks run on one lane or are striped
        across many, and identical between the two opcodes."""
        n = self._world_size
        reduce_fn = _REDUCE_FNS.get(
            ReduceOp.SUM if p.op == ReduceOp.AVG else p.op
        )
        if reduce_fn is None:
            raise ValueError(f"unsupported reduce op: {p.op}")
        # In place on the donated chunk views — no accumulator copy.
        # Rank-parts are disjoint regions of `flats`, so the full-duplex
        # send of part (r-s) never overlaps the concurrent receive+reduce
        # of part (r-s-1).
        flats = p.chunks
        owned: "Optional[List[bool]]" = None
        if p.opcode == _OP_REDUCE_SCATTER:
            owned = [o == self._rank for o in p.owners]
        vote = self._ctx._vote_health_bit()
        # seconds exchanging, seconds reducing, when the first hop ended
        seams = [0.0, 0.0, 0.0]
        t_start = time.perf_counter()
        cpu0 = time.thread_time()
        vote = self._ring_reduce_scatter_phase(
            p, flats, reduce_fn, vote, seams
        )
        vote = self._ring_allgather_phase(p, flats, owned, vote, seams)
        self._ctx._record_vote(vote)
        # The sub-op along its seams, beside its wall (comm_wire_reduce):
        # wall − exchange − reduce is Python between the seams, which a
        # lane only spends waiting for the GIL; wall − cpu is time this
        # thread did not run at all (neighbour, socket buffer, GIL).
        # Of the exchange, the first hop apart: sub-op start → the end of
        # its first _ring_sendrecv is the wait for the ring neighbours to
        # take up the same op (plus one hop's bytes).
        metrics = self._ctx.metrics
        metrics.observe("comm_subop_first_hop", seams[2] - t_start)
        metrics.observe("comm_subop_exchange", seams[0])
        metrics.observe("comm_subop_reduce", seams[1])
        metrics.observe("comm_subop_cpu", time.thread_time() - cpu0)
        # What this sub-op's hops carried: 2(n-1) hops of one rank-part
        # of each of the lane's views. The gauge is the median hop (raw
        # bytes; the n parts differ by an element a view) of the sub-op
        # that finished last.
        metrics.incr("comm_ring_hops", float(2 * (n - 1)))
        metrics.incr("comm_ring_views", float(2 * (n - 1) * len(flats)))
        if flats:
            metrics.gauge("comm_hop_bytes", float(sorted(
                sum(v.nbytes for v in self._part_views(flats, n, c))
                for c in range(n)
            )[n // 2]))
        if p.op == ReduceOp.AVG:
            for i, f in enumerate(flats):
                if owned is None or owned[i]:
                    np.divide(f, n, out=f)


# ------------------------------------------------------ hierarchical tier
# The DynamiQ-shaped multi-hop data plane (docs/architecture.md,
# "Hierarchical data plane"): reduce-within a domain at FULL precision
# over a private intra-tier star (the ICI/rack hop — cheap bytes), then
# exchange ACROSS domains through one elected egress rank per domain with
# the configured wire codec applied (the DCN hop — the expensive bytes,
# encoded exactly once), then broadcast the decoded global result back
# within each domain. Cross-DCN bytes therefore scale with DOMAIN
# fan-out, not world size: only egress ranks touch the inter tier, and
# they ship encoded domain sums. Composed from child TcpCommContexts so
# every wire property (framing, duplex exchange, chunk grid, codec bits,
# error latching) is the one existing implementation.


class _HierState:
    """One configure-epoch's hierarchical machinery: the resolved
    :class:`~torchft_tpu.comm.topology.DomainAssignment`, the intra-tier
    child context (absent for a 1-member domain), the inter-tier child
    context (egress ranks only), and the 1-thread executor running each
    op's three-phase composition in submission order (the same
    per-stream ordering contract as the lanes)."""

    __slots__ = ("assignment", "intra", "inter", "exec", "rank",
                 "group", "n_domains", "inter_hops")

    def __init__(self, assignment, rank: int) -> None:
        import concurrent.futures as _cf

        self.assignment = assignment
        self.rank = rank
        self.group = assignment.group_of(rank)
        self.n_domains = assignment.n_domains
        self.intra: "Optional[TcpCommContext]" = None
        self.inter: "Optional[TcpCommContext]" = None
        self.inter_hops = 0
        self.exec = _cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="torchft_tpu_hier"
        )

    def shutdown(self) -> None:
        self.exec.shutdown(wait=False)
        for ctx in (self.intra, self.inter):
            if ctx is not None:
                ctx.shutdown()

    def hops(self) -> int:
        """Sequential point-to-point exchange rounds on THIS rank's
        critical path for one hier op: reduce-to-egress (1, the
        narrowed reduce_scatter — no wasted fan-out of a value the
        global broadcast overwrites) + the inter tier (2 for star
        fan-in, 2(d-1) for the multi-hop ring) + broadcast-within (1).
        A function of domain size and domain COUNT — never of world
        size (the counter-shaped win `comm_hops` pins; flat ring is
        2(world-1))."""
        m = len(self.group)
        hops = 0
        if m > 1:
            hops += 2  # reduce-to-egress + broadcast-within
        if self.n_domains > 1:
            hops += self.inter_hops
        return hops


class TcpCommContext(CommContext):
    """Reconfigurable collective context over TCP (star or ring wire
    topology; see class ctor)."""

    backend_name = "host"

    def __init__(self, timeout: "float | timedelta" = 60.0,
                 algorithm: str = "auto", channels: int = 4,
                 compression: str = "none",
                 chunk_bytes: Optional[int] = None,
                 topology: str = "flat",
                 domain_resolver=None) -> None:
        """``algorithm``: "star" (rank 0 reduces and fans out — lowest
        latency for tiny payloads / few replicas), "ring" (bandwidth-optimal
        reduce-scatter + all-gather: each link moves ~2B/n per allreduce
        instead of the star root's 2B·(n-1)), or "auto" (ring for
        world_size >= 3).

        ``channels``: number of independent socket lanes; ops are assigned
        round-robin by submission index, so up to ``channels`` collectives
        progress on the wire concurrently (backward/comm overlap for DDP
        buckets). Must match across ranks.

        ``compression``: wire codec for ALLREDUCE payloads — "none",
        "bf16" (2 bytes/elem), "fp16", or "int8" (absmax-scaled,
        ~1 byte/elem). Lossy codecs still yield IDENTICAL decoded values
        on every rank (encoded bytes are fanned out / forwarded
        verbatim), so replica trajectories stay consistent; allgather and
        broadcast are never compressed. Must match across ranks.

        ``chunk_bytes``: the allreduce chunk grid — payloads are split
        into contiguous chunks of at most this many bytes (per flat view;
        0 keeps each view whole) and chunk c rides lane
        ``(base + c) % channels``. The grid is the lossy codecs' encode
        granularity (int8 scales are per chunk) and the star's pipeline
        depth, so it is part of what is computed. ``None`` (the default)
        keeps a 1 MiB grid for those two, and lets the identity-codec
        ring cut each op from its own size instead
        (:func:`_chunk_grid_owned`, ``ring=``): a hop then carries one
        contiguous rank-part of megabytes, on as few lanes as that takes.
        Must match across ranks.

        ``topology``: the DEFAULT data path for allreduce ops — "flat"
        (one tier spanning the whole wire; the historical behavior) or
        "hier" (the domain hierarchy: configure additionally builds the
        intra/inter tier child transports and allreduce rides
        reduce-within → compress → exchange-across → broadcast-within;
        per-op ``allreduce(..., topology=...)`` overrides, which is the
        bench's A/B lever). Must match across ranks.

        ``domain_resolver``: a ``comm.topology.DomainTopology`` naming
        each replica's domain; wire rank 0 resolves the cohort and
        publishes the assignment on the rendezvous store, so only one
        rank strictly needs a resolver. Default: built from the
        ``TORCHFT_TPU_DOMAINS`` env map on first hier configure."""
        super().__init__()
        if isinstance(timeout, timedelta):
            timeout = timeout.total_seconds()
        reason = self.unsupported_reason(
            algorithm, compression, topology=topology
        )
        if reason is not None:
            raise ValueError(reason)
        if channels < 1:
            raise ValueError("channels must be >= 1")
        if chunk_bytes is not None and chunk_bytes < 0:
            raise ValueError("chunk_bytes must be >= 0")
        self._codec = _CODECS[compression]()
        self._compression = compression
        # None: no grid was asked for (see the ctor doc); _grid_bytes is
        # the grid every codec-facing surface then uses
        self._chunk_bytes = None if chunk_bytes is None else int(chunk_bytes)
        self._algorithm = algorithm
        self._channels = int(channels)
        self._topology_default = topology
        self._domain_resolver = domain_resolver
        self._wire_members: "Optional[List[str]]" = None
        self._hier: "Optional[_HierState]" = None
        self._use_ring = False
        self._timeout = float(timeout)
        self._generation = 0
        self._lock = threading.Lock()
        self._lanes: List[_Lane] = []
        self._rr = 0
        # Gradient ops submitted so far, over every configure: the next
        # one's number (next_grad_op), never reused in a process
        self._grad_ops = 0
        self._listener: Optional[socket.socket] = None
        self._error: Optional[Exception] = None
        self._op_delay = 0.0  # test hook: simulated per-op wire latency
        # Data-plane commit votes (set_vote_health / take_commit_vote):
        # windowed aggregate of the health bytes that rode this
        # context's gradient collectives since the last take.
        self._vote_health = None
        self._vote_lock = threading.Lock()
        self._vote_ops = 0
        self._vote_unhealthy = False
        # Per-lane phase timers (comm_submit_wire / comm_wire_reduce +
        # comm_l{i}_wire_reduce, and a ring sub-op's comm_subop_*
        # seams). The Manager shares its own Metrics in via set_metrics,
        # so they land in its snapshot and carry its replica on a trace.
        self.metrics = Metrics()
        self.metrics.label("comm_backend", self.backend_name)
        self._events = None  # flight recorder (set_events)

    @classmethod
    def unsupported_reason(cls, algorithm: str, compression: str,
                           op: str = ReduceOp.SUM,
                           topology: str = "flat") -> Optional[str]:
        return host_unsupported_reason(algorithm, compression, op, topology)

    def set_wire_members(self, members: "Sequence[str]") -> None:
        """Replica ids of the upcoming cohort in transport rank order
        (the Manager calls this from each quorum before ``configure``) —
        what the domain resolver maps to tier structure. Without it, a
        hier configure synthesizes ``rank{r}`` names so harnesses and
        benches can address ranks in a ``TORCHFT_TPU_DOMAINS`` map."""
        self._wire_members = [str(m) for m in members]

    def set_domain_resolver(self, resolver) -> None:
        """Install a DomainTopology unless the ctor already provided
        one (explicit wins) — the Manager wires a resolver homed to the
        job's lighthouse ``/status.json`` here, so a managed hier job
        needs zero topology plumbing. Only wire rank 0 ever consults it
        (the resolved assignment is published on the rendezvous store
        for the rest of the cohort)."""
        if self._domain_resolver is None:
            self._domain_resolver = resolver

    def set_metrics(self, metrics: Metrics) -> None:
        """Record lane phase timings into ``metrics`` (call before
        ``configure``; lanes bind it at thread start). The sink is
        tagged with this context's ``comm_backend`` so host-vs-xla
        trajectories stay distinguishable in evidence JSONs."""
        self.metrics = metrics
        metrics.label("comm_backend", self.backend_name)

    def set_events(self, events) -> None:
        """Share a flight recorder (the Manager's): the transport emits
        one ``error_latched`` event at the START of each latch episode —
        the wire-level timestamp of a fault, which lands in the merged
        fleet recording ahead of the step_discard it causes."""
        self._events = events

    # ------------------------------------------------------------ lifecycle

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self.shutdown()
        with self._lock:
            self._generation += 1
            self._rank = rank
            self._world_size = world_size
            self._error = None
            self._rr = 0
        with self._vote_lock:
            # votes from a previous membership describe a wire that no
            # longer exists — never let them commit a step on this one
            self._vote_ops = 0
            self._vote_unhealthy = False

        n_lanes = 1 if world_size == 1 else self._channels
        lanes = [_Lane(self, i) for i in range(n_lanes)]

        if world_size == 1:
            # Solo quorum: everything is an identity op, no sockets needed.
            self._install_lanes(lanes)
            return

        store = create_store_client(store_addr, timeout=self._timeout)
        self._use_ring = self._algorithm == "ring" or (
            self._algorithm == "auto" and world_size >= 3
        )
        if self._use_ring:
            self._configure_ring(store, rank, world_size, lanes)
        else:
            self._configure_star(store, rank, world_size, lanes)
        self._install_lanes(lanes)
        if self._topology_default == "hier":
            try:
                self._configure_hier(store_addr, rank, world_size, store)
            except Exception:
                # a half-built tier must not leak child sockets; the
                # caller (Manager) latches and retries next quorum
                self.shutdown()
                raise

    def _install_lanes(self, lanes: List[_Lane]) -> None:
        for lane in lanes:
            lane.start()
        with self._lock:
            self._lanes = lanes

    def _configure_star(
        self, store, rank: int, world_size: int, lanes: List[_Lane]
    ) -> None:
        """Star rendezvous: rank 0 listens; every peer dials one connection
        per lane, tagged [rank u32][lane u32]."""
        n_lanes = len(lanes)
        if rank == 0:
            listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            listener.bind(("0.0.0.0", 0))
            listener.listen(world_size * n_lanes)
            listener.settimeout(self._timeout)
            self._listener = listener
            from torchft_tpu.utils.net import advertised_host

            store.set(
                "comm_addr",
                f"{advertised_host()}:{listener.getsockname()[1]}",
            )
            expected = (world_size - 1) * n_lanes
            accepted = 0
            try:
                while accepted < expected:
                    conn, _ = listener.accept()
                    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    conn.settimeout(self._timeout)
                    peer_rank, lane_id = struct.unpack(
                        "<II", _recv_exact(conn, 8)
                    )
                    if lane_id >= n_lanes:
                        conn.close()  # belongs to no lane; close directly
                        raise ConnectionError(
                            f"peer {peer_rank} sent lane {lane_id}, have "
                            f"{n_lanes} lanes (channels mismatch across "
                            "ranks?)"
                        )
                    lane_socks = lanes[lane_id]._peer_socks
                    if peer_rank in lane_socks:
                        # redial (crash-restart inside the configure
                        # window): newest connection wins, count unchanged
                        lane_socks[peer_rank].close()
                        lane_socks[peer_rank] = conn
                    else:
                        lane_socks[peer_rank] = conn
                        accepted += 1
            except (OSError, socket.timeout, ConnectionError) as e:
                for lane in lanes:
                    lane.close_sockets()
                listener.close()
                self._listener = None
                raise TimeoutError(
                    f"comm configure: rank 0 failed waiting for "
                    f"{expected} lane connections ({accepted} joined): {e}"
                ) from e
        else:
            addr = store.wait("comm_addr", timeout=self._timeout).decode()
            host, port_s = addr.rsplit(":", 1)
            try:
                for lane in lanes:
                    sock = socket.create_connection(
                        (host, int(port_s)), timeout=self._timeout
                    )
                    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    sock.settimeout(self._timeout)
                    sock.sendall(struct.pack("<II", rank, lane._lane_id))
                    lane._root_sock = sock
            except OSError as e:
                for lane in lanes:
                    lane.close_sockets()
                raise TimeoutError(
                    f"comm configure: rank {rank} could not reach root: {e}"
                ) from e

    def _configure_ring(
        self, store, rank: int, world_size: int, lanes: List[_Lane]
    ) -> None:
        """Ring rendezvous: every rank publishes a listener; rank r dials
        (r+1) % n once per lane and accepts one connection per lane from
        (r-1) % n, matched by the [rank u32][lane u32] tag."""
        from torchft_tpu.utils.net import advertised_host

        n_lanes = len(lanes)
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(("0.0.0.0", 0))
        listener.listen(2 * n_lanes)
        listener.settimeout(self._timeout)
        self._listener = listener
        store.set(
            f"ring_addr_{rank}",
            f"{advertised_host()}:{listener.getsockname()[1]}",
        )

        next_rank = (rank + 1) % world_size
        expected_prev = (rank - 1) % world_size
        addr = store.wait(
            f"ring_addr_{next_rank}", timeout=self._timeout
        ).decode()
        host, port_s = addr.rsplit(":", 1)
        try:
            for lane in lanes:
                next_sock = socket.create_connection(
                    (host, int(port_s)), timeout=self._timeout
                )
                next_sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                )
                next_sock.settimeout(self._timeout)
                next_sock.sendall(
                    struct.pack("<II", rank, lane._lane_id)
                )
                lane._next_sock = next_sock
            accepted = 0
            while accepted < n_lanes:
                prev_sock, _ = listener.accept()
                prev_sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                )
                prev_sock.settimeout(self._timeout)
                prev_rank, lane_id = struct.unpack(
                    "<II", _recv_exact(prev_sock, 8)
                )
                if prev_rank != expected_prev:
                    prev_sock.close()  # belongs to no lane; close directly
                    raise ConnectionError(
                        f"ring configure: rank {rank} accepted rank "
                        f"{prev_rank}, expected {expected_prev} (stale "
                        "round?)"
                    )
                if lane_id >= n_lanes or lanes[lane_id]._prev_sock is not None:
                    prev_sock.close()
                    raise ConnectionError(
                        f"ring configure: bad/duplicate lane {lane_id} "
                        "(channels mismatch across ranks?)"
                    )
                lanes[lane_id]._prev_sock = prev_sock
                accepted += 1
        except (OSError, socket.timeout, ConnectionError) as e:
            for lane in lanes:
                lane.close_sockets()
            listener.close()
            self._listener = None
            if isinstance(e, ConnectionError):
                raise
            raise TimeoutError(
                f"ring configure: rank {rank} could not link the ring: {e}"
            ) from e

    # --------------------------------------------------- hierarchical tier

    def _resolved_inter_algorithm(self, n_domains: int) -> str:
        """The cross-domain tier's wire. "auto" picks STAR regardless of
        domain count: the egress fan-in encodes each contribution
        exactly once (the single-quantization error bound) and every
        cross-DCN byte rides the codec — the property the inter-bytes
        envelope is graded on. Explicit "ring" selects the multi-hop
        rotation (bandwidth-optimal at many domains; its reduce-scatter
        hops carry partial sums UNCOMPRESSED by the PR 2 rule, so more
        of the cross-tier traffic is raw — the documented trade)."""
        return "star" if self._algorithm == "auto" else self._algorithm

    def _configure_hier(self, store_addr: str, rank: int,
                        world_size: int, store) -> None:
        """Build this epoch's domain tier on top of the flat lanes:
        resolve (or receive) the cohort's DomainAssignment, then
        configure the intra-tier child (this rank's domain, rank 0 = the
        elected egress) and — on egress ranks — the inter-tier child
        (one rank per domain, domain order = sorted names).

        Cohort synchronization: wire rank 0 resolves through the
        DomainTopology resolver and PUBLISHES the assignment on the
        rendezvous store; every other rank adopts the published copy, so
        a mid-quorum live-map refresh can never split the cohort into
        disagreeing tier structures."""
        from torchft_tpu.comm.topology import DomainAssignment

        members = self._wire_members
        if members is None or len(members) != world_size:
            members = [f"rank{r}" for r in range(world_size)]
        if rank == 0:
            resolver = self._domain_resolver
            if resolver is None:
                from torchft_tpu.comm.topology import DomainTopology

                resolver = self._domain_resolver = DomainTopology()
            assignment = resolver.assign(members)
            store.set("hier_map", assignment.to_json())
        else:
            assignment = DomainAssignment.from_json(
                store.wait("hier_map", timeout=self._timeout)
            )
        h = _HierState(assignment, rank)
        group = h.group
        d_idx = assignment.domain_index(rank)
        try:
            if len(group) > 1:
                # reduce-within rides a full-precision star: the egress
                # (intra rank 0) is the root whose accumulator the
                # domain sum lands in, and the same child later serves
                # the broadcast-within fan-out.
                h.intra = TcpCommContext(
                    timeout=self._timeout, algorithm="star",
                    channels=self._channels, compression="none",
                    chunk_bytes=self._chunk_bytes,
                )
                h.intra.configure(
                    f"{store_addr}/hier_intra_{d_idx}",
                    group.index(rank), len(group),
                )
            if h.n_domains > 1:
                inter_algo = self._resolved_inter_algorithm(h.n_domains)
                use_ring = inter_algo == "ring"
                h.inter_hops = (
                    2 * (h.n_domains - 1) if use_ring else 2
                )
                if assignment.is_egress(rank):
                    # the only rank of this domain whose bytes cross
                    # DCN — encoded through the configured codec
                    h.inter = TcpCommContext(
                        timeout=self._timeout, algorithm=inter_algo,
                        channels=self._channels,
                        compression=self._compression,
                        chunk_bytes=self._chunk_bytes,
                    )
                    h.inter.configure(
                        f"{store_addr}/hier_inter", d_idx, h.n_domains
                    )
        except Exception:
            h.shutdown()
            raise
        with self._lock:
            self._hier = h
        ev = self._events
        if ev:
            # one event per installed exchange plan (configure-rate, not
            # op-rate): the postmortem anchor for "which tier structure
            # was this cohort reducing over?"
            ev.emit(
                "hier_exchange", world=world_size,
                domains=h.n_domains, egress=list(assignment.egress),
                domain=assignment.domains[rank],
                is_egress=assignment.is_egress(rank),
                fingerprint=assignment.fingerprint,
            )

    def _submit_hier(self, arrays: Sequence[np.ndarray], op: str) -> Work:
        fut: Future = Future()
        fut.set_running_or_notify_cancel()
        err = self.errored()
        if err is not None:
            fut.set_exception(
                ConnectionError(f"comm context previously errored: {err}")
            )
            return Work(fut)
        prepared = [self._prepare(a) for a in arrays]
        with self._lock:
            h = self._hier
            world = self._world_size
            configured = bool(self._lanes)
            # the op's number, so that next_grad_op stays true; the tiers'
            # sub-ops ride child contexts and carry THEIR numbers
            seq = self._grad_ops
            self._grad_ops += 1
        if world == 1:
            # solo wire: identity, exactly like the flat path
            if not configured:
                fut.set_exception(
                    RuntimeError("comm context not configured")
                )
            else:
                fut.set_result(prepared)
            return Work(fut)
        if h is None:
            fut.set_exception(RuntimeError(
                "topology='hier' requires a context configured with the "
                "hierarchical tier — construct TcpCommContext("
                "topology='hier') (and configure it) or use "
                "topology='flat' for this op"
            ))
            return Work(fut)
        h.exec.submit(self._run_hier, h, prepared, op, fut)
        return Work(fut, op=seq)

    def _run_hier(self, h: "_HierState", arrays: List[np.ndarray],
                  op: str, fut: Future) -> None:
        """One op's three-phase composition, on the hier executor:
        reduce-within (full-precision star SUM/MAX/... — the donated
        arrays hold the domain sum in place), exchange-across (egress
        only; the codec encodes each domain sum exactly once and every
        domain decodes identical bytes), broadcast-within (raw f32 —
        all ranks globally identical afterwards), then the AVG divide.
        Any phase failure latches like a dead socket: an egress dying
        mid-exchange fails its domain's broadcast by timeout and the
        next quorum re-elects (min surviving rank)."""
        t0 = time.perf_counter()
        metrics = self.metrics
        phase_timeout = self._timeout + 15.0
        try:
            tier_op = ReduceOp.SUM if op == ReduceOp.AVG else op
            m = len(h.group)
            if m > 1:
                # reduce-TO-EGRESS: the narrowed reduce_scatter (every
                # array owned by intra rank 0) delivers the domain sum
                # to the egress alone, bitwise identical to what an
                # allreduce would produce there — without fanning out a
                # value the global broadcast below overwrites unread on
                # every other member (one hop, not two)
                h.intra.reduce_scatter(
                    arrays, tier_op, owners=[0] * len(arrays)
                ).future().result(timeout=phase_timeout)
            if h.n_domains > 1 and h.inter is not None:
                h.inter.allreduce(arrays, tier_op).future().result(
                    timeout=phase_timeout
                )
            if m > 1:
                res = h.intra.broadcast(arrays, root=0).future().result(
                    timeout=phase_timeout
                )
                for a, r in zip(arrays, res):
                    np.copyto(a, r)
            if op == ReduceOp.AVG:
                for a in arrays:
                    np.divide(a, self._world_size, out=a)
            # Tier byte accounting, same convention as comm_raw_bytes/
            # comm_encoded_bytes (ONE direction, THIS rank's
            # contribution): intra = the raw full-precision domain hop,
            # inter = the encoded cross-DCN hop — zero on non-egress
            # ranks, which is exactly the scaling the hier path exists
            # for (Δinter sums over ranks to f(domains), not f(world)).
            raw_b = float(sum(a.nbytes for a in arrays))
            metrics.incr("comm_intra_bytes", raw_b if m > 1 else 0.0)
            inter_b = 0.0
            if h.inter is not None and h.n_domains > 1:
                enc_b = float(sum(self.wire_nbytes(a) for a in arrays))
                if h.inter._use_ring:
                    # multi-hop honesty: the ring's reduce-scatter hops
                    # carry RAW partial sums (the PR 2 no-recompression
                    # rule) and only the all-gather rotation is
                    # encoded — charge (d-1)/d of each, per direction
                    d = h.n_domains
                    inter_b = (raw_b + enc_b) * (d - 1) / d
                else:
                    inter_b = enc_b  # star: the encoded contribution
            metrics.incr("comm_inter_bytes", inter_b)
            metrics.incr("comm_hops", float(h.hops()))
            metrics.observe("comm_op_wire", time.perf_counter() - t0)
            fut.set_result(arrays)
        except Exception as e:  # noqa: BLE001 — latch every tier error
            self._latch_error(e)
            logger.warning(
                "hier comm op failed (rank %d world %d domain %s): %s",
                self._rank, self._world_size,
                h.assignment.domains[h.rank], e,
            )
            try:
                fut.set_exception(e)
            except Exception:
                pass

    def shutdown(self) -> None:
        with self._lock:
            lanes = self._lanes
            self._lanes = []
            hier, self._hier = self._hier, None
            for lane in lanes:
                lane._queue.put(None)  # sentinel; guarded so no op can be
                # enqueued after it (see _submit)
        if hier is not None:
            hier.shutdown()
        for lane in lanes:
            lane.close_sockets()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
            self._listener = None
        for lane in lanes:
            if lane._thread is not None:
                lane._thread.join(timeout=5.0)
                lane._thread = None

    def errored(self) -> Optional[Exception]:
        with self._lock:
            return self._error

    def _latch_error(self, e: Exception,
                     lane: "Optional[_Lane]" = None) -> None:
        """Latch ``e`` and, on the latch edge, fail the WIRE and not only
        the op: every lane's sockets are shut down, so both neighbours see
        the end of the stream now, latch in turn and pass it on. Without
        this a rank that is not the dead member's neighbour learns
        nothing from its own (healthy) neighbours: it sat in a hop until
        the timeout unless a later op's header happened to arrive where
        it expected this one's — and with a bucket a hop-sized frame there
        is often no later op (PERF.md, PR 27: a 77 s stall, ``correct``
        false). Nothing is lost: a latched context fails every op until
        the next ``configure`` anyway. ``lane``: the failing lane; one left
        over from an earlier configure closes nothing of this one's."""
        with self._lock:
            first = self._error is None
            if first:
                self._error = e
            lanes = list(self._lanes) if first and (
                lane is None or lane in self._lanes
            ) else []
        for ln in lanes:
            ln.close_sockets()
        if first:
            # Emit OUTSIDE self._lock (the recorder has its own lock; no
            # nesting) and only on the latch edge — follow-on op
            # failures during the same episode add nothing.
            ev = self._events
            if ev:
                ev.emit(
                    "error_latched", source="host", error=repr(e)[:200]
                )

    # ------------------------------------------- data-plane commit votes
    # The 1-byte health votes riding the gradient opcodes (see the star
    # frame comment above _star_allreduce_root_chunks and _RING_HDR). A
    # voted op proves, with step-fresh evidence carried by the step's own
    # collective, that every wire participant completed the op and
    # reported healthy — the Manager's zero-RPC should_commit substrate.

    def set_vote_health(self, fn) -> None:
        """Install the local health provider (``fn() -> bool``, True =
        healthy) sampled when each gradient op ships its vote byte. The
        Manager wires its error-latch state here; default (None) votes
        healthy unless this context itself has latched an error."""
        self._vote_health = fn

    def _vote_health_bit(self) -> int:
        """This rank's vote byte: 1 = unhealthy. A latched transport
        error always votes unhealthy regardless of the provider; a
        provider that raises is itself evidence of trouble."""
        if self.errored() is not None:
            return 1
        fn = self._vote_health
        if fn is None:
            return 0
        try:
            return 0 if fn() else 1
        except Exception:  # noqa: BLE001 — a broken provider is unhealthy
            return 1

    def _record_vote(self, bit: int) -> None:
        with self._vote_lock:
            self._vote_ops += 1
            if bit & 1:
                self._vote_unhealthy = True

    def take_commit_vote(self) -> "Optional[bool]":
        """Aggregate of the votes recorded since the last call: True
        (>= 1 voted op, all participants healthy on every one), False
        (any dissent), None (no voted op completed in the window — e.g.
        the hier topology, whose three-phase composition rides child
        contexts: vote ABSENT, caller must run the full barrier)."""
        with self._vote_lock:
            ops, bad = self._vote_ops, self._vote_unhealthy
            self._vote_ops = 0
            self._vote_unhealthy = False
        if ops == 0:
            return None
        return not bad

    # ------------------------------------------------- wire introspection
    # (CommContext API; the DDP error-feedback arena keys off these.)

    def wire_codec_name(self) -> str:
        return self._codec.name

    def wire_is_lossy(self) -> bool:
        return type(self._codec) is not _NoCodec

    def wire_generation(self) -> int:
        """Monotonic transport incarnation, bumped by every configure().
        Step-persistent state derived from wire behavior (the DDP
        error-feedback residuals) must be reset when this changes — a new
        membership means the residual no longer describes error this
        cohort saw."""
        with self._lock:
            return self._generation

    def wire_compensable(self) -> bool:
        """True when THIS rank's allreduce contribution actually crosses
        the wire through a lossy codec — the precondition for an
        error-feedback residual to describe anything real. Role-aware,
        not just codec-aware: the star root's contribution is the
        in-place accumulator (never encoded) and ring contributions ride
        uncompressed partial sums, so only star PEERS are compensable.

        Hier default topology: the codec runs ONLY on the inter tier, so
        the compensable roles are the inter tier's — an EGRESS rank
        whose encoded domain sum crosses DCN through a role the inter
        child reports compensable (star inter: every egress but the
        fan-in root). The residual the EF arena banks is then the codec
        image of this rank's OWN contribution — an approximation of the
        domain-sum error that is exact for 1-member domains and feeds
        the quantization error back into the system exactly once per
        round either way (the toy-quadratic convergence oracle pins
        that it tracks fp32). Non-egress ranks ship only raw
        full-precision bytes: never compensable.
        Valid only after configure() for the current membership."""
        with self._lock:
            hier_mode = self._topology_default == "hier"
            h = self._hier
            flat = (
                type(self._codec) is not _NoCodec
                and self._world_size > 1
                and not self._use_ring
                and self._rank != 0
            )
        if hier_mode:
            # child lock taken OUTSIDE ours (no nesting)
            return (
                type(self._codec) is not _NoCodec
                and h is not None
                and h.inter is not None
                and h.inter.wire_compensable()
            )
        return flat

    def wire_roundtrip(self, src: np.ndarray, out: np.ndarray) -> None:
        """Write the wire's image of THIS rank's allreduce contribution
        into ``out`` — what an error-feedback residual must be computed
        against, so it depends on topology and role, not just the codec:

        * star peer: decode(encode(src)) per grid chunk — the
          contribution crosses the wire quantized.
        * star root: IDENTITY — the root's contribution is the in-place
          accumulator itself and never rides the codec (compensating
          "error" the wire never made would inject noise, measured as a
          10x EF regression on the toy quadratic).
        * ring: IDENTITY — reduce-scatter hops carry partial sums
          uncompressed; the all-gather quantizes completed SUMS, a common
          (all-ranks-identical) error no per-rank residual can describe.

        Valid only after configure() for the current membership (DDP
        calls it post-wait_quorum)."""
        if src.shape != out.shape or src.dtype != out.dtype:
            raise ValueError("wire_roundtrip: src/out layout mismatch")
        if not self.wire_compensable():
            np.copyto(out, src)
            return
        codec_roundtrip(self._codec, self._grid_bytes, src, out)

    @property
    def _grid_bytes(self) -> int:
        """The chunk grid where one is needed: the ctor's, or 1 MiB."""
        if self._chunk_bytes is None:
            return _DEFAULT_GRID_BYTES
        return self._chunk_bytes

    def wire_nbytes(self, a: np.ndarray) -> int:
        """Encoded one-direction payload size of ``a`` over the chunk
        grid (see module-level :func:`codec_wire_nbytes`)."""
        return codec_wire_nbytes(self._codec, self._grid_bytes, a)

    # ----------------------------------------------------------- collectives
    # _prepare (the donation-contract input normalization) is inherited
    # from CommContext — one definition for every data plane.

    def next_grad_op(self) -> int:
        """The number the next gradient op (allreduce, reduce_scatter)
        submitted to this context gets: its ``Work.op`` and the ``op=``
        of its sub-ops' ``tft.comm_wire_reduce`` and of its
        ``tft.comm_op_resolve``. For a caller that must name the op
        BEFORE it submits it (a span's arguments are fixed when it
        opens: ``tft.ddp_submit``). Exact because a context's ops are
        submitted in one order, the same on every rank (the lane map and
        the frame sequence hang on it): no other thread submits between
        this read and that submit. Counted over every configure, so a
        number is not reused in a process; ranks that configured
        together from new count alike."""
        with self._lock:
            return self._grad_ops

    def _submit(self, opcode: int, arrays: Sequence[np.ndarray], op: str,
                root: int,
                owners: "Optional[Sequence[int]]" = None) -> Work:
        fut: Future = Future()
        fut.set_running_or_notify_cancel()
        err = self.errored()
        if err is not None:
            fut.set_exception(
                ConnectionError(f"comm context previously errored: {err}")
            )
            return Work(fut)
        prepared = [self._prepare(a) for a in arrays]
        # Lock pairs with shutdown(): either we enqueue before the sentinel
        # (op will be drained) or we observe no lanes and fail fast.
        with self._lock:
            if not self._lanes:
                fut.set_exception(
                    RuntimeError("comm context not configured")
                )
                return Work(fut)
            n_lanes = len(self._lanes)
            base = self._rr % n_lanes
            self._rr += 1
            seq = None
            if opcode in _GRAD_OPCODES:
                seq = self._grad_ops
                self._grad_ops += 1
            if opcode in _GRAD_OPCODES and self._world_size > 1:
                if opcode == _OP_REDUCE_SCATTER:
                    if owners is None:
                        owners = [
                            i % self._world_size
                            for i in range(len(prepared))
                        ]
                    owners = [int(o) for o in owners]
                    if len(owners) != len(prepared) or any(
                        not 0 <= o < self._world_size for o in owners
                    ):
                        fut.set_exception(ValueError(
                            f"reduce_scatter owners {owners} must name a "
                            f"rank in [0, {self._world_size}) per array "
                            f"({len(prepared)} arrays submitted)"
                        ))
                        return Work(fut)
                else:
                    owners = None
                # Deterministic cut + chunk->lane map (identical on
                # every rank — see module docstring), one sub-op per
                # involved lane sharing the op's future/state. A
                # chunk_bytes grid deals its chunks round-robin; the
                # identity-codec ring with no grid asked for sizes the
                # cut from the op and names each chunk's lane share.
                derive = (
                    self._chunk_bytes is None and self._use_ring
                    and type(self._codec) is _NoCodec
                )
                chunks, chunk_owners, shares = _chunk_grid_owned(
                    [a.reshape(-1) for a in prepared], owners,
                    self._grid_bytes,
                    ring=(self._world_size, n_lanes) if derive else None,
                )
                per_lane: Dict[int, List[np.ndarray]] = {}
                per_lane_owner: Dict[int, List[int]] = {}
                for c, ch in enumerate(chunks):
                    lane_id = (
                        base + (c if shares is None else shares[c])
                    ) % n_lanes
                    per_lane.setdefault(lane_id, []).append(ch)
                    if chunk_owners is not None:
                        per_lane_owner.setdefault(lane_id, []).append(
                            chunk_owners[c]
                        )
                if not per_lane:  # all views empty: nothing to reduce
                    per_lane = {base: []}
                state = _OpState(prepared, fut, len(per_lane),
                                 self.metrics)
                self.metrics.incr("comm_chunks", float(len(chunks)))
                # Bytes-on-wire accounting (one direction, THIS rank's
                # contribution): cumulative raw vs encoded counters so a
                # compression ratio is a Δcounter division, not a guess.
                # Same keys as the xla plane — codec honesty is a
                # cross-backend invariant.
                self.metrics.incr("comm_raw_bytes", float(sum(
                    ch.nbytes for ch in chunks
                )))
                self.metrics.incr("comm_encoded_bytes", float(sum(
                    self._codec.wire_nbytes(ch) for ch in chunks
                )))
                if len(per_lane) > 1:
                    self.metrics.incr("comm_striped_ops")
                for lane_id in sorted(per_lane):
                    self._lanes[lane_id]._queue.put(_PendingOp(
                        opcode, prepared, op, root, fut,
                        chunks=per_lane[lane_id], state=state,
                        owners=per_lane_owner.get(lane_id), seq=seq,
                        nbytes=sum(
                            ch.nbytes for ch in per_lane[lane_id]
                        ),
                    ))
                return Work(fut, op=seq)
            pending = _PendingOp(
                opcode, prepared, op, root, fut, seq=seq,
                nbytes=sum(a.nbytes for a in prepared),
            )
            self._lanes[base]._queue.put(pending)
        return Work(fut, op=seq)

    def allreduce(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        topology: Optional[str] = None,
    ) -> Work:
        topo = topology if topology is not None else self._topology_default
        if (
            topo != self._topology_default
            and type(self._codec) is not _NoCodec
        ):
            # The EF arena keys its residual roles off the CONTEXT's
            # wire_compensable (which reflects the default topology's
            # encoding roles); a per-op override under a lossy codec
            # would bank residuals against a wire the op never rode —
            # a systematic gradient bias. Refuse prescriptively: the
            # per-op lever stays for codec='none' A/Bs; lossy arms get
            # their own context.
            fut: Future = Future()
            fut.set_running_or_notify_cancel()
            fut.set_exception(ValueError(
                f"per-op topology={topo!r} differs from this context's "
                f"default {self._topology_default!r} under the lossy "
                f"{self._codec.name!r} codec — the error-feedback roles "
                "(wire_compensable) follow the default topology, so the "
                "override would desynchronize EF from the actual wire. "
                "Construct a context with topology="
                f"{topo!r} for this arm, or use compression='none' for "
                "a per-op A/B"
            ))
            return Work(fut)
        if topo == "hier":
            return self._submit_hier(arrays, op)
        if topo != "flat":
            fut = Future()
            fut.set_running_or_notify_cancel()
            fut.set_exception(ValueError(
                host_unsupported_reason(
                    self._algorithm, self._codec.name, op, topo
                ) or f"unknown topology {topo!r}"
            ))
            return Work(fut)
        return self._submit(_OP_ALLREDUCE, arrays, op, 0)

    def reduce_scatter(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        owners: "Optional[Sequence[int]]" = None,
    ) -> Work:
        """Reduce ``arrays`` across ranks and deliver each array's
        reduced values ONLY to its owner rank (``owners[i]``, default
        ``i % world_size`` — the torch ``reduce_scatter`` layout when one
        array per rank is submitted). Every rank must submit identical
        layouts AND identical owners.

        The future resolves to the same donated array list; arrays owned
        by THIS rank hold the reduced result — bitwise identical to what
        :meth:`allreduce` over the same arrays/grid would have produced
        there (same accumulation order, same per-chunk codec scales) —
        while arrays owned by other ranks have UNSPECIFIED contents
        (donation contract). This is the collective under the sharded
        1/N weight update: each replica receives exactly the gradient
        shard its optimizer-state shard consumes."""
        return self._submit(
            _OP_REDUCE_SCATTER, arrays, op, 0, owners=owners
        )

    def allgather(self, arrays: Sequence[np.ndarray]) -> Work:
        return self._submit(_OP_ALLGATHER, arrays, ReduceOp.SUM, 0)

    def broadcast(self, arrays: Sequence[np.ndarray], root: int = 0) -> Work:
        return self._submit(_OP_BROADCAST, arrays, ReduceOp.SUM, root)
