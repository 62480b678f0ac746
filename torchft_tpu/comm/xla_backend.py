"""On-device data plane: collectives lowered to ``jax.lax`` ops over a
reconfigurable mesh (ROADMAP item 1, the SNIPPETS.md ProcessGroupXla
target).

The host transport (transport.py) moves gradient bytes over TCP sockets
— the right plane for cross-host DCN traffic and the bitwise oracle for
everything else. On real TPU hardware the fast path is ICI: collectives
belong INSIDE a jitted computation, where XLA schedules them against
compute. ``XlaCommContext`` implements the same ``CommContext`` surface
(allreduce with the donation contract, broadcast, allgather, the
``wire_*`` introspection the error-feedback arena keys off) but its
ALLREDUCE lowers to ``jax.lax.all_gather``/``psum`` inside ``shard_map``
over a named mesh axis, with the PR 2 chunk grid and wire codecs
(bf16/int8 + per-chunk scales) fused into the SAME jitted computation —
encode → exchange → decode-accumulate as one executable. On the
hardware-native ``psum`` path a lossy codec runs the EQuARX-style
QUANTIZED exchange (:func:`_build_quantized_psum` /
:func:`_build_quantized_psum_scatter`): block-quantize on the chunk
grid → ``all_to_all`` of int8/bf16 payloads (+ compact f32 scales) →
dequantize-accumulate → re-encode → ``all_gather``, so encoded bytes —
not f32 — are what crosses every link (ROADMAP item 2, finished).

Membership churn without retrace storms
---------------------------------------
The perf architecture is the :class:`MeshManager`. Each
``Manager.quorum()`` that changes the wire membership triggers
``configure(store_addr, rank, world_size)`` exactly as for the host
transport; here that rebuilds the ``jax.sharding.Mesh`` from the device
pool — ALWAYS ``devices[:world_size]``, never the identity of surviving
ranks, so every quorum at the same world size maps to the SAME mesh
object — and swaps in a compiled executable from a cache keyed by
``(world_size, algorithm, codec, chunk grid, op, array layouts)``. A
replica dying therefore costs one cache lookup at the step boundary (or
one compile on FIRST sight of that world size), never a per-step
retrace: ``MeshManager.compile_count`` is pinned by
tests/test_xla_backend.py. Contrast with baking the replica dimension
into the train step itself, where every membership change recompiles
the model.

Bitwise parity with the socket transport
----------------------------------------
The host transport is the oracle: for a fixed chunk grid, the on-device
allreduce reproduces the socket transport's results BIT FOR BIT, for
every codec, in both topologies' accumulation orders —

* ``star``: acc = v_0 + Σ_{r>0} dec(enc(v_r)) in rank order per chunk,
  the root's own contribution raw, the result re-encoded once (lossy
  codecs), exactly like ``_star_allreduce_root_chunks``.
* ``ring``: per grid chunk, per rank-part c (``_chunk_bounds`` split),
  partial sums accumulate uncompressed in ring order
  v_c, then v_{c+1} + acc, ... (the reduce-scatter), and the completed
  part is encoded ONCE (per-part scales) like the all-gather phase.

Floating-point accumulation order is reproduced exactly; the remaining
hazard is XLA itself changing rounding behavior — on CPU/TPU the
backend contracts ``a*b + c`` into a fused multiply-add (skipping the
product's rounding; ``lax.optimization_barrier`` does NOT stop it) and
keeps f64→f32 converts in excess precision. Every host-rounding point
therefore passes through :func:`_hardround`: a bitcast → XOR with a
RUNTIME zero → bitcast identity that no compiler pass can see through,
costing one integer op per element. int8 scales are computed via an
f64 divide (under ``enable_x64`` at trace time only) to reproduce
numpy's ``np.float32(absmax / 127.0)`` double-precision rounding.

Single-process rendezvous
-------------------------
On real multi-host TPU, jax is multi-controller: every process calls
the same jitted function and the rendezvous IS the collective. The CPU
sandbox (``--xla_force_host_platform_device_count=N``) is single
process, so ``_XlaGroup`` stands in for the SPMD launch: contexts
configured against the same store prefix join one group; each rank's
submit deposits its donated arrays, and when the full cohort has
submitted a sequence number the group's executor runs ONE jitted
computation over the mesh and copies each rank's result back into its
donated buffers. Op pairing is by per-rank submission order — the same
contract as the host transport's lanes — and a missing rank fails the
op with ``ConnectionError`` after the timeout, which the Manager
latches exactly like a dead socket. Broadcast/allgather carry state
(checkpoint-adjacent, never the gradient hot path) and ride a plain
host-side exchange inside the group.

64-bit payloads (f64/i64/u64) reduce on a host-side simulation of the
same topology/codec math (bitwise-identical by construction — it runs
the transport's own codec code); everything the DDP/outer planes
actually ship (f32 buckets, the f32 outer staging arena) runs on
device.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from datetime import timedelta
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.comm.context import CommContext, ReduceOp, Work
from torchft_tpu.comm.transport import (
    _CODECS,
    _REDUCE_FNS,
    _Lane,
    _NoCodec,
    _chunk_grid,
    _iov_join,
    codec_roundtrip,
    codec_wire_nbytes,
)
from torchft_tpu.utils.metrics import Metrics

logger = logging.getLogger(__name__)

__all__ = [
    "XlaCommContext",
    "MeshManager",
    "default_mesh_manager",
    "device_codec_roundtrip",
]

_AXIS = "replica"
# Second mesh axis: intra-replica model sharding (HSDP — FSDP inside a
# replica group x DDP across replicas). The WIRE collectives stay
# 1-D (axis-scoped to "replica"); the fused step builders compose both
# axes inside one executable (torchft_tpu/fused.py).
_MODEL_AXIS = "model"

# Dtypes the on-device path carries. f32 is the codec plane; the rest
# pass through uncompressed (matching the host codecs' _is_compressible
# gate) but still accumulate in the topology's exact order. 64-bit
# dtypes fall back to the in-group host simulation (module docstring).
_DEVICE_DTYPES = {
    "<f4", "<f2", "bfloat16",
    "|i1", "<i2", "<i4", "|u1", "<u2", "<u4",
}


def _dtype_key(dt: np.dtype) -> str:
    s = np.dtype(dt).str
    return np.dtype(dt).name if s.lstrip("<>|=").startswith("V") else s


def _is_device_dtype(dt: np.dtype) -> bool:
    return _dtype_key(dt) in _DEVICE_DTYPES


# --------------------------------------------------------------- mesh plane


class MeshManager:
    """Mesh + compiled-executable cache across quorum epochs.

    ``mesh_for(world_size)`` always builds over ``devices[:world_size]``
    — rank r of the wire maps to pool device r regardless of WHICH
    replicas survived, so the mesh (and every executable compiled
    against it) is reusable for any future quorum at that world size.
    ``executable`` returns the cached compiled computation or builds it
    once (AOT ``lower().compile()`` so the compile is counted and paid
    at a known point, not mid-collective on some later shape-dependent
    call). Thread-safe; shared process-wide by default so several
    contexts (one per Manager in a test harness) hit one cache."""

    def __init__(self, devices: Optional[Sequence[Any]] = None,
                 axis_name: str = _AXIS,
                 model_axis_name: str = _MODEL_AXIS) -> None:
        self._devices = tuple(devices) if devices is not None else None
        self.axis_name = axis_name
        self.model_axis_name = model_axis_name
        # 1-D meshes keyed by int world_size (the wire plane — every
        # existing executable key embeds that int, so the key space is
        # stable); 2-D meshes keyed by (replicas, model_shards).
        self._meshes: Dict[Any, Any] = {}
        self._execs: Dict[Tuple, Any] = {}
        self._building: Dict[Tuple, Future] = {}
        self._lock = threading.Lock()
        # compile_count: executables actually built (lower+compile).
        # trace_count: times a builder's python body ran (re-traces).
        # hit_count: cache hits. Pinned by the reconfiguration tests.
        self.compile_count = 0
        self.trace_count = 0
        self.hit_count = 0
        # Optional flight recorder: every executable build emits one
        # mesh_compile event (a compile mid-training is exactly the kind
        # of rare stall a postmortem timeline must show). Set by
        # XlaCommContext.set_events; with several contexts sharing this
        # pool the most recently wired Manager's ring receives them —
        # a compile is process-wide work, any one ring is the truth.
        self.events = None

    def devices(self) -> Tuple:
        if self._devices is None:
            import jax

            self._devices = tuple(jax.devices())
        return self._devices

    def _note_trace(self) -> None:
        # under the lock like compile_count/hit_count: trace_count is
        # the retrace-storm regression signal — a lost increment from
        # two concurrent first-sight builds would hide a real retrace
        with self._lock:
            self.trace_count += 1

    def device_count(self) -> int:
        return len(self.devices())

    def mesh_for(self, world_size: int, model_shards: int = 1):
        """Mesh over ``devices[:world_size * model_shards]``.

        ``model_shards == 1`` (the wire plane) keeps the historical 1-D
        ``("replica",)`` mesh under its int cache key — every existing
        executable key and test pin is untouched. ``model_shards > 1``
        builds the 2-D ``("replica", "model")`` mesh: replica group r is
        the device ROW ``devices[r*M : (r+1)*M]``, so shrinking the
        replica axis at a fixed model axis drops whole rows and every
        surviving group keeps its device identity — the property that
        makes churn at a seen (R, M) shape a cache lookup."""
        from jax.sharding import Mesh

        m = max(1, int(model_shards))
        with self._lock:
            key: Any = world_size if m == 1 else (world_size, m)
            mesh = self._meshes.get(key)
            if mesh is None:
                devs = self.devices()
                need = world_size * m
                if need > len(devs):
                    raise ValueError(
                        f"mesh {world_size}x{m} needs {need} devices, "
                        f"which exceeds the device pool ({len(devs)}); "
                        "raise --xla_force_host_platform_device_count or "
                        "pass a larger `devices` pool to MeshManager"
                    )
                if m == 1:
                    mesh = Mesh(devs[:world_size], (self.axis_name,))
                else:
                    mesh = Mesh(
                        np.array(devs[:need]).reshape(world_size, m),
                        (self.axis_name, self.model_axis_name),
                    )
                self._meshes[key] = mesh
            return mesh

    def executable(self, key: Tuple, build):
        """Cached compiled executable for ``key``; ``build()`` runs at
        most once per key for the life of the pool (across quorum
        epochs — this is what makes a world-size change a cache lookup
        instead of a retrace)."""
        with self._lock:
            ex = self._execs.get(key)
            if ex is not None:
                self.hit_count += 1
                return ex
            pending = self._building.get(key)
            if pending is None:
                pending = self._building[key] = Future()
                owner = True
            else:
                owner = False
        if not owner:
            # Another thread is already compiling this key (two Managers
            # sharing the default pool can race on first sight): wait for
            # its result instead of duplicating a multi-second compile —
            # this is what keeps compile_count exactly 1 per key.
            ex = pending.result()
            with self._lock:
                self.hit_count += 1
            return ex
        try:
            ex = build()  # compile outside the lock: compiles are slow
            # and jax's own dispatch is thread-safe.
        except Exception as e:
            with self._lock:
                del self._building[key]
            pending.set_exception(e)
            raise
        with self._lock:
            self._execs[key] = ex
            self.compile_count += 1
            compile_count = self.compile_count
            del self._building[key]
        pending.set_result(ex)
        ev = self.events
        if ev:
            ev.emit(
                "mesh_compile", key=repr(key)[:200],
                compile_count=compile_count,
            )
        return ex


_DEFAULT_MESH_MANAGER: Optional[MeshManager] = None
_DEFAULT_MM_LOCK = threading.Lock()


def default_mesh_manager() -> MeshManager:
    """Process-wide MeshManager over ``jax.devices()``."""
    global _DEFAULT_MESH_MANAGER
    with _DEFAULT_MM_LOCK:
        if _DEFAULT_MESH_MANAGER is None:
            _DEFAULT_MESH_MANAGER = MeshManager()
        return _DEFAULT_MESH_MANAGER


# ------------------------------------------------------- traced collective


def _hardround(x, z):
    """Opaque identity forcing ``x`` to materialize at its own
    precision: bitcast to the width-matched int, XOR with a RUNTIME
    zero, bitcast back. This is the parity linchpin — XLA's backends
    contract ``a*b + c`` into an FMA (skipping the product rounding the
    host performed) and carry f64→f32 converts in excess precision, and
    ``lax.optimization_barrier`` does not reliably stop either. No pass
    can fold an XOR with a value only known at run time."""
    import jax.numpy as jnp
    from jax import lax

    itemsize = np.dtype(x.dtype).itemsize
    int_dt = {1: jnp.int8, 2: jnp.int16, 4: jnp.int32, 8: jnp.int64}[itemsize]
    zz = z.astype(int_dt) if itemsize != 4 else z
    return lax.bitcast_convert_type(
        lax.bitcast_convert_type(x, int_dt) ^ zz, x.dtype
    )


def _dev_quant_int8(x, z):
    """``(q int8, scale f32)`` for ONE chunk view — THE device-side
    int8 block quantizer, bit-matching the host ``_Int8Codec._quantize``
    (transport.py): numpy computes the scale as f32(f64(absmax)/127.0);
    the f64 divide (real, thanks to enable_x64 at trace time) plus the
    hardrounds reproduce it exactly — see module docstring. Shared by
    the enc-dec roundtrip (parity paths, EF image) and the quantized
    psum exchange (phase-1 encode), so the residual the EF arena banks
    is computed against the exact bytes the wire carries."""
    import jax.numpy as jnp

    absmax = jnp.max(jnp.abs(x))
    scale64 = absmax.astype(jnp.float64) / np.float64(127.0)
    scale = jnp.where(
        absmax > 0, scale64, np.float64(1.0)
    ).astype(jnp.float32)
    scale = jnp.where(jnp.isfinite(absmax), scale, jnp.float32(np.nan))
    scale = _hardround(scale, z)
    q = jnp.clip(
        jnp.rint(_hardround(x / scale, z)), -127, 127
    ).astype(jnp.int8)
    q = jnp.where(jnp.isfinite(absmax), q, jnp.int8(0))
    return q, scale


def _dev_dequant_int8(q, scale, z):
    """``q * scale`` back to f32, hardrounded like the host decode."""
    import jax.numpy as jnp

    return _hardround(q.astype(jnp.float32) * scale, z)


def _dev_enc_dec(codec_name: str, x, z):
    """decode(encode(x)) for one chunk view, bit-matching the host
    codec (transport.py) for f32 inputs; identity for dtypes the host
    wire does not compress."""
    import jax.numpy as jnp

    if codec_name == "none" or x.dtype != jnp.float32:
        return x
    if codec_name == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if codec_name == "fp16":
        return x.astype(jnp.float16).astype(jnp.float32)
    if codec_name == "int8":
        return _dev_dequant_int8(*_dev_quant_int8(x, z), z)
    raise ValueError(f"unknown codec {codec_name!r}")


def device_codec_roundtrip(codec_name: str, chunk_bytes: int,
                           src: np.ndarray) -> np.ndarray:
    """decode(encode(src)) computed ON DEVICE over the PR 2 chunk grid —
    the device image of one wire contribution. Exists for the parity
    tests: the host ``codec_roundtrip`` (transport.py) is what the EF
    arena actually runs (wire_roundtrip), and this function is how the
    suite PROVES the two are bit-identical at matching chunk grids, so
    "the host codec path stays the convergence oracle" is a pinned
    fact, not a hope."""
    import jax
    import jax.numpy as jnp

    src = np.ascontiguousarray(src, dtype=np.float32).reshape(-1)
    step = (
        max(1, chunk_bytes // 4) if chunk_bytes > 0 else max(1, src.size)
    )

    def fn(z, x):
        parts = [
            _dev_enc_dec(codec_name, x[s: s + step], z)
            for s in range(0, x.shape[0], step)
        ]
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    with _x64_trace():
        out = jax.jit(fn)(np.int32(0), src)
    return np.asarray(out)


def _is_float(dt) -> bool:
    return np.dtype(dt).kind == "f" or "float" in np.dtype(dt).name


def _build_allreduce(mesh_mgr: MeshManager, world_size: int,
                     algorithm: str, codec_name: str, chunk_bytes: int,
                     op: str, layouts: Sequence[Tuple[int, np.dtype]]):
    """Compile ONE allreduce executable: inputs are a runtime int32
    zero plus one (world, size) stacked flat array per payload array;
    outputs mirror the stacked shape, every row carrying the identical
    reduced value. ``layouts`` is [(flat_size, dtype), ...]."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = world_size
    mesh = mesh_mgr.mesh_for(n)
    axis = mesh_mgr.axis_name
    lossy = codec_name != "none"

    def bounds_of(size: int, itemsize: int) -> List[Tuple[int, int]]:
        return _grid_bounds(size, chunk_bytes, itemsize)

    def comb(acc, new, z):
        # host: reduce_fn(left, incoming) writes into LEFT — star keeps
        # the accumulator left, ring keeps the local (newer) value left.
        if op in (ReduceOp.SUM, ReduceOp.AVG):
            out = acc + new
            return _hardround(out, z) if _is_float(out.dtype) else out
        if op == ReduceOp.MAX:
            return jnp.maximum(acc, new)
        if op == ReduceOp.MIN:
            return jnp.minimum(acc, new)
        raise ValueError(f"unsupported reduce op: {op}")

    def reduce_chunk_star(g, s, e, z):
        acc = g[0, s:e]
        for r in range(1, n):
            acc = comb(acc, _dev_enc_dec(codec_name, g[r, s:e], z), z)
        if op == ReduceOp.AVG:
            acc = acc / jnp.float32(n)
            acc = _hardround(acc, z) if _is_float(acc.dtype) else acc
        if lossy:
            acc = _dev_enc_dec(codec_name, acc, z)
        return acc

    def reduce_chunk_ring(g, s, e, z):
        # per rank-part accumulation in ring order; completed parts are
        # encoded once each (per-part scales), AVG divides post-decode —
        # _ring_allreduce_chunks semantics exactly.
        sub = []
        for c in range(n):
            ps, pe = _Lane._chunk_bounds(e - s, n, c)
            if ps == pe:
                continue
            acc = g[c % n, s + ps: s + pe]
            for i in range(1, n):
                acc = comb(g[(c + i) % n, s + ps: s + pe], acc, z)
            if lossy:
                acc = _dev_enc_dec(codec_name, acc, z)
            if op == ReduceOp.AVG:
                acc = acc / jnp.float32(n)
                acc = _hardround(acc, z) if _is_float(acc.dtype) else acc
            sub.append(acc)
        return jnp.concatenate(sub) if len(sub) > 1 else sub[0]

    def fn(z, *stacked):
        def local(z, *rows):
            outs = []
            for row, (size, dt) in zip(rows, layouts):
                if algorithm == "psum":
                    if op in (ReduceOp.SUM, ReduceOp.AVG):
                        red = jax.lax.psum(row[0], axis)
                        if op == ReduceOp.AVG:
                            red = red / jnp.float32(n)
                    elif op == ReduceOp.MAX:
                        red = jax.lax.pmax(row[0], axis)
                    else:
                        red = jax.lax.pmin(row[0], axis)
                    outs.append(jnp.expand_dims(red, 0))
                    continue
                # all_gather only on the oracle paths — the psum branch
                # above must not depend on DCE to avoid shipping it
                g = jax.lax.all_gather(row[0], axis)
                reduce_chunk = (
                    reduce_chunk_star if algorithm == "star"
                    else reduce_chunk_ring
                )
                parts = [
                    reduce_chunk(g, s, e, z)
                    for (s, e) in bounds_of(size, np.dtype(dt).itemsize)
                ]
                out = (
                    jnp.concatenate(parts) if len(parts) > 1
                    else parts[0] if parts
                    else jnp.zeros((0,), dt)
                )
                outs.append(jnp.expand_dims(out, 0))
            return tuple(outs)

        mesh_mgr._note_trace()  # python body runs once per trace
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(),) + tuple(P(axis) for _ in stacked),
            out_specs=tuple(P(axis) for _ in stacked),
            check_vma=False,
        )(z, *stacked)

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(axis))
    avals = [jax.ShapeDtypeStruct((), np.int32, sharding=rep)] + [
        jax.ShapeDtypeStruct((n, size), np.dtype(dt), sharding=row)
        for (size, dt) in layouts
    ]
    with _x64_trace():
        return jax.jit(fn).lower(*avals).compile(), (rep, row)


def _x64_trace():
    """x64 enabled for TRACE/LOWER time only (the int8 scale's f64
    divide); runtime execution is config-independent."""
    import jax

    return jax.enable_x64(True)


def _build_psum_scatter(mesh_mgr: MeshManager, world_size: int, op: str,
                        sizes: Sequence[int]):
    """Compile ONE reduce_scatter executable over ``lax.psum_scatter``:
    input is a (world, world*L) stacked f32 array (each rank's
    contributions to every shard, padded to the common slot length L);
    output is (world, L) where row r is rank r's reduced shard. The
    hardware-native sharded-update collective — each link moves ~1/n of
    the payload and no rank ever materializes the full reduction."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = world_size
    mesh = mesh_mgr.mesh_for(n)
    axis = mesh_mgr.axis_name
    L = max(sizes) if sizes else 1

    def fn(stacked):
        def local(row):
            x = row[0].reshape(n, L)
            red = jax.lax.psum_scatter(
                x, axis, scatter_dimension=0, tiled=False
            )
            if op == ReduceOp.AVG:
                red = red / jnp.float32(n)
            return jnp.expand_dims(red, 0)

        mesh_mgr._note_trace()
        return shard_map(
            local, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
            check_vma=False,
        )(stacked)

    row = NamedSharding(mesh, P(axis))
    aval = jax.ShapeDtypeStruct((n, n * L), np.float32, sharding=row)
    return jax.jit(fn).lower(aval).compile(), row


# ------------------------------------------------- quantized psum builders


def _grid_bounds(size: int, chunk_bytes: int,
                 itemsize: int = 4) -> List[Tuple[int, int]]:
    """THE device-side chunk grid over one flat view (_chunk_grid's
    step rule) — the int8 scale granularity. One definition shared by
    _build_allreduce and both quantized builders, so no future edit
    can move one builder's grid off the host codec's."""
    if size == 0:
        return []
    if chunk_bytes <= 0:
        return [(0, size)]
    step = max(1, chunk_bytes // itemsize)
    return [(s, min(size, s + step)) for s in range(0, size, step)]


def _quantize_chunks(x, z, bounds):
    """``(q int8 (size,), scales f32 (len(bounds),))`` over a non-empty
    chunk-bound list — the ONE phase-1 quantizer shared by
    :func:`_build_quantized_psum`,
    :func:`_build_quantized_psum_scatter` and the fused step (a fix
    lands on every wire): the per-chunk jnp loop XLA fuses into the
    exchange."""
    import jax.numpy as jnp

    qs, scs = [], []
    for s, e in bounds:
        q, sc = _dev_quant_int8(x[s:e], z)
        qs.append(q)
        scs.append(sc)
    return (
        jnp.concatenate(qs) if len(qs) > 1 else qs[0]
    ), jnp.stack(scs)


def _build_quantized_psum(mesh_mgr: MeshManager, world_size: int,
                          codec_name: str, chunk_bytes: int, op: str,
                          layouts: Sequence[Tuple[int, np.dtype]]):
    """Compile ONE quantized allreduce on the hardware-native exchange
    path (EQuARX-style, ROADMAP item 2): for each f32 payload —

    1. **quantize** this rank's contribution per chunk on the PR 2 grid
       (int8 + one f32 scale per chunk; bf16/fp16 = elementwise astype),
    2. **exchange** the ENCODED payload: ``all_to_all`` scatters int8
       shards to their reducer (plus an ``all_gather`` of the compact
       per-chunk scales — 4 bytes per 1MB chunk, noise), each link
       carrying ~1/4 (int8) or ~1/2 (bf16) of the raw bytes,
    3. **dequantize-accumulate** the received shards in f32 rank order,
    4. **requantize** the reduced shard on the shard-local grid and
       ``all_gather`` it encoded; every rank decodes identical bytes, so
       the trajectory-consistency invariant holds (all replicas see the
       SAME reduced values).

    One executable, cached per ``(world, codec, chunk grid, op,
    layouts)`` like every PR 6 collective — a kill/reform
    at a seen world size is a cache lookup, never a retrace. Like raw
    ``psum``, XLA owns scheduling, so this path is NUMERIC (outside the
    bitwise A/B); the phase-1 encode is bit-matched to the host codec
    (shared ``_dev_quant_int8``), which is what makes the host
    ``codec_roundtrip`` the honest EF image of this wire. Non-f32
    device dtypes ride a raw ``psum`` branch uncompressed, exactly like
    the host codecs' ``_is_compressible`` gate."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = world_size
    mesh = mesh_mgr.mesh_for(n)
    axis = mesh_mgr.axis_name
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(
            f"quantized psum only accumulates (sum/avg); got op={op!r}"
        )

    def reduce_int8(x, z, size, L, padn, d):
        bounds = _grid_bounds(size, chunk_bytes)
        lens = np.array([e - s for s, e in bounds])
        q_full, scales = _quantize_chunks(x, z, bounds)
        qt = lax.all_to_all(
            jnp.pad(q_full, (0, padn)).reshape(n, L), axis, 0, 0
        )
        sc_all = lax.all_gather(scales, axis)
        acc = jnp.zeros((L,), jnp.float32)
        for r in range(n):
            # expand rank r's compact scales to per-element over the
            # full payload (static chunk lengths), then slice MY shard
            # — all local math, zero extra wire bytes
            sc_elem = jnp.repeat(
                sc_all[r], jnp.asarray(lens), total_repeat_length=size
            )
            sc_elem = jnp.pad(
                sc_elem, (0, padn), constant_values=np.float32(1.0)
            )
            sc_mine = lax.dynamic_slice(sc_elem, (d * L,), (L,))
            acc = _hardround(
                acc + _dev_dequant_int8(qt[r], sc_mine, z), z
            )
        if op == ReduceOp.AVG:
            acc = _hardround(acc / jnp.float32(n), z)
        # phase 2: re-encode the reduced shard (shard-local grid) and
        # broadcast it encoded — every rank decodes identical bytes
        shard_bounds = _grid_bounds(L, chunk_bytes)
        q_shard, sc_shard = _quantize_chunks(acc, z, shard_bounds)
        qg = lax.all_gather(q_shard, axis)
        sg = lax.all_gather(sc_shard, axis)
        parts = [
            _dev_dequant_int8(qg[r, s:e], sg[r, ci], z)
            for r in range(n)
            for ci, (s, e) in enumerate(shard_bounds)
        ]
        full = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        return full[:size]

    def reduce_astype(x, z, size, L, padn, wd):
        et = lax.all_to_all(
            jnp.pad(x.astype(wd), (0, padn)).reshape(n, L), axis, 0, 0
        )
        acc = jnp.zeros((L,), jnp.float32)
        for r in range(n):
            acc = _hardround(acc + et[r].astype(jnp.float32), z)
        if op == ReduceOp.AVG:
            acc = _hardround(acc / jnp.float32(n), z)
        g = lax.all_gather(acc.astype(wd), axis)
        return g.astype(jnp.float32).reshape(-1)[:size]

    def fn(z, *stacked):
        def local(z, *rows):
            d = lax.axis_index(axis)
            outs = []
            for row, (size, dt) in zip(rows, layouts):
                x = row[0]
                if size == 0:
                    # every other path supports size-0 arrays; the
                    # exchange has nothing to ship — emit the empty row
                    outs.append(jnp.zeros((1, 0), np.dtype(dt)))
                    continue
                if np.dtype(dt) != np.float32:
                    # uncompressed native reduce — the host codecs do
                    # not compress these dtypes either
                    red = lax.psum(x, axis)
                    if op == ReduceOp.AVG:
                        red = red / jnp.float32(n)
                    outs.append(jnp.expand_dims(red, 0))
                    continue
                L = -(-size // n)
                padn = n * L - size
                if codec_name == "int8":
                    out = reduce_int8(x, z, size, L, padn, d)
                else:
                    wd = {"bf16": jnp.bfloat16,
                          "fp16": jnp.float16}[codec_name]
                    out = reduce_astype(x, z, size, L, padn, wd)
                outs.append(jnp.expand_dims(out, 0))
            return tuple(outs)

        mesh_mgr._note_trace()
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(),) + tuple(P(axis) for _ in stacked),
            out_specs=tuple(P(axis) for _ in stacked),
            check_vma=False,
        )(z, *stacked)

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(axis))
    avals = [jax.ShapeDtypeStruct((), np.int32, sharding=rep)] + [
        jax.ShapeDtypeStruct((n, size), np.dtype(dt), sharding=row)
        for (size, dt) in layouts
    ]
    with _x64_trace():
        return jax.jit(fn).lower(*avals).compile(), (rep, row)


def _build_quantized_psum_scatter(mesh_mgr: MeshManager, world_size: int,
                                  codec_name: str, chunk_bytes: int,
                                  op: str, sizes: Sequence[int]):
    """Quantized reduce_scatter on the native path: phase 1 of
    :func:`_build_quantized_psum` alone — each rank quantizes its
    contribution to every destination array (per-chunk scales on each
    array's slot grid), ``all_to_all`` ships the int8/bf16 payload to
    its owner, and the owner dequantize-accumulates its own reduced
    shard in f32. No broadcast phase: the sharded weight update
    allgathers PARAMS after the optimizer step, not gradients. Input
    layout matches :func:`_build_psum_scatter` ((world, world*L)
    stacked f32, one slot per destination rank); cached per (world,
    codec, chunk grid, op, sizes)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = world_size
    mesh = mesh_mgr.mesh_for(n)
    axis = mesh_mgr.axis_name
    L = max(sizes) if sizes else 1
    bounds = _grid_bounds(L, chunk_bytes)
    lens = np.array([e - s for s, e in bounds])

    def fn(z, stacked):
        def local(z, row):
            x = row[0].reshape(n, L)
            d = lax.axis_index(axis)
            if codec_name == "int8":
                q_rows, s_rows = [], []
                for j in range(n):
                    q_j, s_j = _quantize_chunks(x[j], z, bounds)
                    q_rows.append(q_j)
                    s_rows.append(s_j)
                qt = lax.all_to_all(jnp.stack(q_rows), axis, 0, 0)
                sc_all = lax.all_gather(jnp.stack(s_rows), axis)
                acc = jnp.zeros((L,), jnp.float32)
                for r in range(n):
                    sc_r = lax.dynamic_index_in_dim(
                        sc_all[r], d, 0, keepdims=False
                    )
                    sc_elem = jnp.repeat(
                        sc_r, jnp.asarray(lens), total_repeat_length=L
                    )
                    acc = _hardround(
                        acc + _dev_dequant_int8(qt[r], sc_elem, z), z
                    )
            else:
                wd = {"bf16": jnp.bfloat16,
                      "fp16": jnp.float16}[codec_name]
                et = lax.all_to_all(x.astype(wd), axis, 0, 0)
                acc = jnp.zeros((L,), jnp.float32)
                for r in range(n):
                    acc = _hardround(acc + et[r].astype(jnp.float32), z)
            if op == ReduceOp.AVG:
                acc = _hardround(acc / jnp.float32(n), z)
            return jnp.expand_dims(acc, 0)

        mesh_mgr._note_trace()
        return shard_map(
            local, mesh=mesh, in_specs=(P(), P(axis)),
            out_specs=P(axis), check_vma=False,
        )(z, stacked)

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(axis))
    avals = [
        jax.ShapeDtypeStruct((), np.int32, sharding=rep),
        jax.ShapeDtypeStruct((n, n * L), np.float32, sharding=row),
    ]
    with _x64_trace():
        return jax.jit(fn).lower(*avals).compile(), (rep, row)


# --------------------------------------------------- fused step builders
#
# The HSDP step over the 2-D ("replica", "model") mesh: each replica
# group is a row of model_shards devices; params are model-sharded and
# replica-replicated, optimizer state is sharded over BOTH axes (each
# device owns the (model shard, replica) sub-shard it updates). The
# fused builder compiles params-allgather(model) → grad →
# reduce-scatter(model) → [EF + encode →] exchange(replica) → sharded
# update → params-allgather(replica) into ONE executable; the staged
# builders compile the SAME local functions as four separate
# executables with host round-trips between them (the live A/B arm).
# _hardround at every stage boundary in BOTH arms is what makes
# fused↔staged a BITWISE identity, not a numeric envelope — the PR 3/5/8
# discipline. Cached in the MeshManager per (mesh shape, codec, chunk
# grid, layouts, fn identity) like every PR 6 collective, so membership
# churn at a seen shape is a cache lookup, never a retrace.


class _FusedSpec:
    """Static description of one fused-step program family — everything
    the builders need to trace, and everything the executable cache key
    must pin. ``q_len`` is the per-device owned sub-shard,
    ``p_len = replicas * q_len`` the per-model-shard param slice,
    ``s_len = model_shards * p_len`` the padded flat param vector."""

    __slots__ = (
        "replicas", "model_shards", "param_size", "batch_size",
        "codec_name", "chunk_bytes", "error_feedback",
        "loss_fn", "tx", "opt_treedef", "opt_leaf_shapes",
        "opt_leaf_dtypes", "fn_key", "q_len", "p_len", "s_len",
    )

    def __init__(self, replicas: int, model_shards: int, param_size: int,
                 batch_size: int, codec_name: str, chunk_bytes: int,
                 error_feedback: bool, loss_fn, tx,
                 opt_treedef, opt_leaf_shapes, opt_leaf_dtypes,
                 fn_key: str) -> None:
        self.replicas = int(replicas)
        self.model_shards = max(1, int(model_shards))
        self.param_size = int(param_size)
        self.batch_size = int(batch_size)
        self.codec_name = codec_name
        self.chunk_bytes = int(chunk_bytes)
        self.error_feedback = bool(error_feedback)
        self.loss_fn = loss_fn
        self.tx = tx
        self.opt_treedef = opt_treedef
        self.opt_leaf_shapes = tuple(tuple(s) for s in opt_leaf_shapes)
        self.opt_leaf_dtypes = tuple(opt_leaf_dtypes)
        self.fn_key = fn_key
        self.q_len = max(
            1, -(-self.param_size // (self.replicas * self.model_shards))
        )
        self.p_len = self.replicas * self.q_len
        self.s_len = self.model_shards * self.p_len

    def exec_key(self, kind: str) -> Tuple:
        """MeshManager executable-cache key for one program of the
        family (``kind``: "fused" or a stage name): pins mesh shape,
        codec, chunk grid, EF arm, layouts and the
        caller-supplied (loss_fn, tx) identity."""
        return (
            "fused_step", kind, self.replicas, self.model_shards,
            self.codec_name, self.chunk_bytes,
            self.error_feedback, self.param_size, self.batch_size,
            self.opt_leaf_shapes,
            tuple(str(d) for d in self.opt_leaf_dtypes), self.fn_key,
        )


def _fused_axes(mesh_mgr: MeshManager, spec: "_FusedSpec"):
    """(mesh, dim-0 partition axes) for the spec's shape — 1-D when the
    model axis is degenerate (4x1 style shapes), 2-D otherwise."""
    mesh = mesh_mgr.mesh_for(spec.replicas, spec.model_shards)
    if spec.model_shards == 1:
        return mesh, (mesh_mgr.axis_name,)
    return mesh, (mesh_mgr.axis_name, mesh_mgr.model_axis_name)


def _fused_local_fns(mesh_mgr: MeshManager, spec: "_FusedSpec"):
    """The four per-device stage bodies, defined ONCE and shared by the
    fused and staged builders — identical traced code either side of
    the _hardround stage fences is the bitwise-identity mechanism.

    Values are LOCAL (unbatched): ``p`` the (p_len,) model-shard param
    slice, ``b`` this device's microbatch, ``e`` the (p_len,) EF
    residual, ``h`` the (q_len,) reduced owned sub-shard gradient."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    R, M = spec.replicas, spec.model_shards
    q_len, p_len, s_len = spec.q_len, spec.p_len, spec.s_len
    codec = spec.codec_name
    axis = mesh_mgr.axis_name
    maxis = mesh_mgr.model_axis_name
    axes = (axis,) if M == 1 else (axis, maxis)
    denom = np.float32(R * M)
    ef = spec.error_feedback

    def loss_body(full, b):
        return spec.loss_fn(full[: spec.param_size], b)

    def local_grad(z, p, b):
        # params allgather over the model axis, per-microbatch grad,
        # grad reduce-scatter back onto the model axis. AVG over the
        # R*M device microbatches happens after the replica exchange.
        full = lax.all_gather(p, maxis).reshape(s_len) if M > 1 else p
        loss, g = jax.value_and_grad(loss_body)(full, b)
        if M > 1:
            g = lax.psum_scatter(
                g.reshape(M, p_len), maxis, scatter_dimension=0,
                tiled=False,
            )
        gm = _hardround(g, z)
        loss = _hardround(lax.psum(loss, axes) / denom, z)
        return gm, loss

    def local_exchange(z, gm, e):
        # cross-replica reduce-scatter of the model-sharded grad, with
        # the wire codec applied exactly as the PR 11 quantized
        # psum_scatter applies it (shared _quantize_chunks / chunk
        # grid); int8 composes the error-feedback residual like the
        # host arena (residual vs the wire image of OWN contribution).
        if codec == "none":
            h = lax.psum_scatter(
                gm.reshape(R, q_len), axis, scatter_dimension=0,
                tiled=False,
            )
            return _hardround(h / denom, z), e
        if codec in ("bf16", "fp16"):
            wd = jnp.bfloat16 if codec == "bf16" else jnp.float16
            et = lax.all_to_all(
                gm.reshape(R, q_len).astype(wd), axis, 0, 0
            )
            acc = jnp.zeros((q_len,), jnp.float32)
            for r in range(R):
                acc = _hardround(acc + et[r].astype(jnp.float32), z)
            return _hardround(acc / denom, z), e
        # int8 (+ EF): phase 1 of the EQuARX exchange on the replica
        # axis — encode per destination slot on the PR 2 chunk grid,
        # ship ENCODED bytes, dequantize-accumulate in rank order.
        gq = _hardround(gm + e, z) if ef else gm
        bounds = _grid_bounds(q_len, spec.chunk_bytes)
        lens = np.array([b1 - b0 for b0, b1 in bounds])
        rows = gq.reshape(R, q_len)
        q_rows, s_rows, w_rows = [], [], []
        for j in range(R):
            q_j, s_j = _quantize_chunks(rows[j], z, bounds)
            q_rows.append(q_j)
            s_rows.append(s_j)
            if ef:
                s_elem = jnp.repeat(
                    s_j, jnp.asarray(lens), total_repeat_length=q_len
                )
                w_rows.append(_dev_dequant_int8(q_j, s_elem, z))
        qt = lax.all_to_all(jnp.stack(q_rows), axis, 0, 0)
        sc_all = lax.all_gather(jnp.stack(s_rows), axis)
        d = lax.axis_index(axis)
        acc = jnp.zeros((q_len,), jnp.float32)
        for r in range(R):
            sc_r = lax.dynamic_index_in_dim(
                sc_all[r], d, 0, keepdims=False
            )
            sc_elem = jnp.repeat(
                sc_r, jnp.asarray(lens), total_repeat_length=q_len
            )
            acc = _hardround(
                acc + _dev_dequant_int8(qt[r], sc_elem, z), z
            )
        h = _hardround(acc / denom, z)
        if ef:
            w = jnp.concatenate(w_rows) if len(w_rows) > 1 else w_rows[0]
            e = _hardround(gq - w, z)
        return h, e

    def local_update(z, h, p, opt_local):
        # the PR 8 sharded update, on-device: this device owns the
        # replica-indexed sub-shard of its model shard
        import optax

        r = lax.axis_index(axis)
        p_sub = lax.dynamic_slice(p, (r * q_len,), (q_len,))
        updates, new_opt = spec.tx.update(h, opt_local, p_sub)
        new_sub = _hardround(optax.apply_updates(p_sub, updates), z)
        return new_sub, new_opt

    def local_gather(new_sub):
        # params allgather over the replica axis: raw bytes, so every
        # replica's model shard is bitwise identical by construction
        return (
            lax.all_gather(new_sub, axis).reshape(p_len)
            if R > 1 else new_sub
        )

    return local_grad, local_exchange, local_update, local_gather


def _fused_avals(mesh_mgr: MeshManager, spec: "_FusedSpec"):
    """(rep_sharding, row_sharding, {name: aval}) for the program
    family's operand layouts — device-stacked (D, ...) arrays
    partitioned on dim 0 over every mesh axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, axes = _fused_axes(mesh_mgr, spec)
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(axes))
    D = spec.replicas * spec.model_shards
    avals = {
        "z": jax.ShapeDtypeStruct((), np.int32, sharding=rep),
        "p": jax.ShapeDtypeStruct(
            (D, spec.p_len), np.float32, sharding=row
        ),
        "b": jax.ShapeDtypeStruct(
            (D, spec.batch_size), np.float32, sharding=row
        ),
        "e": jax.ShapeDtypeStruct(
            (D, spec.p_len), np.float32, sharding=row
        ),
        "h": jax.ShapeDtypeStruct(
            (D, spec.q_len), np.float32, sharding=row
        ),
        "ns": jax.ShapeDtypeStruct(
            (D, spec.q_len), np.float32, sharding=row
        ),
        "opt": [
            jax.ShapeDtypeStruct(
                (D,) + tuple(shape), np.dtype(dt), sharding=row
            )
            for shape, dt in zip(
                spec.opt_leaf_shapes, spec.opt_leaf_dtypes
            )
        ],
    }
    return rep, row, avals


def _build_fused_step(mesh_mgr: MeshManager, spec: "_FusedSpec"):
    """Compile the ENTIRE training step into ONE executable over the
    (replica, model) mesh: grad-apply → quantize → psum_scatter →
    sharded optimizer update → params allgather, with zero host
    round-trips between them. Signature:
    ``fn(z, p, b, e, *opt) -> (new_p, loss, new_e, *new_opt)`` over
    device-stacked operands. The donation contract holds at the step
    surface exactly as for every staged collective: the caller's
    buffers are replaced wholesale by the outputs (torchft_tpu/fused.py
    copies back), never partially mutated mid-flight."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, axes = _fused_axes(mesh_mgr, spec)
    local_grad, local_exchange, local_update, local_gather = (
        _fused_local_fns(mesh_mgr, spec)
    )
    treedef = spec.opt_treedef

    def fn(z, p, b, e, *opt_leaves):
        def local(z, p, b, e, *opt_leaves):
            opt_local = jax.tree_util.tree_unflatten(
                treedef, [leaf[0] for leaf in opt_leaves]
            )
            gm, loss = local_grad(z, p[0], b[0])
            h, new_e = local_exchange(z, gm, e[0])
            new_sub, new_opt = local_update(z, h, p[0], opt_local)
            new_p = local_gather(new_sub)
            outs = [new_p[None], loss.reshape(1), new_e[None]]
            outs.extend(
                jnp.expand_dims(leaf, 0)
                for leaf in jax.tree_util.tree_leaves(new_opt)
            )
            return tuple(outs)

        mesh_mgr._note_trace()
        n = 3 + len(opt_leaves)
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(),) + (P(axes),) * n,
            out_specs=(P(axes),) * n,
            check_vma=False,
        )(z, p, b, e, *opt_leaves)

    rep, row, avals = _fused_avals(mesh_mgr, spec)
    args = [avals["z"], avals["p"], avals["b"], avals["e"]] + avals["opt"]
    with _x64_trace():
        return jax.jit(fn).lower(*args).compile(), (rep, row)


def _build_step_stage(mesh_mgr: MeshManager, spec: "_FusedSpec",
                      stage: str):
    """Compile ONE stage of the staged A/B arm — the same local bodies
    the fused builder composes, as a standalone executable whose
    inputs/outputs cross the host between dispatches. Stages:
    ``grad``     ``fn(z, p, b) -> (gm, loss)``
    ``exchange`` ``fn(z, gm, e) -> (h, new_e)``
    ``update``   ``fn(z, h, p, *opt) -> (new_sub, *new_opt)``
    ``gather``   ``fn(new_sub) -> (new_p,)``"""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, axes = _fused_axes(mesh_mgr, spec)
    local_grad, local_exchange, local_update, local_gather = (
        _fused_local_fns(mesh_mgr, spec)
    )
    treedef = spec.opt_treedef
    rep, row, avals = _fused_avals(mesh_mgr, spec)

    if stage == "grad":
        def fn(z, p, b):
            def local(z, p, b):
                gm, loss = local_grad(z, p[0], b[0])
                return gm[None], loss.reshape(1)

            mesh_mgr._note_trace()
            return shard_map(
                local, mesh=mesh,
                in_specs=(P(), P(axes), P(axes)),
                out_specs=(P(axes), P(axes)), check_vma=False,
            )(z, p, b)

        args = [avals["z"], avals["p"], avals["b"]]
    elif stage == "exchange":
        def fn(z, gm, e):
            def local(z, gm, e):
                h, new_e = local_exchange(z, gm[0], e[0])
                return h[None], new_e[None]

            mesh_mgr._note_trace()
            return shard_map(
                local, mesh=mesh,
                in_specs=(P(), P(axes), P(axes)),
                out_specs=(P(axes), P(axes)), check_vma=False,
            )(z, gm, e)

        args = [avals["z"], avals["p"], avals["e"]]
    elif stage == "update":
        def fn(z, h, p, *opt_leaves):
            def local(z, h, p, *opt_leaves):
                opt_local = jax.tree_util.tree_unflatten(
                    treedef, [leaf[0] for leaf in opt_leaves]
                )
                new_sub, new_opt = local_update(
                    z, h[0], p[0], opt_local
                )
                outs = [new_sub[None]]
                outs.extend(
                    jnp.expand_dims(leaf, 0)
                    for leaf in jax.tree_util.tree_leaves(new_opt)
                )
                return tuple(outs)

            mesh_mgr._note_trace()
            n = 2 + len(opt_leaves)
            return shard_map(
                local, mesh=mesh,
                in_specs=(P(),) + (P(axes),) * n,
                out_specs=(P(axes),) * (1 + len(opt_leaves)),
                check_vma=False,
            )(z, h, p, *opt_leaves)

        args = [avals["z"], avals["h"], avals["p"]] + avals["opt"]
    elif stage == "gather":
        def fn(new_sub):
            def local(new_sub):
                return (local_gather(new_sub[0])[None],)

            mesh_mgr._note_trace()
            return shard_map(
                local, mesh=mesh, in_specs=(P(axes),),
                out_specs=(P(axes),), check_vma=False,
            )(new_sub)

        args = [avals["ns"]]
    else:
        raise ValueError(f"unknown step stage {stage!r}")

    with _x64_trace():
        return jax.jit(fn).lower(*args).compile(), (rep, row)


# ------------------------------------------------- hierarchical builders


def _build_hier_allreduce(mesh_mgr: MeshManager, world_size: int,
                          codec_name: str, chunk_bytes: int, op: str,
                          layouts: Sequence[Tuple[int, np.dtype]],
                          groups: Sequence[Sequence[int]]):
    """Compile ONE deterministic hierarchical allreduce (the parity
    composition — bit-matching the host transport's hier path, which is
    the bitwise oracle at ``codec="none"``): per grid chunk,

    1. **reduce-within**: each domain's rows accumulate at full
       precision in wire-rank order (the host intra star's order),
    2. **exchange-across**: domain sums combine in domain order with
       the star fan-in semantics — domain 0's sum raw, every other
       domain's sum ``dec(enc(·))`` through the wire codec, the result
       re-encoded once so every rank decodes identical bytes (lossy
       codecs; trajectory consistency),
    3. **broadcast-within** is implicit (every rank computes the same
       composition from the gathered rows — on the single-process
       emulation the rows are already co-resident; the
       ``comm_intra_bytes``/``comm_inter_bytes`` counters model the
       real tiered wire, exactly like the flat parity modes).

    ``groups`` lists each domain's wire ranks in domain order. Cached
    per (world, codec, grid, op, layouts, domain structure) like every
    PR 6 collective."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = world_size
    mesh = mesh_mgr.mesh_for(n)
    axis = mesh_mgr.axis_name
    lossy = codec_name != "none"
    groups = tuple(tuple(int(r) for r in g) for g in groups)

    def comb(acc, new, z):
        if op in (ReduceOp.SUM, ReduceOp.AVG):
            out = acc + new
            return _hardround(out, z) if _is_float(out.dtype) else out
        if op == ReduceOp.MAX:
            return jnp.maximum(acc, new)
        if op == ReduceOp.MIN:
            return jnp.minimum(acc, new)
        raise ValueError(f"unsupported reduce op: {op}")

    def reduce_chunk_hier(g, s, e, z):
        dsums = []
        for ranks in groups:
            acc = g[ranks[0], s:e]
            for r in ranks[1:]:
                acc = comb(acc, g[r, s:e], z)
            dsums.append(acc)
        acc = dsums[0]
        if len(dsums) > 1:
            for dsum in dsums[1:]:
                acc = comb(acc, _dev_enc_dec(codec_name, dsum, z), z)
            if lossy:
                # encode-once of the global result: the host inter
                # star root's final re-encode, so every domain decodes
                # identical bytes
                acc = _dev_enc_dec(codec_name, acc, z)
        if op == ReduceOp.AVG:
            acc = acc / jnp.float32(n)
            acc = _hardround(acc, z) if _is_float(acc.dtype) else acc
        return acc

    def fn(z, *stacked):
        def local(z, *rows):
            outs = []
            for row, (size, dt) in zip(rows, layouts):
                g = jax.lax.all_gather(row[0], axis)
                parts = [
                    reduce_chunk_hier(g, s, e, z)
                    for (s, e) in _grid_bounds(
                        size, chunk_bytes, np.dtype(dt).itemsize
                    )
                ]
                out = (
                    jnp.concatenate(parts) if len(parts) > 1
                    else parts[0] if parts
                    else jnp.zeros((0,), dt)
                )
                outs.append(jnp.expand_dims(out, 0))
            return tuple(outs)

        mesh_mgr._note_trace()
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(),) + tuple(P(axis) for _ in stacked),
            out_specs=tuple(P(axis) for _ in stacked),
            check_vma=False,
        )(z, *stacked)

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(axis))
    avals = [jax.ShapeDtypeStruct((), np.int32, sharding=rep)] + [
        jax.ShapeDtypeStruct((n, size), np.dtype(dt), sharding=row)
        for (size, dt) in layouts
    ]
    with _x64_trace():
        return jax.jit(fn).lower(*avals).compile(), (rep, row)


def _build_hier_psum(mesh_mgr: MeshManager, world_size: int,
                     codec_name: str, chunk_bytes: int, op: str,
                     layouts: Sequence[Tuple[int, np.dtype]],
                     groups: Sequence[Sequence[int]],
                     egress: Sequence[int]):
    """Compile the HARDWARE-NATIVE hierarchical allreduce: a
    full-precision ``psum`` restricted to each domain via
    ``axis_index_groups`` (the ICI hop XLA schedules natively), then —
    for lossy codecs — a per-chunk encode of the domain sum on the PR 2
    grid (shared ``_dev_enc_dec`` scale math, bit-matching the host
    codec), and a second ``psum`` of the egress-masked decoded images
    (each domain contributes its encoded sum exactly once — the
    cross-DCN hop, encoded bytes only). Like raw ``psum``, XLA owns the
    reduction order, so this path is NUMERIC (outside the bitwise A/B);
    extrema are idempotent across tiers and lower to a plain
    ``pmax``/``pmin`` (lossy extrema are refused by the capability
    rule). Cached per (world, codec, grid, op, layouts, domain
    structure)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = world_size
    mesh = mesh_mgr.mesh_for(n)
    axis = mesh_mgr.axis_name
    lossy = codec_name != "none"
    groups = [list(int(r) for r in g) for g in groups]
    n_domains = len(groups)
    egress_mask_np = np.zeros((n,), np.bool_)
    for r in egress:
        egress_mask_np[int(r)] = True

    def fn(z, *stacked):
        def local(z, *rows):
            d = lax.axis_index(axis)
            is_egress = jnp.asarray(egress_mask_np)[d]
            outs = []
            for row, (size, dt) in zip(rows, layouts):
                x = row[0]
                if size == 0:
                    outs.append(jnp.zeros((1, 0), np.dtype(dt)))
                    continue
                if op == ReduceOp.MAX:
                    outs.append(jnp.expand_dims(lax.pmax(x, axis), 0))
                    continue
                if op == ReduceOp.MIN:
                    outs.append(jnp.expand_dims(lax.pmin(x, axis), 0))
                    continue
                if np.dtype(dt) != np.float32 or n_domains == 1:
                    # non-f32 never compresses (the host gate) and a
                    # single domain has no cross tier: accumulate flat
                    red = lax.psum(x, axis)
                else:
                    dsum = lax.psum(
                        x, axis, axis_index_groups=groups
                    )
                    if lossy:
                        parts = [
                            _dev_enc_dec(codec_name, dsum[s:e], z)
                            for s, e in _grid_bounds(size, chunk_bytes)
                        ]
                        y = (
                            jnp.concatenate(parts) if len(parts) > 1
                            else parts[0]
                        )
                    else:
                        y = dsum
                    # where(), not multiply-by-mask: a poisoned NaN
                    # image on a non-egress rank must not leak through
                    # NaN * 0
                    contrib = jnp.where(is_egress, y, jnp.zeros_like(y))
                    red = lax.psum(contrib, axis)
                if op == ReduceOp.AVG:
                    red = red / jnp.float32(n)
                    red = _hardround(red, z) if _is_float(red.dtype) else red
                outs.append(jnp.expand_dims(red, 0))
            return tuple(outs)

        mesh_mgr._note_trace()
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(),) + tuple(P(axis) for _ in stacked),
            out_specs=tuple(P(axis) for _ in stacked),
            check_vma=False,
        )(z, *stacked)

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(axis))
    avals = [jax.ShapeDtypeStruct((), np.int32, sharding=rep)] + [
        jax.ShapeDtypeStruct((n, size), np.dtype(dt), sharding=row)
        for (size, dt) in layouts
    ]
    with _x64_trace():
        return jax.jit(fn).lower(*avals).compile(), (rep, row)


def _host_hier_allreduce(contribs: List[List[np.ndarray]],
                         codec_name: str, chunk_bytes: int, op: str,
                         groups: Sequence[Sequence[int]],
                         world_size: int) -> List[np.ndarray]:
    """Host simulation of the hierarchical composition, running the
    REAL codec code over the real chunk grid — bitwise-identical to the
    socket transport's hier path by construction. Serves the 64-bit
    dtype fallback (like ``_host_allreduce``) AND doubles as THE
    deterministic reference composition the bench's sha256 oracle
    grades both planes against. Returns ONE result list (all ranks
    decode identical values on the hier path)."""
    codec = _CODECS[codec_name]()
    reduce_fn = _REDUCE_FNS.get(ReduceOp.SUM if op == ReduceOp.AVG else op)
    if reduce_fn is None:
        raise ValueError(f"unsupported reduce op: {op}")
    lossy = type(codec) is not _NoCodec
    copy = lambda v, inc: np.copyto(v, inc)  # noqa: E731

    # reduce-within: wire-rank order per domain (the intra star's order)
    dsums: List[List[np.ndarray]] = []
    for ranks in groups:
        acc = [a.copy() for a in contribs[ranks[0]]]
        acc_chunks = _chunk_grid([a.reshape(-1) for a in acc], chunk_bytes)
        for r in ranks[1:]:
            peer_chunks = _chunk_grid(
                [a.reshape(-1) for a in contribs[r]], chunk_bytes
            )
            for ch, inc in zip(acc_chunks, peer_chunks):
                reduce_fn(ch, inc)
        dsums.append(acc)
    # exchange-across: star fan-in over the domain tier (domain 0 raw,
    # the rest encoded once), then the root's final re-encode
    total = dsums[0]
    total_chunks = _chunk_grid(
        [a.reshape(-1) for a in total], chunk_bytes
    )
    for dsum in dsums[1:]:
        d_chunks = _chunk_grid([a.reshape(-1) for a in dsum], chunk_bytes)
        for ch, inc in zip(total_chunks, d_chunks):
            codec.decode_into(
                _iov_join(codec.encode_iovecs([inc])), [ch], reduce_fn
            )
    if len(dsums) > 1 and lossy:
        for ch in total_chunks:
            codec.decode_into(
                _iov_join(codec.encode_iovecs([ch])), [ch], copy
            )
    if op == ReduceOp.AVG:
        for a in total:
            np.divide(a, world_size, out=a)
    return total


# ------------------------------------------------------ host-side fallback


def _host_allreduce(contribs: List[List[np.ndarray]], algorithm: str,
                    codec_name: str, chunk_bytes: int,
                    op: str) -> List[List[np.ndarray]]:
    """In-group host simulation of the transport's star/ring math for
    payload dtypes the device plane cannot hold (64-bit). Runs the REAL
    codec code over the real chunk grid, so it is bitwise-identical to
    the socket transport by construction. ``algorithm="psum"`` payloads
    map onto the ring simulation (psum has no host accumulation order
    to reproduce — it is the numeric path either way). Returns per-rank
    results."""
    n = len(contribs)
    codec = _CODECS[codec_name]()
    reduce_fn = _REDUCE_FNS.get(ReduceOp.SUM if op == ReduceOp.AVG else op)
    if reduce_fn is None:
        raise ValueError(f"unsupported reduce op: {op}")
    lossy = type(codec) is not _NoCodec
    copy = lambda v, inc: np.copyto(v, inc)  # noqa: E731

    if algorithm == "star":
        acc = [a.copy() for a in contribs[0]]
        acc_chunks = _chunk_grid([a.reshape(-1) for a in acc], chunk_bytes)
        peer_chunks = [
            _chunk_grid([a.reshape(-1) for a in contribs[r]], chunk_bytes)
            for r in range(1, n)
        ]
        for ci, ch in enumerate(acc_chunks):
            for pi in range(n - 1):
                enc = codec.encode_iovecs([peer_chunks[pi][ci]])
                codec.decode_into(_iov_join(enc), [ch], reduce_fn)
            if op == ReduceOp.AVG:
                np.divide(ch, n, out=ch)
            if lossy:
                enc = codec.encode_iovecs([ch])
                codec.decode_into(_iov_join(enc), [ch], copy)
        return [acc for _ in range(n)]

    # ring: simulate every rank's reduce-scatter + encode-once all-gather
    ranks = [[a.copy() for a in contribs[r]] for r in range(n)]
    flats = [
        _chunk_grid([a.reshape(-1) for a in ranks[r]], chunk_bytes)
        for r in range(n)
    ]

    def views(r: int, c: int) -> List[np.ndarray]:
        out = []
        for f in flats[r]:
            s, e = _Lane._chunk_bounds(f.size, n, c)
            out.append(f[s:e])
        return out

    for step in range(n - 1):
        sent = {
            r: [v.copy() for v in views(r, (r - step) % n)] for r in range(n)
        }
        for r in range(n):
            for v, inc in zip(views(r, (r - step - 1) % n), sent[(r - 1) % n]):
                reduce_fn(v, inc)
    for c in range(n):
        enc = _iov_join(codec.encode_iovecs(views((c - 1) % n, c)))
        for r in range(n):
            codec.decode_into(enc, views(r, c), copy)
    if op == ReduceOp.AVG:
        for r in range(n):
            for f in flats[r]:
                np.divide(f, n, out=f)
    return ranks


# ---------------------------------------------------------- group rendezvous


class _Sub:
    __slots__ = ("opcode", "arrays", "op", "root", "fut", "owners",
                 "topology", "vote", "t_submit")

    def __init__(self, opcode: str, arrays: List[np.ndarray], op: str,
                 root: int, fut: Future,
                 owners: "Optional[List[int]]" = None,
                 topology: "Optional[str]" = None,
                 vote: int = 0) -> None:
        self.opcode = opcode
        self.arrays = arrays
        self.op = op
        self.root = root
        self.fut = fut
        self.owners = owners  # reduce_scatter: destination rank per array
        # allreduce: per-op topology override (None = context default)
        self.topology = topology
        # this rank's commit-vote health bit (1 = unhealthy), sampled at
        # submit; gradient opcodes only (0 elsewhere)
        self.vote = vote
        self.t_submit = time.perf_counter()


class _XlaGroup:
    """In-process rendezvous standing in for the SPMD launch (module
    docstring): one group per store prefix, executing each fully-
    subscribed op on a 1-thread executor so submits stay O(enqueue)."""

    _registry: Dict[str, "_XlaGroup"] = {}
    _registry_lock = threading.Lock()

    @classmethod
    def join(cls, key: str, rank: int, world_size: int,
             ctx: "XlaCommContext", timeout: float) -> "_XlaGroup":
        with cls._registry_lock:
            group = cls._registry.get(key)
            if group is None:
                group = cls(key, world_size, ctx._mesh_mgr)
                cls._registry[key] = group
        group._add_member(rank, world_size, ctx)
        # Block until the full cohort arrives — the host transport's
        # configure blocks on socket rendezvous the same way, and a
        # peer that died pre-rendezvous must fail configure, not the
        # first collective.
        deadline = time.time() + timeout
        try:
            with group._cond:
                while (len(group._members) < world_size
                       and not group._closed):
                    remaining = deadline - time.time()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"xla comm configure: {len(group._members)} of "
                            f"{world_size} ranks joined {key!r} before "
                            "timeout"
                        )
                    group._cond.wait(timeout=min(0.1, remaining))
                if group._closed:
                    raise ConnectionError(
                        f"xla comm configure: group {key!r} closed during "
                        "rendezvous (a member reconfigured or shut down)"
                    )
        except Exception:
            group._abandon(rank)
            raise
        return group

    def __init__(self, key: str, world_size: int,
                 mesh_mgr: MeshManager) -> None:
        self.key = key
        self.world_size = world_size
        self.mesh_mgr = mesh_mgr
        self._members: Dict[int, "XlaCommContext"] = {}
        self._pending: Dict[int, Dict[int, _Sub]] = {}
        self._timers: Dict[int, threading.Timer] = {}
        self._cond = threading.Condition()
        self._closed = False
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"torchft_tpu_xla_{id(self)}"
        )

    def _add_member(self, rank: int, world_size: int,
                    ctx: "XlaCommContext") -> None:
        with self._cond:
            if self._closed:
                raise ConnectionError(
                    f"xla comm configure: group {self.key!r} already closed"
                )
            if world_size != self.world_size:
                raise ValueError(
                    f"xla comm configure: rank {rank} joined {self.key!r} "
                    f"with world_size {world_size}, group has "
                    f"{self.world_size}"
                )
            if rank in self._members:
                raise ValueError(
                    f"xla comm configure: duplicate rank {rank} in "
                    f"{self.key!r}"
                )
            first = next(iter(self._members.values()), None)
            if first is None:
                # The FIRST MEMBER's pool owns the group's executables:
                # the creating context can lose the join race to a
                # mismatched peer and never become a member, and
                # collectives must never run (nor count compiles) on a
                # pool no member passed in.
                self.mesh_mgr = ctx._mesh_mgr
            else:
                mine = (ctx._codec_name, ctx._chunk_bytes, ctx._algorithm)
                theirs = (first._codec_name, first._chunk_bytes,
                          first._algorithm)
                if mine != theirs or ctx._mesh_mgr is not self.mesh_mgr:
                    raise ValueError(
                        f"xla comm configure: rank {rank} joined "
                        f"{self.key!r} with (codec, chunk_bytes, "
                        f"algorithm)={mine} but the group runs {theirs} "
                        "(settings and mesh_manager must match across "
                        "ranks, like the host transport's)"
                    )
            self._members[rank] = ctx
            self._cond.notify_all()

    def _abandon(self, rank: int) -> None:
        """Failed rendezvous: deregister the waiting rank so a retried
        configure on the same store address re-attempts the rendezvous
        instead of failing on 'duplicate rank'; the last member to give
        up disposes the group (still-waiting peers keep it alive — a
        retry can complete their rendezvous)."""
        with self._cond:
            self._members.pop(rank, None)
            dispose = not self._members and not self._closed
            if dispose:
                # Mark closed BEFORE dropping from the registry: a racing
                # joiner that fetched this group object must fail fast in
                # _add_member, not wait out its timeout on a zombie.
                self._close_locked(ConnectionError(
                    f"xla comm group {self.key!r} disposed after a "
                    "failed rendezvous"
                ))
            self._cond.notify_all()
        if dispose:
            with self._registry_lock:
                if self._registry.get(self.key) is self:
                    del self._registry[self.key]
            self._executor.shutdown(wait=False)

    def leave(self, ctx: "XlaCommContext") -> None:
        """A member reconfiguring/shutting down closes the whole group —
        the analog of the host transport closing its sockets: peers'
        in-flight and future ops on the stale round must fail fast."""
        with self._cond:
            if ctx not in self._members.values():
                return
            self._close_locked(
                ConnectionError(
                    f"xla comm group {self.key!r} torn down "
                    "(member reconfigured or shut down)"
                )
            )
        with self._registry_lock:
            if self._registry.get(self.key) is self:
                del self._registry[self.key]
        self._executor.shutdown(wait=False)

    def _close_locked(self, exc: Exception) -> None:
        if self._closed:
            return
        self._closed = True
        for timer in self._timers.values():
            timer.cancel()
        self._timers.clear()
        pend, self._pending = self._pending, {}
        for subs in pend.values():
            for sub in subs.values():
                try:
                    sub.fut.set_exception(exc)
                except Exception:  # noqa: BLE001 — already resolved
                    pass
        self._cond.notify_all()

    # ------------------------------------------------------------- submit

    def submit(self, rank: int, seq: int, sub: _Sub,
               timeout: float) -> None:
        run_now = None
        with self._cond:
            if self._closed:
                sub.fut.set_exception(ConnectionError(
                    f"xla comm group {self.key!r} is closed"
                ))
                return
            subs = self._pending.setdefault(seq, {})
            subs[rank] = sub
            if len(subs) == self.world_size:
                del self._pending[seq]
                timer = self._timers.pop(seq, None)
                if timer is not None:
                    timer.cancel()
                run_now = subs
            elif seq not in self._timers:
                # First arrival arms the straggler deadline: a peer that
                # died mid-step must fail the survivors' op (which the
                # Manager latches) rather than hang them.
                timer = threading.Timer(
                    timeout, self._expire, args=(seq,)
                )
                timer.daemon = True
                self._timers[seq] = timer
                timer.start()
        if run_now is not None:
            # Enqueue only — completion order across seqs is monotonic
            # (each rank submits in program order), so the 1-thread
            # executor preserves the per-group op sequence.
            try:
                self._executor.submit(self._execute_safe, seq, run_now)
            except RuntimeError as e:
                # A member tore the group down between our lock release
                # and the enqueue: this seq already left _pending (so
                # _close_locked could not fail it) and its watchdog is
                # cancelled — fail every rank's future here or the
                # survivors block in .result() forever.
                exc = ConnectionError(
                    f"xla comm group {self.key!r} closed while "
                    f"dispatching seq={seq}: {e}"
                )
                for sub in run_now.values():
                    try:
                        sub.fut.set_exception(exc)
                    except Exception:  # noqa: BLE001
                        pass
                for ctx in list(self._members.values()):
                    ctx._latch_group_error(self, exc)

    def _expire(self, seq: int) -> None:
        with self._cond:
            subs = self._pending.pop(seq, None)
            self._timers.pop(seq, None)
        if not subs:
            return
        missing = sorted(set(range(self.world_size)) - set(subs))
        exc = ConnectionError(
            f"xla comm op seq={seq} timed out waiting for ranks {missing} "
            f"in group {self.key!r}"
        )
        for sub in subs.values():
            try:
                sub.fut.set_exception(exc)
            except Exception:  # noqa: BLE001
                pass
        for ctx in list(self._members.values()):
            ctx._latch_group_error(self, exc)

    # ------------------------------------------------------------ execute

    def _execute_safe(self, seq: int, subs: Dict[int, _Sub]) -> None:
        try:
            self._execute(seq, subs)
        except Exception as e:  # noqa: BLE001 — fail the op, latch all
            logger.warning(
                "xla comm op failed (group %s seq %d): %s",
                self.key, seq, e,
            )
            for sub in subs.values():
                try:
                    sub.fut.set_exception(e)
                except Exception:  # noqa: BLE001
                    pass
            for ctx in list(self._members.values()):
                ctx._latch_group_error(self, e)

    def _execute(self, seq: int, subs: Dict[int, _Sub]) -> None:
        n = self.world_size
        ordered = [subs[r] for r in range(n)]
        first = ordered[0]
        sig = [
            (sub.opcode, sub.op, sub.root, tuple(sub.owners or ()),
             sub.topology,
             [(a.shape, _dtype_key(a.dtype)) for a in sub.arrays])
            for sub in ordered
        ]
        if first.opcode in ("broadcast", "allgather"):
            # layouts may legally differ per rank: broadcast discards
            # non-root contributions, allgather self-describes each
            # rank's arrays (host-plane semantics — variable-length
            # state is the normal allgather use)
            sig = [s[:3] for s in sig]
        if any(s != sig[0] for s in sig):
            raise ConnectionError(
                f"xla comm collective mismatch at seq={seq}: ranks "
                "submitted divergent ops/layouts/owners"
            )
        # Per-rank spans land in each member's OWN sink (each Manager
        # shares its Metrics in via set_metrics), same as the host
        # transport's lanes — a host-vs-xla A/B compares like with like.
        # ALLREDUCE ONLY, matching the host plane: a heal broadcast or
        # state allgather landing in comm_* would pin gradient-path
        # regressions on checkpoint traffic.
        sinks = [self._members[r].metrics for r in range(n)]
        t_exec = time.perf_counter()

        if first.opcode in ("allreduce", "reduce_scatter"):
            for sub, m in zip(ordered, sinks):
                m.observe("comm_submit_wire", t_exec - sub.t_submit)
            self._execute_allreduce(ordered)
            # Commit vote: the group rendezvous already gathered every
            # rank's health bit with the op, so the aggregate is an OR
            # folded HERE — the single-process lowering of the 1-element
            # error-bit psum (a real SPMD launch would append the bit to
            # the executable's psum; the rendezvous IS the collective on
            # this plane, module docstring). An expired/failed op records
            # nothing: vote absent, the Manager falls back to the full
            # barrier.
            agg = 0
            for sub in ordered:
                agg |= sub.vote & 1
            for r in range(n):
                self._members[r]._record_vote(agg)
            # Spans observed BEFORE the futures resolve: a caller that
            # snapshots metrics right after .result() must see them
            # (the smoke gate does exactly that).
            t_done = time.perf_counter()
            for sub, m in zip(ordered, sinks):
                m.observe("comm_wire_reduce", t_done - t_exec)
                m.observe("comm_op_wire", t_done - sub.t_submit)
            for sub in ordered:
                sub.fut.set_result(sub.arrays)
        elif first.opcode == "broadcast":
            src = ordered[first.root].arrays
            for r, sub in enumerate(ordered):
                sub.fut.set_result([np.array(a, copy=True) for a in src])
        else:  # allgather
            # fresh buffers PER RECEIVING RANK (the host plane decodes
            # into per-rank buffers): a rank mutating its result in
            # place must not be visible in a peer's
            for sub in ordered:
                sub.fut.set_result([
                    [np.array(a, copy=True) for a in src.arrays]
                    for src in ordered
                ])

    def _execute_allreduce(self, ordered: List[_Sub]) -> None:
        import jax

        n = self.world_size
        op = ordered[0].op
        ctx0 = self._members[0]
        algorithm = ctx0._resolved_algorithm(n)
        codec_name = ctx0._codec_name
        chunk_bytes = ctx0._chunk_bytes
        arrays0 = ordered[0].arrays
        topo = ordered[0].topology or ctx0._topology_default
        # Op-dependent capability (the ctor vetted the static combo):
        # e.g. int8 psum with op='max' — per-chunk scales cannot ride a
        # max reduction. ONE definition (unsupported_reason) shared with
        # Manager.comm_supports and the bench sweeps. Hier checks the
        # RAW ctor algorithm (its "auto" resolves to star composition,
        # not the flat path's world-size rule).
        reason = XlaCommContext.unsupported_reason(
            ctx0._algorithm if topo == "hier" else algorithm,
            codec_name, op, topo,
        )
        if reason is not None:
            raise ValueError(reason)
        if op == ReduceOp.AVG and not all(
            _is_float(a.dtype) for a in arrays0
        ):
            # The host plane's integer divide raises (np.divide into an
            # int chunk is an invalid cast); the device path would
            # silently promote-and-truncate — fail alike instead.
            raise TypeError(
                "ReduceOp.AVG requires float arrays (matching the host "
                "transport, whose in-place integer divide raises)"
            )
        if topo == "hier":
            self._execute_hier(ordered, op)
            return
        # REDUCE_SCATTER: same math, narrowed delivery. ``owners[j]`` is
        # the only rank whose copy of array j is written back (the
        # others stay unspecified — donation contract). Parity
        # algorithms (star/ring) REUSE the allreduce executable — same
        # cache key, zero extra compiles, trivially bitwise with the
        # replicated arm; the hardware-native path below
        # (_execute_psum_scatter) lowers to jax.lax.psum_scatter.
        # Bytes-on-wire accounting (one direction, one rank's encoded
        # contribution — the wire_nbytes definition): cumulative raw vs
        # encoded counters in EVERY member's sink, so a quantized-psum
        # run's compression ratio is a Δcounter division. Same keys as
        # the host transport's.
        raw_b = float(sum(a.nbytes for a in arrays0))
        enc_b = float(sum(ctx0.wire_nbytes(a) for a in arrays0))
        for r in range(n):
            m = self._members[r].metrics
            m.incr("comm_raw_bytes", raw_b)
            m.incr("comm_encoded_bytes", enc_b)
        owners = (
            ordered[0].owners
            if ordered[0].opcode == "reduce_scatter" else None
        )
        if owners is not None:
            if len(owners) != len(arrays0) or any(
                not 0 <= o < n for o in owners
            ):
                raise ValueError(
                    f"reduce_scatter owners {owners} must name a rank in "
                    f"[0, {n}) per array ({len(arrays0)} submitted)"
                )
            if (
                algorithm == "psum"
                and op in (ReduceOp.SUM, ReduceOp.AVG)
                and list(owners) == list(range(n))
                and all(
                    _dtype_key(a.dtype) == "<f4"
                    and _is_device_dtype(a.dtype)
                    for a in arrays0
                )
            ):
                self._execute_psum_scatter(ordered, op)
                return

        dev_idx = [
            j for j, a in enumerate(arrays0) if _is_device_dtype(a.dtype)
        ]
        host_idx = [
            j for j in range(len(arrays0)) if j not in dev_idx
        ]

        if host_idx:
            host_results = _host_allreduce(
                [[sub.arrays[j] for j in host_idx] for sub in ordered],
                algorithm, codec_name, chunk_bytes, op,
            )
        outs: List[Any] = []
        if dev_idx:
            layouts = tuple(
                (int(arrays0[j].size), _dtype_key(arrays0[j].dtype))
                for j in dev_idx
            )
            mm = self.mesh_mgr
            if algorithm == "psum" and codec_name != "none":
                # the quantized native exchange (EQuARX): encode →
                # all_to_all/all_gather of encoded payloads → decode-
                # accumulate, one executable cached per (world, codec,
                # grid, op, layouts) like every collective
                key = (n, "psum_q", codec_name, chunk_bytes, op, layouts)
                build = lambda: _build_quantized_psum(  # noqa: E731
                    mm, n, codec_name, chunk_bytes, op,
                    [(s, np.dtype(d)) for (s, d) in layouts],
                )
            else:
                key = (n, algorithm, codec_name, chunk_bytes, op, layouts)
                build = lambda: _build_allreduce(  # noqa: E731
                    mm, n, algorithm, codec_name, chunk_bytes, op,
                    [(s, np.dtype(d)) for (s, d) in layouts],
                )
            compiled, (rep, row) = mm.executable(key, build)
            n_chunks = float(sum(
                len(_chunk_grid([arrays0[j].reshape(-1)], chunk_bytes))
                for j in dev_idx
            ))
            for r in range(n):
                self._members[r].metrics.incr("comm_chunks", n_chunks)
            with _x64_trace():
                ins = [jax.device_put(np.int32(0), rep)] + [
                    jax.device_put(
                        np.stack([
                            np.ascontiguousarray(sub.arrays[j]).reshape(-1)
                            for sub in ordered
                        ]),
                        row,
                    )
                    for j in dev_idx
                ]
            outs = [np.asarray(o) for o in compiled(*ins)]

        # Donation contract: copy the reduced values back into every
        # rank's submitted arrays — callers (the DDP staging arena) rely
        # on the result aliasing what they submitted. REDUCE_SCATTER
        # narrows the write-back to each array's owner rank. The caller
        # (_execute) resolves the futures after observing the op spans.
        for r, sub in enumerate(ordered):
            for k, j in enumerate(dev_idx):
                if owners is not None and owners[j] != r:
                    continue
                a = sub.arrays[j]
                np.copyto(a.reshape(-1), outs[k][0].astype(a.dtype,
                                                           copy=False))
            for k, j in enumerate(host_idx):
                if owners is not None and owners[j] != r:
                    continue
                np.copyto(sub.arrays[j], host_results[r][k])

    def _execute_hier(self, ordered: List[_Sub], op: str) -> None:
        """Hierarchical allreduce over the domain tree: reduce-within →
        compress → exchange-across → broadcast-within, as ONE cached
        executable (the PR 6 pattern — a kill→reform at a seen (world,
        codec, topology, domain-structure) key is a cache lookup, never
        a retrace). Composition: the deterministic star fan-in
        (bit-matching the host transport's hier path — THE parity arm,
        bitwise at codec='none') or, for ``algorithm='psum'``, the
        native grouped-psum tiers (numeric; XLA owns the order).
        The ``comm_intra_bytes``/``comm_inter_bytes``/``comm_hops``
        counters model the real tiered wire: raw full-precision bytes
        inside a domain, encoded bytes for egress ranks only across
        domains — the surface the hier path exists for."""
        import jax

        n = self.world_size
        ctx0 = self._members[0]
        codec_name = ctx0._codec_name
        chunk_bytes = ctx0._chunk_bytes
        arrays0 = ordered[0].arrays
        assigns = [
            self._members[r]._resolve_assignment() for r in range(n)
        ]
        fps = {a.fingerprint for a in assigns}
        if len(fps) != 1:
            raise ConnectionError(
                "hier allreduce with divergent domain assignments "
                f"across ranks: {sorted(fps)} — resolver maps must "
                "match across the cohort"
            )
        a0 = assigns[0]
        if a0.world_size() != n:
            raise ConnectionError(
                f"domain assignment spans {a0.world_size()} ranks but "
                f"the wire has {n}"
            )
        hier_algo = ctx0._resolved_hier_algorithm()
        groups = a0.groups

        # Tier byte/hop accounting, per member, same convention as the
        # host hier path (one direction, that rank's contribution).
        raw_b = float(sum(a.nbytes for a in arrays0))
        enc_b = float(sum(ctx0.wire_nbytes(a) for a in arrays0))
        for r in range(n):
            m = self._members[r].metrics
            m_r = len(a0.group_of(r))
            m.incr("comm_intra_bytes", raw_b if m_r > 1 else 0.0)
            m.incr(
                "comm_inter_bytes",
                enc_b if (a0.is_egress(r) and a0.n_domains > 1) else 0.0,
            )
            # reduce-to-egress (1) + broadcast-within (1) + star
            # fan-in (2) — the host hier path's hop model
            hops = (2 if m_r > 1 else 0) + (2 if a0.n_domains > 1 else 0)
            m.incr("comm_hops", float(hops))

        dev_idx = [
            j for j, a in enumerate(arrays0) if _is_device_dtype(a.dtype)
        ]
        host_idx = [j for j in range(len(arrays0)) if j not in dev_idx]
        if host_idx:
            host_result = _host_hier_allreduce(
                [[sub.arrays[j] for j in host_idx] for sub in ordered],
                codec_name, chunk_bytes, op, groups, n,
            )
        outs: List[Any] = []
        if dev_idx:
            layouts = tuple(
                (int(arrays0[j].size), _dtype_key(arrays0[j].dtype))
                for j in dev_idx
            )
            mm = self.mesh_mgr
            if hier_algo == "psum":
                key = (n, "hier_psum", codec_name, chunk_bytes, op,
                       layouts, groups)
                build = lambda: _build_hier_psum(  # noqa: E731
                    mm, n, codec_name, chunk_bytes, op,
                    [(s, np.dtype(d)) for (s, d) in layouts],
                    groups, a0.egress,
                )
            else:
                key = (n, "hier", codec_name, chunk_bytes, op, layouts,
                       groups)
                build = lambda: _build_hier_allreduce(  # noqa: E731
                    mm, n, codec_name, chunk_bytes, op,
                    [(s, np.dtype(d)) for (s, d) in layouts], groups,
                )
            compiled, (rep, row) = mm.executable(key, build)
            n_chunks = float(sum(
                len(_chunk_grid([arrays0[j].reshape(-1)], chunk_bytes))
                for j in dev_idx
            ))
            for r in range(n):
                self._members[r].metrics.incr("comm_chunks", n_chunks)
            with _x64_trace():
                ins = [jax.device_put(np.int32(0), rep)] + [
                    jax.device_put(
                        np.stack([
                            np.ascontiguousarray(sub.arrays[j]).reshape(-1)
                            for sub in ordered
                        ]),
                        row,
                    )
                    for j in dev_idx
                ]
            outs = [np.asarray(o) for o in compiled(*ins)]

        for r, sub in enumerate(ordered):
            for k, j in enumerate(dev_idx):
                a = sub.arrays[j]
                np.copyto(
                    a.reshape(-1), outs[k][0].astype(a.dtype, copy=False)
                )
            for k, j in enumerate(host_idx):
                np.copyto(sub.arrays[j], host_result[k])

    def _execute_psum_scatter(self, ordered: List[_Sub], op: str) -> None:
        """Hardware-native reduce_scatter: ``jax.lax.psum_scatter``
        inside shard_map, one cached executable per (world, sizes)
        layout like every other collective (the PR 6 pattern). Arrays
        are padded to one common slot length and stacked (n, n*L); the
        scatter hands device r the reduced slot r, which lands back in
        rank r's owned array. SUM/AVG only, f32 only, owners ==
        range(n) — the sharded-update layout; anything else runs the
        parity path. A lossy codec swaps in the QUANTIZED variant
        (_build_quantized_psum_scatter: encoded all_to_all, owner-side
        decode-accumulate) with zero call-site changes. Like
        algorithm='psum' allreduce, the reduction order is XLA's to
        choose, so this path is outside the bitwise A/B by
        construction."""
        import jax

        n = self.world_size
        ctx0 = self._members[0]
        codec_name = ctx0._codec_name
        chunk_bytes = ctx0._chunk_bytes
        arrays0 = ordered[0].arrays
        sizes = tuple(int(a.size) for a in arrays0)
        mm = self.mesh_mgr
        L = max(sizes) if sizes else 0
        if L == 0:
            return
        if codec_name != "none":
            # quantized native reduce_scatter: phase 1 of the quantized
            # psum alone — encoded all_to_all, owner-side decode-
            # accumulate (the sharded weight update's gradient hop)
            key = (n, "psum_scatter_q", codec_name, chunk_bytes, op,
                   sizes)
            compiled, (rep, row) = mm.executable(
                key, lambda: _build_quantized_psum_scatter(
                    mm, n, codec_name, chunk_bytes, op, sizes
                )
            )
        else:
            rep = None
            key = (n, "psum_scatter", op, sizes)
            compiled, row = mm.executable(
                key, lambda: _build_psum_scatter(mm, n, op, sizes)
            )
        stacked = np.zeros((n, n * L), np.float32)
        for r, sub in enumerate(ordered):
            for j, a in enumerate(sub.arrays):
                stacked[r, j * L: j * L + sizes[j]] = (
                    np.ascontiguousarray(a).reshape(-1)
                )
        with _x64_trace():
            ins = [jax.device_put(stacked, row)]
            if rep is not None:
                ins.insert(0, jax.device_put(np.int32(0), rep))
        out = np.asarray(compiled(*ins))
        for r, sub in enumerate(ordered):
            a = sub.arrays[r]
            np.copyto(a.reshape(-1), out[r, : sizes[r]])


# --------------------------------------------------------------- the context


class XlaCommContext(CommContext):
    """Reconfigurable on-device collective context (module docstring).

    ``algorithm``: "star"/"ring" reproduce the socket transport's
    accumulation order and codec bits exactly (the bitwise-oracle
    modes; "auto" picks ring at world_size >= 3 like the host), "psum"
    is the hardware-native fast path whose reduction order is XLA's to
    choose: codec "none" lowers straight to ``jax.lax.psum``; a lossy
    codec runs the QUANTIZED exchange (_build_quantized_psum — encode
    on the chunk grid, all_to_all/all_gather of encoded payloads,
    decode-accumulate, one executable; sum/avg only).

    ``compression``/``chunk_bytes`` mirror TcpCommContext: same codecs,
    same chunk grid (also the int8 scale granularity), must match the
    host transport's settings for A/B parity.

    ``mesh_manager``: the mesh + executable cache, shared process-wide
    by default; pass a private pool to isolate devices or pin compile
    counters in tests."""

    backend_name = "xla"

    def __init__(self, timeout: "float | timedelta" = 60.0,
                 algorithm: str = "auto",
                 compression: str = "none",
                 chunk_bytes: int = 1 << 20,
                 mesh_manager: Optional[MeshManager] = None,
                 topology: str = "flat",
                 domain_resolver=None,
                 model_shards: int = 1) -> None:
        super().__init__()
        if isinstance(timeout, timedelta):
            timeout = timeout.total_seconds()
        reason = self.unsupported_reason(
            algorithm, compression, topology=topology
        )
        if reason is not None:
            raise ValueError(reason)
        if chunk_bytes < 0:
            raise ValueError("chunk_bytes must be >= 0")
        self._timeout = float(timeout)
        self._algorithm = algorithm
        self._codec_name = compression
        self._codec = _CODECS[compression]()
        self._chunk_bytes = int(chunk_bytes)
        self._mesh_mgr = mesh_manager or default_mesh_manager()
        # Default data path for allreduce ops ("flat"/"hier"; per-op
        # override rides _Sub.topology). The domain resolver maps the
        # cohort to tier structure at every world>1 configure — cheap
        # cached dict work in process, so even a flat-default context
        # can serve per-op hier ops (the bench's A/B lever).
        self._topology_default = topology
        self._domain_resolver = domain_resolver
        # 2-D mesh declaration: the model-axis extent of each replica
        # group on the fused-step plane (fused.py). The WIRE collectives
        # this context serves stay 1-D (axis-scoped to "replica"), so
        # this is introspection — mesh_shape() — plus plumbing for the
        # fused builders, never a change to the exchange sequence.
        self._model_shards = max(1, int(model_shards))
        self._wire_members: "Optional[List[str]]" = None
        self._configured_members: "Optional[List[str]]" = None
        self._hier_assignment = None
        self._group: Optional[_XlaGroup] = None
        self._seq = 0
        self._generation = 0
        self._error: Optional[Exception] = None
        self._lock = threading.Lock()
        # Data-plane commit votes (set_vote_health / take_commit_vote):
        # same window semantics as TcpCommContext's.
        self._vote_health = None
        self._vote_lock = threading.Lock()
        self._vote_ops = 0
        self._vote_unhealthy = False
        self.metrics = Metrics()
        self.metrics.label("comm_backend", self.backend_name)
        self._events = None  # flight recorder (set_events)

    @classmethod
    def unsupported_reason(cls, algorithm: str, compression: str,
                           op: str = ReduceOp.SUM,
                           topology: str = "flat") -> Optional[str]:
        """THE xla-plane capability rule (CommContext surface): every
        codec runs on star/ring (the bitwise parity paths) for every
        reduce op; the hardware-native ``psum`` path carries every codec
        too (the quantized exchange — EQuARX) but a LOSSY codec only
        accumulates: per-chunk scales cannot ride a max/min reduction,
        so that combo gets a prescriptive error instead of silently
        wrong extrema. ``topology="hier"`` composes the domain tree on
        this plane as star fan-in (the deterministic parity builder) or
        the native grouped-psum exchange — the multi-hop RING inter
        tier is a host-plane arm, refused prescriptively here."""
        if algorithm not in ("auto", "star", "ring", "psum"):
            return f"unknown algorithm {algorithm!r}"
        if compression not in _CODECS:
            return (
                f"unknown compression {compression!r}; have "
                f"{sorted(_CODECS)}"
            )
        if topology not in ("flat", "hier"):
            return (
                f"unknown topology {topology!r}; have 'flat' (one tier "
                "spanning the wire) and 'hier' (domain tree: "
                "reduce-within -> compress -> exchange-across -> "
                "broadcast-within)"
            )
        if topology == "hier" and algorithm == "ring":
            return (
                "topology='hier' with algorithm='ring' is the multi-hop "
                "cross-domain rotation, a host-plane arm (comm_backend="
                "'host'); the xla hier path composes star fan-in or the "
                "native grouped psum — use algorithm='star'/'auto'/"
                "'psum' here, or select the host backend for the ring "
                "inter tier"
            )
        if (
            algorithm == "psum"
            and compression != "none"
            and op not in (ReduceOp.SUM, ReduceOp.AVG)
        ):
            return (
                f"algorithm='psum' with compression={compression!r} "
                "runs the quantized exchange, which only ACCUMULATES "
                f"(sum/avg) — block scales cannot ride op={op!r}. Use "
                "compression='none' for max/min on the psum path, or "
                "the star/ring parity paths (their fused codecs handle "
                "every op)"
            )
        return None

    def mesh_shape(self) -> Tuple[int, int]:
        """(replicas, model_shards): the wire world times the declared
        model-axis extent (CommContext introspection override)."""
        return (self.world_size(), self._model_shards)

    def set_metrics(self, metrics: Metrics) -> None:
        """Share the Manager's sink (same contract as TcpCommContext);
        per-op spans land under the host transport's span names so a
        host-vs-xla A/B compares identical keys, distinguished by the
        ``comm_backend`` label."""
        self.metrics = metrics
        metrics.label("comm_backend", self.backend_name)

    def set_events(self, events) -> None:
        """Share a flight recorder (the Manager's): this context emits
        ``mesh_reconfigure`` at every configure and ``error_latched`` on
        each latch edge; the mesh manager emits ``mesh_compile`` when an
        executable is actually built (first sight of a world size /
        codec / layout combination)."""
        self._events = events
        self._mesh_mgr.events = events

    def set_wire_members(self, members: "Sequence[str]") -> None:
        """Replica ids of the upcoming cohort in transport rank order
        (Manager-fed, pre-configure) — what the domain resolver maps to
        tier structure; ``rank{r}`` names are synthesized without it
        (so ``TORCHFT_TPU_DOMAINS`` maps can address bench ranks)."""
        self._wire_members = [str(m) for m in members]

    def set_domain_resolver(self, resolver) -> None:
        """Install a DomainTopology unless the ctor already provided
        one (explicit wins) — the Manager wires a resolver homed to the
        job's lighthouse ``/status.json`` here, so a managed hier job
        needs zero topology plumbing."""
        if self._domain_resolver is None:
            self._domain_resolver = resolver

    def _resolve_assignment(self):
        """The cohort's DomainAssignment, resolved at most once per
        configure (cached): eagerly for hier-default contexts, lazily
        from the first per-op hier op otherwise."""
        if self._hier_assignment is not None:
            return self._hier_assignment
        members = getattr(self, "_configured_members", None)
        if members is None:
            raise RuntimeError(
                "hier allreduce before configure: the cohort is unknown"
            )
        resolver = self._domain_resolver
        if resolver is None:
            from torchft_tpu.comm.topology import DomainTopology

            resolver = self._domain_resolver = DomainTopology()
        self._hier_assignment = resolver.assign(members)
        return self._hier_assignment

    def _resolved_algorithm(self, world_size: int) -> str:
        if self._algorithm == "auto":
            return "ring" if world_size >= 3 else "star"
        return self._algorithm

    def _resolved_hier_algorithm(self) -> str:
        """The hier path's composition: "psum" stays native (grouped
        psum tiers); everything else — including "auto" at ANY world
        size — is the deterministic star fan-in (the host hier's
        composition, hence the bitwise-parity arm)."""
        return "psum" if self._algorithm == "psum" else "star"

    # ------------------------------------------------------------ lifecycle

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self.shutdown()
        with self._lock:
            self._generation += 1
            self._rank = rank
            self._world_size = world_size
            self._error = None
            self._seq = 0
            generation = self._generation
        with self._vote_lock:
            # votes from a previous membership describe a cohort that no
            # longer exists — never let them commit a step on this one
            self._vote_ops = 0
            self._vote_unhealthy = False
        ev = self._events
        if world_size == 1:
            if ev:
                ev.emit(
                    "mesh_reconfigure", world_size=1,
                    generation=generation, solo=True,
                )
            return  # solo: every op is an identity, no group needed
        # The store address is the cohort-shared rendezvous namespace,
        # exactly as for the host transport: every member of a transport
        # cohort passes the SAME full address (the Manager's trailing
        # segment is the intra-replica rank, identical across the
        # cohort's replica groups — stripping it would merge the
        # per-intra-rank cohorts of a multi-rank replica group into one
        # colliding group). Building the mesh happens here — the
        # step-boundary reconfiguration the quorum drives — and is a
        # cache lookup for any previously-seen world size.
        key = store_addr
        self._mesh_mgr.mesh_for(world_size)
        # Pin the cohort for domain resolution. A hier-DEFAULT context
        # resolves eagerly (the tier structure is this configure's
        # contract, and a live /status.json resolver should pay its
        # walk at the quorum boundary, not mid-op); a flat-default
        # context resolves LAZILY on its first per-op hier op, so flat
        # jobs never touch the resolver at all.
        self._configured_members = (
            self._wire_members
            if self._wire_members is not None
            and len(self._wire_members) == world_size
            else [f"rank{r}" for r in range(world_size)]
        )
        self._hier_assignment = None
        assignment = (
            self._resolve_assignment()
            if self._topology_default == "hier" else None
        )
        group = _XlaGroup.join(key, rank, world_size, self, self._timeout)
        with self._lock:
            self._group = group
        if ev:
            # after the join so a failed rendezvous doesn't record a
            # mesh the context never actually entered
            ev.emit(
                "mesh_reconfigure", world_size=world_size,
                generation=generation,
                algorithm=self._resolved_algorithm(world_size),
            )
            if assignment is not None:
                # configure-rate plan anchor, same as the host plane
                ev.emit(
                    "hier_exchange", world=world_size,
                    domains=assignment.n_domains,
                    egress=list(assignment.egress),
                    domain=assignment.domains[rank],
                    is_egress=assignment.is_egress(rank),
                    fingerprint=assignment.fingerprint,
                )

    def shutdown(self) -> None:
        with self._lock:
            group, self._group = self._group, None
        if group is not None:
            group.leave(self)

    def errored(self) -> Optional[Exception]:
        with self._lock:
            return self._error

    def _latch_error(self, e: Exception) -> None:
        with self._lock:
            first = self._error is None
            if first:
                self._error = e
        if first:
            self._emit_latched(e)

    def _latch_group_error(self, group: "_XlaGroup", e: Exception) -> None:
        """Latch only while this context still belongs to ``group``: a
        stale group's straggler timer or executor firing after the
        context reconfigured into a new quorum epoch must not poison
        the healthy epoch's first op."""
        with self._lock:
            first = self._group is group and self._error is None
            if first:
                self._error = e
        if first:
            self._emit_latched(e)

    def _emit_latched(self, e: Exception) -> None:
        # outside self._lock (the recorder has its own lock; no nesting),
        # on the latch edge only — same contract as the host transport
        ev = self._events
        if ev:
            ev.emit("error_latched", source="xla", error=repr(e)[:200])

    # ------------------------------------------- data-plane commit votes
    # Same surface and window semantics as TcpCommContext's: a voted op
    # proves every cohort member reached the step's collective and
    # reported healthy. On this plane the evidence is the group
    # rendezvous itself — see the vote fold in _XlaGroup._execute.

    def set_vote_health(self, fn) -> None:
        """Install the local health provider (``fn() -> bool``, True =
        healthy) sampled when each gradient op is submitted."""
        self._vote_health = fn

    def _vote_health_bit(self) -> int:
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
        """Aggregate of the votes since the last call: True (>= 1 voted
        op, all healthy), False (any dissent), None (no voted op — the
        caller must run the full commit barrier)."""
        with self._vote_lock:
            ops, bad = self._vote_ops, self._vote_unhealthy
            self._vote_ops = 0
            self._vote_unhealthy = False
        if ops == 0:
            return None
        return not bad

    # ------------------------------------------------- wire introspection

    def wire_codec_name(self) -> str:
        return self._codec_name

    def wire_is_lossy(self) -> bool:
        return self._codec_name != "none"

    def wire_generation(self) -> int:
        with self._lock:
            return self._generation

    def wire_compensable(self) -> bool:
        """Role-aware like the host transport: a star PEER's
        contribution crosses the (emulated) wire through the lossy
        codec (the root's stays raw; ring partial sums ride
        uncompressed) — and on the quantized ``psum`` path EVERY rank's
        contribution is phase-1 encoded before the exchange, so every
        rank is compensable. The EF residual is computed against the
        host ``codec_roundtrip`` image, which the device phase-1 encode
        bit-matches (same grid, same scale math — the convergence-
        oracle discipline)."""
        with self._lock:
            world = self._world_size
            rank = self._rank
        if self._codec_name == "none" or world <= 1:
            return False
        if self._topology_default == "hier":
            # codec bytes exist only on the cross-domain tier: an
            # EGRESS rank's domain sum is what gets encoded. Star
            # fan-in leaves domain 0's sum raw (the inter root), the
            # native grouped psum encodes EVERY domain's sum.
            a = self._hier_assignment
            if a is None or a.n_domains <= 1 or not a.is_egress(rank):
                return False
            if self._resolved_hier_algorithm() == "psum":
                return True
            return a.domain_index(rank) != 0
        algo = self._resolved_algorithm(world)
        return (algo == "star" and rank != 0) or algo == "psum"

    def wire_roundtrip(self, src: np.ndarray, out: np.ndarray) -> None:
        """The host codec IS the device codec bit for bit (pinned by
        tests/test_xla_backend.py), so the error-feedback arena's
        roundtrip runs the cheap numpy implementation — no device
        dispatch on the EF path."""
        if src.shape != out.shape or src.dtype != out.dtype:
            raise ValueError("wire_roundtrip: src/out layout mismatch")
        if not self.wire_compensable():
            np.copyto(out, src)
            return
        codec_roundtrip(self._codec, self._chunk_bytes, src, out)

    def wire_nbytes(self, a: np.ndarray) -> int:
        return codec_wire_nbytes(self._codec, self._chunk_bytes, a)

    # ----------------------------------------------------------- collectives
    # _prepare (the donation-contract input normalization) is inherited
    # from CommContext — one definition for every data plane.

    def _submit(self, opcode: str, arrays: Sequence[np.ndarray], op: str,
                root: int,
                owners: "Optional[Sequence[int]]" = None,
                topology: "Optional[str]" = None) -> Work:
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
            world = self._world_size
            group = self._group
            if world > 1 and group is None:
                fut.set_exception(
                    RuntimeError("comm context not configured")
                )
                return Work(fut)
            self._seq += 1
            seq = self._seq
        grad_op = opcode in ("allreduce", "reduce_scatter")
        if world == 1:
            if grad_op:
                # solo: the op's vote is this rank's own health (same
                # degenerate evidence as the host transport's solo wire)
                self._record_vote(self._vote_health_bit())
            if opcode == "allgather":
                fut.set_result([prepared])
            else:
                fut.set_result(prepared)
            return Work(fut)
        if opcode == "reduce_scatter" and owners is None:
            owners = [i % world for i in range(len(prepared))]
        group.submit(
            self._rank, seq,
            _Sub(
                opcode, prepared, op, root, fut,
                owners=None if owners is None else [int(o) for o in owners],
                topology=topology,
                vote=self._vote_health_bit() if grad_op else 0,
            ),
            self._timeout,
        )
        return Work(fut)

    def allreduce(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        topology: Optional[str] = None,
    ) -> Work:
        if (
            topology is not None
            and topology != self._topology_default
            and self._codec_name != "none"
        ):
            # Same rule as the host plane: EF roles (wire_compensable)
            # follow the DEFAULT topology, so a lossy per-op override
            # would bank residuals against a wire the op never rode.
            fut: Future = Future()
            fut.set_running_or_notify_cancel()
            fut.set_exception(ValueError(
                f"per-op topology={topology!r} differs from this "
                f"context's default {self._topology_default!r} under "
                f"the lossy {self._codec_name!r} codec — construct a "
                f"context with topology={topology!r} for this arm, or "
                "use compression='none' for a per-op A/B (the "
                "error-feedback roles follow the default topology)"
            ))
            return Work(fut)
        return self._submit("allreduce", arrays, op, 0, topology=topology)

    def reduce_scatter(
        self, arrays: Sequence[np.ndarray], op: str = ReduceOp.SUM,
        owners: "Optional[Sequence[int]]" = None,
    ) -> Work:
        """Reduce across ranks, delivering each array's result only to
        its owner (``owners[i]``, default ``i % world_size``) — the host
        transport's reduce_scatter semantics. Parity algorithms reuse
        the allreduce executable (bitwise with the replicated arm);
        ``algorithm='psum'`` with the canonical one-f32-array-per-rank
        layout lowers to ``jax.lax.psum_scatter``."""
        return self._submit("reduce_scatter", arrays, op, 0, owners=owners)

    def allgather(self, arrays: Sequence[np.ndarray]) -> Work:
        return self._submit("allgather", arrays, ReduceOp.SUM, 0)

    def broadcast(self, arrays: Sequence[np.ndarray], root: int = 0) -> Work:
        return self._submit("broadcast", arrays, ReduceOp.SUM, root)
