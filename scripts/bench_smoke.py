#!/usr/bin/env python
"""Metric-surface smoke: in-process rounds, each asserting that one
plane's gauges and events are present and finite — one tiny heal round
(heal_* gauges), one short streaming-DiLoCo round (outer_* gauges), one
xla-backend allreduce round under a forced host device count
(backend-tagged comm_* gauges + comm_backend label,
comm/xla_backend.py), one flight-recorder round (a solo manager's
lifecycle events dumped and converted with to_chrome_trace — fails on
invalid Chrome-trace JSON or missing quorum/step_commit events), and the
sharded, redistribution, fused, fleet, pipeline, fastpath, multi-job and
serve planes' own rounds.

Driven by ``BENCH_SMOKE=1 scripts/test.sh``. The point is that a metric
regression (a renamed key, a gauge that silently stopped being computed,
a pipeline that stopped recording stage timers) fails tier-1-adjacent
tooling loudly. These are counts and presence checks on the CPU, never
times; bench.py itself runs on a TPU only and is not driven from here.
"""

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)  # run as a script: the repo root is not on
# sys.path (heal_smoke imports torchft_tpu in-process)



def heal_smoke() -> "list[str]":
    """One tiny in-process heal round; returns failure strings if the
    heal_* metric surface is missing or non-finite. Runs the REAL
    streaming plane: lazy-staged donor, raw-bytes chunked healer."""
    import math

    import numpy as np

    import jax.numpy as jnp
    from torchft_tpu.checkpointing import CheckpointServer
    from torchft_tpu.utils.metrics import Metrics

    failures = []
    state = {
        "w": jnp.asarray(
            np.random.default_rng(0).standard_normal(1 << 16),
            dtype=jnp.float32,
        ),
        "torchft": {"step": 1},
    }
    donor = CheckpointServer(timeout=30.0)
    healer = CheckpointServer(timeout=30.0, num_chunks=2)
    dm, hm = Metrics(), Metrics()
    donor.set_metrics(dm)
    healer.set_metrics(hm)
    try:
        donor.send_checkpoint([], 1, state, 30.0)
        got = healer.recv_checkpoint(0, donor.metadata(), 1, 30.0)
        donor.disallow_checkpoint()
        if np.asarray(got["w"]).tobytes() != np.asarray(
            state["w"]
        ).tobytes():
            failures.append("heal smoke: healed state not bitwise")
        d, h = dm.snapshot(), hm.snapshot()
        for src, key in (
            (d, "heal_stage_avg_ms"),
            (h, "heal_wire_avg_ms"),
            (h, "heal_wall_ms"),
            (h, "heal_bytes_per_s"),
        ):
            v = src.get(key)
            if v is None or not math.isfinite(float(v)) or v < 0:
                failures.append(
                    f"heal smoke: gauge {key!r} missing/non-finite: {v!r}"
                )
    finally:
        donor.shutdown()
        healer.shutdown()
    return failures


def diloco_smoke() -> "list[str]":
    """One short streaming-DiLoCo round over a real 2-rank loopback
    transport; returns failure strings if the outer-sync metric surface
    (outer_wire_ms / outer_overlap + stage timers) is missing or
    non-finite. Runs the REAL fragment scheduler: staggered boundaries,
    non-blocking wire, staged landings, round commit."""
    import math
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np
    import optax

    import jax.numpy as jnp
    from torchft_tpu.comm import StoreServer, TcpCommContext
    from torchft_tpu.local_sgd import DiLoCo
    # The shared round-surface stub (also drives
    # tests/test_localsgd_streaming.py and scripts/bench_diloco.py).
    from torchft_tpu.comm.wire_stub import WireStubManager as _Stub

    failures = []
    world, sync_every, fragments = 2, 4, 2
    store = StoreServer()
    ctxs = [TcpCommContext(timeout=30.0, algorithm="star", channels=2)
            for _ in range(world)]
    snaps = [None] * world
    committed = [None] * world

    def _worker(rank):
        ctx = ctxs[rank]
        ctx.configure(f"{store.addr}/diloco_smoke", rank, world)
        manager = _Stub(ctx, world)
        wrapper = DiLoCo(manager, optax.sgd(0.7), sync_every=sync_every,
                         num_fragments=fragments, streaming=True)
        rng = np.random.default_rng(0)  # identical init on every rank
        params = wrapper.register({
            "w": jnp.asarray(
                rng.standard_normal(1 << 14).astype(np.float32)
            ),
            "b": jnp.asarray(
                rng.standard_normal(1 << 12).astype(np.float32)
            ),
        })
        for t in range(sync_every):
            # rank-dependent inner movement: the average is the thing
            # being synced, the starting point must agree
            scale = np.float32(0.99 - 0.01 * rank)
            params = {k: params[k] * scale for k in params}
            params = wrapper.step(params)
        committed[rank] = {
            k: np.asarray(v).tobytes() for k, v in params.items()
        }
        snaps[rank] = manager.metrics.snapshot()

    try:
        with ThreadPoolExecutor(max_workers=world) as pool:
            for f in [pool.submit(_worker, r) for r in range(world)]:
                f.result(timeout=120)
    finally:
        for ctx in ctxs:
            ctx.shutdown()
        store.shutdown()

    if committed[0] != committed[1]:
        failures.append("diloco smoke: ranks committed divergent rounds")
    snap = snaps[0] or {}
    for key in ("outer_wire_ms", "outer_overlap", "outer_wire_bytes",
                "outer_d2h_avg_ms", "outer_wire_avg_ms",
                "outer_land_avg_ms"):
        v = snap.get(key)
        if v is None or not math.isfinite(float(v)) or v < 0:
            failures.append(
                f"diloco smoke: gauge {key!r} missing/non-finite: {v!r}"
            )
    return failures


# One in-process xla-backend allreduce round, exec'd in a child so the
# forced host device count lands BEFORE jax initializes (env vars cannot
# retrofit an already-built backend). Prints the backend-tagged gauge
# surface as one JSON line.
_XLA_SMOKE = r"""
import json, sys, threading
import numpy as np
sys.path.insert(0, sys.argv[1])
from torchft_tpu.comm.xla_backend import MeshManager, XlaCommContext

world = 2
mm = MeshManager()
ctxs = [
    XlaCommContext(timeout=30.0, algorithm="star", compression="int8",
                   chunk_bytes=1 << 14, mesh_manager=mm)
    for _ in range(world)
]
errs = []

def worker(rank):
    try:
        ctx = ctxs[rank]
        ctx.configure("xla://smoke", rank, world)
        data = (np.arange(12345, dtype=np.float32) + 1) * (rank + 1)
        ctx.allreduce([data]).future().result(timeout=30)
    except Exception as e:
        errs.append(repr(e))

threads = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=120)
snap = ctxs[0].metrics.snapshot()
print(json.dumps({
    "errors": errs,
    "compile_count": mm.compile_count,
    "gauges": {
        k: snap.get(k)
        for k in ("comm_backend", "comm_chunks", "comm_submit_wire_avg_ms",
                  "comm_wire_reduce_avg_ms", "comm_op_wire_avg_ms")
    },
}))
for c in ctxs:
    c.shutdown()
"""


def xla_smoke() -> "list[str]":
    """One on-device (forced-host-device) xla-backend allreduce round;
    returns failure strings if the round fails or any backend-tagged
    comm_* gauge is missing/non-finite. Extends the PR 3/4/5 smoke-gate
    pattern to the new data plane."""
    import math

    env = {
        k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
    out = None
    try:
        out = subprocess.run(
            [sys.executable, "-c", _XLA_SMOKE, _REPO],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, timeout=240,
        )
        payload = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        # The actual cause (jax import failure, crash before the JSON
        # line) is on the child's stderr — surface it, not just the
        # parse error. TimeoutExpired carries its own .stderr.
        stderr = getattr(e, "stderr", None)
        if stderr is None and out is not None:
            stderr = out.stderr
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        tail = (stderr or "").strip()[-2000:]
        suffix = f"\n  child stderr: {tail}" if tail else ""
        return [f"xla smoke: child failed to produce JSON: {e!r}{suffix}"]
    failures = [f"xla smoke: {e}" for e in payload.get("errors", [])]
    gauges = payload.get("gauges", {})
    if gauges.get("comm_backend") != "xla":
        failures.append(
            "xla smoke: metrics sink not tagged comm_backend='xla': "
            f"{gauges.get('comm_backend')!r}"
        )
    if not payload.get("compile_count"):
        failures.append("xla smoke: no executable was compiled")
    for key in ("comm_chunks", "comm_submit_wire_avg_ms",
                "comm_wire_reduce_avg_ms", "comm_op_wire_avg_ms"):
        v = gauges.get(key)
        if v is None or not math.isfinite(float(v)) or float(v) < 0:
            failures.append(
                f"xla smoke: gauge {key!r} missing/non-finite: {v!r}"
            )
    return failures


# One in-process QUANTIZED-PSUM round (the ISSUE 11 gate), exec'd in a
# child for the forced device count. Three rounds of one layout so the
# compile cache is actually exercised; prints compile/trace counts, the
# encoded-bytes counters, and the numeric error vs the exact f64 sum.
_QPSUM_SMOKE = r"""
import json, sys, threading
import numpy as np
sys.path.insert(0, sys.argv[1])
from torchft_tpu.comm.xla_backend import MeshManager, XlaCommContext

world = 2
mm = MeshManager()
ctxs = [
    XlaCommContext(timeout=30.0, algorithm="psum", compression="int8",
                   chunk_bytes=1 << 20, mesh_manager=mm)
    for _ in range(world)
]
rng = np.random.default_rng(0)
srcs = [
    (rng.standard_normal(1 << 16) * (r + 1)).astype(np.float32)
    for r in range(world)
]
last = [None] * world
errs = []

def worker(rank):
    try:
        ctx = ctxs[rank]
        ctx.configure("xla://qpsum_smoke", rank, world)
        for _ in range(3):
            data = srcs[rank].copy()
            ctx.allreduce([data]).future().result(timeout=60)
        last[rank] = data
    except Exception as e:
        errs.append(repr(e))

threads = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=180)
payload = {"errors": errs, "compile_count": mm.compile_count,
           "trace_count": mm.trace_count}
if not errs:
    exact = np.sum(srcs, axis=0, dtype=np.float64)
    absmax = float(max(np.abs(s).max() for s in srcs))
    payload["max_abs_err"] = float(np.abs(last[0] - exact).max())
    payload["err_bound"] = (world + 1) * absmax / 100.0
    snap = ctxs[0].metrics.snapshot()
    payload["gauges"] = {
        k: snap.get(k)
        for k in ("comm_backend", "comm_encoded_bytes", "comm_raw_bytes")
    }
print(json.dumps(payload))
for c in ctxs:
    c.shutdown()
"""


def quantized_psum_smoke() -> "list[str]":
    """One in-process quantized-psum round under a forced host device
    count: fails on missing/non-finite encoded-bytes gauges, an
    encoded/raw ratio above the int8 envelope (0.3 at the 1MB grid),
    compile_count != 1 across repeated rounds (a retrace storm), or a
    reduction outside the quantization-error bound."""
    import math

    env = {
        k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
    out = None
    try:
        out = subprocess.run(
            [sys.executable, "-c", _QPSUM_SMOKE, _REPO],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, timeout=300,
        )
        payload = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        stderr = getattr(e, "stderr", None)
        if stderr is None and out is not None:
            stderr = out.stderr
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        tail = (stderr or "").strip()[-2000:]
        suffix = f"\n  child stderr: {tail}" if tail else ""
        return [
            f"quantized psum smoke: child failed to produce JSON: "
            f"{e!r}{suffix}"
        ]
    failures = [
        f"quantized psum smoke: {e}" for e in payload.get("errors", [])
    ]
    if failures:
        return failures
    if payload.get("compile_count") != 1 or payload.get("trace_count") != 1:
        failures.append(
            "quantized psum smoke: expected exactly 1 compile/trace for "
            "3 rounds of one layout, got "
            f"compile={payload.get('compile_count')} "
            f"trace={payload.get('trace_count')}"
        )
    gauges = payload.get("gauges", {})
    for key in ("comm_encoded_bytes", "comm_raw_bytes"):
        v = gauges.get(key)
        if v is None or not math.isfinite(float(v)) or float(v) <= 0:
            failures.append(
                f"quantized psum smoke: gauge {key!r} missing/non-finite: "
                f"{v!r}"
            )
    if not failures:
        ratio = float(gauges["comm_encoded_bytes"]) / float(
            gauges["comm_raw_bytes"]
        )
        if ratio > 0.3:
            failures.append(
                "quantized psum smoke: encoded/raw bytes ratio "
                f"{ratio:.4f} > 0.3 — the int8 wire is not compressing"
            )
        err = payload.get("max_abs_err")
        bound = payload.get("err_bound")
        if err is None or not math.isfinite(float(err)) or err > bound:
            failures.append(
                f"quantized psum smoke: reduction error {err!r} outside "
                f"the quantization envelope {bound!r}"
            )
    return failures


# One in-process HIERARCHICAL allreduce round (the ISSUE 13 gate):
# 2 domains x 2 groups over the xla plane under a forced host device
# count, int8 cross-tier. Three rounds of one layout so the (world,
# codec, topology, domain-structure) executable cache is exercised;
# prints compile/trace counts and the per-rank tier counters.
_HIER_SMOKE = r"""
import json, sys, threading
import numpy as np
sys.path.insert(0, sys.argv[1])
from torchft_tpu.comm.topology import DomainTopology
from torchft_tpu.comm.xla_backend import MeshManager, XlaCommContext

world = 4
smap = {"d0": ["rank0", "rank1"], "d1": ["rank2", "rank3"]}
mm = MeshManager()
ctxs = [
    XlaCommContext(timeout=30.0, algorithm="star", compression="int8",
                   chunk_bytes=1 << 14, mesh_manager=mm,
                   topology="hier",
                   domain_resolver=DomainTopology(static_map=smap))
    for _ in range(world)
]
rng = np.random.default_rng(0)
srcs = [
    (rng.standard_normal(1 << 15) * (r + 1)).astype(np.float32)
    for r in range(world)
]
errs = []

def worker(rank):
    try:
        ctx = ctxs[rank]
        ctx.configure("xla://hier_smoke", rank, world)
        for _ in range(3):
            data = srcs[rank].copy()
            ctx.allreduce([data]).future().result(timeout=60)
    except Exception as e:
        errs.append(repr(e))

threads = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=180)
snaps = [c.metrics.snapshot() for c in ctxs]
print(json.dumps({
    "errors": errs, "compile_count": mm.compile_count,
    "trace_count": mm.trace_count,
    "raw_bytes_per_rank": int(srcs[0].nbytes) * 3,
    "tiers": [
        {k: s.get(k)
         for k in ("comm_intra_bytes", "comm_inter_bytes", "comm_hops")}
        for s in snaps
    ],
}))
for c in ctxs:
    c.shutdown()
"""


def hier_smoke() -> "list[str]":
    """One in-process 2-domain x 2-group hierarchical round under a
    forced host device count: fails on missing/non-finite tier counters
    (``comm_intra_bytes``/``comm_inter_bytes``/``comm_hops``), an
    inter/intra byte ratio above the int8 envelope, inter bytes on a
    non-egress rank, or a compile count != 1 across repeated rounds of
    one (world, codec, topology) key."""
    import math

    env = {
        k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    out = None
    try:
        out = subprocess.run(
            [sys.executable, "-c", _HIER_SMOKE, _REPO],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, timeout=300,
        )
        payload = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        stderr = getattr(e, "stderr", None)
        if stderr is None and out is not None:
            stderr = out.stderr
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        tail = (stderr or "").strip()[-2000:]
        suffix = f"\n  child stderr: {tail}" if tail else ""
        return [f"hier smoke: child failed to produce JSON: {e!r}{suffix}"]
    failures = [f"hier smoke: {e}" for e in payload.get("errors", [])]
    if failures:
        return failures
    if payload.get("compile_count") != 1 or payload.get("trace_count") != 1:
        failures.append(
            "hier smoke: expected exactly 1 compile/trace for 3 rounds "
            "of one (world, codec, topology) key, got "
            f"compile={payload.get('compile_count')} "
            f"trace={payload.get('trace_count')}"
        )
    tiers = payload.get("tiers") or []
    raw = float(payload.get("raw_bytes_per_rank") or 0)
    if len(tiers) != 4 or raw <= 0:
        return failures + [
            f"hier smoke: malformed tier payload: {payload!r}"
        ]
    for rank, t in enumerate(tiers):
        for key in ("comm_intra_bytes", "comm_inter_bytes", "comm_hops"):
            v = t.get(key)
            if v is None or not math.isfinite(float(v)) or float(v) < 0:
                failures.append(
                    f"hier smoke: tier counter {key!r} missing/"
                    f"non-finite on rank {rank}: {v!r}"
                )
    if failures:
        return failures
    intra = sum(t["comm_intra_bytes"] for t in tiers)
    inter = sum(t["comm_inter_bytes"] for t in tiers)
    if not intra or inter / intra > 0.3:
        failures.append(
            "hier smoke: inter/intra byte ratio "
            f"{inter}/{intra} above the int8 envelope (0.3) — the "
            "cross-domain tier is not compressing/narrowing"
        )
    for rank in (1, 3):  # non-egress ranks of the 2x2 map
        if tiers[rank]["comm_inter_bytes"] != 0.0:
            failures.append(
                f"hier smoke: non-egress rank {rank} reported inter "
                f"bytes {tiers[rank]['comm_inter_bytes']!r}"
            )
    return failures


def events_smoke() -> "list[str]":
    """One in-process flight-recorder round: a solo Manager over a live
    lighthouse runs two committed steps, its event ring is dumped, and
    ``to_chrome_trace`` must produce valid Chrome-trace JSON containing
    the quorum and step_commit lifecycle — so a renamed event kind, a
    dead emit path, or a broken converter fails this gate loudly."""
    from torchft_tpu.comm.store import StoreServer
    from torchft_tpu.control import Lighthouse
    from torchft_tpu.manager import Manager
    from torchft_tpu.utils.events import (
        to_chrome_trace,
        validate_chrome_trace,
    )

    failures = []
    lighthouse = Lighthouse(min_replicas=1, join_timeout_ms=100)
    store = StoreServer()
    manager = None
    try:
        manager = Manager(
            min_replica_size=1,
            timeout=20.0, quorum_timeout=20.0, connect_timeout=20.0,
            rank=0, world_size=1,
            store_addr=store.addr,
            lighthouse_addr=lighthouse.address(),
            replica_id="events_smoke_",
            heartbeat_interval=0.05,
        )
        import numpy as np

        for _ in range(2):
            manager.start_quorum(allow_heal=False)
            manager.allreduce_arrays(
                [np.ones(8, np.float32)]
            ).future().result(timeout=20)
            if not manager.should_commit():
                failures.append("events smoke: solo step did not commit")
        dump = manager.events.dump()
        kinds = {e["kind"] for e in dump["events"]}
        for want in ("quorum_start", "quorum_complete", "step_commit"):
            if want not in kinds:
                failures.append(
                    f"events smoke: no {want!r} event recorded "
                    f"(have {sorted(kinds)})"
                )
        trace = to_chrome_trace([dump])
        # round-trip through real JSON — the artifact contract
        trace = json.loads(json.dumps(trace))
        problems = validate_chrome_trace(trace)
        failures += [f"events smoke: trace invalid: {p}" for p in problems]
        names = {e.get("name") for e in trace.get("traceEvents", [])}
        for want in ("quorum", "step_commit"):
            if want not in names:
                failures.append(
                    f"events smoke: merged trace missing {want!r} "
                    f"(have {sorted(n for n in names if n)})"
                )
    except Exception as e:  # noqa: BLE001
        failures.append(f"events smoke: round failed: {e!r}")
    finally:
        if manager is not None:
            manager.shutdown(wait=False)
        store.shutdown()
        lighthouse.shutdown()
    return failures


def sharded_smoke() -> "list[str]":
    """One 2-rank sharded step over a real loopback wire; fails on
    missing/non-finite shard gauges (opt_state_bytes /
    opt_update_elems / opt_update span) or a non-committing step —
    the ISSUE 9 byte-accounting surface."""
    import math

    import numpy as np
    import optax

    import jax
    import jax.numpy as jnp
    from torchft_tpu.comm.store import StoreServer
    from torchft_tpu.comm.transport import TcpCommContext
    from torchft_tpu.optim import ShardedOptimizerWrapper
    from torchft_tpu.comm.wire_stub import run_stub_ranks

    failures: "list[str]" = []
    world = 2
    store = StoreServer()
    rng = np.random.default_rng(0)
    params0 = {
        f"w{i}": rng.standard_normal(256 + i).astype(np.float32)
        for i in range(6)
    }

    def _fn(mgr, rank: int) -> dict:
        opt = ShardedOptimizerWrapper(mgr, optax.adam(1e-2), sharded=True)
        params = jax.tree_util.tree_map(jnp.asarray, params0)
        state = opt.init(params)
        mgr.start_quorum()
        grads = jax.tree_util.tree_map(lambda x: x * 0.1, params)
        params, state, ok = opt.step(params, state, grads)
        if not ok:
            raise RuntimeError("sharded step discarded")
        return mgr.metrics.snapshot()

    try:
        snaps = run_stub_ranks(
            store.addr, "sharded_smoke", world, _fn,
            lambda: TcpCommContext(timeout=15.0), timeout=90,
        )
    except Exception as e:  # noqa: BLE001
        store.shutdown()
        return [f"sharded smoke: {e!r}"]
    store.shutdown()
    for rank, snap in enumerate(snaps):
        for key in ("opt_state_bytes", "opt_update_elems",
                    "opt_update_avg_ms"):
            v = snap.get(key)
            if v is None or not math.isfinite(float(v)) or float(v) <= 0:
                failures.append(
                    f"sharded smoke: gauge {key!r} missing/non-finite "
                    f"on rank {rank}: {v!r}"
                )
    return failures


def redist_smoke() -> "list[str]":
    """One in-process w2→w3 grow through the planned redistribution
    exchange (the ISSUE 14 gate): fails on missing/non-finite redist
    gauges, moved_bytes > lower_bound_bytes (the plan over-shipped),
    zero bytes moved (the grow tested nothing), or a plan-cache miss
    on the second identical transition (the spec-pair cache
    regressed)."""
    import copy
    import math

    import numpy as np
    import optax

    import jax
    import jax.numpy as jnp
    from torchft_tpu.comm.redistribute import RedistPlanner
    from torchft_tpu.comm.store import StoreServer
    from torchft_tpu.comm.transport import TcpCommContext
    from torchft_tpu.comm.wire_stub import run_stub_ranks
    from torchft_tpu.optim import ShardedOptimizerWrapper

    failures: "list[str]" = []
    store = StoreServer()
    rng = np.random.default_rng(11)
    params0 = {
        f"w{i}": rng.standard_normal(96 + 8 * i).astype(np.float32)
        for i in range(6)
    }

    def _run(prefix, world, carried=None, planners=None):
        def _fn(mgr, rank):
            opt = ShardedOptimizerWrapper(
                mgr, optax.adam(1e-2), sharded=True,
                planner=None if planners is None else planners[rank],
            )
            params = jax.tree_util.tree_map(jnp.asarray, params0)
            state = (
                copy.deepcopy(carried[rank])
                if carried is not None and carried[rank] is not None
                else opt.init(params)
            )
            mgr.start_quorum()
            grads = jax.tree_util.tree_map(lambda x: x * 0.1, params)
            params, state, ok = opt.step(params, state, grads)
            if not ok:
                raise RuntimeError("redist smoke step discarded")
            return state, mgr.metrics.snapshot()

        return run_stub_ranks(
            store.addr, prefix, world, _fn,
            lambda: TcpCommContext(timeout=15.0), timeout=90,
        )

    try:
        w2 = _run("redist_w2", 2)
        planners = [RedistPlanner() for _ in range(3)]
        carried = [w2[0][0], w2[1][0], None]
        grown = _run("redist_w3a", 3, carried=carried, planners=planners)
        total_moved = 0.0
        for rank, (_, snap) in enumerate(grown):
            for key in ("redist_plan_builds", "redist_moved_bytes",
                        "redist_lower_bound_bytes"):
                v = snap.get(key)
                if v is None or not math.isfinite(float(v)) or v < 0:
                    failures.append(
                        f"redist smoke: gauge {key!r} missing/non-finite "
                        f"on rank {rank}: {v!r}"
                    )
            moved = float(snap.get("redist_moved_bytes") or 0)
            lower = float(snap.get("redist_lower_bound_bytes") or 0)
            if moved != lower:
                failures.append(
                    f"redist smoke: rank {rank} moved {moved} != lower "
                    f"bound {lower} — the planned exchange over-shipped"
                )
            total_moved += moved
        if not failures and total_moved <= 0:
            failures.append(
                "redist smoke: the w2→w3 grow moved zero bytes — the "
                "transition exercised nothing"
            )
        builds_first = [p.builds for p in planners]
        _run("redist_w3b", 3, carried=carried, planners=planners)
        for rank, p in enumerate(planners):
            if p.builds != builds_first[rank]:
                failures.append(
                    f"redist smoke: rank {rank} recompiled a seen spec "
                    f"pair on the second identical transition "
                    f"(builds {builds_first[rank]} -> {p.builds})"
                )
    except Exception as e:  # noqa: BLE001
        failures.append(f"redist smoke: {e!r}")
    finally:
        store.shutdown()
    return failures


_FUSED_SMOKE = r"""
import json, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
import jax.numpy as jnp
import optax
from torchft_tpu.comm.xla_backend import MeshManager
from torchft_tpu.fused import FusedStepEngine
from torchft_tpu.utils.metrics import Metrics

rng = np.random.default_rng(5)
params = rng.standard_normal(777).astype(np.float32)

def loss_fn(w, b):
    return 0.5 * jnp.sum((w - jnp.mean(b)) ** 2)

def mk(mm):
    return FusedStepEngine(
        mm, 2, 2, params, 8, loss_fn,
        optax.sgd(0.05, momentum=0.9), codec="int8",
        chunk_bytes=256, metrics=Metrics(),
    )

payload = {"errors": []}
try:
    mm = MeshManager()
    fused, staged = mk(mm), mk(mm)
    batch = rng.standard_normal((4, 8)).astype(np.float32)
    lf = fused.step_fused(batch)
    ls = staged.step_staged(batch)
    payload["loss_fused"] = float(lf)
    payload["loss_staged"] = float(ls)
    payload["bitwise"] = fused.digest() == staged.digest()
    payload["counters"] = fused.counters()
    compiles_seen = mm.compile_count
    fused.step_fused(rng.standard_normal((4, 8)).astype(np.float32))
    payload["compiles_seen_shape_delta"] = mm.compile_count - compiles_seen
except Exception as e:
    payload["errors"].append(repr(e))
print(json.dumps(payload))
"""


def fused_smoke() -> "list[str]":
    """One in-process 2x2 forced-host-device fused step round (the
    ISSUE 16 gate): fails on step_dispatch_count != 1, host hops != 0,
    missing/non-finite loss gauges, compile growth on a repeated mesh
    shape, or a staged<->fused bitwise mismatch."""
    import math

    env = {
        k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    out = None
    try:
        out = subprocess.run(
            [sys.executable, "-c", _FUSED_SMOKE, _REPO],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, timeout=300,
        )
        payload = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        stderr = getattr(e, "stderr", None)
        if stderr is None and out is not None:
            stderr = out.stderr
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        tail = (stderr or "").strip()[-2000:]
        suffix = f"\n  child stderr: {tail}" if tail else ""
        return [
            f"fused smoke: child failed to produce JSON: {e!r}{suffix}"
        ]
    failures = [f"fused smoke: {e}" for e in payload.get("errors", [])]
    if failures:
        return failures
    c = payload.get("counters", {})
    if c.get("step_dispatch_count") != 1:
        failures.append(
            "fused smoke: fused step must be exactly ONE dispatch, got "
            f"{c.get('step_dispatch_count')!r}"
        )
    if c.get("step_host_hops") != 0:
        failures.append(
            f"fused smoke: fused step hopped the host "
            f"{c.get('step_host_hops')!r} times (expected 0)"
        )
    if c.get("step_executable_count") != 1 or c.get("mesh_shape") != "2x2":
        failures.append(
            "fused smoke: executable gauge/mesh label wrong: "
            f"executables={c.get('step_executable_count')!r} "
            f"mesh={c.get('mesh_shape')!r}"
        )
    for key in ("loss_fused", "loss_staged"):
        v = payload.get(key)
        if v is None or not math.isfinite(float(v)):
            failures.append(
                f"fused smoke: gauge {key!r} missing/non-finite: {v!r}"
            )
    if payload.get("compiles_seen_shape_delta") != 0:
        failures.append(
            "fused smoke: a second step at a SEEN mesh shape compiled "
            f"{payload.get('compiles_seen_shape_delta')!r} more "
            "executables (expected a pure cache lookup)"
        )
    if payload.get("bitwise") is not True:
        failures.append(
            "fused smoke: staged and fused arms diverged bitwise on the "
            "same batch"
        )
    return failures


def fleet_smoke() -> "list[str]":
    """One in-process 32-group control-plane sweep point (the ISSUE 10
    gate): real HTTP against a live cached-quorum lighthouse plus the
    incremental-vs-kernel decision replay. Fails on missing/non-finite
    quorum_ms, a missing recompute counter surface, a liveness-oracle
    miss, or ANY cached-vs-recompute decision mismatch."""
    import math

    sys.path.insert(0, os.path.join(_REPO, "scripts"))
    import bench_fleet

    failures: "list[str]" = []
    try:
        orc = bench_fleet.oracle_replay(32)
        if orc["mismatches"]:
            failures.append(
                f"fleet smoke: {orc['mismatches']}/{orc['checks']} "
                "incremental-vs-kernel decision mismatches"
            )
        if orc["counters"].get("cache_hits", 0) <= 0:
            failures.append(
                "fleet smoke: incremental plane recorded zero cache hits "
                "over a steady-heartbeat replay — epoch cache regressed"
            )
        row = bench_fleet.run_point(32, cache_quorum=True, hb_ticks=3)
    except Exception as e:  # noqa: BLE001
        return [f"fleet smoke: sweep point failed: {e!r}"]
    for key in ("quorum_ms", "quorum2_ms"):
        v = row.get(key)
        if v is None or not math.isfinite(float(v)) or float(v) <= 0:
            failures.append(
                f"fleet smoke: {key!r} missing/non-finite: {v!r}"
            )
    total = row.get("total") or {}
    for key in ("quorum_compute_count", "quorum_cache_hits",
                "heartbeat_rpcs", "membership_epoch"):
        if not isinstance(total.get(key), int):
            failures.append(
                f"fleet smoke: control counter {key!r} missing: "
                f"{total.get(key)!r}"
            )
    if not row.get("responses_identical"):
        failures.append(
            "fleet smoke: quorum responses diverged across groups"
        )
    st = row.get("steady") or {}
    if not st.get("all_healthy"):
        failures.append(
            "fleet smoke: liveness oracle failed — parked/batched groups "
            f"went unhealthy ({st.get('healthy')}/32)"
        )
    if st.get("status_poll_compute_delta", 1) != 0:
        failures.append(
            "fleet smoke: cached plane recomputed on membership-stable "
            f"status polls ({st.get('status_poll_compute_delta')} times) "
            "— the epoch cache is not serving"
        )
    return failures


def pipeline_smoke() -> "list[str]":
    """One in-process 2-stage x 4-microbatch pipeline round per
    schedule arm; returns failure strings if any ``pipe_*`` gauge is
    missing/non-finite or the pipelined step is not bitwise-identical
    to the stage-serial one (the MPMD plane's correctness oracle)."""
    import math

    import torchft_tpu.pipeline as P

    failures: "list[str]" = []
    hashes = {}
    snaps = {}
    for arm, streaming in (("1f1b", True), ("serial", False)):
        pipe = P.Pipeline(P.PipelineConfig(
            num_stages=2, replicas=1, microbatches=4,
            step_timeout=60.0, streaming=streaming,
        ))
        try:
            r = pipe.run_step()
            if r["aborted"] or r["killed"]:
                failures.append(f"pipeline smoke: {arm} step failed: {r}")
            hashes[arm] = pipe.global_param_hash()
            snaps[arm] = pipe.metrics_snapshots()
        finally:
            pipe.close()
    if failures:
        return failures
    if hashes["1f1b"] != hashes["serial"]:
        failures.append(
            "pipeline smoke: pipelined step not bitwise with the "
            "stage-serial arm"
        )
    for rid, snap in snaps["1f1b"].items():
        for key in ("pipe_inflight", "pipe_stage_index",
                    "pipe_stage_count", "pipe_bubble_steps",
                    "pipe_sched_ticks", "microbatch_send",
                    "microbatch_recv"):
            v = snap.get(key)
            if v is None or not math.isfinite(float(v)) or float(v) < 0:
                failures.append(
                    f"pipeline smoke: {rid} gauge {key!r} "
                    f"missing/non-finite: {v!r}"
                )
    return failures


def fastpath_smoke() -> "list[str]":
    """Steady-state fast path (ISSUE 18), in-process: a solo Manager over
    a lease-granting lighthouse steps until the lease arms, then every
    further committed step must issue EXACTLY 0 control RPCs; the
    fastpath/fallback/lease counters must exist and be finite; and an
    injected error mid-lease must NOT commit (the full-barrier fallback
    is the only path that may decide a faulted step)."""
    import math

    import numpy as np

    from torchft_tpu.comm.store import StoreServer
    from torchft_tpu.control import Lighthouse
    from torchft_tpu.manager import Manager

    failures: "list[str]" = []
    lighthouse = Lighthouse(
        min_replicas=1, join_timeout_ms=100, quorum_tick_ms=10,
        lease_ms=2000,
    )
    store = StoreServer()
    manager = None
    try:
        manager = Manager(
            min_replica_size=1,
            timeout=20.0, quorum_timeout=20.0, connect_timeout=20.0,
            rank=0, world_size=1,
            store_addr=store.addr,
            lighthouse_addr=lighthouse.address(),
            replica_id="fastpath_smoke_",
            heartbeat_interval=0.05,
            use_async_quorum=False,
        )

        def _step() -> bool:
            manager.start_quorum(allow_heal=False)
            manager.allreduce_arrays(
                [np.ones(8, np.float32)]
            ).future().result(timeout=20)
            return manager.should_commit()

        # step 0 arms the lease through the full path; steps 1-4 must be
        # zero-RPC steady state
        for i in range(5):
            if not _step():
                failures.append(f"fastpath smoke: step {i} did not commit")
            elif i >= 1 and manager._control_rpcs != 0:
                failures.append(
                    f"fastpath smoke: steady-state step {i} issued "
                    f"{manager._control_rpcs} control RPCs (want 0)"
                )
        snap = manager.metrics.snapshot()
        for key in ("fastpath_steps", "fallback_steps", "lease_grants",
                    "control_rpcs_per_step"):
            v = snap.get(key)
            if v is None or not math.isfinite(float(v)) or float(v) < 0:
                failures.append(
                    f"fastpath smoke: counter {key!r} "
                    f"missing/non-finite: {v!r}"
                )
        if float(snap.get("fastpath_steps") or 0) < 4:
            failures.append(
                "fastpath smoke: expected >= 4 fastpath steps, got "
                f"{snap.get('fastpath_steps')!r}"
            )
        # injected error mid-lease: must discard, never fast-commit
        manager.start_quorum(allow_heal=False)
        manager.report_error(RuntimeError("fastpath_smoke injected"))
        if manager.should_commit():
            failures.append(
                "fastpath smoke: step with an injected error COMMITTED"
            )
        if manager._lease_valid():
            failures.append(
                "fastpath smoke: latch edge did not break the lease"
            )
    except Exception as e:  # noqa: BLE001
        failures.append(f"fastpath smoke: round failed: {e!r}")
    finally:
        if manager is not None:
            manager.shutdown(wait=False)
        store.shutdown()
        lighthouse.shutdown()
    return failures


def multijob_smoke() -> "list[str]":
    """Multi-tenant control plane (ISSUE 19), in-process, three gates:

    1. **interference oracle**: two jobs behind ONE lighthouse; a churn
       storm in job A must leave job B at exactly 0 recomputes, 0 epoch
       moves and 0 lease breaks (bench_fleet's multijob point).
    2. **prescriptive preemption**: with ``fleet_capacity`` exhausted, a
       higher-priority join evicts exactly one group from the
       over-budget low-priority job, and the evicted member learns it
       from the decision body (an immediate ``evicted: true`` answer),
       never by timeout.
    3. **planner-lower-bound shrink**: the victim job's live w3→w2
       shrink rides the planned redistribution exchange with
       ``redist_moved_bytes == redist_lower_bound_bytes`` on every
       surviving rank (and a non-zero total — the shrink moved real
       state)."""
    import copy
    import math

    sys.path.insert(0, os.path.join(_REPO, "scripts"))
    import bench_fleet

    from torchft_tpu.control import Lighthouse, LighthouseClient

    failures: "list[str]" = []

    # -- 1. cross-job interference ------------------------------------
    try:
        row = bench_fleet.run_multijob_point(
            2, 2, cache_quorum=True, storm_rounds=2
        )
        failures += [
            f"multijob smoke: {f}" for f in row["oracle_failures"]
        ]
    except Exception as e:  # noqa: BLE001
        failures.append(f"multijob smoke: interference point failed: {e!r}")

    # -- 2. priority preemption over capacity -------------------------
    lh = Lighthouse(
        min_replicas=1, join_timeout_ms=100, quorum_tick_ms=10,
        heartbeat_timeout_ms=30000, fleet_capacity=3,
    )
    try:
        addr = lh.address()
        client = LighthouseClient(addr)
        client.register_job("lo", priority=0, group_budget=2)
        client.register_job("hi", priority=10)
        bench_fleet._form_round(
            addr, "lo", [f"lo_{i:02d}" for i in range(3)], 0, 30.0
        )
        bench_fleet._form_round(addr, "hi", ["hi_00"], 0, 30.0)
        status = bench_fleet._status(addr)
        jobs = status.get("jobs") or {}
        lo = jobs.get("lo") or {}
        if lo.get("preemptions") != 1:
            failures.append(
                "multijob smoke: expected exactly 1 preemption in the "
                f"low job, got {lo.get('preemptions')!r}"
            )
        if lo.get("evicted") != ["lo_02"]:
            failures.append(
                "multijob smoke: expected lo_02 (max id, minimal "
                f"eviction) evicted, got {lo.get('evicted')!r}"
            )
        if (jobs.get("hi") or {}).get("healthy") != 1:
            failures.append(
                "multijob smoke: high-priority job did not seat its "
                f"group: {jobs.get('hi')!r}"
            )
        # prescriptive, not by timeout: the evicted member's next quorum
        # request is answered immediately with the eviction in the body
        t0 = time.perf_counter()
        resp = client.quorum(
            bench_fleet._jmember("lo", 2, step=1), timeout=30.0,
            job_id="lo",
        )
        answer_ms = (time.perf_counter() - t0) * 1e3
        if resp.get("evicted") is not True:
            failures.append(
                "multijob smoke: evicted member's quorum answer lacks "
                f"the prescriptive eviction: {resp!r}"
            )
        if answer_ms > 5000:
            failures.append(
                "multijob smoke: eviction answer took "
                f"{answer_ms:.0f}ms — that is a timeout, not a decision"
            )
    except Exception as e:  # noqa: BLE001
        failures.append(f"multijob smoke: preemption gate failed: {e!r}")
    finally:
        lh.shutdown()

    # -- 3. victim shrink at the planner lower bound ------------------
    import numpy as np
    import optax

    import jax
    import jax.numpy as jnp
    from torchft_tpu.comm.store import StoreServer
    from torchft_tpu.comm.transport import TcpCommContext
    from torchft_tpu.comm.wire_stub import run_stub_ranks
    from torchft_tpu.optim import ShardedOptimizerWrapper

    store = StoreServer()
    rng = np.random.default_rng(19)
    params0 = {
        f"w{i}": rng.standard_normal(96 + 8 * i).astype(np.float32)
        for i in range(6)
    }

    def _run(prefix, world, carried=None):
        def _fn(mgr, rank):
            opt = ShardedOptimizerWrapper(mgr, optax.adam(1e-2),
                                          sharded=True)
            params = jax.tree_util.tree_map(jnp.asarray, params0)
            state = (
                copy.deepcopy(carried[rank])
                if carried is not None and carried[rank] is not None
                else opt.init(params)
            )
            mgr.start_quorum()
            grads = jax.tree_util.tree_map(lambda x: x * 0.1, params)
            params, state, ok = opt.step(params, state, grads)
            if not ok:
                raise RuntimeError("multijob smoke step discarded")
            return state, mgr.metrics.snapshot()

        return run_stub_ranks(
            store.addr, prefix, world, _fn,
            lambda: TcpCommContext(timeout=15.0), timeout=90,
        )

    try:
        w3 = _run("multijob_w3", 3)
        shrunk = _run(
            "multijob_w2", 2, carried=[w3[0][0], w3[1][0]]
        )
        total_moved = 0.0
        for rank, (_, snap) in enumerate(shrunk):
            moved = snap.get("redist_moved_bytes")
            lower = snap.get("redist_lower_bound_bytes")
            if (moved is None or lower is None
                    or not math.isfinite(float(moved))):
                failures.append(
                    f"multijob smoke: shrink rank {rank} redist gauges "
                    f"missing: moved={moved!r} lower={lower!r}"
                )
                continue
            if float(moved) != float(lower):
                failures.append(
                    f"multijob smoke: shrink rank {rank} moved {moved} "
                    f"!= lower bound {lower} — the victim's shrink "
                    "over-shipped"
                )
            total_moved += float(moved)
        if not failures and total_moved <= 0:
            failures.append(
                "multijob smoke: the w3→w2 victim shrink moved zero "
                "bytes — the transition exercised nothing"
            )
    except Exception as e:  # noqa: BLE001
        failures.append(f"multijob smoke: shrink gate failed: {e!r}")
    finally:
        store.shutdown()
    return failures


def serve_smoke() -> "list[str]":
    """One in-process train→serve adoption round (the ISSUE 20 gate):
    a DeployPublisher stages two committed versions, a replication-2
    cohort adopts both through the planner-compiled deploy plane, and
    inference requests are answered between them. Fails on
    missing/non-finite ``deploy_*``/``serve_*`` gauges, a per-member
    byte count off the planner's lower bound (the deploy over-shipped
    or full-fetched), a member left behind the published version, or
    ANY dropped / stale-read request."""
    import math

    import numpy as np

    from torchft_tpu.serve import DeployPublisher, ServeCohort

    failures: "list[str]" = []
    rng = np.random.default_rng(20)
    pub = DeployPublisher()
    cohort = ServeCohort(2, replication=2)
    try:
        for version in (1, 2):
            leaves = [
                (rng.standard_normal(512 + 32 * i) * version).astype(
                    np.float32
                )
                for i in range(6)
            ]
            unit_bytes = [int(a.nbytes) for a in leaves]
            pre = [
                (m.metrics.snapshot().get("deploy_bytes_moved", 0.0) or 0.0)
                for m in cohort.members
            ]
            addr = pub.publish(version, leaves)
            cohort.deploy(version, [addr], unit_bytes)
            for m, pm in zip(cohort.members, pre):
                snap = m.metrics.snapshot()
                moved = (snap.get("deploy_bytes_moved", 0.0) or 0.0) - pm
                lower = snap.get("deploy_lower_bound_bytes")
                if moved <= 0 or float(snap.get(
                        "deploy_bytes_moved") or 0) != float(lower or -1):
                    failures.append(
                        f"serve smoke: v{version} member moved {moved} "
                        f"(cumulative lower bound {lower!r}) — not the "
                        "planner minimum"
                    )
                for key in ("deploy_wall_ms", "serve_version",
                            "serve_version_lag", "deploy_adoptions"):
                    v = snap.get(key)
                    if v is None or not math.isfinite(float(v)) or v < 0:
                        failures.append(
                            f"serve smoke: gauge {key!r} missing/"
                            f"non-finite: {v!r}"
                        )
            for u in range(len(leaves)):
                got_v, val = cohort.answer(u, 1.0)
                if got_v != version:
                    failures.append(
                        f"serve smoke: unit {u} answered at version "
                        f"{got_v} after deploy of {version}"
                    )
                elif not math.isfinite(val):
                    failures.append(
                        f"serve smoke: unit {u} answered non-finite {val!r}"
                    )
        rsnap = cohort.metrics.snapshot()
        for key in ("serve_dropped", "serve_stale_reads"):
            total = float(rsnap.get(key) or 0) + sum(
                float(m.metrics.snapshot().get(key) or 0)
                for m in cohort.members
            )
            if total != 0:
                failures.append(
                    f"serve smoke: {key} = {total} across the round "
                    "(must be exactly 0)"
                )
    except Exception as e:  # noqa: BLE001
        failures.append(f"serve smoke: round failed: {e!r}")
    finally:
        cohort.shutdown()
        pub.close()
    return failures


def main() -> int:
    failures = heal_smoke()
    failures += diloco_smoke()
    failures += xla_smoke()
    failures += quantized_psum_smoke()
    failures += hier_smoke()
    failures += events_smoke()
    failures += sharded_smoke()
    failures += redist_smoke()
    failures += fused_smoke()
    failures += fleet_smoke()
    failures += pipeline_smoke()
    failures += fastpath_smoke()
    failures += multijob_smoke()
    failures += serve_smoke()
    if failures:
        print("bench smoke FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print(
        "bench smoke OK: "
        "heal_gauges=ok outer_gauges=ok xla_gauges=ok qpsum_gauges=ok "
        "hier_gauges=ok chrome_trace=ok sharded_gauges=ok "
        "redist_gauges=ok fused_gauges=ok fleet_gauges=ok "
        "pipe_gauges=ok multijob=ok serve=ok"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
