#!/usr/bin/env python
"""Loopback transport microbenchmark: allreduce latency vs payload size.

Gives the DCN allreduce a trajectory independent of the full bench.py run:
one PROCESS per rank (like production — one trainer process per host), a
real StoreServer rendezvous, real TCP sockets over loopback — the same
code path bench.py's t1_overhead_ms allreduce numbers come from, minus
jax and the manager. Sweeps payload size × {star, ring} × channels and
prints ONE JSON line so CI can diff runs.

Ranks were threads in one process through r06; that shares a single GIL
across every "rank", so the measurement was dominated by GIL handoffs
between lane/rank threads (observed 3x swings) rather than transport
behavior. Worker processes each carry their own interpreter, matching
the deployment topology.

    python scripts/bench_transport.py            # CI-sized
    python scripts/bench_transport.py --full     # adds 32MB payloads
    python scripts/bench_transport.py --stripe-sweep   # chunk x lanes x codec
    python scripts/bench_transport.py --overlap-ab 5   # serial vs streamed
                                                       # multi-bucket schedule
    python scripts/bench_transport.py --backend xla    # sweep the on-device
                                                       # backend instead
    python scripts/bench_transport.py --backend-ab 3   # host vs xla,
                                                       # rep-interleaved
    python scripts/bench_transport.py --backend-ab 3 --codec int8
                             # + the quantized-psum arm: quant vs raw
                             # psum with an encoded-bytes-on-wire oracle

--backend-ab runs the host (socket) and xla (on-device jax.lax,
comm/xla_backend.py) data planes against identical seeded payloads,
alternated rep-for-rep, with a BITWISE oracle every rep: both arms must
produce byte-identical reduced results for every codec at the same
chunk grid, or the run fails. Adding --codec restricts the codec grid
AND appends the quantized-psum sweep arm (xla-only — the shared
capability query says the host plane has no psum): quantized vs raw
psum, rep-interleaved, graded by the comm_encoded_bytes/comm_raw_bytes
counters (int8 must be <= 0.3x raw at the 1MB grid), a numeric
envelope vs the exact f64 sum (psum cannot enter the bitwise oracle —
XLA owns its reduction order), and a 1-compile-per-child pin. Both arms use the SAME harness — one
process per cell, one thread per rank (the xla group is in-process by
construction) — so cells are comparable to each other but NOT to the
process-per-rank cells above: the host arm's rank threads share a GIL
(the r06 convoy effect), while the xla arm's compiled collective
releases it. On the 2-core CPU sandbox the xla arm also pays device_put
staging of every rank's contribution through one host — the ICI win
this backend exists for is structurally invisible here; the evidence
README carries the honest-null note.

With chunk striping (PR 2) a single op rides ALL lanes, so channels>1
changes single-op latency, not just multi-op overlap. `gbps` is the
aggregate goodput 2*payload*(n-1)/n per link equivalent — comparable
across runs on the same host, not an absolute wire number.

--stripe-sweep grids chunk size x channels x codec at a fixed payload
(default 8MB, --sweep-payload-mb to change) for star w2 and ring w3, and
reports per-cell `lane_balance` (max/mean of the per-lane wire_reduce
averages — 1.0 is perfectly balanced). Add --ab-baseline PATH (a
checkout of the pre-striping tree) to interleave baseline cells into the
same artifact: baseline and current cells alternate within one run, so
host drift between rounds cannot fake a win. Evidence for the striping
PR lives under docs/evidence/bench_transport_stripe_*.json.
"""

from __future__ import annotations

import argparse
import json
import math
import subprocess
import sys
import time
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))

from torchft_tpu.comm import StoreServer  # noqa: E402

# Rank worker, exec'd as `python -c` so a baseline tree's transport can be
# measured by inserting THAT tree on sys.path — no imports leak between
# versions. Prints one JSON line (rank 0: latencies + lane balance).
_WORKER = r"""
import json, sys, time
spec = json.loads(sys.argv[1])
sys.path.insert(0, spec["tree"])
import numpy as np
from torchft_tpu.comm.transport import TcpCommContext

ctx = TcpCommContext(
    timeout=30.0, algorithm=spec["algorithm"], channels=spec["channels"],
    **spec["extra"],
)
ctx.configure(spec["store"], spec["rank"], spec["world"])
# buckets > 1 splits the payload into equal per-bucket arrays (the DDP
# bucket shape); mode picks the submission schedule — "serial" waits each
# bucket out before submitting the next (the lock-step step loop's wire
# shape), "streamed" keeps every bucket in flight at once (the streamed
# step pipeline's wire shape). buckets=1 is the classic single-op cell
# and both modes coincide.
buckets = int(spec.get("buckets", 1))
mode = spec.get("mode", "streamed")
elems = spec["nbytes"] // 4 // buckets
datas = [np.empty(elems, dtype=np.float32) for _ in range(buckets)]
fill = np.float32(spec["rank"] + 1)
lat = []
for i in range(spec["warmup"] + spec["iters"]):
    # allreduce reduces IN PLACE (donation contract): refill each
    # iteration outside the timed region, mirroring the DDP arena pack.
    for data in datas:
        data.fill(fill)
    t0 = time.perf_counter()
    if mode == "serial":
        for data in datas:
            ctx.allreduce([data]).future().result(timeout=30)
    else:
        works = [ctx.allreduce([data]) for data in datas]
        for w in works:
            w.future().result(timeout=30)
    if spec["rank"] == 0 and i >= spec["warmup"]:
        lat.append(time.perf_counter() - t0)
if spec["rank"] == 0:
    snap = ctx.metrics.snapshot()
    lanes = [
        v for k, v in snap.items()
        if k.startswith("comm_l") and k.endswith("_wire_reduce_avg_ms")
    ]
    balance = (
        max(lanes) / (sum(lanes) / len(lanes))
        if len(lanes) >= 2 and any(lanes) else None
    )
    print(json.dumps({"lat": lat, "lane_balance": balance}))
ctx.shutdown()
"""

# Thread-per-rank worker for --backend/--backend-ab cells: ONE process
# hosts the whole cohort (the xla group's single-process rendezvous
# requires it; the host arm uses the same shape so the A/B harness is
# identical). Prints one JSON line: rank-0 cohort latencies + a sha256
# of rank 0's reduced bytes after the last iteration — the bitwise
# oracle the driver compares across arms.
_THREAD_WORKER = r"""
import hashlib, json, sys, threading, time
spec = json.loads(sys.argv[1])
sys.path.insert(0, spec["tree"])
import numpy as np

backend = spec["backend"]
world = spec["world"]
kw = dict(timeout=60.0, algorithm=spec["algorithm"],
          chunk_bytes=spec["chunk_bytes"],
          compression=spec["compression"])
if backend == "xla":
    from torchft_tpu.comm.xla_backend import XlaCommContext
    ctxs = [XlaCommContext(**kw) for _ in range(world)]
    addr_of = lambda r: "xla://%s" % spec["cell"]
else:
    from torchft_tpu.comm.transport import TcpCommContext
    ctxs = [TcpCommContext(channels=spec["channels"], **kw)
            for _ in range(world)]
    addr_of = lambda r: spec["store"]

elems = spec["nbytes"] // 4
srcs = [
    np.random.default_rng(spec["seed"] + r)
    .standard_normal(elems).astype(np.float32)
    for r in range(world)
]
datas = [np.empty(elems, dtype=np.float32) for _ in range(world)]
barrier = threading.Barrier(world)
lat = []
digest = [None]
errs = []

def worker(rank):
    try:
        ctx = ctxs[rank]
        ctx.configure(addr_of(rank), rank, world)
        for i in range(spec["warmup"] + spec["iters"]):
            np.copyto(datas[rank], srcs[rank])  # donation refill,
            barrier.wait()                      # outside the window
            if rank == 0:
                t0 = time.perf_counter()
            ctx.allreduce([datas[rank]]).future().result(timeout=60)
            barrier.wait()
            if rank == 0 and i >= spec["warmup"]:
                lat.append(time.perf_counter() - t0)
        if rank == 0:
            digest[0] = hashlib.sha256(datas[0].tobytes()).hexdigest()
    except Exception as e:
        errs.append("rank %d: %r" % (rank, e))
        try:
            barrier.abort()
        except Exception:
            pass

threads = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=600)
if errs:
    print(json.dumps({"error": "; ".join(errs)}))
    sys.exit(1)
snap = ctxs[0].metrics.snapshot()
payload = {
    "lat": lat, "digest": digest[0],
    "comm_backend": snap.get("comm_backend"),
    "comm_op_wire_avg_ms": snap.get("comm_op_wire_avg_ms"),
    # bytes-on-wire counters (one rank's cumulative raw vs encoded
    # contributions) — the --codec sweep's compression oracle
    "comm_encoded_bytes": snap.get("comm_encoded_bytes"),
    "comm_raw_bytes": snap.get("comm_raw_bytes"),
}
if backend == "xla":
    from torchft_tpu.comm.xla_backend import default_mesh_manager
    payload["compile_count"] = default_mesh_manager().compile_count
if spec.get("check_numeric"):
    # numeric oracle for order-free paths (psum): rank 0's reduced
    # bytes vs the exact f64 sum of the seeded inputs
    exact = np.sum([s.astype(np.float64) for s in srcs], axis=0)
    payload["max_abs_err"] = float(np.max(np.abs(datas[0] - exact)))
    payload["absmax"] = float(max(np.abs(s).max() for s in srcs))
print(json.dumps(payload))
for c in ctxs:
    c.shutdown()
"""

_CELL_SEQ = [0]


def _percentiles(vals):
    vals = sorted(vals)
    n = len(vals)
    return {
        "avg_ms": sum(vals) / n * 1e3,
        "p50_ms": vals[n // 2] * 1e3,
        "p95_ms": vals[max(0, math.ceil(n * 0.95) - 1)] * 1e3,
        "max_ms": vals[-1] * 1e3,
    }


def _bench_config(store, algorithm, world, channels, nbytes, iters, warmup,
                  tree=None, buckets=1, mode="streamed", **extra):
    """One (tree, algorithm, world, channels, extra-ctx-kwargs) cell;
    returns rank-0 latency percentiles + lane balance. ``buckets``/
    ``mode`` select the multi-bucket submission schedule (--overlap-ab);
    the defaults reproduce the classic single-op cell."""
    _CELL_SEQ[0] += 1
    prefix = f"bt{_CELL_SEQ[0]}"
    procs = []
    for rank in range(world):
        spec = {
            "tree": str(tree or _REPO),
            "store": f"{store.addr}/{prefix}",
            "rank": rank, "world": world,
            "algorithm": algorithm, "channels": channels,
            "nbytes": nbytes, "iters": iters, "warmup": warmup,
            "buckets": buckets, "mode": mode,
            "extra": extra,
        }
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER, json.dumps(spec)],
            stdout=subprocess.PIPE if rank == 0 else subprocess.DEVNULL,
        ))
    out, _ = procs[0].communicate(timeout=300)
    for p in procs[1:]:
        p.wait(timeout=60)
    if procs[0].returncode != 0:
        raise RuntimeError(f"cell {prefix} rank 0 failed")
    payload = json.loads(out.decode().strip().splitlines()[-1])
    res = _percentiles(payload["lat"])
    balance = payload.get("lane_balance")
    res["lane_balance"] = None if balance is None else round(balance, 3)
    return res


def _finish_cell(res, nbytes, **tags) -> dict:
    cell = {
        **tags,
        "payload_bytes": nbytes,
        **{
            k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in res.items()
        },
    }
    # star moves B up + B down on the root link; ring moves
    # 2B(n-1)/n per link. Report payload/latency goodput.
    cell["gbps"] = round(2 * nbytes / (res["avg_ms"] / 1e3) / 1e9, 3)
    return cell


def _stripe_sweep(store, payload_mb: int, iters_override,
                  baseline_tree=None) -> list:
    """chunk size x channels x codec grid at one payload, star and ring.
    channels=1 rows are the single-lane baseline of the CURRENT tree;
    tree="baseline" rows (with --ab-baseline) are the pre-striping
    transport, interleaved cell-for-cell against the striped ones."""
    nbytes = payload_mb << 20
    iters = iters_override or 12
    cells = []

    def run(algorithm, world, channels, tree=None, **extra):
        res = _bench_config(
            store, algorithm, world, channels, nbytes,
            iters=iters, warmup=3, tree=tree, **extra,
        )
        cell = _finish_cell(
            res, nbytes,
            tree="baseline" if tree else "current",
            algorithm=algorithm, world=world, channels=channels,
            iters=iters, **{
                k: (v >> 10 if k == "chunk_bytes" else v)
                for k, v in extra.items()
            },
        )
        if "chunk_bytes" in extra:
            cell["chunk_kb"] = cell.pop("chunk_bytes")
        cells.append(cell)
        print(
            f"# {'BASE' if tree else 'new '} {algorithm} w{world} "
            f"c{channels} {extra or ''}: avg {cell['avg_ms']}ms "
            f"p50 {cell['p50_ms']}ms bal {cell['lane_balance']}",
            file=sys.stderr,
        )
        return cell

    for algorithm, world in (("star", 2), ("ring", 3)):
        # Interleave: baseline / single-lane current / striped grid, so
        # slow host drift hits all arms equally.
        if baseline_tree:
            run(algorithm, world, 1, tree=baseline_tree)
        run(algorithm, world, 1, chunk_bytes=0)  # whole-payload, 1 lane
        if baseline_tree:
            run(algorithm, world, 4, tree=baseline_tree)  # PR1 default
        for codec in ("none", "bf16", "int8"):
            for chunk_kb in (1024, 4096):
                for channels in (2, 4):
                    run(
                        algorithm, world, channels,
                        chunk_bytes=chunk_kb << 10, compression=codec,
                    )
    return cells


def _ab_focus(store, payload_mb: int, iters_override, baseline_tree,
              reps: int) -> list:
    """Tight A/B on the acceptance-criterion cells only: PR1 single-lane
    vs striped, alternated rep-for-rep (this host's load drifts on a
    minutes scale — run-level A/Bs swing 2x, so pairs must interleave).
    Per config the artifact carries every rep plus the median-of-reps
    avg, the honest summary under load spikes."""
    nbytes = payload_mb << 20
    iters = iters_override or 10
    configs = []
    for algorithm, world in (("star", 2), ("ring", 3)):
        configs += [
            dict(algorithm=algorithm, world=world, channels=1,
                 tree=baseline_tree, label=f"{algorithm}_base_c1"),
            dict(algorithm=algorithm, world=world, channels=2,
                 chunk_bytes=1 << 20, label=f"{algorithm}_striped_c2"),
            dict(algorithm=algorithm, world=world, channels=4,
                 chunk_bytes=1 << 20, label=f"{algorithm}_striped_c4"),
            dict(algorithm=algorithm, world=world, channels=4,
                 chunk_bytes=4 << 20, label=f"{algorithm}_striped_c4_4mb"),
        ]
    runs = {c["label"]: [] for c in configs}
    for rep in range(reps):
        for c in configs:
            kw = {k: v for k, v in c.items()
                  if k not in ("label", "algorithm", "world", "channels",
                               "tree")}
            res = _bench_config(
                store, c["algorithm"], c["world"], c["channels"], nbytes,
                iters=iters, warmup=3, tree=c.get("tree"), **kw,
            )
            runs[c["label"]].append(res)
            print(
                f"# rep{rep} {c['label']}: avg {res['avg_ms']:.1f}ms "
                f"p50 {res['p50_ms']:.1f}ms",
                file=sys.stderr,
            )
    cells = []
    for c in configs:
        reps_res = runs[c["label"]]
        avgs = sorted(r["avg_ms"] for r in reps_res)
        cells.append({
            "label": c["label"],
            "tree": "baseline" if c.get("tree") else "current",
            "algorithm": c["algorithm"], "world": c["world"],
            "channels": c["channels"],
            "chunk_kb": (c.get("chunk_bytes", 0) >> 10) or None,
            "payload_bytes": nbytes, "iters": iters, "reps": reps,
            "median_avg_ms": round(avgs[len(avgs) // 2], 3),
            "min_avg_ms": round(avgs[0], 3),
            "rep_avg_ms": [round(a, 3) for a in avgs],
            "lane_balance": reps_res[-1]["lane_balance"],
        })
    return cells


def _overlap_ab(store, payload_mb: int, iters_override, buckets: int,
                reps: int) -> list:
    """Same-run interleaved A/B of per-bucket wire overlap: ``serial``
    submits bucket k+1 only after bucket k's future resolves (the
    lock-step step loop's wire schedule); ``streamed`` keeps every
    bucket in flight at once (the streamed step pipeline's schedule).
    Arms alternate rep-for-rep so host-load drift hits both equally;
    each config reports every rep plus the median-of-reps avg and the
    derived ``overlap_gain`` = 1 - streamed/serial (median avg)."""
    nbytes = payload_mb << 20
    iters = iters_override or 10
    runs: dict = {}
    order = []
    for rep in range(reps):
        for algorithm, world in (("star", 2), ("ring", 3)):
            for mode in ("serial", "streamed"):
                label = f"{algorithm}_{mode}"
                res = _bench_config(
                    store, algorithm, world, 4, nbytes,
                    iters=iters, warmup=2, buckets=buckets, mode=mode,
                )
                if label not in runs:
                    runs[label] = []
                    order.append((label, algorithm, world, mode))
                runs[label].append(res)
                print(
                    f"# rep{rep} {label} b{buckets}: "
                    f"avg {res['avg_ms']:.1f}ms p50 {res['p50_ms']:.1f}ms",
                    file=sys.stderr,
                )
    cells = []
    medians = {}
    for label, algorithm, world, mode in order:
        reps_res = runs[label]
        avgs = sorted(r["avg_ms"] for r in reps_res)
        p50s = sorted(r["p50_ms"] for r in reps_res)
        medians[label] = avgs[len(avgs) // 2]
        cells.append({
            "label": label,
            "algorithm": algorithm, "world": world, "mode": mode,
            "channels": 4, "buckets": buckets,
            "payload_bytes": nbytes, "iters": iters, "reps": reps,
            "median_avg_ms": round(avgs[len(avgs) // 2], 3),
            "median_p50_ms": round(p50s[len(p50s) // 2], 3),
            "min_avg_ms": round(avgs[0], 3),
            "rep_avg_ms": [round(a, 3) for a in avgs],
        })
    for algorithm in ("star", "ring"):
        serial = medians.get(f"{algorithm}_serial")
        streamed = medians.get(f"{algorithm}_streamed")
        if serial and streamed:
            cells.append({
                "label": f"{algorithm}_overlap_gain",
                "algorithm": algorithm, "buckets": buckets,
                "overlap_gain": round(1.0 - streamed / serial, 4),
            })
    return cells


def _thread_cell(store, backend, algorithm, world, nbytes, iters, warmup,
                 channels=4, chunk_bytes=1 << 20, compression="none",
                 seed=0, env=None, check_numeric=False):
    """One thread-per-rank cell (see _THREAD_WORKER). Returns latency
    percentiles + the rank-0 result digest (the bitwise oracle) + the
    bytes-on-wire counters; ``check_numeric`` adds the max-abs-err
    oracle for order-free (psum) cells."""
    import os

    _CELL_SEQ[0] += 1
    prefix = f"bt{_CELL_SEQ[0]}"
    spec = {
        "tree": str(_REPO), "backend": backend, "cell": prefix,
        "store": f"{store.addr}/{prefix}",
        "world": world, "algorithm": algorithm, "channels": channels,
        "chunk_bytes": chunk_bytes, "compression": compression,
        "nbytes": nbytes, "iters": iters, "warmup": warmup, "seed": seed,
        "check_numeric": bool(check_numeric),
    }
    child_env = dict(os.environ)
    child_env.pop("PYTHONPATH", None)
    # The xla arm needs >= world virtual CPU devices BEFORE jax inits;
    # harmless for the host arm (which never imports jax). RESPECT a
    # caller-set JAX_PLATFORMS: on a real TPU host `JAX_PLATFORMS=tpu
    # bench_transport.py --backend xla` must measure the device plane,
    # not a silently CPU-emulated one tagged "xla".
    child_env.setdefault("JAX_PLATFORMS", "cpu")
    if child_env["JAX_PLATFORMS"] == "cpu":
        child_env["XLA_FLAGS"] = (
            child_env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(world, 4)}"
        ).strip()
    if env:
        child_env.update(env)
    out = subprocess.run(
        [sys.executable, "-c", _THREAD_WORKER, json.dumps(spec)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=600,
        env=child_env,
    )
    lines = out.stdout.decode().strip().splitlines()
    if not lines:
        raise RuntimeError(
            f"cell {prefix} ({backend}) produced no output "
            f"(rc={out.returncode}): {out.stderr.decode()[-2000:]}"
        )
    payload = json.loads(lines[-1])
    if out.returncode != 0 or "error" in payload:
        raise RuntimeError(
            f"cell {prefix} ({backend}) failed: {payload.get('error')}"
        )
    res = _percentiles(payload["lat"])
    res["digest"] = payload["digest"]
    res["comm_backend"] = payload["comm_backend"]
    for key in ("comm_encoded_bytes", "comm_raw_bytes", "compile_count",
                "max_abs_err", "absmax"):
        if payload.get(key) is not None:
            res[key] = payload[key]
    return res


def _backend_ab(store, payload_mb: int, iters_override, reps: int,
                codecs=("none", "bf16", "int8")) -> list:
    """Rep-interleaved host-vs-xla A/B with a bitwise oracle every rep
    (PR 2-5 pattern: warmup reps inside each cell, gc outside windows,
    arms alternated so host-load drift hits both equally). Fails loudly
    if any (config, rep) pair's reduced bytes diverge across arms."""
    import gc

    nbytes = payload_mb << 20
    iters = iters_override or 8
    configs = [
        dict(algorithm=algorithm, world=world, compression=codec,
             label=f"{algorithm}_w{world}_{codec}")
        for algorithm, world in (("star", 2), ("ring", 3))
        for codec in codecs
    ]
    runs: dict = {c["label"]: {"host": [], "xla": []} for c in configs}
    oracle_ok = True
    for rep in range(reps):
        for c in configs:
            digests = {}
            for backend in ("host", "xla"):
                gc.collect()
                res = _thread_cell(
                    store, backend, c["algorithm"], c["world"], nbytes,
                    iters=iters, warmup=2, compression=c["compression"],
                    seed=1000 + rep,  # same inputs across arms, per rep
                )
                digests[backend] = res["digest"]
                runs[c["label"]][backend].append(res)
                print(
                    f"# rep{rep} {c['label']} {backend}: "
                    f"avg {res['avg_ms']:.1f}ms p50 {res['p50_ms']:.1f}ms",
                    file=sys.stderr,
                )
            if digests["host"] != digests["xla"]:
                oracle_ok = False
                print(
                    f"# BITWISE MISMATCH rep{rep} {c['label']}: "
                    f"{digests}", file=sys.stderr,
                )
    cells = []
    for c in configs:
        cell = {
            "label": c["label"], "algorithm": c["algorithm"],
            "world": c["world"], "compression": c["compression"],
            "payload_bytes": nbytes, "iters": iters, "reps": reps,
            "workers": "thread-per-rank",
        }
        for backend in ("host", "xla"):
            avgs = sorted(r["avg_ms"] for r in runs[c["label"]][backend])
            cell[f"{backend}_median_avg_ms"] = round(avgs[len(avgs) // 2], 3)
            cell[f"{backend}_rep_avg_ms"] = [round(a, 3) for a in avgs]
        cell["bitwise"] = all(
            runs[c["label"]]["host"][i]["digest"]
            == runs[c["label"]]["xla"][i]["digest"]
            for i in range(reps)
        )
        cells.append(cell)
    if not oracle_ok:
        raise SystemExit("backend A/B: bitwise oracle FAILED (see stderr)")
    return cells


# Encoded/raw envelopes for the quantized-psum arm: int8 = 1B payload +
# 4B scale per (1MB) chunk over 4B elems; bf16/fp16 = 2B payload. A
# quant arm above its envelope means the wire stopped compressing.
_PSUM_RATIO_ENVELOPE = {"int8": 0.30, "bf16": 0.51, "fp16": 0.51}
# Numeric envelopes: max abs error of the reduced SUM vs the exact f64
# sum, as a fraction of (world+1)*absmax — int8's per-element error is
# absmax/254 per contribution plus the phase-2 re-encode, bf16 keeps 8
# mantissa bits, fp16 10.
_PSUM_ERR_DIV = {"int8": 100.0, "bf16": 100.0, "fp16": 400.0}


def _psum_codec_cells(store, payload_mb: int, iters_override, reps: int,
                      codecs) -> list:
    """The --codec sweep arm of --backend-ab: quantized psum vs raw
    psum (both xla — the host plane has no psum, says the shared
    capability query), rep-interleaved, with THREE oracles every rep:

    * **encoded bytes on wire** (the graded one): the quant arm's
      ``comm_encoded_bytes / comm_raw_bytes`` counter ratio must sit
      inside the codec's envelope (int8 <= 0.3x at the 1MB grid) and
      the raw arm's must be exactly 1.0;
    * **numeric**: rank 0's reduced bytes within the codec's
      quantization-error envelope of the exact f64 sum (psum cannot
      enter the bitwise A/B — XLA owns the reduction order);
    * **compile**: exactly 1 executable per child (one layout — more
      means a retrace storm).

    Fails the run loudly on any oracle miss."""
    import gc

    from torchft_tpu.comm.xla_backend import XlaCommContext

    nbytes = payload_mb << 20
    iters = iters_override or 8
    world = 2
    cells = []
    failures = []
    for codec in [c for c in codecs if c != "none"]:
        if not XlaCommContext.supports("psum", codec):
            print(f"# psum_{codec}: unsupported, skipped", file=sys.stderr)
            continue
        runs = {"raw": [], "quant": []}
        for rep in range(reps):
            for arm, compression in (("raw", "none"), ("quant", codec)):
                gc.collect()
                res = _thread_cell(
                    store, "xla", "psum", world, nbytes,
                    iters=iters, warmup=2, compression=compression,
                    seed=3000 + rep, check_numeric=True,
                )
                runs[arm].append(res)
                ratio = res["comm_encoded_bytes"] / res["comm_raw_bytes"]
                print(
                    f"# rep{rep} psum_{codec} {arm}: "
                    f"avg {res['avg_ms']:.1f}ms ratio {ratio:.4f} "
                    f"err {res['max_abs_err']:.3g} "
                    f"compiles {res.get('compile_count')}",
                    file=sys.stderr,
                )
                if arm == "quant" and ratio > _PSUM_RATIO_ENVELOPE[codec]:
                    failures.append(
                        f"rep{rep} psum_{codec} quant: encoded/raw "
                        f"{ratio:.4f} > {_PSUM_RATIO_ENVELOPE[codec]}"
                    )
                if arm == "raw" and abs(ratio - 1.0) > 1e-9:
                    failures.append(
                        f"rep{rep} psum_{codec} raw: encoded/raw "
                        f"{ratio:.6f} != 1.0"
                    )
                err_div = (
                    _PSUM_ERR_DIV[codec] if arm == "quant" else 1e5
                )
                bound = (world + 1) * res["absmax"] / err_div
                if res["max_abs_err"] > bound:
                    failures.append(
                        f"rep{rep} psum_{codec} {arm}: err "
                        f"{res['max_abs_err']:.4g} > bound {bound:.4g}"
                    )
                if res.get("compile_count") != 1:
                    failures.append(
                        f"rep{rep} psum_{codec} {arm}: "
                        f"{res.get('compile_count')} compiles for one "
                        "layout (retrace storm)"
                    )
        cell = {
            "label": f"psum_w{world}_{codec}", "algorithm": "psum",
            "world": world, "compression": codec,
            "payload_bytes": nbytes, "iters": iters, "reps": reps,
            "workers": "thread-per-rank",
            "ratio_envelope": _PSUM_RATIO_ENVELOPE[codec],
        }
        for arm in ("raw", "quant"):
            avgs = sorted(r["avg_ms"] for r in runs[arm])
            cell[f"{arm}_median_avg_ms"] = round(avgs[len(avgs) // 2], 3)
            cell[f"{arm}_rep_avg_ms"] = [round(a, 3) for a in avgs]
            cell[f"{arm}_encoded_ratio"] = round(
                runs[arm][-1]["comm_encoded_bytes"]
                / runs[arm][-1]["comm_raw_bytes"], 4
            )
            cell[f"{arm}_max_abs_err"] = max(
                r["max_abs_err"] for r in runs[arm]
            )
        cell["encoded_bytes_oracle"] = not any(
            "encoded/raw" in f for f in failures
        )
        cells.append(cell)
    if failures:
        raise SystemExit(
            "psum --codec sweep: oracle FAILED:\n  " + "\n  ".join(failures)
        )
    return cells


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="add 32MB payloads")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument(
        "--stripe-sweep", action="store_true",
        help="chunk size x lanes x codec grid at a fixed payload",
    )
    ap.add_argument("--sweep-payload-mb", type=int, default=8)
    ap.add_argument(
        "--ab-baseline", default=None, metavar="TREE",
        help="path to a pre-striping checkout; interleaves its cells "
        "into the --stripe-sweep artifact for a same-run A/B",
    )
    ap.add_argument(
        "--ab-repeat", type=int, default=0, metavar="N",
        help="with --ab-baseline: run ONLY the acceptance-criterion "
        "cells (PR1 single-lane vs striped), alternated N times",
    )
    ap.add_argument(
        "--overlap-ab", type=int, default=0, metavar="N",
        help="per-bucket overlap A/B: serial (lock-step) vs streamed "
        "multi-bucket submission, alternated N reps",
    )
    ap.add_argument(
        "--overlap-buckets", type=int, default=4, metavar="B",
        help="bucket count for --overlap-ab (payload is split B ways)",
    )
    ap.add_argument(
        "--backend", choices=("host", "xla"), default="host",
        help="data plane for the default sweep: host sockets "
        "(process-per-rank) or on-device jax.lax collectives "
        "(thread-per-rank, comm/xla_backend.py)",
    )
    ap.add_argument(
        "--backend-ab", type=int, default=0, metavar="N",
        help="host-vs-xla A/B at --sweep-payload-mb, alternated N reps "
        "with a bitwise oracle every rep (both arms thread-per-rank)",
    )
    ap.add_argument(
        "--codec", action="append", default=None, metavar="CODEC",
        choices=("none", "bf16", "fp16", "int8"),
        help="with --backend-ab: restrict the star/ring codec grid to "
        "these codecs AND add the quantized-psum sweep arm (quant vs "
        "raw psum, xla only, rep-interleaved) with an encoded-bytes-"
        "on-wire + numeric + compile-count oracle per rep; repeatable",
    )
    args = ap.parse_args()
    if args.codec and not args.backend_ab:
        ap.error("--codec applies only to --backend-ab")
    if args.backend == "xla" and (
        args.stripe_sweep or args.overlap_ab
        or (args.ab_repeat and args.ab_baseline)
    ):
        # Those modes run host-plane cells regardless of --backend; an
        # artifact claiming "xla" for them would lie about its numbers.
        ap.error(
            "--backend xla applies only to the default sweep (or use "
            "--backend-ab); --stripe-sweep/--overlap-ab/--ab-repeat "
            "measure the host plane's lane machinery"
        )

    cells = []
    t_start = time.perf_counter()
    store = StoreServer()
    try:
        if args.backend_ab:
            codecs = tuple(args.codec) if args.codec else (
                "none", "bf16", "int8"
            )
            cells = _backend_ab(
                store, args.sweep_payload_mb, args.iters, args.backend_ab,
                codecs=codecs,
            )
            if args.codec:
                cells += _psum_codec_cells(
                    store, args.sweep_payload_mb, args.iters,
                    args.backend_ab, codecs,
                )
        elif args.overlap_ab:
            cells = _overlap_ab(
                store, args.sweep_payload_mb, args.iters,
                args.overlap_buckets, args.overlap_ab,
            )
        elif args.ab_repeat and args.ab_baseline:
            cells = _ab_focus(
                store, args.sweep_payload_mb, args.iters,
                args.ab_baseline, args.ab_repeat,
            )
        elif args.stripe_sweep:
            cells = _stripe_sweep(
                store, args.sweep_payload_mb, args.iters,
                baseline_tree=args.ab_baseline,
            )
        else:
            sizes = [64 << 10, 1 << 20, 8 << 20]
            if args.full:
                sizes.append(32 << 20)
            for nbytes in sizes:
                iters = args.iters or max(5, min(30, (8 << 20) // nbytes * 4))
                for algorithm, world in (("star", 2), ("ring", 3)):
                    # lanes are a host-plane concept: the xla backend
                    # rides one fused executable, so one cell per config
                    for channels in ((1, 4) if args.backend == "host"
                                     else (1,)):
                        if args.backend == "xla":
                            res = _thread_cell(
                                store, "xla", algorithm, world, nbytes,
                                iters=iters, warmup=3,
                            )
                            res.pop("digest", None)
                        else:
                            res = _bench_config(
                                store, algorithm, world, channels, nbytes,
                                iters=iters, warmup=3,
                            )
                        cell = _finish_cell(
                            res, nbytes,
                            backend=args.backend,
                            algorithm=algorithm, world=world,
                            channels=channels, iters=iters,
                        )
                        cells.append(cell)
                        print(
                            f"# {args.backend} {algorithm} w{world} "
                            f"c{channels} {nbytes >> 10}KB: "
                            f"avg {cell['avg_ms']}ms "
                            f"p95 {cell['p95_ms']}ms",
                            file=sys.stderr,
                        )
    finally:
        store.shutdown()

    print(json.dumps({
        "bench": (
            "transport_backend_ab" if args.backend_ab
            else "transport_overlap_ab" if args.overlap_ab
            else "transport_stripe_ab" if args.ab_repeat and args.ab_baseline
            else "transport_stripe_sweep" if args.stripe_sweep
            else "transport_loopback_allreduce"
        ),
        # Only the default sweep and --backend-ab ever run xla cells;
        # the guard above rejects --backend xla for the other modes.
        "comm_backend": "host+xla" if args.backend_ab else args.backend,
        "workers": (
            "thread-per-rank"
            if args.backend_ab or args.backend == "xla"
            else "process-per-rank"
        ),
        "wall_s": round(time.perf_counter() - t_start, 1),
        "cells": cells,
    }))


if __name__ == "__main__":
    main()
