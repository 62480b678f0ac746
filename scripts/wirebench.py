"""The socket plane alone, at the kill cell's gradient traffic.

    python scripts/wirebench.py                  # this tree's defaults
    python scripts/wirebench.py --channels 4 --chunk-bytes 1048576
    python scripts/wirebench.py --grid --out chiprun_out/wirebench.json

``world`` TcpCommContexts as threads of ONE process over a StoreServer (as
the benchmark's groups are), each submitting the step's f32 buckets at
once with ``ReduceOp.SUM`` (what ``Manager.allreduce_arrays`` sends) and
waiting for all of them; the rounds' times are the wall clock of the
slowest rank. No jax, no chip: the transport is host code, but only the
chip's host says what it costs there (PERF.md, PR 27).

``--plan cell``: the 13 buckets ``ddp._BucketPlan`` makes of
cerebras-gpt-111m's 105 leaves at 32 MiB (598.6 MB; element counts
below). ``--plan a``: eight arrays of 32 MiB.

``--probe``: rank 0's lane 0 runs behind timing proxies (its sockets,
``select``, the decode's ``np.add`` / ``np.copyto``), so the table says
where a hop's time goes: seconds and calls in each, the rest being
Python between them, the wait for the GIL and the lane's queue.
"""

from __future__ import annotations

import argparse
import json
import os
import select
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from torchft_tpu.comm import ReduceOp, StoreServer, TcpCommContext  # noqa: E402
from torchft_tpu.comm import transport  # noqa: E402

# ddp._BucketPlan over cerebras-gpt-111m's parameter tree, elements a bucket
CELL_PLAN = [8260608, 8263680] + [7080960] * 7 + [
    4718592, 38633472, 1574400, 38633472,
]
PLANS = {"cell": CELL_PLAN, "a": [8 << 20] * 8}


class _Probe:
    """Seconds and calls by section, for one thread."""

    def __init__(self) -> None:
        self.ident: Optional[int] = None
        self.s: Dict[str, float] = {}
        self.n: Dict[str, int] = {}

    def add(self, key: str, dt: float) -> None:
        self.s[key] = self.s.get(key, 0.0) + dt
        self.n[key] = self.n.get(key, 0) + 1

    def mine(self) -> bool:
        return threading.get_ident() == self.ident


class _TimedSock:
    """A socket whose sendmsg / recv_into are timed into a probe."""

    def __init__(self, sock: Any, probe: _Probe) -> None:
        self._s, self._p = sock, probe

    def __getattr__(self, name: str) -> Any:
        return getattr(self._s, name)

    def fileno(self) -> int:
        return self._s.fileno()

    def sendmsg(self, *a: Any) -> int:
        t = time.perf_counter()
        try:
            return self._s.sendmsg(*a)
        finally:
            self._p.add("sendmsg", time.perf_counter() - t)

    def recv_into(self, *a: Any) -> int:
        t = time.perf_counter()
        try:
            return self._s.recv_into(*a)
        finally:
            self._p.add("recv_into", time.perf_counter() - t)


def _install_probe(ctx: TcpCommContext, probe: _Probe) -> Any:
    """Put lane 0 of ``ctx`` behind the probe (while it idles on its
    queue); returns the function that takes the patches out again."""
    lane = ctx._lanes[0]
    probe.ident = lane._thread.ident
    for attr in ("_next_sock", "_prev_sock", "_root_sock"):
        s = getattr(lane, attr)
        if s is not None:
            setattr(lane, attr, _TimedSock(s, probe))
    real_select = select.select

    def timed_select(*a: Any) -> Any:
        if not probe.mine():
            return real_select(*a)
        t = time.perf_counter()
        try:
            return real_select(*a)
        finally:
            probe.add("select", time.perf_counter() - t)

    select.select = timed_select
    # the decode: np.add (reduce-scatter hops, through the transport's
    # table of reduce functions) and np.copyto (all-gather hops)
    real_add = transport._REDUCE_FNS[ReduceOp.SUM]
    real_copyto = np.copyto

    def timed(key: str, fn: Any) -> Any:
        def wrapper(*a: Any, **k: Any) -> Any:
            if not probe.mine():
                return fn(*a, **k)
            t = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                probe.add(key, time.perf_counter() - t)
        return wrapper

    transport._REDUCE_FNS[ReduceOp.SUM] = timed("np_add", real_add)
    np.copyto = timed("np_copyto", real_copyto)

    def undo() -> None:
        select.select = real_select
        transport._REDUCE_FNS[ReduceOp.SUM] = real_add
        np.copyto = real_copyto

    return undo


_MASTERS: Dict[str, List[np.ndarray]] = {}
_BUFS: Dict[Any, List[List[np.ndarray]]] = {}


def _buffers(plan: str, world: int) -> List[List[np.ndarray]]:
    """Rank r's arrays: one seeded draw a plan, times r + 1; allocated
    once a (plan, world) and refilled a run (the reduction is in place)."""
    if plan not in _MASTERS:
        rng = np.random.default_rng(7)
        _MASTERS[plan] = [
            rng.standard_normal(n, dtype=np.float32) for n in PLANS[plan]
        ]
    masters = _MASTERS[plan]
    if (plan, world) not in _BUFS:
        _BUFS[(plan, world)] = [
            [np.empty_like(m) for m in masters] for _ in range(world)
        ]
    bufs = _BUFS[(plan, world)]
    for r in range(world):
        for m, b in zip(masters, bufs[r]):
            np.multiply(m, np.float32(r + 1), out=b)
    return bufs


def run(plan: str, world: int, rounds: int, probe: bool,
        **kw: Any) -> Dict[str, Any]:
    sizes = PLANS[plan]
    store = StoreServer()
    ctxs = [TcpCommContext(timeout=120.0, **kw) for _ in range(world)]
    bufs = _buffers(plan, world)
    total_mb = sum(sizes) * 4 / 1e6
    barrier = threading.Barrier(world)
    times: List[List[float]] = [[] for _ in range(world)]
    p = _Probe()
    undo: List[Any] = []

    def worker(r: int) -> None:
        ctxs[r].configure(f"{store.addr}/wb", r, world)
        barrier.wait()
        for rd in range(rounds):
            if probe and r == 0 and rd == rounds - 1:
                undo.append(_install_probe(ctxs[0], p))
            barrier.wait()
            t0 = time.perf_counter()
            ws = [ctxs[r].allreduce([b], ReduceOp.SUM) for b in bufs[r]]
            for w in ws:
                w.wait()
            barrier.wait()
            times[r].append(time.perf_counter() - t0)

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for u in undo:
        u()
    snap = ctxs[0].metrics.snapshot()
    for c in ctxs:
        c.shutdown()
    store.shutdown()
    last = max(t[-1] for t in times)
    out: Dict[str, Any] = {
        "plan": plan, "world": world, "kw": dict(kw),
        "mb": total_mb, "round_s": [max(t[i] for t in times)
                                    for i in range(rounds)],
        "last_s": last, "mb_per_s": total_mb / last,
        "comm_chunks": snap.get("comm_chunks"),
        "comm_hop_bytes": snap.get("comm_hop_bytes"),
        "comm_wire_reduce_p50_ms": snap.get("comm_wire_reduce_p50_ms"),
        "comm_submit_wire_p50_ms": snap.get("comm_submit_wire_p50_ms"),
        "lanes_wire_reduce_p50_ms": [
            snap.get(f"comm_l{i}_wire_reduce_p50_ms") for i in range(8)
            if f"comm_l{i}_wire_reduce_p50_ms" in snap
        ],
    }
    if probe:
        out["probe"] = {"round_s": times[0][-1], "seconds": p.s,
                        "calls": p.n}
    return out


def _grid() -> List[Dict[str, Any]]:
    """The cells of the table: this tree's defaults first, then the
    explicit grids of the parent's lever space."""
    mib = 1 << 20
    cells: List[Dict[str, Any]] = [dict(plan="cell"), dict(plan="a")]
    for ch, cb in ((4, mib), (1, mib), (2, mib), (8, mib), (4, 4 * mib),
                   (4, 16 * mib), (4, 40 * mib), (4, 0), (2, 40 * mib),
                   (2, 0), (1, 0)):
        cells.append(dict(plan="cell", channels=ch, chunk_bytes=cb))
    for ch, cb in ((4, mib), (4, 32 * mib), (1, 32 * mib)):
        cells.append(dict(plan="a", channels=ch, chunk_bytes=cb))
    cells.append(dict(plan="cell", world=3))
    cells.append(dict(plan="cell", world=3, channels=4, chunk_bytes=mib))
    cells.append(dict(plan="cell", world=2))
    cells.append(dict(plan="cell", world=2, channels=4, chunk_bytes=mib))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", choices=sorted(PLANS), default="cell")
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--channels", type=int)
    ap.add_argument("--chunk-bytes", type=int)
    ap.add_argument("--algorithm")
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--grid", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    rows: List[Dict[str, Any]] = []
    if args.grid:
        for cell in _grid():
            cell = dict(cell)
            row = run(cell.pop("plan"), cell.pop("world", args.world),
                      args.rounds, True, **cell)
            print(json.dumps(row), flush=True)
            rows.append(row)
    else:
        kw = {k: getattr(args, k) for k in ("channels", "chunk_bytes",
                                             "algorithm")
              if getattr(args, k) is not None}
        rows.append(run(args.plan, args.world, args.rounds, args.probe, **kw))
        print(json.dumps(rows[0]))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"cpus": os.cpu_count(), "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
