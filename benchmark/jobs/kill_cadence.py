"""Job ``kill_cadence``: the product. ``groups`` replica groups as threads
of the one process that holds the chips, group g on chip g, each with its
own StoreServer, Manager, CheckpointServer, DistributedDataParallel and
OptimizerWrapper, loops running free and averaging gradients every step,
while a wall-clock schedule drawn from ``--seed`` tears groups down from
outside and replaces each at once with new objects from a poisoned seed,
which only a heal can make right.

One process, not one per chip: only the process that holds a chip can
trace it, PR 21 measured the same step times either way, and a relaunched
process's boot and cache load are what ``setup_s`` measures on every run.
"""

from __future__ import annotations

import gc
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness
from benchmark.group import ReplicaGroup
from benchmark.traffic_gen import BatchSource, kill_schedule

_WARM_COMMITS = 2      # classic steps with every group before the window
_SETTLE_TIMEOUT_S = 300.0
_STOP_TIMEOUT_S = 150.0


class _Cohort:
    """The live groups, their loop threads, and everything that ever
    lived (for the records)."""

    def __init__(self, ctx: harness.Context, model: Any, rows: int,
                 lighthouse_addr: str) -> None:
        self.ctx, self.model, self.rows = ctx, model, rows
        self.lighthouse_addr = lighthouse_addr
        self.live: Dict[int, ReplicaGroup] = {}
        self.threads: Dict[ReplicaGroup, threading.Thread] = {}
        self.everyone: List[ReplicaGroup] = []
        self.dead: List[ReplicaGroup] = []
        self.stop_at: Optional[int] = None
        self.lock = threading.Lock()

    def build(self, gid: int, incarnation: int, init_seed: int) -> ReplicaGroup:
        ctx, model = self.ctx, self.model
        chip = gid % len(ctx.devices)
        source = BatchSource(ctx.seed, gid, incarnation, self.rows,
                             model.seq_len, model.vocab_draw)
        group = ReplicaGroup(
            gid, incarnation, model, ctx.family, ctx.devices[chip], chip,
            self.lighthouse_addr, init_seed, source,
        )
        with self.lock:
            self.live[gid] = group
            self.everyone.append(group)
        return group

    def keep_going(self, group: ReplicaGroup) -> bool:
        stop_at = self.stop_at
        return stop_at is None or group.manager.current_step() < stop_at

    def start_loop(self, group: ReplicaGroup) -> None:
        thread = threading.Thread(
            target=group.run, args=(self.keep_going,), daemon=True,
            name=f"bm_group_{group.gid}_{group.incarnation}",
        )
        self.threads[group] = thread
        thread.start()

    def kill(self, gid: int, poison_seed: int) -> Dict[str, Any]:
        """Tear the live group ``gid`` down while its loop runs; build and
        start its replacement at once, on a thread of its own."""
        with self.lock:
            victim = self.live.pop(gid)
            self.dead.append(victim)
        victim.end_snapshot = victim.snapshots()  # its counters die with it
        kill = {"gid": gid, "incarnation": victim.incarnation + 1,
                "t_kill": time.perf_counter()}
        victim.teardown()

        def relaunch() -> None:
            try:
                self.start_loop(
                    self.build(gid, victim.incarnation + 1, poison_seed)
                )
            except BaseException as e:  # noqa: BLE001 — judged by the job
                kill["relaunch_error"] = repr(e)

        threading.Thread(target=relaunch, daemon=True,
                         name=f"bm_relaunch_{gid}").start()
        return kill

    def reap(self) -> None:
        """Free the HBM of torn-down groups whose loop has ended."""
        for group in list(self.dead):
            thread = self.threads.get(group)
            if thread is None or not thread.is_alive():
                group.free()
                self.dead.remove(group)
                gc.collect()  # programs and fences sit in reference cycles


def _first_commit(group: ReplicaGroup) -> Optional[Dict[str, Any]]:
    return next((r for r in list(group.records) if r["committed"]), None)


def _survivor_stall(kill: Dict[str, Any], everyone: List[ReplicaGroup],
                    horizon: float) -> Optional[float]:
    """Longest gap between consecutive commits of any group that lived
    through the kill, among gaps that overlap [t_kill, horizon]."""
    worst = None
    for group in everyone:
        if group.gid == kill["gid"]:
            continue
        ends = [r["t1"] for r in group.records if r["committed"]]
        for a, b in zip(ends, ends[1:]):
            if b > kill["t_kill"] and a < horizon:
                worst = max(worst or 0.0, b - a)
    return worst


def run(ctx: harness.Context) -> Dict[str, Any]:
    import jax

    from torchft_tpu.control import Lighthouse

    fam, traffic = ctx.family, ctx.traffic
    model = fam.build(ctx.config)
    rows, n = int(traffic["rows"]), int(traffic["groups"])
    poison_seed = ctx.seed + int(traffic["poison_seed_offset"])
    schedule = kill_schedule(ctx.seed, ctx.seconds, traffic, n)
    checks: Dict[str, Any] = {}
    notes: List[str] = []

    # -- reference and (traced run) the bare step, on chip 0, then freed
    device0 = ctx.devices[0]
    state = fam.init_state(model, ctx.seed, device0)
    checks["reference"] = fam.check_reference(
        model, state["params"], ctx.seed, device0
    )
    bare = None
    if ctx.trace:
        source = BatchSource(ctx.seed, n, 0, rows, model.seq_len,
                             model.vocab_draw)
        train_step = fam.make_train_step(model)
        state = harness.bare_step_loop(
            train_step, state, source, device0, 0, 2
        )["state"]
        bare = harness.bare_step_loop(
            train_step, state, source, device0, 2, int(traffic["bare_steps"])
        )
        state = bare.pop("state")
        del train_step
    harness.free(state)
    del state

    lighthouse = Lighthouse(**traffic["lighthouse"])
    cohort = _Cohort(ctx, model, rows, lighthouse.address())
    try:
        # -- every group up (and heartbeating) before any asks for a
        # quorum, then the loops run free until all have warmed up
        with ThreadPoolExecutor(max_workers=n) as pool:
            list(pool.map(lambda g: cohort.build(g, 0, ctx.seed), range(n)))
        t_first = time.perf_counter()
        for group in list(cohort.live.values()):
            cohort.start_loop(group)

        def warmed(group: ReplicaGroup) -> int:
            return sum(1 for r in list(group.records)
                       if r["committed"] and r["participants"] == n)

        first_step_s = None
        deadline = time.perf_counter() + _SETTLE_TIMEOUT_S
        while min(warmed(g) for g in cohort.live.values()) < _WARM_COMMITS:
            if first_step_s is None and all(
                g.records for g in cohort.live.values()
            ):
                first_step_s = time.perf_counter() - t_first
            errors = [g.error for g in cohort.live.values() if g.error]
            if errors:
                raise errors[0]
            if time.perf_counter() > deadline:
                raise TimeoutError("the groups never warmed up together")
            time.sleep(0.05)

        # -- the window opens
        for group in cohort.live.values():
            group.reset_timings()
            group.start_snapshot = group.snapshots()
        compiles0 = ctx.counter.compiles
        setup_s = time.perf_counter() - ctx.t_start
        t0 = time.perf_counter()
        t_end = t0 + ctx.seconds
        events: List[Tuple[float, str, int]] = [
            (t, "kill", gid) for t, gid in schedule
        ]
        if ctx.trace:
            a = float(traffic["trace_from_s"])
            events += [(a, "trace_start", -1),
                       (a + float(traffic["trace_for_s"]), "trace_stop", -1)]
        kills: List[Dict[str, Any]] = []
        for at, what, gid in sorted(events):
            while time.perf_counter() < min(t0 + at, t_end):
                time.sleep(0.01)
                cohort.reap()
            if what == "kill" and t0 + at <= t_end:
                kills.append(cohort.kill(gid, poison_seed))
            elif what == "trace_start":
                ctx.start_trace()
            elif what == "trace_stop":
                ctx.stop_trace()
        while time.perf_counter() < t_end:
            time.sleep(0.01)
            cohort.reap()
        compiles_in_window = ctx.counter.compiles - compiles0

        # -- a recovery still open is waited for: it counts for recover_s
        # and not for tokens
        grace_end = t_end + float(traffic["recovery_grace_s"])

        def recovered(kill: Dict[str, Any]) -> bool:
            group = cohort.live.get(kill["gid"])
            return (group is not None
                    and group.incarnation == kill["incarnation"]
                    and _first_commit(group) is not None)

        while (not all(recovered(k) for k in kills)
               and time.perf_counter() < grace_end):
            time.sleep(0.05)
            cohort.reap()
        if ctx.trace and len(ctx.trace_span or []) == 1:
            ctx.stop_trace()

        # -- bring every live group to rest at one common step
        with cohort.lock:
            resting = [g for g in cohort.live.values()
                       if g.error is None
                       and (g.incarnation == 0 or _first_commit(g))]
        cohort.stop_at = max(g.manager.current_step() for g in resting) + 2
        stop_deadline = time.perf_counter() + _STOP_TIMEOUT_S
        for group in resting:
            cohort.threads[group].join(
                max(0.0, stop_deadline - time.perf_counter())
            )
        stuck = [g.gid for g in resting if cohort.threads[g].is_alive()]
        jax.block_until_ready([g.state for g in resting if g.gid not in stuck])

        # -- what the run shows
        everyone = cohort.everyone
        records = [r for g in everyone for r in g.records]
        in_window = [r for r in records if t0 <= r["t0"] <= t_end]
        for i, kill in enumerate(kills):
            group = next((g for g in everyone if g.gid == kill["gid"]
                          and g.incarnation == kill["incarnation"]), None)
            first = _first_commit(group) if group is not None else None
            kill["recover_s"] = (
                first["t1"] - kill["t_kill"] if first else None
            )
            kill["t_recovered"] = first["t1"] if first else None
            nxt = kills[i + 1]["t_kill"] if i + 1 < len(kills) else t_end + 1e9
            kill["survivor_stall_s"] = _survivor_stall(
                kill, everyone, min(kill["t_recovered"] or 1e18, nxt)
            )
        in_flight = [(k["t_kill"], k["t_recovered"] or float("inf"))
                     for k in kills]

        def excused(r: Dict[str, Any]) -> bool:
            return any(r["t1"] >= a and r["t0"] <= b for a, b in in_flight)

        refused = [r for r in in_window
                   if not r["committed"] and not excused(r)]
        crashed = [g for g in everyone if g.error is not None
                   and not g.torn_down]
        never = [k for k in kills if k["recover_s"] is None]
        failed = len(refused) + len(crashed) + len(never) + len(stuck)
        for g in crashed:
            notes.append(f"group {g.gid}.{g.incarnation} loop died: {g.error!r}")
        for k in never:
            notes.append(f"kill of group {k['gid']} never recovered: "
                         f"{k.get('relaunch_error', 'no commit in time')}")
        if refused:
            notes.append(f"{len(refused)} refused commits with no kill in "
                         f"flight, first: { {k: v for k, v in refused[0].items() if k != 'loss'} }")

        rest = [g for g in resting if g.gid not in stuck]
        steps = sorted({g.manager.current_step() for g in rest})
        with ThreadPoolExecutor(max_workers=max(1, len(rest))) as pool:
            digests = dict(zip(
                (f"{g.gid}.{g.incarnation}" for g in rest),
                pool.map(lambda g: g.digest(), rest),
            ))
        checks["commit_and_heal"] = {
            "ok": len(rest) == n and len(steps) == 1
            and len(set(digests.values())) == 1 and not stuck,
            "groups_at_rest": len(rest), "steps": steps,
            "sha256": sorted({d[:16] for d in digests.values()}),
            "replacements_at_rest": sum(1 for g in rest if g.incarnation),
        }
        checks["recovered"] = {
            "ok": not never and not crashed,
            "kills": len(kills),
            "recover_s": [k["recover_s"] for k in kills],
        }
        checks["losses_finite"] = {"ok": harness.losses_finite(records)}

        goodput = harness.commit_aligned_rate(
            records, t0, t_end, rows * model.seq_len
        )
        rate = goodput["tokens_per_s"] / ctx.chips
        notes.append(f"goodput over {goodput['steps']} committed steps in "
                     f"{goodput['span_s']:.2f}s between commits")
        sinks = []
        for g in everyone:
            sinks.append(dict(
                g.end_snapshot or g.snapshots(), start=g.start_snapshot,
                replacement=g.incarnation > 0,
            ))
        for k in kills:
            notes.append(
                f"kill g{k['gid']} at {k['t_kill'] - t0:.2f}s: recover_s "
                f"{k['recover_s']}, survivor_stall_s {k['survivor_stall_s']}"
            )
        return {
            "checks": checks,
            "notes": notes,
            "attempted": len(in_window),
            "failed": failed,
            "end_to_end": {
                "goodput_tokens_per_s": rate,
                "peak_hbm_gib": harness.peak_hbm_bytes(ctx.devices) / 2**30,
                "setup_s": setup_s,
            },
            "records": records,
            "ft_tokens_per_s_per_chip": rate,
            "bare": bare,
            "boot_s": ctx.boot_s,
            "first_step_s": first_step_s,
            "compiles_in_window": compiles_in_window,
            "sinks": sinks,
            "flops_per_token": fam.flops_per_token(model),
            "kills": kills,
        }
    finally:
        for group in list(cohort.live.values()) + list(cohort.dead):
            try:
                group.teardown()
            except Exception:  # noqa: BLE001 — already torn down
                pass
        lighthouse.shutdown()
