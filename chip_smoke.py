"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run it from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # stages kernels, landing, ft, procs
    python chip_smoke.py --xla-plane  # stage ft over Manager(comm_backend="xla")
    python chip_smoke.py --landing    # stage landing alone

It drives the fault-tolerant training loop once, through the entry points a
user calls, at the full configured width of ``CONFIGS["125m"]`` with seeded
random weights, and checks what comes out by the repo's own means:

``kernels``  the Mosaic flash-attention kernels (forward and backward, the
             resident and the streamed regime, at the heads of the 125m/350m
             and the 1b presets) against ``ops.attention.reference_attention``,
             and the chunked cross entropy against the dense one.
``landing``  one group on one chip averages a gradient tree of the 111m
             benchmark cell's bucket sizes through a one-arena
             ``DistributedDataParallel``; the arena is overwritten with
             NaN the moment each step's future resolves, and the landed
             leaves must still equal the reduced values bit for bit, with
             every byte landed straight from the arena
             (``ddp_land_borrowed_bytes``).
``ft``       every chip of the machine in ONE process: ``max(2, chips)``
             replica groups as threads, group g pinned to chip g mod chips,
             each with its own StoreServer, Manager, DistributedDataParallel
             and OptimizerWrapper, through solo → join+heal → all → kill →
             survivors → relaunch+heal → all.
``procs``    (two or more chips) one worker PROCESS per chip, started with
             ``launcher.hsdp_spec`` + ``launch_local``, through a real SIGKILL,
             a relaunch and a heal over HTTP.

A chip belongs to one process at a time, so this parent never initialises a
jax backend: it builds the native library, starts the native Lighthouse, and
runs each stage as a child that has exited before the next starts. Every
child asks for the TPU by name and fails without one; there is no CPU path
in this script. Any failed check raises, the stage exits non-zero, and so
does the run. Step and compile seconds are printed as observations beside
the device line; they are not benchmark metrics.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import hashlib
import json
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

_EVT = "EVT "          # worker -> parent: one JSON event per command
_RESULT = "RESULT "    # stage child -> parent: the stage's summary
_POISON_SEED = 99      # a relaunched group's init: only a heal can fix it
_HEAD_SHAPES = ((8, 1024, 12, 64), (4, 2048, 16, 128))  # 125m/350m, 1b
_TIMEOUT_S = 180.0     # every wait of a Manager, transport or heal


def _log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- the device


def _claim_tpu() -> Dict[str, Any]:
    """Claim the TPU (or fail), place the compile cache, print and return
    the device line every result is read beside."""
    import jax
    import jaxlib

    from torchft_tpu.utils.device import place_compile_cache, require_tpu

    devices = require_tpu()
    cache = place_compile_cache()
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "unknown"
    line = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "compile_cache": cache,
    }
    _log("device " + json.dumps(line))
    return line


class _CompileCounter:
    """Counts, per thread, the programs jax hands to the backend compiler
    (one per jit-cache miss, whether the persistent cache then serves it
    or not), and process-wide the persistent cache's hits and misses."""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self._by_thread: Dict[int, int] = {}
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw: Any) -> None:
        if event == self._BACKEND_COMPILE:
            ident = threading.get_ident()
            with self._lock:
                self._by_thread[ident] = self._by_thread.get(ident, 0) + 1

    def _on_event(self, event: str, **_kw: Any) -> None:
        with self._lock:
            if event == self._HIT:
                self.cache_hits += 1
            elif event == self._MISS:
                self.cache_misses += 1

    def mine(self) -> int:
        with self._lock:
            return self._by_thread.get(threading.get_ident(), 0)


_COUNTER: Optional[_CompileCounter] = None


def _compile_counter() -> _CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    return _COUNTER


# ------------------------------------------------------- one replica group


class ReplicaGroup:
    """One replica group's fault-tolerant training loop, through the
    public entry points only: StoreServer, Manager, CheckpointServer,
    DistributedDataParallel, OptimizerWrapper, make_grad_step,
    make_train_step. Everything it owns lives on ``device``; every
    committed step re-asserts that."""

    def __init__(self, gid: int, cfg_name: str, device: Any, batch: int,
                 lighthouse_addr: str, seed: int = 0,
                 comm_options: Optional[Dict[str, Any]] = None,
                 timeout: float = _TIMEOUT_S, store_port: int = 0) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from torchft_tpu import (
            DistributedDataParallel,
            Manager,
            OptimizerWrapper,
            TcpCommContext,
        )
        from torchft_tpu.checkpointing import CheckpointServer
        from torchft_tpu.comm.store import StoreServer
        from torchft_tpu.models import (
            CONFIGS,
            init_params,
            loss_fn,
            make_grad_step,
            make_train_step,
        )

        self.gid = gid
        self.device = device
        self.healed = 0
        self._warm: set = set()
        self._last_avg: Any = None
        self._last_loss: Any = None
        cfg = CONFIGS[cfg_name]
        tx = optax.adamw(1e-3)

        # Seeded state, made on and committed to this group's chip by its
        # owner — the one placement the library cannot make for the caller.
        # One fixed batch per group: overfitting it is what makes the loss
        # on a fixed batch fall within a few steps.
        tokens = np.random.default_rng(1000 + gid).integers(
            0, cfg.vocab_size, (batch, cfg.max_seq_len)
        )
        with jax.default_device(device):
            params = init_params(cfg, jax.random.key(seed))
            self.state = jax.device_put(
                {"params": params, "opt": tx.init(params)}, device
            )
            self.tokens = jax.device_put(
                jnp.asarray(tokens, jnp.int32), device
            )
            self.targets = jnp.roll(self.tokens, -1, axis=1)

        def state_dict() -> Dict[str, Any]:
            return dict(self.state)

        def load_state_dict(sd: Dict[str, Any]) -> None:
            self.state.update(sd)
            self.healed += 1

        self.store = StoreServer(port=store_port)
        plane: Dict[str, Any] = (
            {"comm": TcpCommContext(timeout=timeout)}
            if comm_options is None
            else {"comm_backend": "xla", "comm_options": dict(comm_options)}
        )
        self.manager = Manager(
            load_state_dict=load_state_dict,
            state_dict=state_dict,
            # template_fn: the heal lands each leaf on the device (and in
            # the sharding) of the healer's own leaf, not as a host array
            checkpoint_transport=CheckpointServer(
                timeout=timeout,
                template_fn=lambda: {
                    "user": state_dict(),
                    "torchft": {"step": 0, "batches_committed": 0},
                },
            ),
            min_replica_size=1,
            timeout=timeout, quorum_timeout=timeout, connect_timeout=timeout,
            rank=0, world_size=1,
            store_addr=self.store.addr,
            lighthouse_addr=lighthouse_addr,
            replica_id=f"chip_smoke_{gid}_",
            **plane,
        )
        self.ddp = DistributedDataParallel(self.manager)
        self.opt = OptimizerWrapper(
            self.manager, tx,
            state_fn=lambda: (self.state["params"], self.state["opt"]),
        )
        self.grad_step = make_grad_step(cfg)
        self.fused = make_train_step(cfg, tx, donate=True)
        self._eval = jax.jit(
            lambda p, t, y: loss_fn(cfg, p, t, y)
        )

    # -- the loop body -------------------------------------------------------

    def step(self, sync: bool = True) -> Dict[str, Any]:
        """One step of the user's loop (examples/train_ddp.py): quorum,
        then the donated fused program on a solo wire or grad → average →
        gated update otherwise. Returns what the scenario asserts on."""
        import jax

        counter = _compile_counter()
        c0, t0 = counter.mine(), time.perf_counter()
        self.opt.begin_step()
        if self.opt.can_fuse():
            path = "fused"
            params, opt_state, loss, committed = self.opt.fused_step(
                self.fused, self.state["params"], self.state["opt"],
                self.tokens, self.targets,
            )
        else:
            path = "classic"
            loss, grads = self.grad_step(
                self.state["params"], self.tokens, self.targets
            )
            self._last_avg = self.ddp.average_gradients(grads)
            del grads  # half a GB at 125m; two groups may share a chip
            params, opt_state, committed = self.opt.step(
                self.state["params"], self.state["opt"], self._last_avg
            )
        if committed:
            self.state["params"], self.state["opt"] = params, opt_state
            self._last_loss = loss
        if sync:
            jax.block_until_ready(self.state)
        wall = time.perf_counter() - t0
        compiles = counter.mine() - c0
        first = path not in self._warm
        if committed:
            self._warm.add(path)
            self.check_resident()
        return {
            "gid": self.gid,
            "committed": bool(committed),
            "step": self.manager.current_step(),
            "participants": self.manager.num_participants(),
            "path": path,
            "healed": bool(committed and self.manager.did_heal()),
            "first": first,
            "compiles": compiles,
            "wall_s": round(wall, 3),
            "hbm_gb": round(
                (self.device.memory_stats() or {}).get("bytes_in_use", 0)
                / 2**30, 2
            ),
        }

    def check_resident(self) -> None:
        """Every leaf of params, optimizer state and the last averaged
        gradients lives on this group's device and nowhere else."""
        import jax

        for name, tree in (("params", self.state["params"]),
                           ("opt", self.state["opt"]),
                           ("avg_grads", self._last_avg)):
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
                where = leaf.devices()
                if where != {self.device}:
                    raise AssertionError(
                        f"group {self.gid}: {name}"
                        f"{jax.tree_util.keystr(path)} lives on {where}, "
                        f"not on {self.device} alone"
                    )
        self._last_avg = None  # checked; free the HBM

    def digest(self, full: bool) -> str:
        """sha256 over the bytes of params (and, if ``full``, optimizer
        state): equal digests are bitwise-equal states."""
        import jax
        import numpy as np

        tree = self.state if full else self.state["params"]
        h = hashlib.sha256()
        for leaf in jax.device_get(jax.tree_util.tree_leaves(tree)):
            a = np.ascontiguousarray(leaf)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(a.view(np.uint8).reshape(-1))
        return h.hexdigest()

    def eval_loss(self) -> float:
        """Loss of the current params on this group's fixed batch."""
        return float(
            self._eval(self.state["params"], self.tokens, self.targets)
        )

    def fence_observation(self, steps: int) -> Dict[str, float]:
        """The same ``steps``-step window of the donated fused path, timed
        once to ``jax.block_until_ready`` and once to a scalar readback
        (recorded for ROADMAP S2; nothing here depends on the answer)."""
        import jax

        out = {}
        for name in ("block_until_ready", "scalar_device_get"):
            jax.block_until_ready(self.state)
            t0 = time.perf_counter()
            for _ in range(steps):
                evt = self.step(sync=False)
                if evt["path"] != "fused" or not evt["committed"]:
                    raise AssertionError(f"fence window left the fused path: {evt}")
            if name == "block_until_ready":
                jax.block_until_ready(self.state)
            else:  # the last step's loss: an output of the donated program
                float(jax.device_get(self._last_loss))
            out[name + "_s"] = round(time.perf_counter() - t0, 4)
        return out

    def report(self) -> Dict[str, Any]:
        stats = self.device.memory_stats() or {}
        counter = _compile_counter()
        return {
            "gid": self.gid,
            "device": str(self.device),
            "fused_steps": self.opt.fused_steps,
            "classic_steps": self.opt.classic_steps,
            "heals": self.healed,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            # process-wide: the persistent compilation cache
            "compile_cache_hits": counter.cache_hits,
            "compile_cache_misses": counter.cache_misses,
        }

    def shutdown(self) -> None:
        """Stop serving and free the state (the in-process 'kill': the
        group's servers and sockets close, its HBM is released at once —
        deleted, not left to the garbage collector, because the next
        incarnation needs the room on a shared chip)."""
        import jax

        self.manager.shutdown(wait=False)
        self.store.shutdown()
        for leaf in jax.tree_util.tree_leaves(
            (self.state, self._last_avg, self.tokens, self.targets)
        ):
            leaf.delete()
        self.state = {}
        self._last_avg = None


# ----------------------------------------------------------------- cohorts
# The scenario talks to its replica groups through five verbs — start,
# send, collect, kill, close — so that the same legs and the same
# assertions run over threads in one process (stage ft) and over one
# process per chip (stage procs).


class ThreadCohort:
    """Replica groups as threads of this process, group g on
    ``devices[g % len(devices)]``."""

    def __init__(self, cfg_name: str, devices: Sequence[Any], batch: int,
                 lighthouse_addr: str,
                 comm_options: Optional[Dict[str, Any]] = None,
                 timeout: float = _TIMEOUT_S) -> None:
        self._make = lambda gid, seed: ReplicaGroup(
            gid, cfg_name, devices[gid % len(devices)], batch,
            lighthouse_addr, seed=seed, comm_options=comm_options,
            timeout=timeout,
        )
        self._groups: Dict[int, ReplicaGroup] = {}
        self._answers: Dict[int, Any] = {}
        self._pool = ThreadPoolExecutor(
            max_workers=64, thread_name_prefix="chip_smoke_group"
        )
        self._timeout = timeout * 1.5  # a step's own waits end at timeout

    def start(self, gids: Sequence[int], poisoned: bool = False) -> None:
        seed = _POISON_SEED if poisoned else 0
        made = self._pool.map(lambda g: (g, self._make(g, seed)), gids)
        self._groups.update(dict(made))

    def send(self, gids: Sequence[int], cmd: str, **kw: Any) -> None:
        for g in gids:
            self._answers[g] = self._pool.submit(
                getattr(self._groups[g], cmd), **kw
            )

    def collect(self, gids: Sequence[int]) -> Dict[int, Any]:
        return {
            g: self._answers.pop(g).result(timeout=self._timeout)
            for g in gids
        }

    def kill(self, gid: int) -> None:
        self._groups.pop(gid).shutdown()
        gc.collect()  # its programs and fences sit in reference cycles

    def close(self) -> None:
        for gid in list(self._groups):
            self.kill(gid)
        self._pool.shutdown(wait=False)


class ProcCohort:
    """One worker process per replica group, each on its own chip: specs
    from ``launcher.hsdp_spec``, processes from ``launcher.launch_local``,
    commands as JSON lines on the worker's stdin, one ``EVT`` line back.
    The parent that owns this cohort never touches a jax backend."""

    def __init__(self, cfg_name: str, n_groups: int, batch: int,
                 lighthouse_addr: str, timeout: float = _TIMEOUT_S) -> None:
        from torchft_tpu.launcher import hsdp_spec

        store_base, manager_base = _free_port_blocks(n_groups)
        self._specs = hsdp_spec(
            script=os.path.abspath(__file__),
            num_replica_groups=n_groups,
            lighthouse_addr=lighthouse_addr,
            base_manager_port=manager_base,
            base_store_port=store_base,
            script_args=["--child", "worker", "--cfg", cfg_name,
                         "--batch", str(batch), "--timeout", str(timeout)],
        )
        self._procs: Dict[int, subprocess.Popen] = {}
        self._lines: Dict[int, "queue.Queue[Optional[str]]"] = {}
        self._timeout = timeout * 1.5
        self.chips: Dict[int, Dict[str, Any]] = {}

    def start(self, gids: Sequence[int], poisoned: bool = False) -> None:
        from torchft_tpu.launcher import launch_local

        specs = []
        for g in gids:
            spec = self._specs[g]
            if poisoned:
                spec = dataclasses.replace(
                    spec, cmd=spec.cmd + ["--seed", str(_POISON_SEED)]
                )
            specs.append(spec)
        procs = launch_local(
            specs, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True
        )
        for g, proc in zip(gids, procs):
            self._procs[g] = proc
            self._lines[g] = queue.Queue()
            threading.Thread(
                target=self._pump, args=(g, proc, self._lines[g]),
                daemon=True, name=f"chip_smoke_pump_{g}",
            ).start()
        # each worker answers once it holds its chip and its Manager is up
        for g in gids:
            self.chips[g] = self._wait(g)

    @staticmethod
    def _pump(gid: int, proc: subprocess.Popen,
              lines: "queue.Queue[Optional[str]]") -> None:
        for line in proc.stdout:  # type: ignore[union-attr]
            if line.startswith(_EVT):
                lines.put(line[len(_EVT):])
            else:
                _log(f"[worker {gid}] {line.rstrip()}")
        lines.put(None)

    def _wait(self, gid: int) -> Any:
        try:
            line = self._lines[gid].get(timeout=self._timeout)
        except queue.Empty:
            raise TimeoutError(f"worker {gid} did not answer") from None
        if line is None:
            raise RuntimeError(
                f"worker {gid} exited (code {self._procs[gid].wait()}) "
                "before answering"
            )
        return json.loads(line)

    def send(self, gids: Sequence[int], cmd: str, **kw: Any) -> None:
        for g in gids:
            stdin = self._procs[g].stdin
            stdin.write(json.dumps({"cmd": cmd, **kw}) + "\n")  # type: ignore[union-attr]
            stdin.flush()  # type: ignore[union-attr]

    def collect(self, gids: Sequence[int]) -> Dict[int, Any]:
        return {g: self._wait(g) for g in gids}

    def kill(self, gid: int) -> None:
        """A real SIGKILL: the worker's manager server, store, checkpoint
        server and sockets die with it, mid-whatever, with no clean-up."""
        proc = self._procs.pop(gid)
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    def close(self) -> None:
        for proc in self._procs.values():
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        self._procs.clear()


def _free_port_blocks(n: int) -> List[int]:
    """Two runs of ``n`` consecutive free TCP ports (group stores, manager
    servers): the launcher numbers a group's ports base + group id."""
    bases: List[int] = []
    port = 20000 + (os.getpid() * 7) % 20000
    while len(bases) < 2:
        held = []
        try:
            for p in range(port, port + n):
                s = socket.socket()
                held.append(s)
                s.bind(("127.0.0.1", p))
            bases.append(port)
        except OSError:
            pass
        finally:
            for s in held:
                s.close()
        port += n
    return bases


# ------------------------------------------------------------ the scenario


def scenario_lighthouse() -> Any:
    """The native Lighthouse a scenario runs under (one per stage: no
    stage sees the last one's dead replicas). The join timeout must
    outlast the head start a round gives its joiners (``one_round``), or
    three joiners of four form a majority quorum of their own; a killed
    group is dropped by its heartbeat timeout instead."""
    from torchft_tpu.control import Lighthouse

    return Lighthouse(
        min_replicas=1, join_timeout_ms=30000, heartbeat_timeout_ms=2000
    )


def run_scenario(cohort: Any, n_groups: int, steps_per_leg: int,
                 solo_first: bool, fence_window: int = 0,
                 log: Callable[[str], None] = _log) -> Dict[str, Any]:
    """Drive ``cohort`` through the fault-tolerance legs in lock-step
    rounds (every live group takes one step per round, then all are at
    rest and can be compared) and assert after every round what must hold.
    Returns the summary; raises ``AssertionError`` on the first violation.

    solo_first: group 0 runs alone first (the solo wire and its donated
    fused step) and the others join behind it and heal; otherwise all
    groups start together at step 0 (where all but one heal from the
    first, the quorum's way of making initial states identical).
    """
    everyone = list(range(n_groups))
    victim = n_groups - 1
    rounds: List[Dict[str, Any]] = []
    heals: List[Dict[str, Any]] = []
    summary: Dict[str, Any] = {"groups": n_groups, "legs": []}

    def ask(gids: Sequence[int], cmd: str, **kw: Any) -> Dict[int, Any]:
        cohort.send(gids, cmd, **kw)
        return cohort.collect(gids)

    def one_round(leg: str, live: Sequence[int],
                  first: Sequence[int] = ()) -> Dict[int, Dict[str, Any]]:
        # A group that is new to the quorum asks first: the lighthouse
        # serves the last quorum's members the moment they have all asked
        # again, and admits whoever else has asked by then.
        if first:
            cohort.send(first, "step")
            time.sleep(1.0)
        cohort.send([g for g in live if g not in first], "step")
        evts = cohort.collect(live)
        digests = ask(live, "digest", full=False)
        rounds.append({"leg": leg, "events": evts})
        log(
            f"round {len(rounds):2d} {leg:<9} "
            + " ".join(
                f"g{g}:{'C' if e['committed'] else '-'}"
                f"{e['step']}/p{e['participants']}/{e['path'][0]}"
                f"{'/healed' if e['healed'] else ''}"
                f"/{e['wall_s']}s/c{e['compiles']}/{e['hbm_gb']}G"
                for g, e in sorted(evts.items())
            )
        )
        # the commit is a vote: all or none
        votes = {e["committed"] for e in evts.values()}
        assert len(votes) == 1, f"{leg}: split commit decision {evts}"
        if votes == {True}:
            steps = {e["step"] for e in evts.values()}
            assert len(steps) == 1, f"{leg}: groups at different steps {evts}"
            assert len(set(digests.values())) == 1, (
                f"{leg}: params differ after common step {steps}: {digests}"
            )
            healed = sorted(g for g, e in evts.items() if e["healed"])
            if healed:
                # healed state == the donor's at the same step, bitwise:
                # params AND optimizer state of every live group agree
                full = ask(live, "digest", full=True)
                assert len(set(full.values())) == 1, (
                    f"{leg}: healed state differs from the donor's: {full}"
                )
                heals.extend(
                    {"leg": leg, "group": g, "step": steps.copy().pop(),
                     "sha256": full[g][:16]}
                    for g in healed
                )
        for e in evts.values():
            assert e["first"] or e["compiles"] == 0, (
                f"{leg}: compilation after the {e['path']} path's first "
                f"step: {e}"
            )
        return evts

    def leg(name: str, live: Sequence[int], participants: int,
            joining: Sequence[int] = ()) -> None:
        """``steps_per_leg`` rounds in which every live group commits
        with exactly ``participants``, after ``joining`` (if any) have
        been admitted and have healed; a few rounds of slack for the
        quorum to settle after a membership change."""
        good, waiting = 0, set(joining)
        for _ in range(steps_per_leg + 4):
            evts = one_round(name, live, first=sorted(waiting))
            waiting -= {g for g in waiting if evts[g]["healed"]}
            if not waiting and all(
                e["committed"] and e["participants"] == participants
                for e in evts.values()
            ):
                good += 1
                if good == steps_per_leg:
                    break
        assert not waiting, f"{name}: groups {sorted(waiting)} never healed"
        assert good == steps_per_leg, (
            f"leg {name}: {good} of {steps_per_leg} rounds committed with "
            f"{participants} participants"
        )
        summary["legs"].append(
            {"leg": name, "live": len(live), "participants": participants,
             "commits": good, "healed": sorted(joining)}
        )

    cohort.start([0] if solo_first else everyone)
    loss0 = ask([0], "eval_loss")[0]
    if solo_first:
        leg("solo", [0], 1)
        if fence_window:
            summary["fence_observation"] = ask(
                [0], "fence_observation", steps=fence_window
            )[0]
            log(f"fence observation {summary['fence_observation']}")
        cohort.start(everyone[1:])
    leg("all", everyone, n_groups,
        joining=everyone[1:] if solo_first else ())
    cohort.kill(victim)
    log(f"killed group {victim}")
    leg("survivors", everyone[:victim], n_groups - 1)
    cohort.start([victim], poisoned=True)
    leg("all_again", everyone, n_groups, joining=[victim])
    loss1 = ask([0], "eval_loss")[0]

    reports = ask(everyone, "report")
    events = [e for r in rounds for e in r["events"].values()]

    def step_seconds(first: bool, pick: Callable[..., float]) -> Dict[str, float]:
        return {
            p: pick(e["wall_s"] for e in events
                    if e["first"] == first and e["path"] == p)
            for p in sorted({e["path"] for e in events if e["first"] == first})
        }

    summary.update(
        rounds=len(rounds),
        heals=heals,
        loss_fixed_batch=[round(loss0, 4), round(loss1, 4)],
        fused_steps=sum(r["fused_steps"] for r in reports.values()),
        classic_steps=sum(r["classic_steps"] for r in reports.values()),
        # observations, not metrics: the slowest first step of each path
        # (it compiles) and the fastest later one
        first_step_s=step_seconds(True, max),
        warm_step_s=step_seconds(False, min),
        reports=[reports[g] for g in everyone],
    )
    assert loss1 < loss0, (
        f"loss on the fixed batch did not fall: {loss0} -> {loss1}"
    )
    assert summary["classic_steps"] > 0, "the classic path never ran"
    if solo_first:
        assert summary["fused_steps"] > 0, "the fused path never ran"
    assert victim in {h["group"] for h in heals if h["leg"] == "all_again"}
    return summary


def run_ft_scenario(cfg_name: str, devices: Sequence[Any], n_groups: int,
                    batch: int, steps_per_leg: int, lighthouse_addr: str,
                    comm_options: Optional[Dict[str, Any]] = None,
                    fence_window: int = 0, timeout: float = _TIMEOUT_S,
                    log: Callable[[str], None] = _log) -> Dict[str, Any]:
    """Stage ``ft``: the scenario over replica-group threads in this
    process, one group per device (round-robin when there are more groups
    than devices). Importable: the CPU tests drive it at ``tiny``."""
    cohort = ThreadCohort(
        cfg_name, devices, batch, lighthouse_addr,
        comm_options=comm_options, timeout=timeout,
    )
    try:
        return run_scenario(
            cohort, n_groups, steps_per_leg, solo_first=True,
            fence_window=fence_window, log=log,
        )
    finally:
        cohort.close()


# ------------------------------------------------------------- the children


def _stage_kernels(_args: argparse.Namespace) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.transformer import ce_from_hidden
    from torchft_tpu.ops.attention import causal_attention, reference_attention
    from torchft_tpu.ops.flash import flash_attention

    def absmax(x: Any) -> float:
        return float(jnp.max(jnp.abs(x.astype(jnp.float32))))

    def errmax(a: Any, b: Any) -> float:
        return absmax(a.astype(jnp.float32) - b.astype(jnp.float32))

    checks = []
    for shape in _HEAD_SHAPES:
        q, k, v, cot = (
            jax.random.normal(key, shape, jnp.bfloat16)
            for key in jax.random.split(jax.random.key(0), 4)
        )

        def ref_loss(q: Any, k: Any, v: Any) -> Any:
            out = reference_attention(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32))

        ref_out = jax.jit(
            lambda q, k, v: reference_attention(q, k, v, causal=True)
        )(q, k, v)
        ref_grads = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
        for regime, kw in (("resident", {}),
                           ("streamed", {"_resident_kv_bytes": 0})):
            def flash(q: Any, k: Any, v: Any) -> Any:
                return flash_attention(q, k, v, causal=True, **kw)

            def flash_loss(q: Any, k: Any, v: Any) -> Any:
                return jnp.sum(
                    flash(q, k, v).astype(jnp.float32)
                    * cot.astype(jnp.float32)
                )

            t0 = time.perf_counter()
            fwd = jax.jit(flash)
            mosaic = "tpu_custom_call" in fwd.lower(q, k, v).as_text()
            out = fwd(q, k, v)
            grads = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
            # bf16 carries 8 bits: two hundredths of the largest value
            errs = [errmax(out, ref_out)] + [
                errmax(g, r) for g, r in zip(grads, ref_grads)
            ]
            tols = [0.02 * max(1.0, absmax(ref_out))] + [
                0.02 * max(1.0, absmax(r)) for r in ref_grads
            ]
            check = {
                "kernel": "flash", "shape": list(shape), "regime": regime,
                "mosaic": mosaic,
                "err_out_dq_dk_dv": [round(e, 5) for e in errs],
                "tol": [round(t, 5) for t in tols],
                "seconds": round(time.perf_counter() - t0, 1),
            }
            _log("kernels " + json.dumps(check))
            assert mosaic, f"flash did not lower to a Mosaic kernel: {check}"
            assert all(e <= t for e, t in zip(errs, tols)), check
            checks.append(check)
    # what the models call must be that same kernel here
    q = jnp.zeros(_HEAD_SHAPES[0], jnp.bfloat16)
    assert "tpu_custom_call" in jax.jit(causal_attention).lower(
        q, q, q
    ).as_text(), "causal_attention did not choose the Mosaic kernel on a TPU"

    # chunked cross entropy (125m's head of 32768 rows, the fused sweep over
    # 8 tiles of 256 rows of logits) vs dense
    kh, kw_, kt = jax.random.split(jax.random.key(1), 3)
    h = jax.random.normal(kh, (2, 1024, 768), jnp.bfloat16)
    w = jax.random.normal(kw_, (768, 32768), jnp.float32) * 0.03
    t = jax.random.randint(kt, (2, 1024), 0, 32768)
    losses, grads = {}, {}
    for chunks in (8, 0):
        losses[chunks], grads[chunks] = jax.jit(jax.value_and_grad(
            lambda h, w: ce_from_hidden(h, w, t, chunks), argnums=(0, 1)
        ))(h, w)
    errs = [abs(float(losses[8]) - float(losses[0]))] + [
        errmax(a, b) for a, b in zip(grads[8], grads[0])
    ]
    check = {"kernel": "xent_chunked_vs_dense", "loss": float(losses[8]),
             "err_loss_dh_dw": errs, "tol": 1e-4}
    _log("kernels " + json.dumps(check))
    assert all(e <= 1e-4 for e in errs), check
    checks.append(check)
    return {"checks": len(checks)}


# Cerebras-GPT-111M's gradient tree (the benchmark's four-group cell): 13
# buckets of the default 32 MiB plan, 598.6 MB, the two tables 154.5 MB each.
_LANDING_SHAPES: Dict[str, Any] = {
    "wte": (50304, 768), "wpe": (2048, 768),
    **{f"h{i}": {"ln1": (2, 768), "qkv": (768, 2304), "proj": (768, 768),
                 "ln2": (2, 768), "fc": (768, 3072), "out": (3072, 768)}
       for i in range(10)},
    "ln_f": (2, 768), "lm_head": (768, 50304),
}


class _HalvingWire:
    """The surface ``DistributedDataParallel`` needs of a Manager, over a
    wire of one: every bucket comes back halved IN PLACE (the donation
    contract) from a thread of its own, as from a transport lane."""

    def __init__(self) -> None:
        from torchft_tpu.utils.metrics import Metrics

        self.metrics = Metrics()

    def wait_quorum(self) -> None:
        pass

    def is_solo_wire(self) -> bool:
        return False

    def allreduce_arrays(self, arrays: Sequence[Any]) -> Any:
        from concurrent.futures import Future

        from torchft_tpu.comm.context import Work

        fut: "Future[List[Any]]" = Future()
        fut.set_running_or_notify_cancel()
        arrays = list(arrays)

        def _reduce() -> None:
            for a in arrays:
                a *= a.dtype.type(0.5)
            fut.set_result(arrays)

        threading.Thread(target=_reduce, daemon=True).start()
        return Work(fut)


def run_landing_check(device: Any, shapes: Any = None,
                      steps: int = 3) -> Dict[str, Any]:
    """Average a seeded tree on ``device`` ``steps`` times through ONE
    staging arena, poisoning the arena the moment each step's future
    resolves; every landed leaf must equal half its gradient bit for bit.
    Returns the sink's landing counters and, as observations, a step's
    summed landing seconds beside the same views landed leaf by leaf
    through ``land_like``'s host copy."""
    import jax
    import numpy as np

    from torchft_tpu.ddp import DistributedDataParallel
    from torchft_tpu.utils.device import land_like

    shapes = _LANDING_SHAPES if shapes is None else shapes
    rng = np.random.default_rng(36)
    host = jax.tree_util.tree_map(
        lambda shape: rng.standard_normal(shape, dtype=np.float32),
        shapes, is_leaf=lambda x: isinstance(x, tuple),
    )
    grads = jax.device_put(host, device)
    wire = _HalvingWire()
    ddp = DistributedDataParallel(wire, staging_arenas=1)

    poisoned = threading.Event()

    def poison(_fut: Any) -> None:
        for buf in ddp._arenas[0].staging:
            buf.fill(np.nan)
        poisoned.set()

    for step in range(steps):
        poisoned.clear()
        fut = ddp.average_gradients_async(grads)
        # on the resolving thread, the moment the arena may be reused
        fut.add_done_callback(poison)
        out = fut.result(timeout=_TIMEOUT_S)
        assert poisoned.wait(_TIMEOUT_S)    # not into the next step's pack
        for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(out),
            jax.tree_util.tree_leaves(host),
        ):
            assert got.devices() == {device}, (path, got.devices())
            assert np.array_equal(
                np.asarray(got).view(np.uint32),
                (want * np.float32(0.5)).view(np.uint32),
            ), f"step {step}: landed leaf {path} is not the reduced value"
    snap = wire.metrics.snapshot()

    # observation: the same bytes through the copying landing, one thread
    leaves = jax.tree_util.tree_leaves(grads)
    t0 = time.perf_counter()
    jax.block_until_ready([
        land_like(h, like)
        for h, like in zip(jax.tree_util.tree_leaves(host), leaves)
    ])
    copied_s = time.perf_counter() - t0
    nbytes = sum(h.nbytes for h in jax.tree_util.tree_leaves(host))
    return {
        "device": str(device), "steps": steps, "bytes_per_step": nbytes,
        "buckets": len(ddp._plan.buckets),
        "borrowed_bytes": int(snap["ddp_land_borrowed_bytes"]),
        "copied_bytes": int(snap["ddp_land_copied_bytes"]),
        "land_workers": int(snap["ddp_land_workers"]),
        "h2d_total_p50_ms": round(snap["ddp_h2d_total_p50_ms"], 1),
        "land_like_ms": round(copied_s * 1e3, 1),
    }


def _stage_landing(_args: argparse.Namespace) -> Dict[str, Any]:
    import jax

    result = run_landing_check(jax.devices()[0])
    assert result["copied_bytes"] == 0 and result["borrowed_bytes"] == \
        result["steps"] * result["bytes_per_step"], (
        f"on a TPU every byte lands straight from the arena: {result}"
    )
    assert result["land_workers"] == 2, result
    return result


def _ft_batch(n_groups: int, n_chips: int) -> int:
    """Rows per group: 8 on a chip of its own, 4 when two groups share the
    16 GB of one (each holds params, optimizer state, gradients, averaged
    gradients and, on the classic path, the update's second copy)."""
    return 8 if n_groups <= n_chips else 4


def _stage_ft(args: argparse.Namespace) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    n_groups = max(2, len(devices))
    batch = _ft_batch(n_groups, len(devices))
    planes: List[Optional[Dict[str, Any]]] = (
        [{"algorithm": "psum"},
         {"algorithm": "psum", "compression": "int8"}]
        if args.xla_plane else [None]
    )
    out: Dict[str, Any] = {}
    for comm_options in planes:
        name = "host" if comm_options is None else "xla:" + "+".join(
            str(v) for v in comm_options.values()
        )
        _log(f"ft plane={name} groups={n_groups} chips={len(devices)} "
             f"batch_per_group={batch} cfg={args.cfg}")
        summary = run_ft_scenario(
            args.cfg, devices, n_groups, batch, args.steps_per_leg,
            args.lighthouse, comm_options=comm_options,
            fence_window=0 if args.xla_plane else 3, timeout=args.timeout,
        )
        peaks = {
            str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices
        }
        summary["peak_bytes_in_use"] = peaks
        _log(f"ft plane={name} peak_bytes_in_use " + json.dumps(peaks))
        out[name] = summary
    return out


def _held_chip_files() -> List[str]:
    """The accelerator device files this process holds open — the physical
    identity of 'its' chip (jax renumbers a lone visible chip to id 0)."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.startswith(("/dev/accel", "/dev/vfio/")) and \
                target != "/dev/vfio/vfio":
            held.add(target)
    return sorted(held)


def _child_worker(args: argparse.Namespace) -> None:
    """Stage ``procs`` worker: one replica group in a process of its own,
    on the one chip the launcher's environment leaves visible; takes the
    cohort's commands on stdin until it closes."""
    import jax

    device_line = _claim_tpu()
    devices = jax.devices()
    assert len(devices) == 1, (
        f"a worker must see exactly its own chip, sees {devices}"
    )
    gid = int(os.environ["REPLICA_GROUP_ID"])
    group = ReplicaGroup(
        gid, args.cfg, devices[0], args.batch,
        os.environ["TORCHFT_TPU_LIGHTHOUSE"], seed=args.seed,
        timeout=args.timeout,
        store_port=int(os.environ["MASTER_PORT"]),
    )
    hello = {
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "chip_files": _held_chip_files(),
        "pid": os.getpid(),
        **device_line,
    }
    print(_EVT + json.dumps(hello), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        answer = getattr(group, cmd.pop("cmd"))(**cmd)
        print(_EVT + json.dumps(answer), flush=True)
    group.shutdown()


def _child_main(args: argparse.Namespace) -> None:
    if args.child == "worker":
        _child_worker(args)
        return
    device_line = _claim_tpu()
    counter = _compile_counter()
    result = {"kernels": _stage_kernels, "landing": _stage_landing,
              "ft": _stage_ft}[args.child](args)
    result["device"] = device_line
    result["compile_cache_hits"] = counter.cache_hits
    result["compile_cache_misses"] = counter.cache_misses
    print(_RESULT + json.dumps(result), flush=True)


# ---------------------------------------------------------------- the parent


def _run_stage(name: str, argv: List[str]) -> Dict[str, Any]:
    """Run one stage as a child that owns the chip(s) until it exits;
    relay its output; return its RESULT. A failed stage ends the run."""
    _log(f"=== stage {name}")
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", name, *argv],
        stdout=subprocess.PIPE, text=True,
    )
    result = None
    try:
        for line in proc.stdout:  # type: ignore[union-attr]
            if line.startswith(_RESULT):
                result = json.loads(line[len(_RESULT):])
            else:
                _log(line.rstrip())
    finally:
        if proc.poll() is None and result is None:
            proc.kill()
        code = proc.wait()
    if code != 0 or result is None:
        raise SystemExit(
            f"chip_smoke: stage {name} failed (exit code {code}); a run "
            "needs a TPU that jax can claim — see the stage's error above"
        )
    _log(f"=== stage {name} ok in {time.perf_counter() - t0:.0f}s: "
         + json.dumps(result))
    return result


def _stage_procs(args: argparse.Namespace, n_chips: int,
                 lighthouse_addr: str) -> Dict[str, Any]:
    _log("=== stage procs")
    t0 = time.perf_counter()
    cohort = ProcCohort(
        args.cfg, n_chips, _ft_batch(n_chips, n_chips), lighthouse_addr,
        timeout=args.timeout,
    )
    try:
        summary = run_scenario(
            cohort, n_chips, args.steps_per_leg, solo_first=False
        )
        chips = cohort.chips
    finally:
        cohort.close()
    _log("procs chips " + json.dumps(chips))
    # Distinct chips: every worker was given another one, saw exactly one
    # (asserted in the worker), and all held theirs at once while stepping
    # together — a chip admits one process. chip_files is printed as the
    # physical evidence, not asserted on: which device files libtpu opens
    # is its own business.
    assigned = [chips[g]["visible_chips"] for g in sorted(chips)]
    assert len(set(assigned)) == n_chips, f"chips assigned twice: {chips}"
    summary["chips"] = chips
    for key in ("compile_cache_hits", "compile_cache_misses"):
        summary[key] = sum(r[key] for r in summary["reports"])
    _log(f"=== stage procs ok in {time.perf_counter() - t0:.0f}s: "
         + json.dumps(summary))
    return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--xla-plane", action="store_true",
                    help="run stage ft alone over Manager(comm_backend="
                         "'xla'), psum uncompressed and int8")
    ap.add_argument("--landing", action="store_true",
                    help="run stage landing alone")
    ap.add_argument("--cfg", default="125m", help=argparse.SUPPRESS)
    ap.add_argument("--steps-per-leg", type=int, default=3,
                    help=argparse.SUPPRESS)
    ap.add_argument("--timeout", type=float, default=_TIMEOUT_S,
                    help=argparse.SUPPRESS)
    # internal: how the parent starts its children
    ap.add_argument("--child",
                    choices=("kernels", "landing", "ft", "worker"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--lighthouse", help=argparse.SUPPRESS)
    ap.add_argument("--batch", type=int, default=8, help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        _child_main(args)
        return

    from torchft_tpu.control import _native

    t0 = time.perf_counter()
    built = _native.ensure_built()
    stamp = _native.built_digest()
    assert stamp == _native.source_digest(), "native library is stale"
    _log(f"native library {'built' if built else 'up to date'}: "
         f"stamp {stamp[:16]} = digest of the sources")

    common = ["--cfg", args.cfg, "--steps-per-leg", str(args.steps_per_leg),
              "--timeout", str(args.timeout)]
    results: Dict[str, Any] = {}
    if args.landing:
        results["landing"] = _run_stage("landing", common)
    elif args.xla_plane:
        lh = scenario_lighthouse()
        try:
            results["ft"] = _run_stage(
                "ft", common + ["--xla-plane", "--lighthouse", lh.address()]
            )
        finally:
            lh.shutdown()
    else:
        results["kernels"] = _run_stage("kernels", common)
        results["landing"] = _run_stage("landing", common)
        lh = scenario_lighthouse()
        try:
            results["ft"] = _run_stage(
                "ft", common + ["--lighthouse", lh.address()]
            )
        finally:
            lh.shutdown()
        n_chips = results["ft"]["device"]["count"]
        if n_chips >= 2:
            lh = scenario_lighthouse()
            try:
                results["procs"] = _stage_procs(args, n_chips, lh.address())
            finally:
                lh.shutdown()
        else:
            _log("=== stage procs not_applicable: 1 chip")
    device = next(iter(results.values()))["device"]
    hits = sum(r.get("compile_cache_hits", 0) for r in results.values())
    misses = sum(r.get("compile_cache_misses", 0) for r in results.values())
    _log(f"chip_smoke ok in {time.perf_counter() - t0:.0f}s on "
         f"{device['count']} x {device['kind']}; stages "
         f"{', '.join(results)}; compile cache {device['compile_cache']}: "
         f"{hits} hits, {misses} misses")
    print(json.dumps({
        "ok": True,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    }), flush=True)


if __name__ == "__main__":
    main()
