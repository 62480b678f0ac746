"""A step program is built once a process (utils/profiling.step_program):
its identity is what it was built from, not the object that asked for
it, so a replica group rebuilt beside its peers runs the programs the
process already holds. Counts only — never a time."""

import dataclasses
import json
import os
import threading
import time

import jax
import numpy as np
import optax
import pytest

from torchft_tpu.models import (
    CONFIGS,
    init_params,
    loss_fn,
    make_grad_step,
    make_train_step,
)
from torchft_tpu.utils import profiling

CFG = dataclasses.replace(CONFIGS["tiny"], xent_chunks=2)


class _Compiles:
    """Traces and backend compiles since ``reset`` (a ``jax.monitoring``
    listener, as ``benchmark/group.CompileCounter``; jax keeps listeners
    for the life of the process, so the module makes one)."""

    _EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/backend_compile_duration": "compiles",
    }

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def reset(self) -> None:
        self.counts = {"traces": 0, "compiles": 0}

    def _on(self, event: str, _secs: float, **_kw) -> None:
        what = self._EVENTS.get(event)
        if what:
            with self._lock:
                self.counts[what] += 1


COMPILES = _Compiles()


def _private_loss():
    """A loss nobody else has: the program made with it is new."""
    return lambda cfg, p, tok, tgt, attn_fn=None: loss_fn(
        cfg, p, tok, tgt, attn_fn)


def _attn(q, k, v):  # never run: the makers only close over it
    return v


# -- identity ----------------------------------------------------------------

_TX, _OTHER_TX = optax.adamw(1e-3), optax.adamw(1e-3)
_LOSS = _private_loss()
_TRAIN = dict(cfg=CFG, tx=_TX, attn_fn=None, donate=True, loss=_LOSS)
_GRAD = dict(cfg=CFG, attn_fn=None, microbatches=1, loss=_LOSS)


_CHANGES = [
    ("same", {}),
    ("equal_cfg", {"cfg": dataclasses.replace(CFG)}),
    ("cfg", {"cfg": dataclasses.replace(CFG, n_layers=1)}),
    ("tx", {"tx": _OTHER_TX}),
    ("donate", {"donate": False}),
    ("microbatches", {"microbatches": 2}),
    ("loss", {"loss": _private_loss()}),
    ("attn_fn", {"attn_fn": _attn}),
]


def _cases():
    for maker, base in ((make_train_step, _TRAIN), (make_grad_step, _GRAD)):
        for name, change in _CHANGES:
            if set(change) <= set(base):    # an argument this maker takes
                yield pytest.param(maker, base, change,
                                   id=f"{maker.__name__}-{name}")


@pytest.mark.parametrize("maker,base,change", list(_cases()))
def test_a_programs_identity_is_what_it_is_built_from(maker, base, change):
    first = maker(**base)
    again = maker(**{**base, **change})
    equal = all(base[k] == v for k, v in change.items())
    assert (again is first) == equal
    assert isinstance(again, profiling.StepProgram)
    # a different program is a different jitted function: jax shares
    # nothing between them, and nothing leaks from one into the other
    assert (again._jitted is first._jitted) == equal


def test_the_optimizer_wrappers_programs_are_keyed_by_tx() -> None:
    from torchft_tpu.optim import OptimizerWrapper, ShardedOptimizerWrapper

    class _Manager:
        model_shards = 1

    tx, other = optax.sgd(0.1), optax.sgd(0.1)
    a, b, c = (OptimizerWrapper(_Manager(), t) for t in (tx, tx, other))
    assert a._update is b._update and a._update_donated is b._update_donated
    assert a._update is not c._update
    assert a._update_donated is not c._update_donated
    assert a._update is not a._update_donated
    assert "update_program_reused" not in a.metrics.snapshot()
    assert b.metrics.snapshot()["update_program_reused"] == 1
    assert "update_program_reused" not in c.metrics.snapshot()
    sa, sb, sc = (ShardedOptimizerWrapper(_Manager(), t)
                  for t in (tx, tx, other))
    assert sa._jit_update is sb._jit_update is not sc._jit_update


def test_an_unhashable_argument_gets_a_fresh_program() -> None:
    class Unhashable:
        __hash__ = None

        def __call__(self, q, k, v):
            return v

    attn = Unhashable()
    before = profiling.program_stats()
    first = make_grad_step(CFG, attn_fn=attn, loss=_LOSS)
    again = make_grad_step(CFG, attn_fn=attn, loss=_LOSS)
    assert first is not again
    after = profiling.program_stats()
    assert after["built"] == before["built"] + 2
    assert after["reused"] == before["reused"]
    assert after["held"] == before["held"]     # and the store never saw it


def test_the_store_is_bounded_and_drops_the_one_asked_for_longest_ago():
    loss = _private_loss()
    size = profiling._STORE_SIZE
    first = make_grad_step(CFG, microbatches=1, loss=loss)
    second = make_grad_step(CFG, microbatches=2, loss=loss)
    for m in range(3, size + 1):
        make_grad_step(CFG, microbatches=m, loss=loss)
    assert profiling.program_stats()["held"] == size
    assert make_grad_step(CFG, microbatches=1, loss=loss) is first
    before = profiling.program_stats()
    make_grad_step(CFG, microbatches=size + 1, loss=loss)   # one too many
    after = profiling.program_stats()
    assert after["held"] == size
    assert (after["built"], after["reused"]) == (
        before["built"] + 1, before["reused"])
    # the first was asked for again and stays; the second went
    assert make_grad_step(CFG, microbatches=1, loss=loss) is first
    assert make_grad_step(CFG, microbatches=2, loss=loss) is not second


# -- what the identity buys: a rebuilt group re-traces nothing ---------------


@jax.jit
def _init(seed):
    params = init_params(CFG, jax.random.key(seed))
    return {"params": params, "opt": _TX.init(params)}


class _Group:
    """The user's loop (``examples/train_ddp.py``) on the classic path:
    state, Manager, DistributedDataParallel, OptimizerWrapper and grad
    step, all built anew, as a replacement's are."""

    def __init__(self, lighthouse_addr: str, seed: int, device) -> None:
        from torchft_tpu import (
            DistributedDataParallel,
            Manager,
            OptimizerWrapper,
            TcpCommContext,
        )
        from torchft_tpu.comm.store import StoreServer

        self.device = device
        self.state = jax.device_put(_init(np.int32(seed)), device)
        self.store = StoreServer()
        self.manager = Manager(
            comm=TcpCommContext(),
            load_state_dict=self.state.update,
            state_dict=lambda: dict(self.state),
            min_replica_size=1, rank=0, world_size=1,
            store_addr=self.store.addr, lighthouse_addr=lighthouse_addr,
            replica_id=f"sp_{seed}_",
        )
        self.ddp = DistributedDataParallel(self.manager)
        self.opt = OptimizerWrapper(self.manager, _TX)
        self.grad_step = make_grad_step(CFG, loss=_LOSS)

    def step(self, i: int):
        tokens = jax.device_put(
            np.full((2, CFG.max_seq_len), i % 7, np.int32), self.device)
        self.opt.begin_step()
        loss, grads = self.grad_step(
            self.state["params"], tokens, tokens)
        avg = self.ddp.average_gradients(grads)
        params, opt_state, committed = self.opt.step(
            self.state["params"], self.state["opt"], avg)
        assert committed
        self.state["params"], self.state["opt"] = params, opt_state
        return jax.block_until_ready(loss)

    def teardown(self) -> None:
        self.manager.shutdown(wait=False)
        self.store.shutdown()


def test_a_group_built_again_on_its_device_traces_and_compiles_nothing():
    from torchft_tpu.control import Lighthouse

    device = jax.devices()[1]
    lighthouse = Lighthouse(min_replicas=1, join_timeout_ms=100)
    groups, lighthouses = [], [lighthouse]
    try:
        groups.append(_Group(lighthouse.address(), 1, device))
        for i in range(2):
            groups[0].step(i)
        groups[0].teardown()
        stats = profiling.program_stats()
        COMPILES.reset()
        groups.append(_Group(lighthouse.address(), 2, device))
        loss = groups[1].step(0)
        assert COMPILES.counts == {"traces": 0, "compiles": 0}
        assert np.isfinite(float(loss))
        after = profiling.program_stats()
        assert after["built"] == stats["built"]
        assert after["reused"] == stats["reused"] + 3   # grad, two updates
        snapshot = groups[1].opt.metrics.snapshot()
        assert snapshot["update_program_reused"] == 1
        # a device the programs have not run on costs a lowering each
        # (grad step, update), not a trace of the Python: the jaxpr is kept
        groups[1].teardown()
        other = Lighthouse(min_replicas=1, join_timeout_ms=100)
        lighthouses.append(other)
        groups.append(_Group(other.address(), 3, jax.devices()[2]))
        COMPILES.reset()
        groups[2].step(0)
        assert COMPILES.counts["compiles"] == 2
        assert COMPILES.counts["traces"] <= 2
    finally:
        for g in groups:
            g.teardown()
        for lh in lighthouses:
            lh.shutdown()


def test_four_threads_making_their_first_call_together_share_one_program():
    loss = _private_loss()
    devices = jax.devices()[:4]
    params = init_params(CFG, jax.random.key(3))
    tokens = np.arange(2 * CFG.max_seq_len, dtype=np.int32).reshape(2, -1) % 97
    gate = threading.Barrier(len(devices))
    programs, results, errors = {}, {}, []
    built = profiling.program_stats()["built"]

    def first_call(i, device):
        try:
            p, t = jax.device_put((params, tokens), device)
            gate.wait(60)
            programs[i] = make_grad_step(CFG, loss=loss)
            results[i] = jax.device_get(programs[i](p, t, t))
        except BaseException as e:  # noqa: BLE001 — shown by the assert
            errors.append(repr(e))

    threads = [threading.Thread(target=first_call, args=(i, d))
               for i, d in enumerate(devices)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not errors and len(results) == len(devices), errors
    assert len({id(p) for p in programs.values()}) == 1
    assert profiling.program_stats()["built"] == built + 1
    value, grads = results[0]
    for other_value, other_grads in list(results.values())[1:]:
        assert np.array_equal(value, other_value)
        for a, b in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(other_grads)):
            assert np.array_equal(a, b)
    assert programs[0]._cache_size() == len(devices)


# -- through the fault-tolerant loop: kill, rejoin ---------------------------


def test_kill_and_rejoin_ends_with_equal_digests_on_reused_programs() -> None:
    """Two groups of the benchmark's ``ReplicaGroup`` on the socket
    plane; one is torn down from outside and replaced at once by new
    objects from another seed on the same device, as the kill cell does.
    The replacement runs the victim's programs and heals to the
    survivor's bits."""
    from benchmark.families import gpt as family
    from benchmark.group import ReplicaGroup
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.control import Lighthouse

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests", "tiny-test.json")
    with open(path) as f:
        model = family.build(json.load(f))
    devices = jax.devices()
    lighthouse = Lighthouse(min_replicas=1, join_timeout_ms=200,
                            heartbeat_timeout_ms=1000)
    stop_at = [None]

    def keep_going(group):
        return stop_at[0] is None or group.manager.current_step() < stop_at[0]

    groups, threads = [], {}

    def start(gid, incarnation, seed):
        source = BatchSource(11, gid, incarnation, model.rows, model.seq_len,
                             model.vocab_draw)
        group = ReplicaGroup(gid, incarnation, model, family, devices[gid],
                             gid, lighthouse.address(), seed, source)
        threads[group] = threading.Thread(
            target=group.run, args=(keep_going,), daemon=True)
        groups.append(group)
        threads[group].start()
        return group

    def wait_for(cond, what):
        deadline = time.monotonic() + 120
        while not cond():
            live = [g for g in groups if not g.torn_down]
            assert all(g.error is None for g in live), [
                repr(g.error) for g in live]
            assert time.monotonic() < deadline, what
            time.sleep(0.02)

    def together(group):
        return sum(1 for r in list(group.records)
                   if r["committed"] and r["participants"] == 2)

    try:
        survivor, victim = start(0, 0, 1), start(1, 0, 1)
        wait_for(lambda: together(survivor) >= 2 and together(victim) >= 2,
                 "classic steps with both groups")
        reused = profiling.program_stats()["reused"]
        victim.teardown()
        replacement = start(1, 1, 99)        # other weights: only a heal
        wait_for(lambda: any(r["committed"] for r in list(replacement.records)),
                 "the replacement's first commit")
        rest = [survivor, replacement]
        stop_at[0] = max(g.manager.current_step() for g in rest) + 2
        for g in groups:
            threads[g].join(120)
        assert not any(t.is_alive() for t in threads.values())
        assert all(g.error is None for g in rest), [g.error for g in rest]
        jax.block_until_ready([g.state for g in rest])
        assert any(r["healed"] for r in replacement.records)
        assert survivor.manager.current_step() == \
            replacement.manager.current_step()
        assert survivor.digest() == replacement.digest()
        assert replacement.grad_step is victim.grad_step
        assert replacement.opt._update is victim.opt._update
        snapshot = replacement.snapshots()["optimizer"]
        assert snapshot["update_program_reused"] >= 1
        assert profiling.program_stats()["reused"] >= reused + 4
    finally:
        for g in groups:
            g.teardown()
        lighthouse.shutdown()
