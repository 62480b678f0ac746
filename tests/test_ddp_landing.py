"""The landing stage of the streamed bucket pipeline (ddp.py →
utils/device.py): who runs a landing — two workers per PLACEMENT, the
devices of the leaves a bucket replaces — and how many times its bytes are
copied — through a fresh host copy where the target may alias host memory
(every device of this suite: the CPU backend), straight from the staging
arena where it cannot, and then the bucket counts as landed only once the
transfers have read the arena.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_ddp_pipeline import _donated_delayed_allreduce, _mock_manager
from torchft_tpu import ddp as ddp_mod
from torchft_tpu.ddp import DistributedDataParallel
from torchft_tpu.utils import device as device_mod
from torchft_tpu.utils.device import land_batch, placement
from torchft_tpu.utils.metrics import Metrics


@pytest.fixture
def fresh_pools(monkeypatch):
    """The process-wide pools, emptied for one test (an xdist worker runs
    many files in one process, each leaving its placements behind)."""
    pools: dict = {}
    monkeypatch.setattr(ddp_mod, "_PIPELINE_EXECUTORS", pools)
    yield pools
    for ex in pools.values():
        ex.shutdown(wait=True)


def _manager(delay: float = 0.01):
    """test_ddp_pipeline's Manager double — the wire resolves to the
    DONATED arrays from a thread of its own — healthy and with a real
    sink."""
    m = _mock_manager()
    m.errored.return_value = None
    m.metrics = Metrics()
    m.allreduce_arrays.side_effect = _donated_delayed_allreduce(delay)
    return m


def _grads(dev, scale: float = 1.0):
    return jax.device_put(
        {"a": np.arange(48, dtype=np.float32) * scale,
         "b": np.full((4, 8), 3.0 * scale, np.float32),
         "c": np.arange(40, dtype=np.float32) - scale},
        dev,
    )


def _land_threads(pools) -> int:
    return sum(len(ex._threads) for (kind, _), ex in pools.items()
               if kind == "land")


# ------------------------------------------------ who runs a landing


def test_placement_is_the_union_of_the_leaves_devices() -> None:
    d0, d1 = jax.devices()[:2]
    on0 = jax.device_put(np.zeros(4, np.float32), d0)
    on1 = jax.device_put(np.zeros(4, np.float32), d1)
    assert placement([on0]) == frozenset({d0})
    assert placement([on0, on1, on0]) == frozenset({d0, d1})
    # a numpy leaf lands on the default device and names none
    assert placement([np.zeros(4, np.float32)]) == frozenset()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("x",))
    spread = jax.device_put(
        np.zeros(8, np.float32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("x")),
    )
    assert placement([spread]) == frozenset(jax.devices()[:4])


def test_landings_of_another_placement_do_not_queue(fresh_pools) -> None:
    # two of placement A's buckets and one of B's run at once; a third of
    # A's waits for one of A's two workers
    d0, d1 = jax.devices()[:2]
    pool_a = ddp_mod._pipeline_executor("land", frozenset({d0}))
    pool_b = ddp_mod._pipeline_executor("land", frozenset({d1}))
    assert pool_a is not pool_b
    assert pool_a is ddp_mod._pipeline_executor("land", frozenset({d0}))
    release = threading.Event()
    started = {name: threading.Event() for name in ("a0", "a1", "a2", "b0")}

    def _landing(name: str) -> None:
        started[name].set()
        assert release.wait(10)

    futs = [pool_a.submit(_landing, "a0"), pool_a.submit(_landing, "a1"),
            pool_a.submit(_landing, "a2"), pool_b.submit(_landing, "b0")]
    try:
        for name in ("a0", "a1", "b0"):
            assert started[name].wait(5), name
        assert not started["a2"].wait(0.2)
    finally:
        release.set()
    for f in futs:
        f.result(timeout=10)
    assert started["a2"].is_set()
    assert ddp_mod._land_workers() == 4


@pytest.mark.parametrize("wrappers", [1, 6])
def test_wrappers_on_one_device_share_two_landing_threads(
    fresh_pools, wrappers
) -> None:
    dev = jax.devices()[0]
    managers = [_manager() for _ in range(wrappers)]
    averagers = [DistributedDataParallel(m, bucket_bytes=64)
                 for m in managers]
    for _ in range(2):
        futs = [a.average_gradients_async(_grads(dev)) for a in averagers]
        for f in futs:
            out = f.result(timeout=10)
            assert out["a"].devices() == {dev}
    assert [key for key in fresh_pools if key[0] == "land"] == [
        ("land", frozenset({dev}))
    ]
    assert 1 <= _land_threads(fresh_pools) <= 2
    for m in managers:
        assert m.metrics.snapshot()["ddp_land_workers"] == 2


def test_groups_on_two_devices_get_two_workers_each(fresh_pools) -> None:
    d0, d1 = jax.devices()[:2]
    managers = [_manager(), _manager()]
    averagers = [DistributedDataParallel(m, bucket_bytes=64)
                 for m in managers]
    futs = [a.average_gradients_async(_grads(d, scale=i + 1.0))
            for i, (a, d) in enumerate(zip(averagers, (d0, d1)))]
    for i, (f, d) in enumerate(zip(futs, (d0, d1))):
        out = f.result(timeout=10)
        assert all(leaf.devices() == {d}
                   for leaf in jax.tree_util.tree_leaves(out))
        np.testing.assert_array_equal(
            np.asarray(out["a"]), np.arange(48, dtype=np.float32) * (i + 1.0)
        )
    assert ddp_mod._land_workers() == 4
    prefixes = {ex._thread_name_prefix for (kind, _), ex
                in fresh_pools.items() if kind == "land"}
    assert prefixes == {f"torchft_tpu_ddp_land_d{d0.id}x1",
                        f"torchft_tpu_ddp_land_d{d1.id}x1"}
    # whoever stepped last has seen both placements
    assert managers[1].metrics.snapshot()["ddp_land_workers"] in (2, 4)


# -------------------------------------- how many times a byte is copied


@pytest.mark.parametrize("streamed", [True, False])
@pytest.mark.parametrize("name,expected", [
    ("ddp_land_borrowed_bytes", 0),
    ("ddp_land_copied_bytes", 3 * (48 + 32 + 40) * 4),
])
def test_cpu_backend_lands_every_byte_through_a_copy(
    fresh_pools, streamed, name, expected
) -> None:
    manager = _manager()
    averager = DistributedDataParallel(manager, bucket_bytes=64,
                                       streamed=streamed)
    for _ in range(3):
        averager.average_gradients(_grads(jax.devices()[0]))
    snap = manager.metrics.snapshot()
    assert snap[name] == expected
    # the lock-step path lands on the wire's last continuation: no pool
    assert snap.get("ddp_land_workers") == (2 if streamed else None)


class _Chip:
    """A device that is not the host's."""

    platform = "tpu"
    id = 0


class _OnChip:
    device_set = frozenset({_Chip()})


class _Leaf:
    """What land_batch reads of the leaf a view replaces."""

    sharding = _OnChip()

    def __init__(self, dtype=np.float32) -> None:
        self.dtype = np.dtype(dtype)


class _Late:
    """What a stand-in ``device_put`` returns: ready only after someone
    has waited for it, and holding what the 'transfer' had read by then."""

    lock = threading.Lock()
    made: list = []

    def __init__(self, source: np.ndarray) -> None:
        self.source = source
        self.value = None
        self.dtype = source.dtype
        with _Late.lock:
            _Late.made.append(self)

    def block_until_ready(self) -> "_Late":
        time.sleep(0.02)
        self.value = self.source.copy()   # the transfer reads the arena
        return self


@pytest.fixture
def late_device_put(monkeypatch):
    _Late.made = []
    real_put = jax.device_put
    monkeypatch.setattr(
        jax, "device_put",
        lambda x, sharding: _Late(x) if isinstance(x, np.ndarray)
        else real_put(x, sharding),   # a test's own tree of gradients
    )
    return _Late.made


def test_land_batch_lends_the_arena_and_waits_for_every_transfer(
    late_device_put,
) -> None:
    arena = np.arange(24, dtype=np.float32)
    views = [arena[:8].reshape(2, 4), arena[8:]]
    out, borrowed, copied = land_batch(views, [_Leaf(), _Leaf()])
    assert (borrowed, copied) == (96, 0)
    assert out == late_device_put and len(out) == 2
    # the views themselves were handed over, both before either was waited
    # for, and all were ready on return
    assert all(np.shares_memory(o.source, arena) for o in out)
    assert all(o.value is not None for o in out)


@pytest.mark.parametrize("why", ["cast", "strided"])
def test_land_batch_copies_where_the_view_cannot_be_lent(
    monkeypatch, why
) -> None:
    seen: list = []
    real_put = jax.device_put

    def _put(x, _sharding):
        seen.append(x)
        return real_put(x, jax.devices()[0])

    monkeypatch.setattr(jax, "device_put", _put)
    arena = np.arange(32, dtype=np.float32)
    view, leaf = {
        "cast": (arena[:16], _Leaf(jnp.bfloat16)),
        "strided": (arena[::2], _Leaf()),
    }[why]
    out, borrowed, copied = land_batch([view], [leaf])
    assert (borrowed, copied) == (0, view.nbytes)
    assert not np.shares_memory(seen[0], arena)
    assert out[0].dtype == leaf.dtype
    np.testing.assert_array_equal(
        np.asarray(out[0], np.float32), np.asarray(view)
    )


def test_land_batch_on_a_cpu_device_never_aliases_the_arena() -> None:
    # 64-byte aligned, as the CPU backend needs to adopt a numpy buffer
    raw = np.zeros(4096 + 64, np.uint8)
    off = (-raw.ctypes.data) % 64
    arena = raw[off: off + 4096].view(np.float32)
    arena[:] = 7.0
    like = jax.device_put(np.zeros(1024, np.float32), jax.devices()[0])
    out, borrowed, copied = land_batch([arena], [like])
    arena[:] = -3.0    # the arena's next pack
    assert (borrowed, copied) == (0, 4096)
    np.testing.assert_array_equal(np.asarray(out[0]), np.full(1024, 7.0))


@pytest.mark.parametrize("streamed", [True, False])
def test_step_resolves_only_after_lent_views_were_read(
    fresh_pools, monkeypatch, late_device_put, streamed
) -> None:
    # every device an accelerator: the landing borrows the arena, and the
    # step's future — the arena's inflight guard — must not resolve before
    # each transfer has read it. The next step's pack (one arena) would
    # otherwise change what a late transfer reads.
    monkeypatch.setattr(device_mod, "_may_alias_host", lambda _s: False)
    manager = _manager()
    averager = DistributedDataParallel(manager, bucket_bytes=64,
                                       staging_arenas=1, streamed=streamed)
    dev = jax.devices()[0]
    first = averager.average_gradients(_grads(dev, 1.0))
    assert late_device_put and all(
        o.value is not None for o in late_device_put
    )
    averager.average_gradients(_grads(dev, 5.0))   # repacks the arena
    np.testing.assert_array_equal(
        first["a"].value, np.arange(48, dtype=np.float32)
    )
    np.testing.assert_array_equal(first["b"].value, np.full((4, 8), 3.0))
    snap = manager.metrics.snapshot()
    assert snap["ddp_land_borrowed_bytes"] == 2 * (48 + 32 + 40) * 4
    assert snap["ddp_land_copied_bytes"] == 0


def test_a_failed_transfer_still_waits_for_the_ones_issued(
    monkeypatch,
) -> None:
    made: list = []

    def _put(x, _sharding):
        if made:
            raise RuntimeError("RESOURCE_EXHAUSTED")
        made.append(_Late(x))
        return made[-1]

    monkeypatch.setattr(jax, "device_put", _put)
    arena = np.arange(16, dtype=np.float32)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        land_batch([arena[:8], arena[8:]], [_Leaf(), _Leaf()])
    assert made[0].value is not None
