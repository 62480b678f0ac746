"""Fault-tolerant DiLoCo training example (BASELINE config #4 shape:
outer-optimizer DP over a transformer; LocalSGD via ALGO=local_sgd).

Inner steps run locally at full speed; every SYNC_EVERY steps the groups
average pseudogradients (DiLoCo) or weights (LocalSGD) through the
manager, with commit/rollback semantics. The outer sync rides the
streaming fragment scheduler: NUM_FRAGMENTS (default 2) byte-balanced
fragments stagger across the round and overlap the wire with inner
compute; STREAMING=0 pins the blocking arm. DiLoCo no longer requires
sync quorum (the round-start fence handles async-quorum heals) — this
example keeps use_async_quorum=False for eager per-round heals.

    python -m torchft_tpu.lighthouse_cli --min_replicas 2 &
    REPLICA_GROUP_ID=0 NUM_REPLICA_GROUPS=2 \
    TORCHFT_TPU_LIGHTHOUSE=http://host:29510 \
        python examples/train_diloco.py
"""

from __future__ import annotations

import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

logging.basicConfig(
    level=os.environ.get("LOGLEVEL", "WARNING"),
    format="%(asctime)s %(name)s: %(message)s",
)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu import DiLoCo, DistributedSampler, LocalSGD, Manager, TcpCommContext
from torchft_tpu.comm.store import StoreServer
from torchft_tpu.models import CONFIGS, init_params, make_train_step
from torchft_tpu.utils.device import place_compile_cache


def main() -> None:
    place_compile_cache()
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", "0"))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", "2"))
    total_syncs = int(os.environ.get("TOTAL_SYNCS", "10"))
    sync_every = int(os.environ.get("SYNC_EVERY", "8"))
    num_fragments = max(1, min(
        int(os.environ.get("NUM_FRAGMENTS", "2")), sync_every
    ))
    streaming = os.environ.get("STREAMING", "1") != "0"
    algo = os.environ.get("ALGO", "diloco")
    if algo not in ("diloco", "local_sgd"):
        raise ValueError(f"ALGO must be diloco or local_sgd, got {algo!r}")

    cfg = CONFIGS[os.environ.get("MODEL", "tiny")]
    inner_tx = optax.adamw(3e-4, weight_decay=0.1, b1=0.9, b2=0.95)

    params = init_params(cfg, jax.random.key(0))
    holder = {"params": params, "opt": inner_tx.init(params)}
    wrapper_ref = {}

    def state_dict():
        sd = {
            "params": holder["params"],
            "opt": holder["opt"],
            "sampler": sampler.state_dict(),
        }
        if "w" in wrapper_ref:
            sd["wrapper"] = wrapper_ref["w"].state_dict()
        return sd

    def load_state_dict(sd):
        holder["params"] = sd["params"]
        holder["opt"] = sd["opt"]
        sampler.load_state_dict(sd["sampler"])
        if "wrapper" in sd and "w" in wrapper_ref:
            wrapper_ref["w"].load_state_dict(sd["wrapper"])

    sampler = DistributedSampler(
        4096, replica_group=replica_group, num_replica_groups=num_groups,
        shuffle=True, seed=1,
    )
    store = StoreServer()
    manager = Manager(
        comm=TcpCommContext(),
        load_state_dict=load_state_dict,
        state_dict=state_dict,
        min_replica_size=1,
        use_async_quorum=False,  # optional since the round-start fence;
        # sync mode keeps heals eager at every quorum
        # the quorum window must cover sync_every inner steps
        quorum_timeout=600.0,
        rank=0,
        world_size=1,
        store_addr=store.addr,
        replica_id=f"diloco_{replica_group}_",
    )
    if algo == "diloco":
        # Nesterov-momentum SGD outer optimizer, the DiLoCo-paper default
        outer_tx = optax.sgd(0.7, momentum=0.9, nesterov=True)
        wrapper = DiLoCo(
            manager, outer_tx, sync_every=sync_every,
            params_fn=lambda: holder["params"],
            num_fragments=num_fragments, streaming=streaming,
        )
    else:
        wrapper = LocalSGD(
            manager, sync_every=sync_every,
            params_fn=lambda: holder["params"],
            num_fragments=num_fragments, streaming=streaming,
        )
    wrapper_ref["w"] = wrapper
    holder["params"] = wrapper.register(holder["params"])

    inner_step = make_train_step(cfg, inner_tx, donate=False)
    # ONE logical dataset shared by all groups (seed fixed); the sampler
    # shards it per group/rank.
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (4096, cfg.max_seq_len))

    batch_size = 8
    it = iter(sampler)
    # manager.current_step() counts COMMITTED syncs and survives heals, so
    # a relaunched group resumes its quota instead of restarting it.
    while manager.current_step() < total_syncs:
        idx = []
        while len(idx) < batch_size:
            try:
                idx.append(next(it))
            except StopIteration:
                sampler.set_epoch(sampler.epoch + 1)
                it = iter(sampler)
        tokens = jnp.asarray(data[idx], dtype=jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        p, o, loss = inner_step(
            holder["params"], holder["opt"], tokens, targets
        )
        holder["params"], holder["opt"] = p, o
        step_before = manager.current_step()
        holder["params"] = wrapper.step(holder["params"])
        if wrapper.local_step == 0:  # a sync boundary just ran
            if manager.current_step() > step_before:
                print(
                    f"[group {replica_group}] sync committed "
                    f"(step {manager.current_step()}) "
                    f"loss {float(loss):.4f} "
                    f"participants {manager.num_participants()}"
                )
            else:
                print(
                    f"[group {replica_group}] sync ABORTED at step "
                    f"{step_before}; rolled back {wrapper._sync_every} "
                    f"inner steps"
                )

    manager.shutdown()
    store.shutdown()
    print(
        f"[group {replica_group}] done after "
        f"{manager.current_step()} committed syncs"
    )


if __name__ == "__main__":
    main()
