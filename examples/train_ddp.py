"""Fault-tolerant DDP training example (the reference train_ddp.py analog,
/root/reference/train_ddp.py:33-156 — CIFAR CNN there; a synthetic-data
transformer here since this image has no dataset downloads).

Run one replica group (repeat per group, or use torchft_tpu.launcher):

    python -m torchft_tpu.lighthouse_cli --min_replicas 1 &
    REPLICA_GROUP_ID=0 NUM_REPLICA_GROUPS=2 \
    TORCHFT_TPU_LIGHTHOUSE=http://host:29510 \
        python examples/train_ddp.py

Kill any replica group at any time: survivors keep committing; the
relaunched group heals from a live checkpoint and rejoins — the loop below
needs zero failure-handling code for that.

SHARDED=1 switches the weight update to the ZeRO-style cross-replica
sharded path (reduce-scatter → 1/N optimizer update → params allgather:
optimizer state/FLOPs/heal bytes ÷ wire world; docs/architecture.md
"Sharded weight update"). The flag must match across replica groups.

MODEL_SHARDS=M declares the 2-D replica×model mesh layout
(docs/architecture.md "Fused step"): the manager labels its telemetry
`mesh_shape="{world}x{M}"` (fleet_top renders it per replica) and the
sharded wrapper prices reshards/heals on the (replica-shard ×
model-shard) sub-unit grid — moved bytes stay at the set-theoretic
minimum at any M. Like SHARDED, it must match across replica groups.
"""

from __future__ import annotations

import logging
import os
import sys

logging.basicConfig(
    level=os.environ.get("LOGLEVEL", "WARNING"),
    format="%(asctime)s %(name)s: %(message)s",
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu import (
    DistributedDataParallel,
    DistributedSampler,
    Manager,
    OptimizerWrapper,
    TcpCommContext,
)
from torchft_tpu.checkpoint_io import (
    AsyncCheckpointWriter,
    latest_checkpoint,
    load_checkpoint,
)
from torchft_tpu.comm.store import StoreServer
from torchft_tpu.models import CONFIGS, init_params, make_grad_step
from torchft_tpu.utils.device import place_compile_cache


def main() -> None:
    place_compile_cache()
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", "0"))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", "2"))
    total_steps = int(os.environ.get("TOTAL_STEPS", "50"))
    ckpt_path = os.environ.get(
        "CKPT_PATH", f"/tmp/torchft_tpu_ddp_{replica_group}.ckpt"
    )

    cfg = CONFIGS[os.environ.get("MODEL", "tiny")]
    tx = optax.adamw(3e-4)
    rank = int(os.environ.get("RANK", "0"))
    world_size = int(os.environ.get("WORLD_SIZE", "1"))

    params = init_params(cfg, jax.random.key(0))
    state = {"params": params, "opt": tx.init(params)}

    # synthetic next-token dataset, sharded across groups x local ranks
    rng = np.random.default_rng(0)
    dataset = rng.integers(0, cfg.vocab_size, (4096, cfg.max_seq_len))
    sampler = DistributedSampler(
        len(dataset),
        replica_group=replica_group,
        num_replica_groups=num_groups,
        rank=rank,
        num_replicas=world_size,
        shuffle=True,
        seed=1,
    )

    # SHARDED=1: the sharded wrapper's opt state rides checkpoints/heals
    # through its fixed-structure shard serialization (a donor ships only
    # its 1/N shard; the healer reshards onto the live grid) — the
    # wrapper is bound below, after the Manager exists.
    sharded = os.environ.get("SHARDED", "0") == "1"
    # MODEL_SHARDS=M: 2-D mesh layout knob — the Manager carries it
    # (mesh_shape telemetry label, re-asserted every quorum) and the
    # sharded wrapper reads it back for 2-D reshard pricing.
    model_shards = int(os.environ.get("MODEL_SHARDS", "1"))

    def load_state_dict(sd):
        train = dict(sd["train"])
        if sharded and isinstance(train.get("opt"), dict) \
                and "slots" in train["opt"]:
            train["opt"] = opt.load_opt_state_dict(train["opt"])
        state.update(train)
        sampler.load_state_dict(sd["sampler"])

    def state_dict():
        train = dict(state)
        if sharded:
            train["opt"] = opt.opt_state_dict(state["opt"])
        return {"train": train, "sampler": sampler.state_dict()}

    # Per-group rendezvous store: rank 0 binds it (the group-master
    # TCPStore role); other local ranks connect via MASTER_ADDR/PORT.
    store = None
    if rank == 0:
        store = StoreServer(
            host="0.0.0.0",
            port=int(os.environ.get("MASTER_PORT", "0")),
        )
        store_addr = store.addr
    else:
        store_addr = (
            f"{os.environ['MASTER_ADDR']}:{os.environ['MASTER_PORT']}"
        )
    manager = Manager(
        comm=TcpCommContext(),
        load_state_dict=load_state_dict,
        state_dict=state_dict,
        min_replica_size=1,
        rank=rank,
        world_size=world_size,
        store_addr=store_addr,
        replica_id=f"train_ddp_{replica_group}_",
        model_shards=model_shards,
    )
    if sharded:
        from torchft_tpu import ShardedOptimizerWrapper

        ddp = None
        opt = ShardedOptimizerWrapper(
            manager, tx,
            state_fn=lambda: (state["params"], state["opt"]),
        )
        state["opt"] = opt.init(state["params"])
    else:
        ddp = DistributedDataParallel(manager)
        opt = OptimizerWrapper(
            manager, tx,
            state_fn=lambda: (state["params"], state["opt"]),
        )
    grad_step = make_grad_step(cfg)
    # One fused grad+update executable for solo-wire steps (no data-plane
    # peer): commit barrier first, then a single donated program — the
    # cheap path a single-group (or temporarily-alone) deployment rides.
    from torchft_tpu.models import make_train_step

    fused_step = make_train_step(cfg, tx, donate=True)

    # Durable-checkpoint resume is the user's job (ref train_ddp.py:141-148)
    # — the manager state_dict MUST be part of it. Checkpoints are
    # step-suffixed so keep=2 retains a previous-step fallback (retention
    # spans kill/relaunch incarnations); resume from the newest.
    newest = latest_checkpoint(ckpt_path)
    if newest is not None:
        saved = load_checkpoint(newest)
        load_state_dict(saved["user"])
        manager.load_state_dict(saved["manager"])
        print(f"resumed from {newest} at step {manager.current_step()}")
    # stage-on-call + background persist: training never waits on disk
    ckpt_writer = AsyncCheckpointWriter(keep=2)

    batch_size = 8
    it = iter(sampler)

    def next_batch():
        nonlocal it
        idx = []
        while len(idx) < batch_size:
            try:
                idx.append(next(it))
            except StopIteration:
                sampler.set_epoch(sampler.epoch + 1)
                it = iter(sampler)
        tokens = jnp.asarray(dataset[idx], dtype=jnp.int32)
        return tokens, jnp.roll(tokens, -1, axis=1)

    try:
        while manager.current_step() < total_steps:
            tokens, targets = next_batch()
            opt.begin_step()
            if sharded:
                # the sharded wrapper owns the whole reduce→update→
                # allgather pipeline: hand it the RAW gradients
                loss, grads = grad_step(state["params"], tokens, targets)
                new_params, new_opt, committed = opt.step(
                    state["params"], state["opt"], grads
                )
            elif opt.can_fuse():  # waits the quorum; latches on failure
                new_params, new_opt, loss, committed = opt.fused_step(
                    fused_step, state["params"], state["opt"],
                    tokens, targets,
                )
            else:
                loss, grads = grad_step(state["params"], tokens, targets)
                avg = ddp.average_gradients(grads)
                new_params, new_opt, committed = opt.step(
                    state["params"], state["opt"], avg
                )
            if committed:
                state["params"], state["opt"] = new_params, new_opt
                step = manager.current_step()
                # Loss is read back only at checkpoint steps: float(loss)
                # is a synchronous D2H that would re-serialize host and
                # device every step — the exact round trip the fused
                # path's delayed fence exists to avoid (optim.py fence
                # rationale).
                loss_part = (
                    f" loss {float(loss):.4f}" if step % 10 == 0 else ""
                )
                print(
                    f"[group {replica_group}] step {step}"
                    f"{loss_part} "
                    f"participants {manager.num_participants()}"
                )
                if step % 10 == 0:
                    ckpt_writer.save_step(
                        ckpt_path, step,
                        {
                            "user": state_dict(),
                            "manager": manager.state_dict(),
                        },
                    )
        # drain pending writes; surface write errors before "done"
        ckpt_writer.close()
    finally:
        manager.shutdown()
        if store is not None:
            store.shutdown()
    print(f"[group {replica_group}] done at step {manager.current_step()}")


if __name__ == "__main__":
    main()
