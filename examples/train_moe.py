"""Fault-tolerant expert-parallel MoE training example.

Composes the three axes this framework adds over the reference (which has
neither a model zoo nor MoE — SURVEY.md §2c: EP absent):

- in-group: expert weights sharded on an ``expert`` ICI mesh axis (GShard
  dispatch/combine, XLA-inserted all_to_alls — parallel/moe.py),
- across groups: per-step quorum + gradient averaging + two-phase commit
  through the Manager (the torchft FT loop),
- heal: a relaunched group fetches the live checkpoint sharded onto its
  own expert-mesh NamedShardings.

    python -m torchft_tpu.lighthouse_cli --min_replicas 1 &
    REPLICA_GROUP_ID=0 NUM_REPLICA_GROUPS=2 \
    TORCHFT_TPU_LIGHTHOUSE=http://host:29510 \
        python examples/train_moe.py
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

from torchft_tpu import Manager, TcpCommContext
from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.comm.store import StoreServer
from torchft_tpu.ddp import DistributedDataParallel
from torchft_tpu.models import MOE_CONFIGS, moe_transformer_loss_fn, moe_init_params
from torchft_tpu.optim import OptimizerWrapper
from torchft_tpu.parallel import ft_mesh, shard_pytree
from torchft_tpu.parallel.moe import moe_rules
from torchft_tpu.utils.device import place_compile_cache


def main() -> None:
    place_compile_cache()
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", "0"))
    total_steps = int(os.environ.get("TOTAL_STEPS", "30"))
    cfg = MOE_CONFIGS[os.environ.get("MODEL", "moe-tiny")]
    tx = optax.adamw(3e-4)

    # In-group mesh over this group's chips: experts sharded on ICI. Chip
    # counts that don't divide num_experts fall back to a 1-wide axis
    # (replicated experts) — the FT loop is unchanged either way.
    n_dev = len(jax.devices())
    ep = n_dev if cfg.num_experts % n_dev == 0 else 1
    mesh = ft_mesh({"expert": ep, "data": n_dev // ep})

    def place(tree):
        return shard_pytree(
            tree, mesh, tp_rules=moe_rules(), fsdp_axis=None
        )

    params = place(moe_init_params(cfg, jax.random.key(0)))
    state = {"params": params, "opt": tx.init(params)}

    def state_dict():
        return dict(state)

    def load_state_dict(sd):
        # sharded heal: leaves arrive carrying OUR expert-mesh shardings
        state.update(sd)

    transport = CheckpointServer(
        timeout=60.0,
        template_fn=lambda: {
            "user": state_dict(),
            "torchft": {"step": 0, "batches_committed": 0},
        },
    )

    store = StoreServer()
    manager = Manager(
        comm=TcpCommContext(),
        load_state_dict=load_state_dict,
        state_dict=state_dict,
        checkpoint_transport=transport,
        min_replica_size=1,
        rank=int(os.environ.get("RANK", "0")),
        world_size=int(os.environ.get("WORLD_SIZE", "1")),
        store_addr=store.addr,
        replica_id=f"moe_{replica_group}_",
    )
    ddp = DistributedDataParallel(manager)
    opt = OptimizerWrapper(
        manager, tx,
        state_fn=lambda: (state["params"], state["opt"]),
    )

    grad_step = jax.jit(
        jax.value_and_grad(
            lambda p, t, y: moe_transformer_loss_fn(cfg, p, t, y),
        ),
    )

    rng = np.random.default_rng(replica_group)
    try:
        while manager.current_step() < total_steps:
            tokens = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (8, cfg.max_seq_len)),
                dtype=jnp.int32,
            )
            targets = jnp.roll(tokens, -1, axis=1)

            opt.begin_step()
            with mesh:
                loss, grads = grad_step(state["params"], tokens, targets)
            avg = ddp.average_gradients(grads)
            # keep expert shardings stable across updates
            avg = jax.tree_util.tree_map(
                lambda g, p: jax.device_put(g, p.sharding),
                avg, state["params"],
            )
            new_params, new_opt, committed = opt.step(
                state["params"], state["opt"], avg
            )
            if committed:
                state["params"], state["opt"] = new_params, new_opt
                print(
                    f"[group {replica_group}] step "
                    f"{manager.current_step()} loss {float(loss):.4f} "
                    f"participants {manager.num_participants()}"
                )
    finally:
        manager.shutdown()
        store.shutdown()
    print(
        f"[group {replica_group}] done at step {manager.current_step()}"
    )


if __name__ == "__main__":
    main()
