"""Fault-tolerant HSDP training example: fsdp-sharded model inside each
replica group, torchft-style fault tolerance across groups (the role of
ref fsdp_test.py:40-74's FSDP2-over-ft_init_device_mesh composition).

Inside the group, every parameter is sharded over the slice's chips with
``shard_pytree`` (XLA inserts the fsdp all-gathers/reduce-scatters over
ICI); across groups, gradients average through the Manager over DCN. A
relaunched group heals via the SHARDED checkpoint path: it fetches only
the shard slices its own devices hold and lands them directly with its
NamedShardings (``CheckpointServer(template_fn=...)``).

Run one replica group per process (8 virtual CPU devices work fine):

    python -m torchft_tpu.lighthouse_cli --min_replicas 1 &
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    REPLICA_GROUP_ID=0 TORCHFT_TPU_LIGHTHOUSE=http://host:29510 \
        python examples/train_hsdp.py

Kill a group at any time; it heals shard-by-shard on relaunch.
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
from torchft_tpu.models import CONFIGS, init_params, make_grad_step
from torchft_tpu.optim import OptimizerWrapper
from torchft_tpu.parallel import ft_mesh, shard_pytree, tp_rules_gpt
from torchft_tpu.utils.device import place_compile_cache


def main() -> None:
    place_compile_cache()
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", "0"))
    total_steps = int(os.environ.get("TOTAL_STEPS", "30"))
    cfg = CONFIGS[os.environ.get("MODEL", "tiny")]
    tx = optax.adamw(3e-4)

    # In-group mesh over this group's chips: fsdp x tensor.
    n_dev = len(jax.devices())
    tensor = 2 if n_dev % 2 == 0 else 1
    mesh = ft_mesh({"fsdp": n_dev // tensor, "tensor": tensor})

    def place(tree):
        return shard_pytree(tree, mesh, tp_rules=tp_rules_gpt())

    params = place(init_params(cfg, jax.random.key(0)))
    state = {"params": params, "opt": tx.init(params)}

    def state_dict():
        return dict(state)

    def load_state_dict(sd):
        # sharded heal: leaves arrive already carrying OUR NamedShardings
        state.update(sd)

    # template_fn -> the heal fetches only this process's shard slices
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
        replica_id=f"hsdp_{replica_group}_",
    )
    ddp = DistributedDataParallel(manager)
    opt = OptimizerWrapper(
        manager, tx,
        state_fn=lambda: (state["params"], state["opt"]),
        # HSDP is the HBM-bound shape: TORCHFT_TPU_DONATE_UPDATE=1 trades
        # the overlapped commit barrier for a fully donated update program
        # (no transient second params+opt footprint) when the model barely
        # fits — see docs/operations.md §6.
        donate_update=os.environ.get("TORCHFT_TPU_DONATE_UPDATE") == "1",
    )
    grad_step = make_grad_step(cfg)

    rng = np.random.default_rng(replica_group)
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
        # keep fsdp/tp shardings stable across updates
        avg = jax.tree_util.tree_map(
            lambda g, p: jax.device_put(jnp.asarray(g), p.sharding),
            avg, state["params"],
        )
        p, s, committed = opt.step(state["params"], state["opt"], avg)
        if committed:
            state["params"], state["opt"] = p, s
            print(
                f"[group {replica_group}] step {manager.current_step()} "
                f"loss {float(loss):.4f} "
                f"participants {manager.num_participants()}"
            )

    manager.shutdown()
    store.shutdown()


if __name__ == "__main__":
    main()
