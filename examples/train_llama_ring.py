"""Fault-tolerant LONG-CONTEXT training: Llama + ring attention + chunked
cross entropy + the FT manager loop, composed in one trainer.

The round-trip the reference cannot make (it has no sequence parallelism
or GQA model family): sequence length is sharded over the in-group mesh's
``seq`` axis — K/V blocks rotate via lax.ppermute while each device runs
its local attention block (einsum ring by default; flash-block pallas
ring with RING_IMPL=flash) — the Llama family supplies RMSNorm/RoPE/
SwiGLU/GQA, the loss never materializes [B, S, V] logits (online
logsumexp over vocab chunks), and gradients average across replica
groups through the Manager, so killing a group mid-run shrinks the
quorum and survivors keep committing.

Run one replica group (8 virtual CPU devices work fine):

    python -m torchft_tpu.lighthouse_cli --min_replicas 1 &
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    REPLICA_GROUP_ID=0 TORCHFT_TPU_LIGHTHOUSE=http://host:29510 \
        python examples/train_llama_ring.py
"""

from __future__ import annotations

import dataclasses
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
from torchft_tpu.comm.store import StoreServer
from torchft_tpu.ddp import DistributedDataParallel
from torchft_tpu.models.common import repeat_kv
from torchft_tpu.models.llama import (
    LlamaConfig,
    llama_init_params,
    llama_loss_fn,
)
from torchft_tpu.optim import OptimizerWrapper
from torchft_tpu.parallel import ft_mesh, make_ring_attention
from torchft_tpu.utils.device import place_compile_cache


def main() -> None:
    place_compile_cache()
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", "0"))
    total_steps = int(os.environ.get("TOTAL_STEPS", "20"))
    seq_len = int(os.environ.get("SEQ_LEN", "256"))

    # sequence axis spans the group's chips; the ring is exact for any
    # divisor of the sequence
    n_dev = len(jax.devices())
    assert seq_len % n_dev == 0, (seq_len, n_dev)
    mesh = ft_mesh({"seq": n_dev})
    ring_impl = os.environ.get("RING_IMPL", "einsum")  # einsum | flash
    ring = make_ring_attention(
        mesh, "seq", causal=True, block_impl=ring_impl,
        block_q=min(128, seq_len // n_dev),
        block_k=min(128, seq_len // n_dev),
    )

    cfg = LlamaConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=176, max_seq_len=seq_len, remat=False,
        xent_chunks=4,  # fused loss: no [B, S, V] logits
    )

    def ring_fn(q, k, v):
        # the model hands k and v at their own head count; the ring's
        # blocks take as many key/value heads as query heads
        return ring(q, repeat_kv(k, cfg.n_heads), repeat_kv(v, cfg.n_heads))

    tx = optax.adamw(3e-4)
    params = llama_init_params(cfg, jax.random.key(0))
    state = {"params": params, "opt": tx.init(params)}

    def load_state_dict(sd):
        state.update(sd)

    store = StoreServer(host="127.0.0.1", port=0)
    manager = Manager(
        comm=TcpCommContext(),
        load_state_dict=load_state_dict,
        state_dict=lambda: dict(state),
        min_replica_size=1,
        rank=0,
        world_size=1,
        store_addr=store.addr,
        replica_id=f"train_llama_ring_{replica_group}_",
    )
    ddp = DistributedDataParallel(manager)
    opt = OptimizerWrapper(
        manager, tx,
        state_fn=lambda: (state["params"], state["opt"]),
    )

    grad_step = jax.jit(
        lambda p, tok, tgt: jax.value_and_grad(
            lambda q: llama_loss_fn(cfg, q, tok, tgt, attn_fn=ring_fn)
        )(p)
    )

    rng = np.random.default_rng(replica_group)
    try:
        while manager.current_step() < total_steps:
            tokens = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (2, seq_len)), jnp.int32
            )
            targets = jnp.roll(tokens, -1, axis=1)
            opt.begin_step()
            loss, grads = grad_step(state["params"], tokens, targets)
            avg = ddp.average_gradients(grads)
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
    print(f"[group {replica_group}] done")


if __name__ == "__main__":
    main()
