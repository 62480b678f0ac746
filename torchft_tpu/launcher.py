"""Job launcher for multi-replica-group training.

Analog of the reference's TorchX component (/root/reference/torchft/
torchx.py:11-76), which emits one torchrun Role per replica group with
TORCHFT_LIGHTHOUSE and per-group env plumbing. TPU-native rendering: each
worker is one host process driving part of a TPU slice (jax handles the
chips), so a "role" is a plain subprocess spec:

    specs = hsdp_spec(num_replica_groups=2, script="examples/train_ddp.py",
                      lighthouse_addr="http://lh:29510")
    procs = launch_local(specs)          # for local/CI runs
    # or feed `specs` to your scheduler of choice (GKE/xmanager/...)

Env contract per worker (consumed by torchft_tpu.Manager):
    TORCHFT_TPU_LIGHTHOUSE  global lighthouse address
    REPLICA_GROUP_ID / NUM_REPLICA_GROUPS   data sharding
    RANK / WORLD_SIZE       local rank within the replica group
    MASTER_ADDR / MASTER_PORT   the group's rendezvous store (rank 0 binds
                                it; other ranks connect)
    TORCHFT_TPU_MANAGER_PORT    the group's manager server port (29600+i,
                                mirroring the reference's convention)
    TPU_VISIBLE_CHIPS / TPU_CHIPS_PER_PROCESS_BOUNDS / TPU_PROCESS_BOUNDS
                            the worker's own chip (:func:`chip_env`)

One process per chip: a TPU chip belongs to one process at a time, and a
process that initialises jax with no restriction claims every chip of the
host, so N unrestricted workers on one host collide (libtpu aborts all but
one on its lock file). Every spec therefore names its worker's chip. The
launching process itself must stay off jax backends — it would hold the
chips its workers need; nothing in this module initialises one.
"""

from __future__ import annotations

import os
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from torchft_tpu.manager import LIGHTHOUSE_ENV, MANAGER_PORT_ENV

__all__ = ["ReplicaGroupSpec", "chip_env", "hsdp_spec", "launch_local",
           "LIGHTHOUSE_ENV"]


def chip_env(worker_index: int) -> Dict[str, str]:
    """libtpu environment that gives one worker process exactly one chip
    of its host: chip ``worker_index`` made the only visible one, and the
    process declared a complete 1x1x1 topology of its own, so libtpu
    neither waits for sibling processes nor needs per-process ports.
    Inside the worker the chip is always ``jax.devices()[0]`` with id 0.
    Established on a four-chip v5e host with libtpu 0.0.34
    (docs/operations.md, "One process per chip"); ``TPU_VISIBLE_CHIPS``
    alone is not enough. Harmless where libtpu is never initialised
    (``JAX_PLATFORMS=cpu``)."""
    return {
        "TPU_VISIBLE_CHIPS": str(worker_index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


@dataclass
class ReplicaGroupSpec:
    """Launch spec for one worker process of a replica group."""

    replica_group_id: int
    rank: int
    cmd: List[str]
    env: Dict[str, str] = field(default_factory=dict)


def hsdp_spec(
    script: str,
    num_replica_groups: int,
    lighthouse_addr: str,
    workers_per_group: int = 1,
    base_manager_port: int = 29600,
    base_store_port: int = 29700,
    master_addr: str = "127.0.0.1",
    extra_env: Optional[Dict[str, str]] = None,
    script_args: Optional[List[str]] = None,
) -> List[ReplicaGroupSpec]:
    """One spec per worker (num_replica_groups × workers_per_group total),
    with full rank/store plumbing — rank 0 of each group binds the group
    store at MASTER_ADDR:MASTER_PORT, other ranks connect to it — and one
    chip per worker, numbered in spec order (:func:`chip_env`; a host
    with fewer chips than workers fails the surplus workers at start-up).
    ``extra_env`` is applied last, so a scheduler that places workers on
    several hosts can override the chip variables per host."""
    specs = []
    for i in range(num_replica_groups):
        for rank in range(workers_per_group):
            env = {
                **chip_env(i * workers_per_group + rank),
                LIGHTHOUSE_ENV: lighthouse_addr,
                "REPLICA_GROUP_ID": str(i),
                "NUM_REPLICA_GROUPS": str(num_replica_groups),
                "RANK": str(rank),
                "WORLD_SIZE": str(workers_per_group),
                "MASTER_ADDR": master_addr,
                "MASTER_PORT": str(base_store_port + i),
                MANAGER_PORT_ENV: str(base_manager_port + i),
            }
            if extra_env:
                env.update(extra_env)
            specs.append(
                ReplicaGroupSpec(
                    replica_group_id=i,
                    rank=rank,
                    cmd=[sys.executable, script, *(script_args or [])],
                    env=env,
                )
            )
    return specs


def launch_local(
    specs: List[ReplicaGroupSpec], **popen_kwargs
) -> List[subprocess.Popen]:
    """Spawn every worker as a local subprocess (CI / single-host
    experiments). The processes inherit the current env overlaid with the
    spec env — which carries each worker's chip — and callers own
    wait/kill (a kill+relaunch is exactly a replica failure + rejoin; a
    SIGKILLed worker's chip is free for its relaunch at once)."""
    procs = []
    for spec in specs:
        env = dict(os.environ)
        env.update(spec.env)
        procs.append(
            subprocess.Popen(spec.cmd, env=env, **popen_kwargs)
        )
    return procs
