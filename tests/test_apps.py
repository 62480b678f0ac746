"""Tests for apps/ops: parameter server, launcher specs, lighthouse CLI."""

import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from torchft_tpu.launcher import LIGHTHOUSE_ENV, hsdp_spec, launch_local
from torchft_tpu.parameter_server import (
    ParameterServer,
    ParameterServerClient,
)


class EchoPS(ParameterServer):
    """Server doubles whatever the client broadcasts to it."""

    def __init__(self):
        super().__init__(timeout=10.0)
        self.sessions = []

    def handle_session(self, session_id, comm):
        self.sessions.append(session_id)
        # receive from client (client is broadcast root)
        received = comm.broadcast(
            [np.zeros(4, np.float32)], root=1
        ).future().result(timeout=10)
        doubled = [a * 2 for a in received]
        comm.broadcast(doubled, root=0).future().result(timeout=10)


def test_parameter_server_session_roundtrip() -> None:
    ps = EchoPS()
    try:
        client = ParameterServerClient(ps.address(), timeout=10.0)
        comm = client.new_session()
        payload = np.full(4, 21.0, dtype=np.float32)
        comm.broadcast([payload], root=1).future().result(timeout=10)
        out = comm.broadcast(
            [np.zeros(4, np.float32)], root=0
        ).future().result(timeout=10)
        np.testing.assert_allclose(out[0], np.full(4, 42.0))
        assert len(ps.sessions) == 1
        comm.shutdown()

        # second session gets a fresh context
        comm2 = client.new_session()
        comm2.broadcast([payload], root=1).future().result(timeout=10)
        comm2.broadcast(
            [np.zeros(4, np.float32)], root=0
        ).future().result(timeout=10)
        assert len(ps.sessions) == 2
        comm2.shutdown()
    finally:
        ps.shutdown()


def test_hsdp_spec_env_plumbing() -> None:
    specs = hsdp_spec(
        script="examples/train_ddp.py",
        num_replica_groups=3,
        lighthouse_addr="http://lh:29510",
        workers_per_group=4,
        extra_env={"MODEL": "tiny"},
        script_args=["--flag"],
    )
    assert len(specs) == 12  # groups x workers
    for spec in specs:
        i, r = spec.replica_group_id, spec.rank
        assert spec.env[LIGHTHOUSE_ENV] == "http://lh:29510"
        assert spec.env["REPLICA_GROUP_ID"] == str(i)
        assert spec.env["NUM_REPLICA_GROUPS"] == "3"
        assert spec.env["RANK"] == str(r)
        assert spec.env["WORLD_SIZE"] == "4"
        assert spec.env["MASTER_PORT"] == str(29700 + i)
        assert spec.env["TORCHFT_TPU_MANAGER_PORT"] == str(29600 + i)
        assert spec.env["MODEL"] == "tiny"
        assert spec.cmd[-1] == "--flag"
    assert {(s.replica_group_id, s.rank) for s in specs} == {
        (i, r) for i in range(3) for r in range(4)
    }


def test_hsdp_spec_gives_each_worker_its_own_chip() -> None:
    # one process per chip: every worker's libtpu environment names a
    # different chip and declares the process a complete 1x1x1 topology
    # (docs/operations.md "One process per chip"); extra_env goes last, so
    # a multi-host scheduler can renumber per host
    specs = hsdp_spec(
        script="x.py", num_replica_groups=2, lighthouse_addr="http://lh:1",
        workers_per_group=2,
    )
    assert [s.env["TPU_VISIBLE_CHIPS"] for s in specs] == ["0", "1", "2", "3"]
    for spec in specs:
        assert spec.env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert spec.env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    moved = hsdp_spec(
        script="x.py", num_replica_groups=2, lighthouse_addr="http://lh:1",
        extra_env={"TPU_VISIBLE_CHIPS": "7"},
    )
    assert [s.env["TPU_VISIBLE_CHIPS"] for s in moved] == ["7", "7"]


def test_launcher_leaves_the_launching_process_off_jax_backends() -> None:
    # a parent that initialised a backend would hold the chips its workers
    # need; importing the launcher and building specs must not
    code = (
        "from torchft_tpu.launcher import hsdp_spec\n"
        "hsdp_spec('x.py', 4, 'http://lh:1')\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "xla_bridge._backends\n"
    )
    import os

    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr


def test_lighthouse_cli_starts_and_serves() -> None:
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "torchft_tpu.lighthouse_cli",
            "--min_replicas", "1", "--bind", "127.0.0.1:0",
            "--hostname", "127.0.0.1",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        line = proc.stdout.readline()
        assert "lighthouse serving at" in line, line
        addr = line.strip().rsplit(" ", 1)[-1]
        import urllib.request

        html = urllib.request.urlopen(addr + "/", timeout=5).read().decode()
        assert "lighthouse" in html
    finally:
        proc.terminate()
        proc.wait(timeout=10)




def _run_example(script, extra_env, timeout=180):
    """Run an examples/ script as a real subprocess against a fresh
    in-process lighthouse (the shared shape of every example-runner
    test): CPU jax, no inherited PYTHONPATH, repo-root cwd."""
    import os

    from torchft_tpu.control import Lighthouse

    lh = Lighthouse(min_replicas=1, join_timeout_ms=200)
    env = dict(os.environ)
    env.update(
        TORCHFT_TPU_LIGHTHOUSE=lh.address(),
        REPLICA_GROUP_ID="0",
        LOGLEVEL="ERROR",
        JAX_PLATFORMS="cpu",
        **extra_env,
    )
    env.pop("PYTHONPATH", None)  # the example finds the repo by itself
    try:
        return subprocess.run(
            [sys.executable, script],
            env=env, capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
    finally:
        lh.shutdown()


def test_train_hsdp_example_runs() -> None:
    # The HSDP example (fsdp/tp-sharded group + sharded-heal transport)
    # must train end-to-end as a real subprocess against a real
    # lighthouse — the apps-level seal on the sharded composition.
    proc = _run_example(
        "examples/train_hsdp.py", {"TOTAL_STEPS": "3"}, timeout=120
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "step 3" in proc.stdout, proc.stdout


def test_train_hsdp_example_donated_update() -> None:
    # The HBM-bound variant: the same example with the donated
    # decide-then-apply commit path (no transient 2x params+opt) must
    # train identically — the apps-level seal on donate_update composing
    # with sharded state.
    proc = _run_example(
        "examples/train_hsdp.py",
        {"TOTAL_STEPS": "3", "TORCHFT_TPU_DONATE_UPDATE": "1"},
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "step 3" in proc.stdout, proc.stdout


@pytest.mark.slow  # tier-1 budget: >=25s on a 2-core host (see pytest.ini)
def test_train_ddp_example_durable_resume(tmp_path) -> None:
    # The DDP example's durable checkpoints are written by the async
    # writer; a second run with the same CKPT_PATH must resume from the
    # persisted step, not step 0 — the apps-level seal on stage-on-call
    # + background-persist durability.
    import os

    from torchft_tpu.control import Lighthouse

    ckpt = str(tmp_path / "ddp.ckpt")

    def run(total_steps: int):
        return _run_example(
            "examples/train_ddp.py",
            {
                "TOTAL_STEPS": str(total_steps),
                "NUM_REPLICA_GROUPS": "1",
                "CKPT_PATH": ckpt,
            },
            timeout=120,
        )

    first = run(10)
    assert first.returncode == 0, first.stderr[-2000:]
    assert "step 10" in first.stdout, first.stdout
    assert os.path.exists(ckpt + ".10")  # step-suffixed durable file

    second = run(13)
    assert second.returncode == 0, second.stderr[-2000:]
    assert "resumed from" in second.stdout, second.stdout
    # resumed past the first run's checkpoint; never reprints step 1
    assert "step 13" in second.stdout, second.stdout
    assert "step 1 " not in second.stdout.replace("step 10", ""), (
        second.stdout
    )


@pytest.mark.slow  # tier-1 budget: >=25s on a 2-core host (see pytest.ini)
def test_train_llama_ring_example_runs() -> None:
    # Llama (GQA/RoPE/SwiGLU) x ring attention (sequence parallelism)
    # x chunked CE x FT manager, end-to-end as a real subprocess — the
    # apps-level seal on the long-context composition.
    proc = _run_example(
        "examples/train_llama_ring.py",
        {
            "TOTAL_STEPS": "3",
            "SEQ_LEN": "128",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "step 3" in proc.stdout, proc.stdout


def test_train_moe_example_runs() -> None:
    # MoE transformer (expert-parallel GShard FFN on an ``expert`` mesh
    # axis) x FT manager loop, end-to-end as a real subprocess — the
    # apps-level seal on the expert-parallel composition.
    proc = _run_example(
        "examples/train_moe.py",
        {
            "TOTAL_STEPS": "3",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "step 3" in proc.stdout, proc.stdout


def test_train_diloco_example_runs() -> None:
    # DiLoCo (outer-optimizer DP, sync quorum, pseudogradient averaging)
    # end-to-end as a real subprocess — the apps-level seal on the
    # infrequent-sync composition (the one example previously without an
    # app-level test).
    proc = _run_example(
        "examples/train_diloco.py",
        {
            "TOTAL_SYNCS": "2",
            "SYNC_EVERY": "2",
            "NUM_REPLICA_GROUPS": "1",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "sync committed" in proc.stdout, proc.stdout
    assert "done after" in proc.stdout, proc.stdout
