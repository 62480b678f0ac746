"""Test configuration: force an 8-device virtual CPU platform so every test
can build multi-device meshes without TPU hardware (SURVEY.md §4 pattern).
The suite never initialises an accelerator backend: a chip belongs to one
process at a time, and the multi-device tests need eight devices.
"""

import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import force_cpu_backend  # noqa: E402

force_cpu_backend(8)

import contextlib  # noqa: E402
import fcntl  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402


@contextlib.contextmanager
def _one_at_a_time(name: str):
    """A lock between the workers of one tier-1 run (``flock`` on a file
    under the run's temporary directory): whoever holds it runs, the
    others wait idle."""
    path = os.path.join(tempfile.gettempdir(), f"torchft_tpu_tests_{name}.lock")
    with open(path, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


@pytest.fixture(scope="module")
def one_compiling_file_at_a_time():
    """For a test file that compiles for minutes (the interpreter-mode
    Pallas scan, the Nemotron-H model and its family: PR 33): its module
    asks for this fixture, and of the files that do, one runs at a time.
    The tier-1 run has six workers on eight cores, each XLA compile takes
    them all, and the suite's wall-clock tests (a kill and a rejoin
    inside eight steps, a p50 against a p50) fail when four or five such
    files run beside them: one a run in 8 whole runs of 11 with PR 33's
    three files free to run together (CHANGES.md, PR 33; ROADMAP D10). A
    worker that waits here takes no core."""
    with _one_at_a_time("compiling_file"):
        yield


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e host, for the tests that compile a
    kernel at a cell's widths with the chip's compiler (nothing runs;
    ``tests/test_kda.py``, ``tests/test_ssm_pointwise.py``). Described
    only once a test asks: never at import."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])
