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

import pytest  # noqa: E402


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
