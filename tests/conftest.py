"""Test configuration: force an 8-device virtual CPU platform so every test
can build multi-device meshes without TPU hardware (SURVEY.md §4 pattern).
The suite never initialises an accelerator backend: a chip belongs to one
process at a time, and the multi-device tests need eight devices.
"""

import os
import shutil
import sys
import tempfile

os.environ.setdefault("JAX_ENABLE_X64", "0")

# One compilation cache a RUN, shared by its workers and gone with it: the
# six workers (and the tests of one worker) compile many equal programs, and
# reading one back is cheaper than compiling it (PR 61: wall 756 -> 675 s,
# sum of case times 4 343 -> 3 889 s, every test's result unchanged). The
# directory is named after the process that owns the run (xdist's workers
# are its children), made fresh when that process starts and removed when
# its session ends, so nothing is carried from one run to the next. Where
# the caller has set a directory, that one is used and left alone.
_OWNS_RUN = "PYTEST_XDIST_WORKER" not in os.environ
_RUN_CACHE = os.path.join(
    tempfile.gettempdir(),
    "torchft_tpu-tier1-jax-cache-%d" % (
        os.getpid() if _OWNS_RUN else os.getppid()))
if "JAX_COMPILATION_CACHE_DIR" in os.environ:
    _RUN_CACHE = None
else:
    if _OWNS_RUN:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _RUN_CACHE

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import force_cpu_backend  # noqa: E402

force_cpu_backend(8)

import pytest  # noqa: E402


def pytest_sessionfinish(session):
    if _OWNS_RUN and _RUN_CACHE:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


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


@pytest.fixture(scope="module")
def one_head_a_step():
    """``ops/kda.py`` at one head a grid step for a whole file (``_LADDER``
    and ``_GDN_LADDER`` bound to ``(1,)``). On the CPU the interpreter runs the heads of a step
    side by side: a body of four heads is four times the program to trace
    and compile, and ``tests/test_kda.py`` holds heads that share a step to
    the one-head kernels bit for bit, once, at the smallest shapes that
    cross a chunk boundary. A file about a model or about the scan's
    mathematics asks for this (``pytestmark = pytest.mark.usefixtures``);
    a test about the ladder sets its own rungs. jit keeps a traced body a
    shape, so whoever moves the ladder clears jax's caches."""
    import jax

    from torchft_tpu.ops import kda

    patch = pytest.MonkeyPatch()
    patch.setattr(kda, "_LADDER", (1,))
    patch.setattr(kda, "_GDN_LADDER", (1,))
    jax.clear_caches()
    yield
    patch.undo()
    jax.clear_caches()
