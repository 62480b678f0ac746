"""benchmark/tests: run by hand, never part of tier-1 (pytest.ini collects
``tests/`` only):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Four virtual CPU devices, so the four-group job can be rehearsed."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
