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
