"""``benchmark/readers/bucket_timeline.py`` on periods built by hand
(``benchmark/tests/test_bucket_timeline.py``: no chip, no jax), guarded by
tier-1 as ``tests/test_benchmark_manifest.py`` guards the manifest."""

from benchmark.tests.test_bucket_timeline import *  # noqa: F401,F403
