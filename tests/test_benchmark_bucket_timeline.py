"""``benchmark/readers/bucket_timeline.py`` on periods built by hand
(``benchmark/tests/test_bucket_timeline.py``: no chip, no jax), guarded by
tier-1 as ``tests/test_benchmark_manifest.py`` guards the manifest."""

import json
import os
import types

import pytest

from benchmark.tests import test_bucket_timeline as _by_hand
from benchmark.tests.test_bucket_timeline import *  # noqa: F401,F403


@pytest.mark.parametrize("name", _by_hand.NEW)
def test_new_metric_file_agrees_with_the_manifest(name, monkeypatch) -> None:
    """The file's own test, held to the manifest as PR 53 left it: it
    asserts that PR 53's ten entries are the LAST ten of ``per_layer``,
    which the first PR to append after them (PR 56: four ``gdn_*``) ends —
    a later PR's entries go at the end too. What PR 53 added still stands
    together, in its order, behind everything accepted before it."""
    with open(os.path.join(_by_hand.rehearse._REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(_by_hand.NEW[0])
    assert tuple(names[first:first + len(_by_hand.NEW)]) == _by_hand.NEW
    # run the benchmark's test on the manifest cut where PR 53 ended it
    cut = dict(manifest, per_layer=manifest["per_layer"][
        :first + len(_by_hand.NEW)])

    def load(f):
        out = json.load(f)
        return cut if "per_layer" in out else out

    # that module's own name ``json``, not the library's function
    monkeypatch.setattr(_by_hand, "json", types.SimpleNamespace(load=load))
    _by_hand.test_new_metric_file_agrees_with_the_manifest(name)
