"""``BENCHMARK.json``'s per-layer list against ``benchmark/layer_metrics/``
(``benchmark/tests/test_manifest.py``: no chip, no jax), guarded by
tier-1."""

from benchmark.tests.test_manifest import *  # noqa: F401,F403


def test_every_free_text_of_the_manifest_fits_the_contract():
    """The driver refuses the file before any run for a ``why``, ``source``
    or ``layer`` outside 1 to 200 printable characters on one line (PR 38's
    first hand-in: a configuration's ``why`` of 210)."""
    import json
    import pathlib

    manifest = json.loads(
        (pathlib.Path(__file__).parents[1] / "BENCHMARK.json").read_text())
    texts = [(f"{kind} {entry['name']}: {key}", entry[key])
             for kind in ("configs", "workloads", "end_to_end", "per_layer")
             for entry in manifest[kind]
             for key in ("why", "source", "layer") if key in entry]
    assert texts
    bad = [where for where, text in texts
           if not (1 <= len(text) <= 200 and text.isprintable())]
    assert not bad, bad
