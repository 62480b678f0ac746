"""``BENCHMARK.json``'s per-layer list against ``benchmark/layer_metrics/``
(``benchmark/tests/test_manifest.py``: no chip, no jax), guarded by
tier-1."""

from benchmark.tests.test_manifest import *  # noqa: F401,F403
from benchmark.tests.test_manifest import _file


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


def test_the_manifest_has_free_places() -> None:
    """Shadows the star import's test of this name, which pins ``<= 124``
    in a file under ``benchmark/`` that a PR of another kind may not edit
    (PR 71 filled the four places PR 70 freed): the bound is the
    contract's; the rest is that test's, word for word."""
    assert len(ENTRIES) <= PER_LAYER_MAX  # noqa: F405
    gone = {f"{p}_flash_{k}_roofline"
            for p in ("mla", "gqa", "swa", "swa4k", "full16k")
            for k in ("fwd", "dq", "dkv")} - {"swa_flash_fwd_roofline"}
    assert not gone & set(ENTRIES)  # noqa: F405
    assert not {"land_pool_full_share", "x4_quorum_ms"} & set(ENTRIES)  # noqa: F405


# PR 73's cell joins the two ``full`` flash rooflines. The table of calls and
# the count of cells a class are pinned in
# ``benchmark/tests/test_flash_rooflines.py``, which a PR of another kind than
# ``benchmark`` may not edit: the cell's row goes into the star import's own
# table here (the test of the table reads it when it runs) and the count's
# test is shadowed, the rest of it word for word (PERF.md section 7).
CALLS["ouro-l8-solo-steady", "full"] = (32, 16, 16, 128, 128, None)  # noqa: F405


@pytest.mark.parametrize("name", sorted(flash_rooflines.WHAT))  # noqa: F405
def test_a_flash_entry_lists_the_cells_whose_configuration_has_the_class(
        name):
    """The manifest's list of a class's cells is what the configurations
    say: fourteen ``full``, three ``swa``."""
    entry = ENTRIES[name]  # noqa: F405
    kind, _direction = flash_rooflines.WHAT[name]  # noqa: F405
    assert set(entry["workloads"]) == {c for c, k in CALLS if k == kind}  # noqa: F405
    assert len(entry["workloads"]) == {"full": 14, "swa": 3}[kind]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "higher", "device_trace", "kernels",
                                "committed_tokens_per_s")


# name -> (layer, unit, sink key, groups, scale, the file that writes the key)
_HEAL_INSIDE = {
    "heal.fetch_s": ("heal", "s", "heal_fetch_ms", "replacements", 0.001,
                     "checkpointing.py"),
    "heal.apply_wait_s": ("heal", "s", "heal_apply_wait_ms", "replacements",
                          0.001, "manager.py"),
    "heal.donor_wait_share": ("heal", "ratio", "heal_donor_wait_share",
                              "replacements", 1, "checkpointing.py"),
    "stall.failed_wire_s": ("control", "s",
                            "episode_shrink_failed_wire_max_ms", None, 0.001,
                            "manager.py"),
}


@pytest.mark.parametrize("name", sorted(_HEAL_INSIDE))  # noqa: F405
def test_a_heals_inside_is_a_key_of_the_managers_sink_in_the_kill_cell_alone(
        name) -> None:
    """PR 71's four: no reader, a key the library writes, the one cell
    that kills, and texts the driver takes."""
    import pathlib

    layer, unit, key, groups, scale, writer = _HEAL_INSIDE[name]
    entry, spec = ENTRIES[name], _file(name)  # noqa: F405
    assert entry["workloads"] == ["c111m-x4-kill"]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["better"],
            entry["source"]) == (layer, unit, "goodput_tokens_per_s", "lower",
                                 "program_span")
    assert "reader" not in spec and spec["sink"] == "manager"
    assert (spec["key"], spec.get("groups"), spec["scale"]) == (
        key, groups, scale)
    for text in (entry["layer"], entry["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    source = (pathlib.Path(__file__).parents[1] / "torchft_tpu" / writer
              ).read_text()
    written = key[len("episode_shrink_"):-len("_max_ms")] if (
        key.startswith("episode_")) else key
    assert f'"{written}"' in source, (written, writer)
