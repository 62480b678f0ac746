"""``BENCHMARK.json``'s ``per_layer`` list against ``layer_metrics/``: one
entry and one file a *measurement*. Which cells report it is a rule, kept
in ``BENCHMARK.json`` alone: every cell that reports the end-to-end metric
it ``moves`` or, where the reading needs what only some programs have (an
expert layer's scopes, a kernel family, a kill), the cells its
``workloads`` names. No file under ``layer_metrics/`` names a cell, so a
PR that may only add files brings a new cell by naming it in the
end-to-end metrics it reports (as PRs 26, 31 and 33 did) and, for the few
listed measurements its program has, in their lists — or, where it may
not extend a list, as entries of its own. PRs 26, 31 and 33 each copied
the 15 solo metrics under a prefix and filled the contract's 128 places:
what is refused here is a cell told one thing under two names. No chip,
no jax; imported as it is by a ``tests/`` file, once a PR may add one."""

import copy
import glob
import json
import os

import pytest

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(_BENCH), "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
ENTRIES = {m["name"]: m for m in MANIFEST["per_layer"]}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
PER_LAYER_MAX = 128                  # the contract's


def _file(name):
    with open(os.path.join(_BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _cells(metric):
    """``run.py::_in_cell``'s rule, as sets."""
    if "workloads" in metric:
        return set(metric["workloads"])
    moved = [m for m in MANIFEST["end_to_end"]
             if m["name"] == metric.get("moves")]
    return set(_cells(moved[0])) if moved else set(CELLS)


def _reads(spec):
    """What a metric file reads, whoever's name it is read under."""
    if "reader" in spec:
        return ("reader", spec["reader"], spec["what"])
    return ("sink", spec["sink"], spec["key"], spec.get("groups"),
            spec.get("scale", 1.0))


def test_every_entry_has_a_file_and_every_file_an_entry() -> None:
    files = {os.path.basename(p)[:-len(".json")] for p in
             glob.glob(os.path.join(_BENCH, "layer_metrics", "*.json"))}
    assert files == set(ENTRIES)
    assert len(ENTRIES) == len(MANIFEST["per_layer"]) <= PER_LAYER_MAX


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_file_agrees_with_its_entry(name) -> None:
    entry, spec = ENTRIES[name], _file(name)
    # the cells are the manifest's to name: a file that named them could
    # not be extended by a PR that may only add files
    assert "workloads" not in spec
    assert {k: spec.get(k) for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    # a key of a sink or a reader, never both and never neither
    assert ("reader" in spec) != ("sink" in spec)
    assert ("what" in spec) == ("reader" in spec)
    assert ("key" in spec) == ("sink" in spec)
    if "reader" in spec:
        assert os.path.exists(
            os.path.join(_BENCH, "readers", spec["reader"] + ".py"))


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_every_listed_cell_reports_the_metric_it_moves(name) -> None:
    entry = ENTRIES[name]
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == entry["moves"])
    assert _cells(entry) and _cells(entry) <= _cells(moved) <= set(CELLS)
    if "workloads" in entry:
        assert len(set(entry["workloads"])) == len(entry["workloads"])


SOLO = {
    "quorum_ms", "commit_barrier_ms", "rpcs_per_step", "bare_step_ms",
    "ft_over_bare", "window_over_blocks", "compiles_in_window", "bare_mfu",
    "device_idle_share", "xent_device_share", "attn_device_share",
    "mlp_device_share", "embed_device_share", "opt_device_share",
    "unnamed_device_share",
}


def test_a_new_cell_is_brought_by_additions_alone() -> None:
    """The 15 that read the loop, the step and the six scopes every step
    program has name no cells: a cell that a later PR names in
    ``committed_tokens_per_s`` (and nowhere else) reports them and the two
    of ``setup_s``, by ``run.py``'s own rule; no existing file is edited
    for it and this suite stays green."""
    from benchmark import run

    for name in SOLO:
        assert "workloads" not in ENTRIES[name], name
        assert ENTRIES[name]["moves"] == "committed_tokens_per_s", name
    end_to_end = copy.deepcopy(MANIFEST["end_to_end"])
    next(m for m in end_to_end if m["name"] == "committed_tokens_per_s")[
        "workloads"].append("new-solo-steady")
    assert {m["name"] for m in MANIFEST["per_layer"]
            if run._in_cell(m, "new-solo-steady", end_to_end)
            } == SOLO | {"boot_s", "first_step_s"}
    # the rule the tests below hold the manifest to is run.py's
    for cell in CELLS:
        for m in MANIFEST["per_layer"]:
            assert run._in_cell(m, cell, MANIFEST["end_to_end"]) == (
                cell in _cells(m)), (cell, m["name"])


@pytest.mark.parametrize("cell", CELLS)
def test_no_cell_is_told_one_thing_under_two_names(cell) -> None:
    """Two entries with the same ``moves`` that read the same thing
    (``reader`` + ``what``, or ``sink`` + ``key`` + ``groups`` + ``scale``)
    in the same cell."""
    seen = {}
    for name, entry in ENTRIES.items():
        if cell not in _cells(entry):
            continue
        read = (entry["moves"],) + _reads(_file(name))
        assert read not in seen, (name, seen[read])
        seen[read] = name


def test_a_twin_differs_from_its_original_in_moves_alone() -> None:
    """``twin_of`` marks the one copy that has to stay: a measurement
    reported under a second end-to-end metric, which ``moves`` (one
    metric an entry) cannot say in one file."""
    for name, entry in ENTRIES.items():
        spec = _file(name)
        if "twin_of" not in spec:
            continue
        original = _file(spec["twin_of"])
        assert _reads(spec) == _reads(original), name
        assert entry["moves"] != ENTRIES[spec["twin_of"]]["moves"], name
        for key in ("unit", "better", "source", "layer"):
            assert spec[key] == original[key], (name, key)
        assert not _cells(entry) & _cells(ENTRIES[spec["twin_of"]]), name


def test_every_cell_has_a_per_layer_metric_and_the_whole_steps_mfu() -> None:
    for cell in CELLS:
        mine = {n for n, m in ENTRIES.items() if cell in _cells(m)}
        assert mine, cell
        reports = {m["name"] for m in MANIFEST["end_to_end"]
                   if cell in _cells(m)}
        if "committed_tokens_per_s" in reports:
            assert "bare_mfu" in mine, cell


# -- the cells that hold a share of their experts ----------------------------

GAUGES = ("moe_held_share", "moe_load_max_over_mean", "moe_row_buffer_share")


def _load(path):
    with open(os.path.join(os.path.dirname(_BENCH), path)) as f:
        return json.load(f)


CONFIGS = {c["name"]: _load(c["file"]) for c in MANIFEST["configs"]}
SHARE_CONFIGS = sorted(n for n, c in CONFIGS.items() if "share" in c)


@pytest.mark.parametrize("gauge", GAUGES)
def test_a_routing_gauge_lists_every_share_cell_and_no_other(gauge) -> None:
    """The three numbers a share cell's balance rule has to hold stand on
    the driver's line of every cell whose configuration holds a share
    (PR 41 was lost to two cells whose routing no line showed)."""
    assert len(SHARE_CONFIGS) >= 4
    cells = {w["name"] for w in MANIFEST["workloads"]
             if w["config"] in SHARE_CONFIGS}
    assert set(ENTRIES[gauge]["workloads"]) == cells
    assert {"joyai-ep16-solo-steady", "nemo3-ep16-solo-steady"} <= cells


@pytest.mark.parametrize("name", SHARE_CONFIGS)
def test_a_share_configuration_names_held_and_the_rule_its_rate_was_set_by(
        name) -> None:
    """The family tells the optimizer which experts are held (so the cell
    emits all three gauges), the configuration states the rule a share
    cell's bias rate is set by, once, with its rate, and a rate that
    differs from a sibling's names that sibling's and says why."""
    config = CONFIGS[name]
    assert {"first_expert", "router_width"} <= set(config["share"])
    # read, not imported: this file stays off jax (the cell tests hold the
    # gauges' emission and ``tx.held_experts``)
    with open(os.path.join(_BENCH, "families",
                           config["family"] + ".py")) as f:
        assert "held=(cfg.first_expert, cfg.n_experts_held)" in f.read()
    rate = config["optimizer"]["balance_bias_rate"]
    rule = config["assumed"]["balance_rule"]
    assert f"b_e += {rate:g} x sign(mean(load) - load_e)" in rule
    assert "0.8 - 1.25" in rule and "load max / mean" in rule
    others = {CONFIGS[n]["optimizer"]["balance_bias_rate"]
              for n in SHARE_CONFIGS} - {rate}
    for other in others:
        assert f"{other:g}" in rule.replace(f"{rate:g} x sign", ""), other


@pytest.mark.parametrize("cell", CELLS)
def test_the_drift_tool_takes_a_share_cell_and_refuses_any_other(cell) -> None:
    """``routing_drift.py --workload``: one tool for the cells that hold a
    share, found through the manifest as ``run.py`` finds them."""
    from benchmark.tests import routing_drift

    config = {w["name"]: w for w in MANIFEST["workloads"]}[cell]["config"]
    if config in SHARE_CONFIGS:
        assert routing_drift.cell_config(cell) == CONFIGS[config]
    else:
        with pytest.raises(SystemExit, match="holds no share"):
            routing_drift.cell_config(cell)


def test_the_drift_tools_band_is_the_rule_the_configurations_state() -> None:
    from benchmark.tests import routing_drift

    sixteenth = 1 / 16
    assert routing_drift.in_band([0.050, 0.078], [1.3, 2.0], sixteenth)
    assert not routing_drift.in_band([0.049, 0.06], [1.3, 1.3], sixteenth)
    assert not routing_drift.in_band([0.06, 0.079], [1.3, 1.3], sixteenth)
    assert not routing_drift.in_band([0.06, 0.06], [1.3, 2.1], sixteenth)
