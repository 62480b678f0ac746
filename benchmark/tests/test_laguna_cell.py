"""The Laguna family through the real ``run.py`` on the CPU at a tiny size
(``tiny-laguna.json``): the contract's line untraced and traced, and the
ten accepted measurements the cell joins, read by their accepted readers
from this program's scopes and counters. Run by hand with the other
benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark.readers import moe_scopes, phi4flash_scopes, ssm_scopes
from benchmark.tests import rehearse

CELL = "laguna-ep8-solo-steady"
JOINED = {"gqa_device_share", "swa_core_device_share",
          "full_core_device_share", "moe_router_device_share",
          "moe_dispatch_device_share", "moe_experts_device_share",
          "moe_shared_device_share", "moe_held_share",
          "moe_load_max_over_mean", "moe_row_buffer_share"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_laguna_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-laguna",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-laguna", "source": "test only",
        "file": "benchmark/tests/tiny-laguna.json", "reduced": [],
        "why": "t",
    }]
    # the copy drops every metric's ``workloads``;
    # ``moe_experts_roofline`` lists the OLMoE cell alone because its
    # reader takes that family's keys of the configuration
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] != "moe_experts_roofline"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2147483659", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for two
    # sequences of 8192 at the published widths (tests/test_laguna.py
    # holds the comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 3e-2
    assert 0.0 <= reference["top8_disagreement"] < 0.1
    assert reference["rms"] < 0.04
    assert reference["tokens"] == 2 * 64
    # four sparse layers of five; the dense first layer routes nothing
    assert len(reference["held_share"]) == 4
    assert all(0 < s < 1 for s in reference["held_share"])
    assert all(m >= 1.0 for m in reference["load_max_over_mean"])
    assert 0 < reference["gate_range"][0] < reference["gate_range"][1] < 1
    assert (reference["yarn_lo"], reference["yarn_hi"],
            reference["yarn_moved"]) == (0, 3, 7)
    assert len(reference["swa_rel_l2"]) == len(reference["full_rel_l2"]) == 4
    if not trace:
        assert set(line["metrics"]) == {"committed_tokens_per_s",
                                        "peak_hbm_gib", "setup_s"}
        return
    got = line["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    six = [got[f"{s}_device_share"]["value"] for s in
           ("xent", "attn", "mlp", "embed", "opt", "unnamed")]
    assert sum(six) == pytest.approx(1.0)
    # gqa_proj + gqa_core tile attn (the rotations and the gate stand
    # inside gqa_proj); swa_core + full_core tile gqa_core
    assert got["gqa_device_share"]["value"] == pytest.approx(
        got["attn_device_share"]["value"], rel=1e-6)
    cores = (got["swa_core_device_share"]["value"]
             + got["full_core_device_share"]["value"])
    assert 0 < cores < got["gqa_device_share"]["value"]
    assert got["swa_core_device_share"]["value"] > 0
    assert got["full_core_device_share"]["value"] > 0
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts", "shared")]
    assert all(v > 0 for v in inner)
    # layer 0's dense MLP is in ``mlp`` and in no inner scope
    assert sum(inner) < got["mlp_device_share"]["value"]
    # the three gauges of the optimizer wrapper's sink
    assert 0.0 < got["moe_held_share"]["value"] < 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["moe_row_buffer_share"]["value"] == 1.0
    # every metric the cell lists: the 2 of set-up, the 15 solo ones and
    # the ten it joins, each read here
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 27 and JOINED <= mine
    missing = mine - set(got)
    assert missing <= {
        # a 4 s window is all traced, so no rate of untraced steps
        "ft_over_bare", "window_over_blocks"}, missing


def test_the_accepted_readers_take_this_programs_paths() -> None:
    step = "jit(tft_train_step)/"
    for inner in ("rope", "rope_yarn", "attn_gate"):
        path = step + f"jvp(attn)/gqa_proj/{inner}/mul"
        assert ssm_scopes.inner_scopes(path) == ("gqa",)
        assert not {"swa_core", "full_core"} & phi4flash_scopes.scopes_of(path)
    for core in ("swa_core", "full_core"):
        path = step + f"transpose(jvp(attn))/gqa_core/{core}/pallas_call"
        assert ssm_scopes.inner_scopes(path) == ("gqa",)
        assert core in phi4flash_scopes.scopes_of(path)
    for scope, share in (("moe_router", "router"), ("moe_dispatch", "dispatch"),
                         ("moe_combine", "dispatch"),
                         ("moe_experts", "experts"), ("moe_shared", "shared")):
        assert moe_scopes.inner_scope(
            step + f"jvp(mlp)/{scope}/dot_general") == share
    # the dense first layer's MLP lies under ``mlp`` alone
    assert moe_scopes.inner_scope(step + "jvp(mlp)/dot_general") is None
