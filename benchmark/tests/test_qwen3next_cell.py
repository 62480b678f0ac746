"""The Qwen3-Next family through the real ``run.py`` on the CPU at a tiny
size (``tiny-qwen3next.json``): the contract's line untraced and traced,
and the eleven accepted measurements the cell joins, read by their
accepted readers from this program's scopes and counters; the family's own
reader (``readers/qwen3next_scopes.py``, which no manifest entry names
yet: the manifest holds its 128 per-layer metrics) on plain data. Run by
hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark.readers import (
    gdn_scopes,
    moe_scopes,
    phi4flash_scopes,
    qwen3next_scopes,
    ssm_scopes,
)
from benchmark.tests import rehearse

CELL = "qwen3next-ep16-solo-steady"
JOINED = {"gqa_device_share", "full_core_device_share", "gdn_device_share",
          "gdn_core_device_share", "moe_router_device_share",
          "moe_dispatch_device_share", "moe_experts_device_share",
          "moe_shared_device_share", "moe_held_share",
          "moe_load_max_over_mean", "moe_row_buffer_share"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_qwen3next_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-qwen3next",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-qwen3next", "source": "test only",
        "file": "benchmark/tests/tiny-qwen3next.json", "reduced": [],
        "why": "t",
    }]
    # the copy drops every metric's ``workloads``;
    # ``moe_experts_roofline`` lists the OLMoE cell alone because its
    # reader takes that family's keys of the configuration, and
    # ``gdn_*_roofline`` the Olmo Hybrid cell alone: ``gdn_scopes`` counts
    # ``linear_num_key_heads`` state heads and reads ``layer_types``
    manifest["per_layer"] = [
        m for m in manifest["per_layer"] if m["name"] not in (
            "moe_experts_roofline", "gdn_fwd_roofline", "gdn_bwd_roofline")]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2147483659", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for sequences
    # of 8192 at the published widths (tests/test_qwen3_next.py holds the
    # comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 3e-2
    assert 0.0 <= reference["top10_disagreement"] < 0.1
    assert reference["rms"] < 0.04
    assert reference["tokens"] == 2 * 64
    assert len(reference["held_share"]) == 4          # every layer sparse
    assert all(0 < s < 1 for s in reference["held_share"])
    assert all(m >= 1.0 for m in reference["load_max_over_mean"])
    lo, hi, slow, fast = reference["beta_decay"]
    assert 0 < lo < hi < 1 and 0 < slow <= fast <= 1
    assert len(reference["gdn_rel_l2"]) == 6
    assert len(reference["flash_rel_l2"]) == 4
    assert len(reference["moe"]) == 4
    if not trace:
        assert set(line["metrics"]) == {"committed_tokens_per_s",
                                        "peak_hbm_gib", "setup_s"}
        return
    got = line["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    six = [got[f"{s}_device_share"]["value"] for s in
           ("xent", "attn", "mlp", "embed", "opt", "unnamed")]
    assert sum(six) == pytest.approx(1.0)
    # the delta-rule mixers' five scopes and gqa_proj + gqa_core tile attn
    assert (got["gdn_device_share"]["value"] + got["gqa_device_share"]["value"]
            == pytest.approx(got["attn_device_share"]["value"], rel=1e-6))
    assert 0 < got["gdn_core_device_share"]["value"] \
        < got["gdn_device_share"]["value"]
    assert 0 < got["full_core_device_share"]["value"] \
        < got["gqa_device_share"]["value"]
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts", "shared")]
    assert all(v > 0 for v in inner)
    assert sum(inner) <= got["mlp_device_share"]["value"] * (1 + 1e-6)
    # the three gauges of the optimizer wrapper's sink
    assert 0.0 < got["moe_held_share"]["value"] < 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["moe_row_buffer_share"]["value"] == 1.0
    # every metric the cell lists: the 2 of set-up, the 15 solo ones and
    # the eleven it joins, each read here
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 28 and JOINED <= mine
    assert not {"gdn_fwd_roofline", "gdn_bwd_roofline"} & mine
    missing = mine - set(got)
    assert missing <= {
        # a 4 s window is all traced, so no rate of untraced steps
        "ft_over_bare", "window_over_blocks"}, missing


def test_the_accepted_readers_take_this_programs_paths() -> None:
    step = "jit(tft_train_step)/"
    for inner in ("rope", "attn_gate"):
        path = step + f"jvp(attn)/gqa_proj/{inner}/mul"
        assert ssm_scopes.inner_scopes(path) == ("gqa",)
        assert "full_core" not in phi4flash_scopes.scopes_of(path)
    path = step + "transpose(jvp(attn))/gqa_core/full_core/pallas_call"
    assert ssm_scopes.inner_scopes(path) == ("gqa",)
    assert "full_core" in phi4flash_scopes.scopes_of(path)
    for scope, shares in (("gdn_in", ("gdn", "gdn_proj")),
                          ("gdn_conv/gdn_repeat", ("gdn", "gdn_conv_gate")),
                          ("gdn_core", ("gdn", "gdn_core")),
                          ("gdn_gate", ("gdn", "gdn_conv_gate")),
                          ("gdn_out", ("gdn", "gdn_proj"))):
        assert gdn_scopes.inner_scopes(
            step + f"jvp(attn)/{scope}/dot_general") == shares
    for scope, share in (("moe_router", "router"), ("moe_dispatch", "dispatch"),
                         ("moe_combine", "dispatch"),
                         ("moe_experts", "experts"), ("moe_shared", "shared")):
        assert moe_scopes.inner_scope(
            step + f"jvp(mlp)/{scope}/dot_general") == share


def test_the_familys_reader_on_plain_data() -> None:
    """Two whole steps of one linear and one full layer under remat, and
    a third that the trace cut: the shares' denominator is every event,
    the rooflines count the whole steps alone, and a flash event outside
    ``full_core`` is nobody's."""
    step = "jit(tft_train_step)/"
    table = {
        "gdn_fwd.1": step + "jvp(attn)/gdn_core/pallas_call",
        "gdn_bwd.1": step + "transpose(jvp(attn))/gdn_core/pallas_call",
        "fusion.1": step + "jvp(attn)/gdn_conv/gdn_repeat/broadcast_in_dim",
        "fusion.2": step + "jvp(attn)/gqa_proj/attn_gate/mul",
        "fusion.3": step + "jvp(mlp)/moe_experts/dot_general",
        "flash_fwd.1": step + "jvp(attn)/gqa_core/full_core/pallas_call",
        "flash_dq.1": step + "transpose(jvp(attn))/gqa_core/full_core/x",
        "flash_dkv.1": step + "transpose(jvp(attn))/gqa_core/full_core/x",
        "flash_fwd.9": step + "jvp(attn)/gqa_core/swa_core/pallas_call",
    }
    names = ["gdn_fwd.1", "gdn_fwd.1", "gdn_bwd.1", "fusion.1", "fusion.2",
             "fusion.3", "flash_fwd.1", "flash_fwd.1", "flash_dq.1",
             "flash_dkv.1", "flash_fwd.9"]
    ops, modules, t = [], [], 0.0
    for whole in (True, True, False):
        start = t
        for name in names if whole else names[:2]:
            ops.append((name, t, t + 1.0))
            t += 1.0
        modules.append(("jit_tft_train_step", start, t))
    got = qwen3next_scopes.reduce({0: ops}, {0: modules},
                                  {"jit_tft_train_step": table})
    assert got["total_s"] == pytest.approx(24.0)
    assert got["shares"] == {"gdn_repeat": pytest.approx(2 / 24),
                             "attn_gate": pytest.approx(2 / 24)}
    assert len(got["steps"]) == 3
    shapes = dict(batch=4, seq_len=8192, n_linear=1, n_full=1, n_heads=16,
                  n_key_heads=16, n_value_heads=32, key_dim=128,
                  value_dim=128, head_dim=256)
    for kernel, seconds in (("gdn_fwd", 4.0), ("gdn_bwd", 2.0),
                            ("flash_fwd", 4.0), ("flash_dq", 2.0)):
        want = 100.0 * 2 * qwen3next_scopes.least_seconds(
            kernel, shapes, "TPU v5 lite") / seconds
        assert qwen3next_scopes.roofline(
            got, kernel, shapes, "TPU v5 lite") == pytest.approx(want)
    # the bytes bind the scan (q and k at the 16 KEY heads), the
    # operations the 256-wide flash call
    assert qwen3next_scopes.least_seconds(
        "gdn_fwd", shapes, "TPU v5 lite") == pytest.approx(
            4 * 8192 * 24832 / 819e9, rel=0.02)
    assert qwen3next_scopes.reduce({0: []}, {0: []}, {}) is None
