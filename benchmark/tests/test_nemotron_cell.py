"""The Nemotron-H family through the real ``run.py`` on the CPU at a tiny
size (``tiny-nemotron.json``), and the ``ssm_scopes`` reader (shares and
the two scan rooflines) on recorded events worked out by hand. Run by
hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import ssd_flops
from benchmark.readers import moe_scopes, ssm_scopes
from benchmark.tests import rehearse

MS = 1e-3
CELL = "nemo3-ep16-solo-steady"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_nemotron_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-nemotron",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-nemotron", "source": "test only",
        "file": "benchmark/tests/tiny-nemotron.json", "reduced": [],
        "why": "t",
    }]
    # the copy drops every metric's ``workloads``; ``moe_experts_roofline``
    # lists the OLMoE cell alone because its reader takes the first
    # layer's ``moe`` shapes, and this family's first layer is a Mamba-2
    # mixer
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] != "moe_experts_roofline"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2147483651", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for two
    # sequences of 8192 at the published widths (tests/test_nemotron_h.py
    # holds the comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 3e-2
    assert 0.0 <= reference["top6_disagreement"] < 0.1
    assert reference["hidden_rel_l2_rms"] < 0.04
    assert reference["tokens"] == 2 * 64
    # the check line says what the share held of the reference batch
    assert len(reference["rows_held"]) == len(reference["held_share"]) == 2
    assert all(0 < s < 1 for s in reference["held_share"])
    assert all(m >= 1.0 for m in reference["load_max_over_mean"])
    if not trace:
        assert set(line["metrics"]) == {"committed_tokens_per_s",
                                        "peak_hbm_gib", "setup_s"}
        return
    got = line["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    six = [got[f"{s}_device_share"]["value"] for s in
           ("xent", "attn", "mlp", "embed", "opt", "unnamed")]
    assert sum(six) == pytest.approx(1.0)
    # both sequence mixers stand under attn: its two parts are they
    assert (got["ssm_device_share"]["value"]
            + got["gqa_device_share"]["value"]) == pytest.approx(
        got["attn_device_share"]["value"], rel=0.02)
    assert (got["ssm_scan_device_share"]["value"]
            + got["ssm_proj_device_share"]["value"]
            + got["ssm_conv_gate_device_share"]["value"]) == pytest.approx(
        got["ssm_device_share"]["value"], rel=1e-6)
    # the sparse sublayer's inner scopes are the whole of mlp here
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts", "shared")]
    assert all(v > 0 for v in inner)
    assert sum(inner) == pytest.approx(
        got["mlp_device_share"]["value"], rel=0.05)
    # the three gauges of the optimizer wrapper's sink: the family says
    # which experts are held
    assert 0.0 < got["moe_held_share"]["value"] < 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 <= got["moe_row_buffer_share"]["value"] <= 1.0
    # every metric the cell lists (the 2 of set-up, the 15 solo ones, the
    # sparse sublayer's 4, the 3 gauges, the family's own 7) but the two
    # rooflines: on the CPU the scan runs in Pallas's interpreter, and no
    # event is named ``ssd_fwd``
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 31
    missing = mine - set(got)
    assert missing <= {"ssd_fwd_roofline", "ssd_bwd_roofline",
                       # a 4 s window is all traced, so no rate of
                       # untraced steps
                       "ft_over_bare", "window_over_blocks"}, missing


def test_inner_scope_classification() -> None:
    step = "jit(tft_train_step)/"
    assert ssm_scopes.inner_scopes(
        step + "jvp(attn)/ssm_scan/pallas_call") == ("ssm", "ssm_scan")
    assert ssm_scopes.inner_scopes(
        step + "transpose(jvp(attn))/ssm_in/dot_general") == (
            "ssm", "ssm_proj")
    assert ssm_scopes.inner_scopes(
        step + "rematted_computation/attn/ssm_gate/mul") == (
            "ssm", "ssm_conv_gate")
    assert ssm_scopes.inner_scopes(
        step + "jvp(attn)/gqa_core/pallas_call") == ("gqa",)
    # the sparse sublayer's inner scopes are moe_scopes'
    assert ssm_scopes.inner_scopes(
        step + "jvp(mlp)/moe_shared/dot_general") == ()
    assert ssm_scopes.inner_scopes(step + "jvp(mlp)/moe_experts/mul") == ()
    assert ssm_scopes.inner_scopes(None) == ()


def test_shares_and_rooflines_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    tables = {"jit_tft_train_step": {
        "fusion.1": step + "jvp(attn)/ssm_in/dot_general",
        "fusion.2": step + "jvp(attn)/ssm_conv/add",
        "ssd_fwd.1": step + "jvp(attn)/ssm_scan/pallas_call",
        "fusion.3": step + "jvp(attn)/ssm_scan/cumsum",
        "ssd_fwd.2": step + "rematted_computation/attn/ssm_scan/pallas_call",
        "ssd_bwd.1": step + "transpose(jvp(attn))/ssm_scan/pallas_call",
        "fusion.4": step + "jvp(attn)/ssm_gate/mul",
        "fusion.5": step + "jvp(attn)/ssm_out/dot_general",
        "flash_fwd.1": step + "jvp(attn)/gqa_core/pallas_call",
        "fusion.6": step + "jvp(attn)/gqa_proj/dot_general",
        "fusion.7": step + "jvp(mlp)/moe_shared/dot_general",
        "fusion.8": step + "jvp(mlp)/moe_experts/mul",
    }}
    ops = {0: [
        ("fusion.1", 0.0, 1 * MS),              # ssm_proj 1
        ("fusion.2", 1 * MS, 2 * MS),           # conv_gate 1
        ("ssd_fwd.1", 2 * MS, 4 * MS),          # scan 2
        ("fusion.3", 4 * MS, 5 * MS),           # scan 1 (XLA around it)
        ("fusion.4", 5 * MS, 6 * MS),           # conv_gate 1
        ("fusion.5", 6 * MS, 7 * MS),           # ssm_proj 1
        ("flash_fwd.1", 7 * MS, 9 * MS),        # gqa 2
        ("fusion.6", 9 * MS, 10 * MS),          # gqa 1
        ("fusion.7", 10 * MS, 11 * MS),         # moe_scopes': shared 1
        ("fusion.8", 11 * MS, 12 * MS),         # moe_scopes': experts 1
        ("ssd_fwd.2", 12 * MS, 14 * MS),        # scan 2, the remat's
        ("ssd_bwd.1", 14 * MS, 19 * MS),        # scan 5
        ("copy.1", 19 * MS, 20 * MS),           # no path
        # a second step, cut by the window's edge after one forward call
        ("ssd_fwd.1", 20 * MS, 24 * MS),        # scan 4, in no whole step
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 20 * MS),
                   ("jit_tft_train_step", 20 * MS, 24 * MS)]}
    got = ssm_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(24 * MS)
    assert got["shares"] == pytest.approx({
        "ssm": 18 / 24, "ssm_scan": 14 / 24, "ssm_proj": 2 / 24,
        "ssm_conv_gate": 2 / 24, "gqa": 3 / 24})
    # the shared expert's share is served beside the other three, with
    # the same denominator
    assert moe_scopes.reduce(ops, modules, tables)["shares"] == pytest.approx({
        "router": 0.0, "dispatch": 0.0, "experts": 1 / 24, "shared": 1 / 24})
    assert [s["calls"] for s in got["steps"]] == [
        {"ssd_fwd": 2, "ssd_bwd": 1}, {"ssd_fwd": 1, "ssd_bwd": 0}]
    # one Mamba-2 layer, 32 768 tokens at 64 x 64, 8 groups, state 128:
    # forward 20 736 B a token = 679.5 MB = 0.8296 ms at 819 GB/s against
    # 2.7576 MFLOP a token = 90.4 GFLOP = 0.4587 ms at 197 TFLOP/s: the
    # bytes bind; backward 33 280 B a token = 1.3315 ms against 0.9174 ms
    shapes = {"tokens": 32768, "heads": 64, "head_dim": 64, "groups": 8,
              "state": 128, "chunk": 128, "n_layers": 1}
    dims = {k: shapes[k] for k in ("heads", "head_dim", "groups", "state")}
    fwd_ms = 32768 * ssd_flops.ssd_bytes_per_token("ssd_fwd", **dims) / 819e9 * 1e3
    bwd_ms = 32768 * ssd_flops.ssd_bytes_per_token("ssd_bwd", **dims) / 819e9 * 1e3
    assert fwd_ms == pytest.approx(0.8296, rel=1e-3)
    assert bwd_ms == pytest.approx(1.3315, rel=1e-3)
    assert 32768 * ssd_flops.ssd_flops_per_token(
        "ssd_fwd", chunk=128, **dims) / 197e12 * 1e3 == pytest.approx(
            0.4587, rel=1e-3)
    assert 32768 * ssd_flops.ssd_flops_per_token(
        "ssd_bwd", chunk=128, **dims) / 197e12 * 1e3 == pytest.approx(
            0.9174, rel=1e-3)
    # the forward ran twice in the whole step (remat): 4 ms for one call's work
    assert ssm_scopes.roofline(got, "ssd_fwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * fwd_ms / 4, rel=1e-6)
    assert ssm_scopes.roofline(got, "ssd_bwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * bwd_ms / 5, rel=1e-6)
    # no whole step: nothing to report
    assert ssm_scopes.roofline(
        got, "ssd_bwd", dict(shapes, n_layers=2), "TPU v5 lite") is None
    # a program without the scopes: nothing, though it has a shared expert
    joyai = {"jit_tft_train_step": {
        "fusion.7": step + "jvp(mlp)/moe_shared/dot_general",
        "fusion.1": step + "jvp(attn)/mla_q/dot_general"}}
    assert ssm_scopes.reduce(ops, modules, joyai) is None
    assert ssm_scopes.reduce(ops, modules, {}) is None
