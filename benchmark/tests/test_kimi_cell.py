"""The Kimi Linear family through the real ``run.py`` on the CPU at a tiny
size (``tiny-kimi.json``), and the ``kda_scopes`` reader (shares and the
two scan rooflines) on recorded events worked out by hand. Run by hand
with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import kda_flops
from benchmark.readers import kda_scopes, mla_scopes, moe_scopes
from benchmark.tests import rehearse

MS = 1e-3
CELL = "kimi-ep32-solo-steady"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_kimi_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-kimi",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-kimi", "source": "test only",
        "file": "benchmark/tests/tiny-kimi.json", "reduced": [],
        "why": "t",
    }]
    # the copy drops every metric's ``workloads``;
    # ``moe_experts_roofline`` lists the OLMoE cell alone because its
    # reader takes the first layer's ``moe`` shapes, and this family's
    # first layer has the dense MLP
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
    # sequences of 8192 at the published widths (tests/test_kimi_linear.py
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
    assert reference["hidden_rel_l2_rms"] < 0.05
    assert reference["tokens"] == 2 * 32
    assert len(reference["rows_held"]) == len(reference["held_share"]) == 2
    assert all(0 < s < 1 for s in reference["held_share"])
    assert all(m >= 1.0 for m in reference["load_max_over_mean"])
    assert set(reference["kda_rel_l2"]) == {"o", "dq", "dk", "dv", "dg",
                                            "dbeta"}
    assert reference["moe_rows"] > 0 and reference["moe_rel_l2_rms"] < 0.02
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
    assert (got["kda_device_share"]["value"]
            + got["mla_proj_device_share"]["value"]
            + got["mla_core_device_share"]["value"]) == pytest.approx(
        got["attn_device_share"]["value"], rel=0.02)
    assert (got["kda_proj_device_share"]["value"]
            + got["kda_conv_gate_device_share"]["value"]
            + got["kda_core_device_share"]["value"]) == pytest.approx(
        got["kda_device_share"]["value"], rel=1e-6)
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts", "shared")]
    assert all(v > 0 for v in inner)
    # the dense MLP of layer 0 stands under mlp beside them
    assert sum(inner) < got["mlp_device_share"]["value"]
    # the three gauges of the optimizer wrapper's sink
    assert 0.0 < got["moe_held_share"]["value"] < 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["moe_row_buffer_share"]["value"] == 1.0
    # every metric the cell lists (the 2 of set-up, the 15 solo ones, the
    # sparse sublayer's 4, MLA's 2, the 3 gauges, this PR's 6) but the
    # two rooflines: on the CPU the kernels run in Pallas's interpreter,
    # and no event is named ``kda_fwd``
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 32
    missing = mine - set(got)
    assert missing <= {"kda_fwd_roofline", "kda_bwd_roofline",
                       # a 4 s window is all traced, so no rate of
                       # untraced steps
                       "ft_over_bare", "window_over_blocks"}, missing


def test_inner_scope_classification() -> None:
    step = "jit(tft_train_step)/"
    assert kda_scopes.inner_scopes(
        step + "jvp(attn)/kda_core/pallas_call") == ("kda", "kda_core")
    assert kda_scopes.inner_scopes(
        step + "transpose(jvp(attn))/kda_in/dot_general") == (
            "kda", "kda_proj")
    assert kda_scopes.inner_scopes(
        step + "rematted_computation/attn/kda_out/dot_general") == (
            "kda", "kda_proj")
    assert kda_scopes.inner_scopes(
        step + "jvp(attn)/kda_conv/pallas_call") == ("kda", "kda_conv_gate")
    assert kda_scopes.inner_scopes(
        step + "jvp(attn)/kda_gate/mul") == ("kda", "kda_conv_gate")
    # the attention mixer's scopes are mla_scopes', the sparse
    # sublayer's moe_scopes'
    assert kda_scopes.inner_scopes(
        step + "jvp(attn)/mla_core/pallas_call") == ()
    assert mla_scopes.inner_scope(
        step + "jvp(attn)/mla_core/pallas_call") == "core"
    assert kda_scopes.inner_scopes(step + "jvp(mlp)/moe_experts/mul") == ()
    assert kda_scopes.inner_scopes(None) == ()


def test_shares_and_rooflines_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    tables = {"jit_tft_train_step": {
        "fusion.1": step + "jvp(attn)/kda_in/dot_general",
        "ssm_conv_fwd.1": step + "jvp(attn)/kda_conv/pallas_call",
        "kda_fwd.1": step + "jvp(attn)/kda_core/pallas_call",
        "kda_fwd.2": step + "rematted_computation/attn/kda_core/pallas_call",
        "kda_bwd.1": step + "transpose(jvp(attn))/kda_core/pallas_call",
        "fusion.2": step + "jvp(attn)/kda_core/copy",
        "fusion.3": step + "jvp(attn)/kda_gate/mul",
        "fusion.4": step + "jvp(attn)/kda_out/dot_general",
        "flash_fwd.1": step + "jvp(attn)/mla_core/pallas_call",
        "fusion.6": step + "jvp(attn)/mla_q/dot_general",
        "fusion.8": step + "jvp(mlp)/moe_shared/dot_general",
    }}
    ops = {0: [
        ("fusion.1", 0.0, 1 * MS),              # kda_proj 1
        ("ssm_conv_fwd.1", 1 * MS, 2 * MS),     # kda_conv_gate 1
        ("kda_fwd.1", 2 * MS, 5 * MS),          # kda_core 3
        ("fusion.2", 5 * MS, 6 * MS),           # kda_core 1 (XLA around it)
        ("fusion.3", 6 * MS, 7 * MS),           # kda_conv_gate 1
        ("fusion.4", 7 * MS, 8 * MS),           # kda_proj 1
        ("flash_fwd.1", 8 * MS, 12 * MS),       # mla core 4
        ("fusion.6", 12 * MS, 13 * MS),         # mla proj 1
        ("fusion.8", 13 * MS, 14 * MS),         # moe_scopes': shared 1
        ("kda_fwd.2", 14 * MS, 17 * MS),        # kda_core 3, the remat's
        ("kda_bwd.1", 17 * MS, 25 * MS),        # kda_core 8
        ("copy.1", 25 * MS, 26 * MS),           # no path
        # a second step, cut by the window's edge after one forward call
        ("kda_fwd.1", 26 * MS, 30 * MS),        # kda_core 4
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 26 * MS),
                   ("jit_tft_train_step", 26 * MS, 30 * MS)]}
    got = kda_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(30 * MS)
    assert got["shares"] == pytest.approx({
        "kda": 23 / 30, "kda_proj": 2 / 30, "kda_conv_gate": 2 / 30,
        "kda_core": 19 / 30})
    mla = mla_scopes.reduce(ops, modules, tables)["shares"]
    assert mla["core"] == pytest.approx(4 / 30)
    assert mla["proj"] == pytest.approx(1 / 30)
    assert moe_scopes.reduce(ops, modules, tables)["shares"][
        "shared"] == pytest.approx(1 / 30)
    assert [s["calls"]["kda_fwd"] for s in got["steps"]] == [2, 1]
    # one KDA layer, 32 768 tokens of 32 heads of 128: forward 49 280 B a
    # token = 1.9716 ms at 819 GB/s, backward 90 368 B = 3.6155 ms; the
    # operations (3.67 and 7.34 MFLOP a token) would take 0.61 and 1.22
    shapes = {"batch": 4, "seq_len": 8192, "n_heads": 32, "head_dim": 128,
              "layers": 1}
    dims = dict(n_heads=32, head_dim=128)
    fwd_ms = 32768 * kda_flops.kda_bytes_per_token(
        "kda_fwd", **dims) / 819e9 * 1e3
    bwd_ms = 32768 * kda_flops.kda_bytes_per_token(
        "kda_bwd", **dims) / 819e9 * 1e3
    assert fwd_ms == pytest.approx(1.9716, rel=1e-3)
    assert bwd_ms == pytest.approx(3.6155, rel=1e-3)
    assert 32768 * kda_flops.kda_flops_per_token(
        "kda_fwd", **dims) / 197e12 * 1e3 == pytest.approx(0.6105, rel=1e-3)
    assert 32768 * kda_flops.kda_flops_per_token(
        "kda_bwd", **dims) / 197e12 * 1e3 < bwd_ms      # the bytes bind
    # the forward ran twice in the whole step (remat): 6 ms for one call's work
    assert kda_scopes.roofline(got, "kda_fwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * fwd_ms / 6, rel=1e-6)
    assert kda_scopes.roofline(got, "kda_bwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * bwd_ms / 8, rel=1e-6)
    # no whole step: nothing to report
    assert kda_scopes.roofline(got, "kda_bwd", dict(shapes, layers=2),
                               "TPU v5 lite") is None
    # a program without the scopes: nothing, though it has latent attention
    joyai = {"jit_tft_train_step": {
        "flash_fwd.1": step + "jvp(attn)/mla_core/pallas_call",
        "fusion.1": step + "jvp(attn)/mla_q/dot_general"}}
    assert kda_scopes.reduce(ops, modules, joyai) is None
    assert kda_scopes.reduce(ops, modules, {}) is None
