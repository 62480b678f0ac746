"""The LFM2 family through the real ``run.py`` on the CPU at a tiny size
(``tiny-lfm2.json``), and the ``lfm2_scopes`` reader (shares, the two
convolution rooflines and the three flash rooflines) on recorded events
worked out by hand. Run by hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import lfm2_flops, mla_flops
from benchmark.readers import lfm2_scopes, moe_scopes, ssm_scopes
from benchmark.tests import rehearse

MS = 1e-3
CELL = "lfm2-ep4-solo-steady"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_lfm2_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-lfm2",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-lfm2", "source": "test only",
        "file": "benchmark/tests/tiny-lfm2.json", "reduced": [],
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
    # sequences of 8192 at the published widths (tests/test_lfm2.py holds
    # the comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 3e-2
    assert 0.0 <= reference["top4_disagreement"] < 0.1
    assert reference["hidden_rel_l2_rms"] < 0.04
    assert reference["tokens"] == 2 * 64
    assert len(reference["rows_held"]) == len(reference["held_share"]) == 3
    assert all(0 < s < 1 for s in reference["held_share"])
    assert all(m >= 1.0 for m in reference["load_max_over_mean"])
    assert set(reference["conv_rel_l2"]) == {"y", "dB", "dC", "dX", "dtaps"}
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
    assert (got["sconv_device_share"]["value"]
            + got["gqa_device_share"]["value"]) == pytest.approx(
        got["attn_device_share"]["value"], rel=0.02)
    assert (got["sconv_proj_device_share"]["value"]
            + got["sconv_core_device_share"]["value"]) == pytest.approx(
        got["sconv_device_share"]["value"], rel=1e-6)
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts")]
    assert all(v > 0 for v in inner)
    # the dense MLP of layer 0 stands under mlp beside them
    assert sum(inner) < got["mlp_device_share"]["value"]
    # the two gauges of the optimizer wrapper's sink
    assert 0.0 < got["moe_held_share"]["value"] < 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    # every metric the cell lists (the 2 of set-up, the 15 solo ones, the
    # sparse sublayer's 3, the GQA share, this PR's 10) but the five
    # rooflines: on the CPU the kernels run in Pallas's interpreter, and
    # no event is named ``sconv_fwd`` or ``flash_fwd``
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 31
    missing = mine - set(got)
    assert missing <= {"sconv_fwd_roofline", "sconv_bwd_roofline",
                       "gqa_flash_fwd_roofline", "gqa_flash_dq_roofline",
                       "gqa_flash_dkv_roofline",
                       # a 4 s window is all traced, so no rate of
                       # untraced steps
                       "ft_over_bare", "window_over_blocks"}, missing


def test_inner_scope_classification() -> None:
    step = "jit(tft_train_step)/"
    assert lfm2_scopes.inner_scopes(
        step + "jvp(attn)/sconv_core/pallas_call") == ("sconv", "sconv_core")
    assert lfm2_scopes.inner_scopes(
        step + "transpose(jvp(attn))/sconv_in/dot_general") == (
            "sconv", "sconv_proj")
    assert lfm2_scopes.inner_scopes(
        step + "rematted_computation/attn/sconv_out/dot_general") == (
            "sconv", "sconv_proj")
    # the attention mixer's scopes are ssm_scopes', the sparse
    # sublayer's moe_scopes'
    assert lfm2_scopes.inner_scopes(
        step + "jvp(attn)/gqa_core/pallas_call") == ()
    assert ssm_scopes.inner_scopes(
        step + "jvp(attn)/gqa_core/pallas_call") == ("gqa",)
    assert lfm2_scopes.inner_scopes(step + "jvp(mlp)/moe_experts/mul") == ()
    assert lfm2_scopes.inner_scopes(None) == ()


def test_shares_and_rooflines_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    tables = {"jit_tft_train_step": {
        "fusion.1": step + "jvp(attn)/sconv_in/dot_general",
        "sconv_fwd.1": step + "jvp(attn)/sconv_core/pallas_call",
        "sconv_fwd.2": step + "rematted_computation/attn/sconv_core/pallas_call",
        "sconv_bwd.1": step + "transpose(jvp(attn))/sconv_core/pallas_call",
        "fusion.2": step + "jvp(attn)/sconv_core/convert",
        "fusion.3": step + "jvp(attn)/sconv_out/dot_general",
        "flash_fwd.1": step + "jvp(attn)/gqa_core/pallas_call",
        "flash_fwd.2": step + "rematted_computation/attn/gqa_core/pallas_call",
        "flash_dq.1": step + "transpose(jvp(attn))/gqa_core/pallas_call",
        "flash_dkv.1": step + "transpose(jvp(attn))/gqa_core/pallas_call",
        "fusion.6": step + "jvp(attn)/gqa_proj/dot_general",
        "fusion.8": step + "jvp(mlp)/moe_experts/mul",
    }}
    ops = {0: [
        ("fusion.1", 0.0, 1 * MS),              # sconv_proj 1
        ("sconv_fwd.1", 1 * MS, 3 * MS),        # sconv_core 2
        ("fusion.2", 3 * MS, 4 * MS),           # sconv_core 1 (XLA around it)
        ("fusion.3", 4 * MS, 5 * MS),           # sconv_proj 1
        ("flash_fwd.1", 5 * MS, 9 * MS),        # gqa 4
        ("fusion.6", 9 * MS, 10 * MS),          # gqa 1
        ("fusion.8", 10 * MS, 11 * MS),         # moe_scopes': experts 1
        ("flash_fwd.2", 11 * MS, 15 * MS),      # gqa 4, the remat's
        ("flash_dq.1", 15 * MS, 20 * MS),       # gqa 5
        ("flash_dkv.1", 20 * MS, 26 * MS),      # gqa 6
        ("sconv_fwd.2", 26 * MS, 28 * MS),      # sconv_core 2, the remat's
        ("sconv_bwd.1", 28 * MS, 32 * MS),      # sconv_core 4
        ("copy.1", 32 * MS, 33 * MS),           # no path
        # a second step, cut by the window's edge after one forward call
        ("sconv_fwd.1", 33 * MS, 36 * MS),      # sconv_core 3
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 33 * MS),
                   ("jit_tft_train_step", 33 * MS, 36 * MS)]}
    got = lfm2_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(36 * MS)
    assert got["shares"] == pytest.approx({
        "sconv": 14 / 36, "sconv_proj": 2 / 36, "sconv_core": 12 / 36})
    assert ssm_scopes.reduce(ops, modules, tables)["shares"][
        "gqa"] == pytest.approx(20 / 36)
    assert moe_scopes.reduce(ops, modules, tables)["shares"][
        "experts"] == pytest.approx(1 / 36)
    assert [s["calls"]["sconv_fwd"] for s in got["steps"]] == [2, 1]
    # one conv layer, 32 768 tokens at 2048 channels, 3 taps: forward
    # 16 384 B a token = 0.6555 ms at 819 GB/s, backward 28 672 B = 1.1471
    shapes = {"batch": 4, "seq_len": 8192, "channels": 2048, "taps": 3,
              "n_heads": 32, "head_dim": 64,
              "layers": {"conv": 1, "attn": 1}}
    fwd_ms = 32768 * lfm2_flops.sconv_bytes_per_token(
        "sconv_fwd", channels=2048) / 819e9 * 1e3
    bwd_ms = 32768 * lfm2_flops.sconv_bytes_per_token(
        "sconv_bwd", channels=2048) / 819e9 * 1e3
    assert fwd_ms == pytest.approx(0.6555, rel=1e-3)
    assert bwd_ms == pytest.approx(1.1471, rel=1e-3)
    # the operations never bind: 16 384 and 47 104 a token
    assert 32768 * lfm2_flops.sconv_flops_per_token(
        "sconv_bwd", channels=2048, taps=3) / 197e12 * 1e3 < 0.01
    # the forward ran twice in the whole step (remat): 4 ms for one call's work
    assert lfm2_scopes.roofline(got, "sconv_fwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * fwd_ms / 4, rel=1e-6)
    assert lfm2_scopes.roofline(got, "sconv_bwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * bwd_ms / 4, rel=1e-6)
    # 128 heads of 8192 at 64 + 64: 128 x 33 558 528 pairs x 256 = 1.0996
    # TFLOP = 5.582 ms at 197 TFLOP/s a kernel
    flash_ms = mla_flops.flash_flops_per_call(128, 8192, 64, 64) / 197e12 * 1e3
    assert flash_ms == pytest.approx(5.582, rel=1e-3)
    assert lfm2_scopes.roofline(got, "flash_fwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * flash_ms / 8, rel=1e-6)
    assert lfm2_scopes.roofline(got, "flash_dq", shapes, "TPU v5 lite") == \
        pytest.approx(100 * flash_ms / 5, rel=1e-6)
    assert lfm2_scopes.roofline(got, "flash_dkv", shapes, "TPU v5 lite") == \
        pytest.approx(100 * flash_ms / 6, rel=1e-6)
    # no whole step: nothing to report
    assert lfm2_scopes.roofline(
        got, "sconv_bwd", dict(shapes, layers={"conv": 2, "attn": 1}),
        "TPU v5 lite") is None
    # a program without the scopes: nothing, though it has a GQA mixer
    nemotron = {"jit_tft_train_step": {
        "flash_fwd.1": step + "jvp(attn)/gqa_core/pallas_call",
        "fusion.1": step + "jvp(attn)/ssm_in/dot_general"}}
    assert lfm2_scopes.reduce(ops, modules, nemotron) is None
    assert lfm2_scopes.reduce(ops, modules, {}) is None
