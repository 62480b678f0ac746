"""The Phi-4-mini-flash family through the real ``run.py`` on the CPU at a
tiny size (``tiny-phi4flash.json``), and the ``phi4flash_scopes`` reader
(shares, the scan's two rooflines and the windowed flash calls' three) on
recorded events worked out by hand. Run by hand with the other benchmark
tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import phi4flash_flops
from benchmark.readers import phi4flash_scopes, ssm_scopes
from benchmark.tests import rehearse

MS = 1e-3
CELL = "phi4flash-vp8-solo-steady"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_phi4flash_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-phi4flash",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-phi4flash", "source": "test only",
        "file": "benchmark/tests/tiny-phi4flash.json", "reduced": [],
        "why": "t",
    }]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2147483651", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for two
    # sequences of 8192 at the published widths (tests/test_phi4flash.py
    # holds the comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 3e-2
    assert reference["hidden_rel_l2_rms"] < 0.04
    assert reference["tokens"] == 2 * 64
    assert set(reference["scan_rel_l2"]) == {
        "y", "dx", "ddt", "dA", "dB", "dC", "dD"}
    if not trace:
        assert set(line["metrics"]) == {"committed_tokens_per_s",
                                        "peak_hbm_gib", "setup_s"}
        return
    got = line["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    six = [got[f"{s}_device_share"]["value"] for s in
           ("xent", "attn", "mlp", "embed", "opt", "unnamed")]
    assert sum(six) == pytest.approx(1.0)
    # all five kinds of mixer stand under attn: the Mamba mixers, the
    # attention mixers and the GMU are its parts (memory_grad's adds lie
    # inside the mixers' backward)
    parts = (got["ssm_device_share"]["value"]
             + got["diff_attn_device_share"]["value"]
             + got["gmu_device_share"]["value"])
    assert parts == pytest.approx(got["attn_device_share"]["value"], rel=0.05)
    assert (got["ssm_proj_device_share"]["value"]
            + got["ssm_conv_gate_device_share"]["value"]
            + got["ssm_scan_device_share"]["value"]) == pytest.approx(
        got["ssm_device_share"]["value"], rel=1e-6)
    inner = [got[f"{s}_device_share"]["value"] for s in
             ("swa_core", "full_core", "diff_combine")]
    assert all(v > 0 for v in inner)
    assert sum(inner) < got["diff_attn_device_share"]["value"]
    # every metric the cell lists (the 2 of set-up, the 15 solo ones, the
    # 4 state-space shares, this PR's 11) but the five rooflines: on the
    # CPU the kernels run in Pallas's interpreter or not at all, and no
    # event is named ``s6_fwd`` or ``flash_fwd``
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 32
    missing = mine - set(got)
    assert missing <= {"s6_fwd_roofline", "s6_bwd_roofline",
                       "swa_flash_fwd_roofline", "swa_flash_dq_roofline",
                       "swa_flash_dkv_roofline",
                       # a 4 s window is all traced, so no rate of
                       # untraced steps
                       "ft_over_bare", "window_over_blocks"}, missing


def test_scope_classification() -> None:
    step = "jit(tft_train_step)/"
    assert {"diff_attn", "swa_core"} <= phi4flash_scopes.scopes_of(
        step + "jvp(attn)/diff_attn/swa_core/pallas_call")
    assert "memory_grad" in phi4flash_scopes.scopes_of(
        step + "transpose(jvp(attn))/diff_attn/diff_proj/memory_grad/add")
    assert "gmu" in phi4flash_scopes.scopes_of(
        step + "rematted_computation/attn/gmu/dot_general")
    assert phi4flash_scopes.scopes_of(None) == set()
    # the Mamba mixer's scopes are ssm_scopes'
    assert ssm_scopes.inner_scopes(
        step + "jvp(attn)/ssm_scan/pallas_call") == ("ssm", "ssm_scan")


def test_shares_and_rooflines_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    swa = "attn/diff_attn/swa_core/pallas_call"
    tables = {"jit_tft_train_step": {
        "fusion.1": step + "jvp(attn)/ssm_in/dot_general",
        "s6_fwd.1": step + "jvp(attn)/ssm_scan/pallas_call",
        "s6_fwd.2": step + "rematted_computation/attn/ssm_scan/pallas_call",
        "s6_bwd.1": step + "transpose(jvp(attn))/ssm_scan/pallas_call",
        # a differential layer: a call a half of its pairs
        "flash_fwd.1": step + f"jvp({swa})", "flash_fwd.2": step + f"jvp({swa})",
        "flash_dq.1": step + f"transpose(jvp({swa}))",
        "flash_dq.2": step + f"transpose(jvp({swa}))",
        "flash_dkv.1": step + f"transpose(jvp({swa}))",
        "flash_dkv.2": step + f"transpose(jvp({swa}))",
        "flash_fwd.3": step + "jvp(attn)/diff_attn/full_core/pallas_call",
        "fusion.2": step + "jvp(attn)/diff_attn/diff_combine/mul",
        "fusion.3": step + "jvp(attn)/diff_attn/diff_proj/dot_general",
        "fusion.4": step + "jvp(attn)/gmu/dot_general",
        "fusion.5": step + "transpose(jvp(attn))/diff_attn/diff_proj/"
                           "memory_grad/add",
    }}
    ops = {0: [
        ("fusion.1", 0.0, 1 * MS),              # ssm_scopes': proj 1
        ("s6_fwd.1", 1 * MS, 4 * MS),           # scan 3
        ("flash_fwd.1", 4 * MS, 5 * MS),        # diff_attn, swa_core 1
        ("flash_fwd.2", 5 * MS, 6 * MS),        # diff_attn, swa_core 1
        ("flash_fwd.3", 6 * MS, 11 * MS),       # diff_attn, full_core 5
        ("fusion.2", 11 * MS, 12 * MS),         # diff_attn, diff_combine 1
        ("fusion.3", 12 * MS, 14 * MS),         # diff_attn 2
        ("fusion.4", 14 * MS, 15 * MS),         # gmu 1
        ("fusion.5", 15 * MS, 16 * MS),         # diff_attn, memory_grad 1
        ("flash_dq.1", 16 * MS, 18 * MS),       # swa_core 2
        ("flash_dq.2", 18 * MS, 19 * MS),       # swa_core 1
        ("flash_dkv.1", 19 * MS, 21 * MS),      # swa_core 2
        ("flash_dkv.2", 21 * MS, 25 * MS),      # swa_core 4
        ("s6_fwd.2", 25 * MS, 28 * MS),         # scan 3, the remat's
        ("s6_bwd.1", 28 * MS, 36 * MS),         # scan 8
        ("copy.1", 36 * MS, 37 * MS),           # no path
        # a second step, cut by the window's edge after one forward call
        ("s6_fwd.1", 37 * MS, 40 * MS),
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 37 * MS),
                   ("jit_tft_train_step", 37 * MS, 40 * MS)]}
    got = phi4flash_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(40 * MS)
    assert got["shares"] == pytest.approx({
        "diff_attn": 20 / 40, "swa_core": 11 / 40, "full_core": 5 / 40,
        "diff_combine": 1 / 40, "gmu": 1 / 40, "memory_grad": 1 / 40})
    assert ssm_scopes.reduce(ops, modules, tables)["shares"][
        "ssm_scan"] == pytest.approx(17 / 40)
    assert [s["calls"]["s6_fwd"] for s in got["steps"]] == [2, 1]
    # the full call's flash_fwd is no windowed call
    assert got["steps"][0]["calls"]["swa_flash_fwd"] == 2
    shapes = dict(batch=4, seq_len=8192, d_inner=5120, state=16, n_heads=40,
                  head_dim=64, window=512, n_mamba=1, n_swa=1)
    # one scan call, 32 768 tokens at 5120 channels and 16 states: forward
    # 41 024 B a token = 1.641 ms at 819 GB/s, backward 71 808 B = 2.873
    fwd_ms = phi4flash_scopes.least_seconds("s6_fwd", shapes,
                                            "TPU v5 lite") * 1e3
    bwd_ms = phi4flash_scopes.least_seconds("s6_bwd", shapes,
                                            "TPU v5 lite") * 1e3
    assert phi4flash_flops.s6_bytes_per_token(
        "s6_fwd", channels=5120, state=16) == 41024
    assert phi4flash_flops.s6_bytes_per_token(
        "s6_bwd", channels=5120, state=16) == 71808
    assert fwd_ms == pytest.approx(1.6413, rel=1e-3)
    assert bwd_ms == pytest.approx(2.8729, rel=1e-3)
    # the forward ran twice in the whole step (remat): 6 ms for one call's
    # work
    assert phi4flash_scopes.roofline(
        got, "s6_fwd", shapes, "TPU v5 lite") == pytest.approx(
        100 * fwd_ms / 6, rel=1e-6)
    assert phi4flash_scopes.roofline(
        got, "s6_bwd", shapes, "TPU v5 lite") == pytest.approx(
        100 * bwd_ms / 8, rel=1e-6)
    # a call: 80 heads of 8192 under 512 keys: 8192 x 512 - 512 x 511 / 2
    # = 4 063 488 live pairs a head, x 2 (64 + 128) = 0.1248 TFLOP = 0.634
    # ms at 197 TFLOP/s a kernel; the forward's bytes take 0.62 ms
    assert phi4flash_flops.live_pairs(8192, 512) == 4063488
    flash_ms = phi4flash_flops.swa_flash_flops_per_call(
        80, 8192, 512, 64, 128) / 197e12 * 1e3
    assert flash_ms == pytest.approx(0.63365, rel=1e-3)
    assert phi4flash_scopes.roofline(
        got, "swa_flash_fwd", shapes, "TPU v5 lite") == pytest.approx(
        100 * 2 * flash_ms / 2, rel=1e-6)
    # under 512 keys the backward kernels move more than they compute:
    # q, k, v, dO and the statistics in, dq (dk and dv) out
    dq_ms, dkv_ms = (phi4flash_scopes.least_seconds(
        k, shapes, "TPU v5 lite") * 1e3 for k in ("swa_flash_dq",
                                                  "swa_flash_dkv"))
    assert dkv_ms > dq_ms > flash_ms
    assert phi4flash_scopes.roofline(
        got, "swa_flash_dq", shapes, "TPU v5 lite") == pytest.approx(
        100 * 2 * dq_ms / 3, rel=1e-6)
    assert phi4flash_scopes.roofline(
        got, "swa_flash_dkv", shapes, "TPU v5 lite") == pytest.approx(
        100 * 2 * dkv_ms / 6, rel=1e-6)
    # no whole step: nothing to report
    assert phi4flash_scopes.roofline(
        got, "s6_bwd", dict(shapes, n_mamba=2), "TPU v5 lite") is None
    # a program without the scopes: nothing to share out
    nemotron = {"jit_tft_train_step": {
        "flash_fwd.1": step + "jvp(attn)/gqa_core/pallas_call",
        "fusion.1": step + "jvp(attn)/ssm_in/dot_general"}}
    assert phi4flash_scopes.reduce(
        {0: ops[0][4:9]}, modules, nemotron) is None
    assert phi4flash_scopes.reduce({0: []}, {0: []}, {}) is None
