"""The SmallThinker family through the real ``run.py`` on the CPU at a
tiny size (``tiny-smallthinker.json``), and the ``smallthinker_scopes``
reader (the six flash rooflines by kind of call) on recorded events
worked out by hand. Run by hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import smallthinker_flops
from benchmark.readers import (
    moe_scopes,
    phi4flash_scopes,
    smallthinker_scopes,
    ssm_scopes,
)
from benchmark.tests import rehearse

MS = 1e-3
CELL = "smallthinker-ep4-solo-steady"
ROOFLINES = {f"{kind}_flash_{k}_roofline" for kind in ("swa4k", "full16k")
             for k in ("fwd", "dq", "dkv")}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_smallthinker_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-smallthinker",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-smallthinker", "source": "test only",
        "file": "benchmark/tests/tiny-smallthinker.json", "reduced": [],
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
        "--workload", "tiny-cell", "--seed", "2147483651", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for two
    # sequences of 16384 at the published widths (tests/test_smallthinker.py
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
    assert len(reference["rows_held"]) == len(reference["held_share"]) == 8
    assert all(0 < s < 1 for s in reference["held_share"])
    assert all(m >= 1.0 for m in reference["load_max_over_mean"])
    assert set(reference["swa_rel_l2"]) == {"o", "dq", "dk", "dv"}
    if not trace:
        assert set(line["metrics"]) == {"committed_tokens_per_s",
                                        "peak_hbm_gib", "setup_s"}
        return
    got = line["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    six = [got[f"{s}_device_share"]["value"] for s in
           ("xent", "attn", "mlp", "embed", "opt", "unnamed")]
    assert sum(six) == pytest.approx(1.0)
    # gqa_proj + gqa_core tile attn; swa_core + full_core tile gqa_core
    # (the reader of the two cores counts nothing else of gqa, so their
    # sum is below the whole mixer's share, and they are the accepted
    # readers' numbers: ``ssm_scopes``' gqa, ``phi4flash_scopes``' cores)
    assert got["gqa_device_share"]["value"] == pytest.approx(
        got["attn_device_share"]["value"], rel=1e-6)
    cores = (got["swa_core_device_share"]["value"]
             + got["full_core_device_share"]["value"])
    assert 0 < cores < got["gqa_device_share"]["value"]
    assert got["swa_core_device_share"]["value"] > 0
    assert got["full_core_device_share"]["value"] > 0
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts")]
    assert all(v > 0 for v in inner)
    # every layer's MLP is the sparse sublayer: its inner scopes (combine
    # has no metric of its own) stay within mlp
    assert sum(inner) < got["mlp_device_share"]["value"]
    # the three gauges of the optimizer wrapper's sink
    assert 0.0 < got["moe_held_share"]["value"] < 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["moe_row_buffer_share"]["value"] == 1.0
    # every metric the cell lists (the 2 of set-up, the 15 solo ones, the
    # sparse sublayer's 3, the 3 gauges, the GQA share, the two cores,
    # this PR's 6) but the six rooflines: on the CPU the kernels run in
    # XLA's reference attention, and no event is named ``flash_fwd``
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 32
    assert ROOFLINES <= mine
    missing = mine - set(got)
    assert missing <= ROOFLINES | {
        # a 4 s window is all traced, so no rate of untraced steps
        "ft_over_bare", "window_over_blocks"}, missing


def test_the_kind_of_a_flash_call_is_read_off_its_path() -> None:
    step = "jit(tft_train_step)/"
    assert smallthinker_scopes.kind_of(
        step + "jvp(attn)/gqa_core/swa_core/pallas_call") == "swa"
    assert smallthinker_scopes.kind_of(
        step + "transpose(jvp(attn))/gqa_core/full_core/pallas_call") == "full"
    assert smallthinker_scopes.kind_of(
        step + "rematted_computation/attn/gqa_core/swa_core/pallas_call"
    ) == "swa"
    # LFM2's and Nemotron-H's flash call stands under gqa_core alone
    assert smallthinker_scopes.kind_of(
        step + "jvp(attn)/gqa_core/pallas_call") is None
    assert smallthinker_scopes.kind_of(None) is None
    # the accepted readers take this program's paths as they are
    path = step + "jvp(attn)/gqa_core/swa_core/pallas_call"
    assert ssm_scopes.inner_scopes(path) == ("gqa",)
    assert "swa_core" in phi4flash_scopes.scopes_of(path)
    assert ssm_scopes.inner_scopes(
        step + "jvp(attn)/gqa_proj/dot_general") == ("gqa",)
    assert moe_scopes.inner_scope(
        step + "jvp(mlp)/moe_router/dot_general") == "router"


def test_rooflines_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    swa = "gqa_core/swa_core/pallas_call"
    full = "gqa_core/full_core/pallas_call"
    tables = {"jit_tft_train_step": {
        "flash_fwd.1": step + "jvp(attn)/" + full,
        "flash_fwd.2": step + "jvp(attn)/" + swa,
        "flash_fwd.3": step + "rematted_computation/attn/" + swa,
        "flash_fwd.4": step + "rematted_computation/attn/" + full,
        "flash_dq.1": step + "transpose(jvp(attn))/" + swa,
        "flash_dkv.1": step + "transpose(jvp(attn))/" + swa,
        "flash_dq.2": step + "transpose(jvp(attn))/" + full,
        "flash_dkv.2": step + "transpose(jvp(attn))/" + full,
        "fusion.1": step + "jvp(attn)/gqa_proj/dot_general",
        "fusion.2": step + "jvp(mlp)/moe_experts/mul",
    }}
    ops = {0: [
        ("flash_fwd.1", 0.0, 40 * MS),           # full 40
        ("flash_fwd.2", 40 * MS, 60 * MS),       # swa 20
        ("fusion.1", 60 * MS, 61 * MS),
        ("fusion.2", 61 * MS, 62 * MS),
        ("flash_fwd.3", 62 * MS, 82 * MS),       # swa 20, the remat's
        ("flash_dq.1", 82 * MS, 104 * MS),       # swa 22
        ("flash_dkv.1", 104 * MS, 130 * MS),     # swa 26
        ("flash_fwd.4", 130 * MS, 170 * MS),     # full 40, the remat's
        ("flash_dq.2", 170 * MS, 210 * MS),      # full 40
        ("flash_dkv.2", 210 * MS, 260 * MS),     # full 50
        # a second step, cut by the window's edge after one forward call
        ("flash_fwd.1", 260 * MS, 300 * MS),
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 260 * MS),
                   ("jit_tft_train_step", 260 * MS, 300 * MS)]}
    got = smallthinker_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(300 * MS)
    assert [s["calls"]["full_flash_fwd"] for s in got["steps"]] == [2, 1]
    assert [s["calls"]["swa_flash_fwd"] for s in got["steps"]] == [2, 0]
    # the accepted readers on the same events
    assert phi4flash_scopes.reduce(ops, modules, tables)["shares"][
        "swa_core"] == pytest.approx(88 / 300)
    assert phi4flash_scopes.reduce(ops, modules, tables)["shares"][
        "full_core"] == pytest.approx(210 / 300)
    assert ssm_scopes.reduce(ops, modules, tables)["shares"][
        "gqa"] == pytest.approx(299 / 300)
    # one layer of each kind, 2 x 16384 at 28 heads of 128, W 4096: 56
    # heads x 58 722 304 live pairs x 512 = 1.684 TFLOP = 8.547 ms at 197
    # TFLOP/s a windowed kernel; 56 x 134 225 920 x 512 = 19.536 ms a
    # full one; the bytes (1.15 ms forward) never bind
    shapes = {"batch": 2, "seq_len": 16384, "n_heads": 28, "head_dim": 128,
              "window": 4096, "n_swa": 1, "n_full": 1}
    swa_ms = smallthinker_flops.flash_flops_per_call(
        56, 16384, 128, 128, 4096) / 197e12 * 1e3
    full_ms = smallthinker_flops.flash_flops_per_call(
        56, 16384, 128, 128) / 197e12 * 1e3
    assert swa_ms == pytest.approx(8.547, rel=1e-3)
    assert full_ms == pytest.approx(19.536, rel=1e-3)
    assert smallthinker_flops.flash_bytes_per_call(
        "flash_dkv", 56, 16384, 128, 128) / 819e9 * 1e3 < 2.1

    def read(kernel, at=shapes):
        return smallthinker_scopes.roofline(got, kernel, at, "TPU v5 lite")

    # the forward ran twice in the whole step (remat): 40 ms for one
    # call's work
    assert read("swa_flash_fwd") == pytest.approx(100 * swa_ms / 40, rel=1e-6)
    assert read("swa_flash_dq") == pytest.approx(100 * swa_ms / 22, rel=1e-6)
    assert read("swa_flash_dkv") == pytest.approx(100 * swa_ms / 26, rel=1e-6)
    assert read("full_flash_fwd") == pytest.approx(
        100 * full_ms / 80, rel=1e-6)
    assert read("full_flash_dq") == pytest.approx(100 * full_ms / 40, rel=1e-6)
    assert read("full_flash_dkv") == pytest.approx(
        100 * full_ms / 50, rel=1e-6)
    assert all(read(k) < 100 for k in smallthinker_scopes.KERNELS)
    # no whole step of three windowed layers: nothing to report
    assert read("swa_flash_dq", dict(shapes, n_swa=3)) is None
    # the note on the tile rule's cost at the cell's two calls
    note = smallthinker_scopes.tile_note(shapes)
    assert "swa 512 x 1024 tiles, 140 grid steps a head" in note
    assert "full 512 x 1024 tiles, 272 grid steps a head" in note
    # a program without the scopes: nothing, though it has flash calls
    lfm2 = {"jit_tft_train_step": {
        "flash_fwd.1": step + "jvp(attn)/gqa_core/pallas_call"}}
    assert smallthinker_scopes.reduce(ops, modules, lfm2) is None
    assert smallthinker_scopes.reduce(ops, modules, {}) is None


def test_every_layer_metric_file_of_the_six_names_the_new_reader() -> None:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ROOFLINES:
        with open(os.path.join(root, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        kind, kernel = name.split("_flash_")
        assert spec["reader"] == "smallthinker_scopes"
        assert spec["what"] == {"swa4k": "swa", "full16k": "full"}[kind] \
            + "_flash_" + kernel
        assert spec["what"][:-len("_roofline")] in smallthinker_scopes.KERNELS
