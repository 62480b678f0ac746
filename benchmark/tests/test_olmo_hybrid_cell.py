"""The Olmo Hybrid family through the real ``run.py`` on the CPU at a tiny
size (``tiny-olmo-hybrid.json``), and the ``gdn_scopes`` reader (shares
and the two scan rooflines) on recorded events worked out by hand. Run by
hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import olmo_hybrid_flops
from benchmark.readers import gdn_scopes, kda_scopes, phi4flash_scopes
from benchmark.readers import ssm_scopes
from benchmark.tests import rehearse

MS = 1e-3
CELL = "olmohybrid-vp8-solo-steady"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_olmo_hybrid_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-olmo-hybrid",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-olmo-hybrid", "source": "test only",
        "file": "benchmark/tests/tiny-olmo-hybrid.json", "reduced": [],
        "why": "t",
    }]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2147483653", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for sequences
    # of 8192 at the published widths (tests/test_olmo_hybrid.py holds the
    # comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 3e-2
    assert reference["hidden_rel_l2_rms"] < 0.08
    assert reference["tokens"] == 2 * 32
    assert set(reference["gdn_rel_l2"]) == {"o", "dq", "dk", "dv", "dg",
                                            "dbeta"}
    # the gauge: the negative-eigenvalue branch and the decay are live
    assert 0.2 < reference["beta_over_1"] < 0.8
    low, high = reference["decay_range"]
    assert 0.0 <= low < high <= 1.0
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
    assert (got["gdn_device_share"]["value"]
            + got["gqa_device_share"]["value"]) == pytest.approx(
        got["attn_device_share"]["value"], rel=0.02)
    assert 0 < got["gdn_core_device_share"]["value"] < \
        got["gdn_device_share"]["value"]
    assert 0 < got["full_core_device_share"]["value"] < \
        got["gqa_device_share"]["value"]
    # every metric the cell lists (the 2 of set-up, the 15 solo ones, gqa
    # and full_core, this PR's 4) but the two rooflines: on the CPU the
    # kernels run in Pallas's interpreter, and no event is named
    # ``gdn_fwd``
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 23
    missing = mine - set(got)
    assert missing <= {"gdn_fwd_roofline", "gdn_bwd_roofline",
                       # a 4 s window is all traced, so no rate of
                       # untraced steps
                       "ft_over_bare", "window_over_blocks"}, missing


def test_inner_scope_classification() -> None:
    step = "jit(tft_train_step)/"
    assert gdn_scopes.inner_scopes(
        step + "jvp(attn)/gdn_core/pallas_call") == ("gdn", "gdn_core")
    assert gdn_scopes.inner_scopes(
        step + "transpose(jvp(attn))/gdn_in/dot_general") == (
            "gdn", "gdn_proj")
    assert gdn_scopes.inner_scopes(
        step + "rematted_computation/attn/gdn_out/dot_general") == (
            "gdn", "gdn_proj")
    assert gdn_scopes.inner_scopes(
        step + "jvp(attn)/gdn_conv/pallas_call") == ("gdn", "gdn_conv_gate")
    assert gdn_scopes.inner_scopes(
        step + "jvp(attn)/gdn_gate/mul") == ("gdn", "gdn_conv_gate")
    # the attention mixer's scopes are ssm_scopes' and phi4flash_scopes',
    # Kimi's delta rule's kda_scopes'
    full = step + "jvp(attn)/gqa_core/full_core/pallas_call"
    assert gdn_scopes.inner_scopes(full) == ()
    assert "gqa" in ssm_scopes.inner_scopes(full)
    assert "full_core" in phi4flash_scopes.scopes_of(full)
    assert kda_scopes.inner_scopes(
        step + "jvp(attn)/gdn_core/pallas_call") == ()
    assert gdn_scopes.inner_scopes(
        step + "jvp(attn)/kda_core/pallas_call") == ()
    assert gdn_scopes.inner_scopes(None) == ()


def test_shares_and_rooflines_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    tables = {"jit_tft_train_step": {
        "fusion.1": step + "jvp(attn)/gdn_in/dot_general",
        "ssm_conv_fwd.1": step + "jvp(attn)/gdn_conv/pallas_call",
        "gdn_fwd.1": step + "jvp(attn)/gdn_core/pallas_call",
        "gdn_fwd.2": step + "rematted_computation/attn/gdn_core/pallas_call",
        "gdn_bwd.1": step + "transpose(jvp(attn))/gdn_core/pallas_call",
        "fusion.2": step + "jvp(attn)/gdn_core/pad",
        "fusion.3": step + "jvp(attn)/gdn_gate/mul",
        "fusion.4": step + "jvp(attn)/gdn_out/dot_general",
        "flash_fwd.1": step + "jvp(attn)/gqa_core/full_core/pallas_call",
        "fusion.6": step + "jvp(attn)/gqa_proj/dot_general",
        "fusion.8": step + "jvp(mlp)/dot_general",
    }}
    ops = {0: [
        ("fusion.1", 0.0, 1 * MS),              # gdn_proj 1
        ("ssm_conv_fwd.1", 1 * MS, 2 * MS),     # gdn_conv_gate 1
        ("gdn_fwd.1", 2 * MS, 5 * MS),          # gdn_core 3
        ("fusion.2", 5 * MS, 6 * MS),           # gdn_core 1 (XLA around it)
        ("fusion.3", 6 * MS, 7 * MS),           # gdn_conv_gate 1
        ("fusion.4", 7 * MS, 8 * MS),           # gdn_proj 1
        ("flash_fwd.1", 8 * MS, 12 * MS),       # gqa: full_core 4
        ("fusion.6", 12 * MS, 13 * MS),         # gqa: proj 1
        ("fusion.8", 13 * MS, 14 * MS),         # mlp
        ("gdn_fwd.2", 14 * MS, 17 * MS),        # gdn_core 3, the remat's
        ("gdn_bwd.1", 17 * MS, 25 * MS),        # gdn_core 8
        ("copy.1", 25 * MS, 26 * MS),           # no path
        # a second step, cut by the window's edge after one forward call
        ("gdn_fwd.1", 26 * MS, 30 * MS),        # gdn_core 4
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 26 * MS),
                   ("jit_tft_train_step", 26 * MS, 30 * MS)]}
    got = gdn_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(30 * MS)
    assert got["shares"] == pytest.approx({
        "gdn": 23 / 30, "gdn_proj": 2 / 30, "gdn_conv_gate": 2 / 30,
        "gdn_core": 19 / 30})
    assert ssm_scopes.reduce(ops, modules, tables)["shares"][
        "gqa"] == pytest.approx(5 / 30)
    assert phi4flash_scopes.reduce(ops, modules, tables)["shares"][
        "full_core"] == pytest.approx(4 / 30)
    # Kimi's reader finds nothing of its own in this program
    assert kda_scopes.reduce(ops, modules, tables) is None
    assert [s["calls"]["gdn_fwd"] for s in got["steps"]] == [2, 1]
    # one linear layer, 8 192 tokens of 30 heads of 96 x 192: forward
    # 34 800 B a token = 0.3481 ms at 819 GB/s, backward 58 080 B = 0.5809
    # ms; the operations (3.87 and 7.74 MFLOP a token) would take 0.161
    # and 0.322
    shapes = {"batch": 1, "seq_len": 8192, "n_heads": 30, "key_dim": 96,
              "value_dim": 192, "layers": 1}
    dims = dict(n_heads=30, key_dim=96, value_dim=192)
    assert olmo_hybrid_flops.gdn_bytes_per_token("gdn_fwd", **dims) == 34800
    assert olmo_hybrid_flops.gdn_bytes_per_token("gdn_bwd", **dims) == 58080
    fwd_ms = 8192 * 34800 / 819e9 * 1e3
    bwd_ms = 8192 * 58080 / 819e9 * 1e3
    assert fwd_ms == pytest.approx(0.3481, rel=1e-3)
    assert bwd_ms == pytest.approx(0.5809, rel=1e-3)
    assert 8192 * olmo_hybrid_flops.gdn_flops_per_token(
        "gdn_fwd", **dims) / 197e12 * 1e3 == pytest.approx(0.1610, rel=1e-3)
    assert 8192 * olmo_hybrid_flops.gdn_flops_per_token(
        "gdn_bwd", **dims) / 197e12 * 1e3 < bwd_ms      # the bytes bind
    # the forward ran twice in the whole step (remat): 6 ms for one call's work
    assert gdn_scopes.roofline(got, "gdn_fwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * fwd_ms / 6, rel=1e-6)
    assert gdn_scopes.roofline(got, "gdn_bwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * bwd_ms / 8, rel=1e-6)
    # no whole step: nothing to report
    assert gdn_scopes.roofline(got, "gdn_bwd", dict(shapes, layers=2),
                               "TPU v5 lite") is None


def test_the_flops_by_part_at_the_cells_cut() -> None:
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "configs",
            "olmo-hybrid-7b-vp8.json")) as f:
        config = json.load(f)
    parts = olmo_hybrid_flops.train_flops_per_token(
        **olmo_hybrid_flops.config_dims(config))
    assert olmo_hybrid_flops.linear_params(3840, 30, 96, 192) == 88704000
    assert parts["total"] == pytest.approx(5.507e9, rel=1e-3)
    assert parts["gdn_core"] == pytest.approx(34.8e6, rel=2e-3)
    assert parts["attn_core"] == pytest.approx(0.1888e9, rel=1e-3)
    assert parts["mlp"] / parts["total"] == pytest.approx(0.5527, abs=2e-3)
    assert parts["gdn_proj"] / parts["total"] == pytest.approx(0.29, abs=5e-3)
