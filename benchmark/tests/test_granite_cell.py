"""The Granite 4.0-H family through the real ``run.py`` on the CPU at a
tiny size (``tiny-granite.json``), and the ``granite_scopes`` reader (the
two scan rooflines at ONE group) on recorded events worked out by hand.
Run by hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import ssd_flops
from benchmark.readers import granite_scopes, ssm_scopes
from benchmark.tests import rehearse

MS = 1e-3
CELL = "granite4h-vp8-solo-steady"
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_granite_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-granite",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-granite", "source": "test only",
        "file": "benchmark/tests/tiny-granite.json", "reduced": [],
        "why": "t",
    }]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2147483657", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for sequences
    # of 8192 at the published widths (tests/test_granite_hybrid.py holds
    # the comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 3e-2
    assert reference["hidden_rel_l2_rms"] < 0.04
    assert reference["tokens"] == 2 * 32
    assert set(reference["scan_rel_l2"]) == {"y", "dx", "ddt", "dA", "dB",
                                             "dC", "dD"}
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
    assert got["mlp_device_share"]["value"] > 0
    # every metric the cell lists: the 2 of set-up, the 15 solo ones, the
    # state-space mixer's 4 and gqa (the manifest has no place for the two
    # rooflines of this family: readers/granite_scopes.py)
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 22
    missing = mine - set(got)
    # a 4 s window is all traced, so no rate of untraced steps
    assert missing <= {"ft_over_bare", "window_over_blocks"}, missing


def test_the_cells_shapes_come_from_granites_own_keys() -> None:
    with open(os.path.join(
            _BENCH, "configs", "granite-4.0-h-micro-vp8.json")) as f:
        config = json.load(f)
    assert granite_scopes.config_shapes(config, 16384) == {
        "tokens": 16384, "heads": 64, "head_dim": 64, "groups": 1,
        "state": 128, "chunk": 256, "n_layers": 9}
    # Nemotron-H's keys are ssm_scopes' to read, and Granite's not its
    with open(os.path.join(
            _BENCH, "configs", "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        assert granite_scopes.config_shapes(json.load(f), 32768) is None
    assert "ssm_state_size" not in config
    assert "hybrid_override_pattern" not in config


def test_rooflines_at_one_group_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    tables = {"jit_tft_train_step": {
        "ssd_fwd.1": step + "jvp(attn)/ssm_scan/pallas_call",
        "ssd_fwd.2": step + "rematted_computation/attn/ssm_scan/pallas_call",
        "ssd_bwd.1": step + "transpose(jvp(attn))/ssm_scan/pallas_call",
        "fusion.1": step + "jvp(mlp)/dot_general",
    }}
    ops = {0: [
        ("ssd_fwd.1", 0.0, 2 * MS), ("fusion.1", 2 * MS, 3 * MS),
        ("ssd_fwd.2", 3 * MS, 5 * MS), ("ssd_bwd.1", 5 * MS, 9 * MS),
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 9 * MS)]}
    got = ssm_scopes.reduce(ops, modules, tables)
    # one Mamba-2 layer, 16 384 tokens at 64 x 64, ONE group, state 128:
    # forward 17 152 B a token = 281.0 MB = 0.3431 ms at 819 GB/s against
    # 3.1827 MFLOP a token = 52.1 GFLOP = 0.2647 ms at 197 TFLOP/s: the
    # bytes bind; backward 26 112 B a token = 0.5224 ms against 0.5294 ms
    # of operations: there the operations bind, by 1.3 %
    shapes = {"tokens": 16384, "heads": 64, "head_dim": 64, "groups": 1,
              "state": 128, "chunk": 256, "n_layers": 1}
    dims = {k: shapes[k] for k in ("heads", "head_dim", "groups", "state")}
    assert ssd_flops.ssd_bytes_per_token("ssd_fwd", **dims) == 17152
    assert ssd_flops.ssd_bytes_per_token("ssd_bwd", **dims) == 26112
    fwd_ms = 16384 * 17152 / 819e9 * 1e3
    bwd_ms = 16384 * ssd_flops.ssd_flops_per_token(
        "ssd_bwd", chunk=256, **dims) / 197e12 * 1e3
    assert fwd_ms == pytest.approx(0.3431, rel=1e-3)
    assert bwd_ms == pytest.approx(0.5294, rel=1e-3)
    assert bwd_ms > 16384 * 26112 / 819e9 * 1e3
    # the forward ran twice in the whole step (remat): 4 ms for one call's work
    assert ssm_scopes.roofline(got, "ssd_fwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * fwd_ms / 4, rel=1e-6)
    assert ssm_scopes.roofline(got, "ssd_bwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * bwd_ms / 4, rel=1e-6)
    assert granite_scopes.READS == {"ssd_g1_fwd_roofline": "ssd_fwd",
                                    "ssd_g1_bwd_roofline": "ssd_bwd"}
