"""The Keye family through the real ``run.py`` on the CPU at a tiny size
(``tiny-keye.json``): the contract's line untraced and traced, and the
seven accepted measurements the cell joins, read by their accepted
readers from this program's scopes and counters; the family's own reader
(``readers/keye_scopes.py``, which no manifest entry names yet: the
manifest holds its 128 per-layer metrics) on plain data; the yardstick's
counts against a hand count at one shape. Run by hand with the other
benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import keye_flops
from benchmark.readers import keye_scopes, moe_scopes, ssm_scopes
from benchmark.tests import rehearse

CELL = "keye2-ep8-solo-steady"
JOINED = {"gqa_device_share", "moe_router_device_share",
          "moe_dispatch_device_share", "moe_experts_device_share",
          "moe_held_share", "moe_load_max_over_mean", "moe_row_buffer_share"}


def test_the_manifest_has_the_cell_and_its_configuration() -> None:
    with open(os.path.join(rehearse._REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = manifest["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="keye-vl-2.0-30b-a3b-ep8",
                        traffic="solo-steady", chips=1)
    entry = manifest["configs"][-1]
    assert entry["file"] == "benchmark/configs/keye-vl-2.0-30b-a3b-ep8.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "num_local_experts", "vocab_size"]
    assert len(manifest["per_layer"]) == 128         # full: nothing added
    mine = rehearse.cell_metrics(CELL)
    assert JOINED <= mine and len(mine) == 2 + 15 + len(JOINED)
    assert not any(name.startswith("dsa_") for name in mine)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_keye_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-keye",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-keye", "source": "test only",
        "file": "benchmark/tests/tiny-keye.json", "reduced": [], "why": "t",
    }]
    # the copy drops every metric's ``workloads``; these read their own
    # family's keys of the configuration
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
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    # the reference's limits are set for 16 384 positions at the published
    # widths (tests/test_keye_family.py holds the comparison at this size)
    reference = line["checks"]["reference"]
    assert reference["bad_sets"] == 0 and reference["late_keys"] == 0
    assert reference["key_set_overlap"] > 0.9
    assert reference["dsa_selected_share"] == pytest.approx(
        702 / 2080, rel=1e-3)
    assert reference["kernels_over"] == []
    if not trace:
        assert set(line["metrics"]) == {"committed_tokens_per_s",
                                        "peak_hbm_gib", "setup_s"}
        return
    got = line["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    six = [got[f"{s}_device_share"]["value"] for s in
           ("xent", "attn", "mlp", "embed", "opt", "unnamed")]
    assert sum(six) == pytest.approx(1.0)
    assert 0 < got["gqa_device_share"]["value"] \
        < got["attn_device_share"]["value"]
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts")]
    assert all(v > 0 for v in inner)
    assert sum(inner) <= got["mlp_device_share"]["value"] * (1 + 1e-6)
    assert 0.0 < got["moe_held_share"]["value"] < 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["moe_row_buffer_share"]["value"] == 1.0
    mine = rehearse.cell_metrics(CELL)
    missing = mine - set(got)
    assert missing <= {"ft_over_bare", "window_over_blocks"}, missing
    # the family's reader reads the share the check counted (its trace
    # side is held on plain data below and on the chip by
    # ``keye_rooflines.py``)
    assert keye_scopes.read({"checks": line["checks"]},
                            {"what": "selected_share"}) == (
        pytest.approx(702 / 2080, rel=1e-3))


def test_the_accepted_readers_take_this_programs_paths() -> None:
    step = "jit(tft_train_step)/"
    for inner in ("rope/pallas_call", "dot_general"):
        assert ssm_scopes.inner_scopes(
            step + f"jvp(attn)/gqa_proj/{inner}") == ("gqa",)
    for scope in keye_scopes.SCOPES:
        assert ssm_scopes.inner_scopes(
            step + f"jvp(attn)/{scope}/pallas_call") == ()
    for scope, share in (("moe_router", "router"), ("moe_dispatch", "dispatch"),
                         ("moe_combine", "dispatch"),
                         ("moe_experts", "experts")):
        assert moe_scopes.inner_scope(
            step + f"jvp(mlp)/{scope}/dot_general") == share


def test_the_familys_reader_on_plain_data() -> None:
    """Two whole steps of two layers (the forward kept across the
    checkpoint: one ``dsa_fwd`` a layer; both calls of ``dsa_kl``) and a
    third that the trace cut: the shares' denominator is every event, the
    rooflines count the whole steps alone."""
    step = "jit(tft_train_step)/"
    table = {
        "dsa_select.1": step + "jvp(attn)/dsa_select/pallas_call",
        "dsa_fwd.1": step + "jvp(attn)/dsa_core/pallas_call",
        "dsa_dq.1": step + "transpose(jvp(attn))/dsa_core/pallas_call",
        "dsa_dkv.1": step + "transpose(jvp(attn))/dsa_core/pallas_call",
        "dsa_kl.1": step + "jvp(attn)/dsa_kl/pallas_call",
        "dsa_kl.2": step + "transpose(jvp(attn))/dsa_kl/pallas_call",
        "fusion.1": step + "jvp(attn)/dsa_index/dot_general",
        "fusion.2": step + "jvp(mlp)/moe_experts/dot_general",
    }
    layer = ["dsa_select.1", "dsa_fwd.1", "dsa_kl.1", "fusion.1", "fusion.2",
             "dsa_kl.2", "dsa_dq.1", "dsa_dkv.1"]
    ops, modules, t = [], [], 0.0
    for whole in (True, True, False):
        start = t
        for name in (layer * 2 if whole else layer[:3]):
            ops.append((name, t, t + 1.0))
            t += 1.0
        modules.append(("jit_tft_train_step", start, t))
    got = keye_scopes.reduce({0: ops}, {0: modules},
                             {"jit_tft_train_step": table})
    assert got["total_s"] == pytest.approx(35.0)
    assert got["shares"] == {
        "dsa_index": pytest.approx(4 / 35), "dsa_select": pytest.approx(5 / 35),
        "dsa_core": pytest.approx(13 / 35), "dsa_kl": pytest.approx(9 / 35)}
    shapes = dict(batch=2, seq_len=16384, n_layers=2, n_heads=32,
                  n_kv_heads=4, head_dim=128, index_heads=16, index_dim=64,
                  topk=2048)
    for kernel, seconds in (("dsa_select", 4.0), ("dsa_fwd", 4.0),
                            ("dsa_dq", 4.0), ("dsa_kl", 8.0)):
        want = 100.0 * 2 * 2 * keye_scopes.least_seconds(
            kernel, shapes, "TPU v5 lite") / seconds
        assert keye_scopes.roofline(
            got, kernel, shapes, "TPU v5 lite") == pytest.approx(want)
    assert keye_scopes.reduce({0: []}, {0: []}, {}) is None
    assert keye_scopes.read({"checks": {}}, {"what": "selected_share"}) is None


def test_the_yardsticks_counts_at_one_shape() -> None:
    """``keye_flops.py`` against a hand count at 2 x 16 384, 32 | 4 heads
    of 128, an indexer of 16 x 64, topk 2048: the operations bind every
    kernel on a v5e (197 TFLOP/s, 819 GB/s)."""
    shapes = dict(batch=2, seq_len=16384, n_heads=32, n_kv_heads=4,
                  head_dim=128, index_heads=16, index_dim=64, topk=2048)
    chosen = 2 * (16384 * 2048 - 2048 * 2047 / 2)
    causal = 2 * 16384 * 16385 / 2
    assert keye_flops.kernel_flops("dsa_select", **shapes) == causal * 2048
    for kernel in ("dsa_fwd", "dsa_dq", "dsa_dkv"):
        assert keye_flops.kernel_flops(kernel, **shapes) == (
            chosen * 32 * 512)
    assert keye_flops.kernel_flops("dsa_kl", **shapes) == chosen * (
        2 * 32 * 128 + 4 * 1024)
    # every operand once: q and o 268 MB each, k and v 33.5, the packed
    # sets 67 MB, the statistics 4 MB
    assert keye_flops.kernel_bytes("dsa_fwd", **shapes) == pytest.approx(
        2 * 268.4e6 + 2 * 33.55e6 + 67.1e6 + 4.19e6, rel=1e-3)
    for kernel in keye_flops.KERNELS:
        assert (keye_flops.kernel_flops(kernel, **shapes) / 197e12
                > keye_flops.kernel_bytes(kernel, **shapes) / 819e9), kernel
    assert keye_scopes.least_seconds(
        "dsa_fwd", shapes, "TPU v5 lite") == pytest.approx(
            chosen * 32 * 512 / 197e12)
