"""The OLMoE family through the real ``run.py`` on the CPU at a tiny
size (``tiny-olmoe.json``), and the ``moe_scopes`` reader on recorded
events worked out by hand. Run by hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import moe_flops
from benchmark.readers import moe_scopes
from benchmark.tests import rehearse

CELL = "olmoe-solo-steady"
MS = 1e-3


@pytest.mark.parametrize("trace", [0, 1])
def test_the_olmoe_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-olmoe", "traffic": "solo-steady",
        "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-olmoe", "source": "test only",
        "file": "benchmark/tests/tiny-olmoe.json", "reduced": [], "why": "t",
    }]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2147483651", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for two
    # sequences of 4096 at the published widths at depth 1: over 128
    # tokens of width 64 bf16 rounding of the loss does not average down
    # (a few 1e-3), and with two layers and 64 positions a flipped token
    # leaks into its neighbours' attention (tests/test_olmoe.py holds the
    # comparison to its limits at this size, on a seed where none does)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 2e-2
    assert 0.0 <= reference["top8_disagreement"] < 0.1
    assert reference["hidden_rel_l2_rms"] < 0.03
    assert reference["tokens_compared"] > 0.8 * reference["tokens"]
    if not trace:
        assert set(line["metrics"]) == {"committed_tokens_per_s",
                                        "peak_hbm_gib", "setup_s"}
        return
    got = line["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    six = [got[s + "_device_share"]["value"] for s in
           ("xent", "attn", "mlp", "embed", "opt", "unnamed")]
    assert sum(six) == pytest.approx(1.0)
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts")]
    assert all(v > 0 for v in inner)
    # the inner scopes lie inside the sparse sublayer, which is all of mlp
    assert sum(inner) == pytest.approx(got["mlp_device_share"]["value"],
                                       rel=0.02)
    # every metric the cell lists (the 2 of set-up, the 15 solo ones, the
    # sparse sublayer's 3 and its roofline) but the two rates of untraced
    # steps (a 4 s window is all traced) and, the kernel being interpreted
    # on the CPU (no gmm event), the roofline
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 21
    assert mine - set(got) <= {"moe_experts_roofline", "ft_over_bare",
                               "window_over_blocks"}
    # no shared expert in this family: nothing to read, so left out
    assert "moe_shared_device_share" not in got


def test_inner_scope_classification() -> None:
    assert moe_scopes.inner_scope(
        "jit(tft_train_step)/jvp(mlp)/moe_experts/jit(gmm)/pallas_call"
    ) == "experts"
    assert moe_scopes.inner_scope(
        "jit(tft_train_step)/transpose(jvp(mlp))/moe_combine/gather"
    ) == "dispatch"
    assert moe_scopes.inner_scope(
        "jit(tft_train_step)/jvp(mlp)/moe_router/top_k") == "router"
    assert moe_scopes.inner_scope(
        "jit(tft_train_step)/jvp(mlp)/dot_general") is None
    assert moe_scopes.inner_scope(None) is None


def test_shares_and_roofline_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    tables = {"jit_tft_train_step": {
        "fusion.1": step + "jvp(mlp)/moe_router/dot_general",
        "custom-call.20": step + "jvp(mlp)/moe_dispatch/gather",
        **{f"gmm.{i}": step + "jvp(mlp)/moe_experts/jit(gmm)/pallas_call"
           for i in (5, 6, 7)},
        "fusion.7": step + "jvp(mlp)/moe_experts/mul",
        **{f"gmm.{i}": step
           + "transpose(jvp(mlp))/moe_experts/jit(gmm)/pallas_call"
           for i in (2, 3, 4)},
        "tgmm.1": step + "transpose(jvp(mlp))/moe_experts/jit(tgmm)/pallas_call",
        "tgmm.2": step + "transpose(jvp(mlp))/moe_experts/jit(tgmm)/pallas_call",
        "tgmm.3": step + "transpose(jvp(mlp))/moe_experts/jit(tgmm)/pallas_call",
        "custom-call.22": step + "jvp(mlp)/moe_combine/gather",
        "fusion.9": step + "jvp(attn)/dot_general",
        # a metadata fusion of the kernel's own jit: in the scope, no kernel
        "fusion.30": step + "jvp(mlp)/moe_experts/jit(gmm)/add",
    }}
    ops = {0: [
        ("fusion.9", 0.0, 5 * MS),             # attn: outside
        ("fusion.1", 5 * MS, 6 * MS),          # router 1
        ("custom-call.20", 6 * MS, 8 * MS),    # dispatch 2
        ("fusion.30", 8 * MS, 9 * MS),         # experts 1, not a kernel
        ("gmm.5", 9 * MS, 10 * MS),            # experts 3, kernels: gate,
        ("gmm.6", 10 * MS, 11 * MS),           # up and down forward
        ("gmm.7", 11 * MS, 12 * MS),
        ("fusion.7", 12 * MS, 13 * MS),        # experts 1, not a kernel
        ("custom-call.22", 13 * MS, 14 * MS),  # combine: dispatch 1
        ("gmm.2", 14 * MS, 14.5 * MS),         # kernels 2: the three
        ("gmm.3", 14.5 * MS, 15 * MS),         # row gradients
        ("gmm.4", 15 * MS, 16 * MS),
        ("tgmm.1", 16 * MS, 17 * MS),          # kernel 1 each: one step of
        ("tgmm.2", 17 * MS, 18 * MS),          # a one-layer model
        ("tgmm.3", 18 * MS, 19 * MS),
        ("copy.1", 19 * MS, 20 * MS),          # no path
        # a second step, cut by the window's edge after its forward pass:
        # its kernel is in the shares, and in no whole step
        ("gmm.5", 20 * MS, 24 * MS),
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 20 * MS),
                   ("jit_tft_train_step", 20 * MS, 24 * MS)]}
    got = moe_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(24 * MS)
    assert got["shares"] == pytest.approx(
        {"router": 1 / 24, "dispatch": 3 / 24, "experts": 14 / 24,
         "shared": 0.0})
    # no shared expert in this program: that share has nothing to read
    assert got["present"] == {"router", "dispatch", "experts"}
    assert moe_scopes.read({"_moe_scopes": got}, {"what": "shared"}) is None
    assert moe_scopes.read(
        {"_moe_scopes": got}, {"what": "router"}) == pytest.approx(1 / 24)
    # a scope the program has and the trace holds no time of reads 0: a
    # share that vanished would hide a scope lost between two runs
    idle = {0: [op for op in ops[0] if op[0] != "fusion.1"]}
    lost = moe_scopes.reduce(idle, modules, tables)
    assert moe_scopes.read({"_moe_scopes": lost}, {"what": "router"}) == 0.0
    assert got["steps"] == [
        {"kernel_s": pytest.approx(8 * MS), "gmm": 6, "tgmm": 3},
        {"kernel_s": pytest.approx(4 * MS), "gmm": 1, "tgmm": 0},
    ]
    shapes = {"tokens": 4096, "top_k": 8, "n_layers": 1,
              "n_experts": 64, "d_model": 2048, "d_expert": 1024}
    # 18 x 2048 x 1024 operations a row, 32768 rows: 1.237 TFLOP in 8 ms
    # of kernels is 154.6 TFLOP/s, 78.5 % of the v5e's 197
    assert moe_flops.expert_flops_per_step(
        4096, 8, 1, 2048, 1024) == pytest.approx(1.2370e12, rel=1e-4)
    assert moe_scopes.roofline(got, shapes, "TPU v5 lite") == pytest.approx(
        100 * 1.2370e12 / 8e-3 / 197e12, rel=1e-4)
    # a program without the scopes: nothing to report
    assert moe_scopes.reduce(ops, modules, {}) is None
    gpt = {"jit_tft_train_step": {"fusion.9": step + "jvp(mlp)/dot_general"}}
    assert moe_scopes.reduce(ops, modules, gpt) is None


def test_byte_count_of_the_grouped_matmuls() -> None:
    # rows [M, d] 2 MiB-elements, [M, f] 1, weights [E, d, f] 134 M: by hand
    m, d, f, e = 1024, 2048, 1024, 64
    rd, rf, w = m * d, m * f, e * d * f
    fwd = 2 * (rd + w + rf) + (rf + w + rd)
    want = 2 * (2 * fwd + 3 * (rd + rf + w))
    assert moe_flops.expert_bytes_per_step(128, 8, 1, e, d, f) == want
