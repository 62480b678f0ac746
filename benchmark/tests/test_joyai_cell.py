"""The JoyAI family through the real ``run.py`` on the CPU at a tiny
size (``tiny-joyai.json``), and the ``mla_scopes`` reader (shares and
the three flash rooflines) on recorded events worked out by hand. Run by
hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import mla_flops
from benchmark.readers import mla_scopes, moe_scopes
from benchmark.tests import rehearse

CELL = "joyai-ep16-solo-steady"
MS = 1e-3


@pytest.mark.parametrize("trace", [0, 1])
def test_the_joyai_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-joyai", "traffic": "solo-steady",
        "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-joyai", "source": "test only",
        "file": "benchmark/tests/tiny-joyai.json", "reduced": [], "why": "t",
    }]
    # the copy drops every metric's ``workloads``; ``moe_experts_roofline``
    # lists the OLMoE cell alone because its reader takes the first
    # layer's ``moe`` shapes, and this family's first layer is dense
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
    # sequences of 8192 at the published widths (tests/test_joyai.py holds
    # the comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["abs_diff"] < 3e-2
    assert 0.0 <= reference["top8_disagreement"] < 0.1
    assert max(reference["hidden_rel_l2_rms"],
               reference["mtp_rel_l2_rms"]) < 0.04
    assert reference["tokens_compared"] > 0.7 * reference["tokens"]
    # the check line says what the share held of the reference batch
    assert len(reference["rows_held"]) == 2          # a layer + the MTP's
    assert all(0 < r < 2 * 64 * 2 for r in reference["rows_held"])
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
    # attn is the projections and the core, in the main layers and the MTP's
    assert (got["mla_proj_device_share"]["value"]
            + got["mla_core_device_share"]["value"]) == pytest.approx(
        got["attn_device_share"]["value"], rel=0.02)
    # the sparse sublayer's five inner scopes and the dense layer's MLP
    inner = [got[f"moe_{s}_device_share"]["value"] for s in
             ("router", "dispatch", "experts", "shared")]
    assert all(v > 0 for v in inner)
    assert sum(inner) < got["mlp_device_share"]["value"]
    assert 0 < got["mtp_device_share"]["value"] < 0.6
    # the three gauges of the optimizer wrapper's sink: the family says
    # which experts are held (4 of the tiny router's 8)
    assert 0.0 < got["moe_held_share"]["value"] < 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["moe_row_buffer_share"]["value"] in (0.0, 0.5, 1.0)
    # every metric the cell lists: the 2 of set-up, the 15 solo ones, the
    # sparse sublayer's 4, the 3 gauges and the family's own 6; all but
    # the three rooflines and the two rates of untraced steps (a 4 s
    # window is all traced) are printed here
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 30
    assert mine - set(got) <= {
        "mla_flash_fwd_roofline", "mla_flash_dq_roofline",
        "mla_flash_dkv_roofline", "ft_over_bare", "window_over_blocks"}
    # on the CPU attention is the XLA path: no flash event, so no roofline
    assert not any(k.endswith("_roofline") for k in got)


def test_the_configurations_first_update_runs_at_the_warm_ups_first_rate(
) -> None:
    """``joyai-llm-flash-ep16``'s optimizer as the family builds it: AdamW
    behind the 2000-step linear warm-up (step c at 4e-4 x (c + 1) / 2000;
    at the constant 4e-4 the router collapsed within 6 - 9 steps), the
    schedule's count in the optimizer state, and the held experts said."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import joyai as family

    with open(os.path.join(rehearse._REPO, "benchmark", "configs",
                           "joyai-llm-flash-ep16.json")) as f:
        config = json.load(f)
    model, opt = family.build(config), config["optimizer"]
    assert model.tx.held_experts == (0, 16)
    first_rate = opt["learning_rate"] / opt["warmup_steps"]
    assert first_rate == pytest.approx(2e-7)
    # Adam's first step on a zero weight is the rate times the gradient's sign
    params = {"w": jnp.zeros((3,), jnp.float32)}
    grads = {"w": jnp.ones((3,), jnp.float32)}
    state = model.tx.init(params)
    for c in range(3):
        update, state = model.tx.update(grads, state, params)
        assert np.allclose(update["w"], -first_rate * (c + 1), rtol=1e-4)
    counts = [int(x) for x in jax.tree_util.tree_leaves(state)
              if x.ndim == 0 and x.dtype == jnp.int32]
    assert counts and set(counts) == {3}


def test_inner_scope_classification() -> None:
    step = "jit(tft_train_step)/"
    assert mla_scopes.inner_scope(
        step + "jvp(attn)/mla_core/pallas_call") == "core"
    assert mla_scopes.inner_scope(
        step + "transpose(jvp(attn))/mla_kv/concatenate") == "proj"
    assert mla_scopes.inner_scope(step + "jvp(attn)/mla_q/mul") == "proj"
    assert mla_scopes.inner_scope(
        step + "jvp(mtp)/attn/mla_out/dot_general") == "proj"
    # the sparse sublayer's inner scopes are moe_scopes'
    assert mla_scopes.inner_scope(
        step + "jvp(mlp)/moe_shared/dot_general") is None
    assert moe_scopes.inner_scope(
        step + "jvp(mlp)/moe_shared/dot_general") == "shared"
    assert mla_scopes.inner_scope(step + "jvp(mlp)/moe_experts/mul") is None
    assert mla_scopes.inner_scope(None) is None


def test_shares_and_rooflines_on_a_small_recorded_table() -> None:
    step = "jit(tft_train_step)/"
    tables = {"jit_tft_train_step": {
        "fusion.1": step + "jvp(attn)/mla_q/dot_general",
        "fusion.2": step + "jvp(attn)/mla_kv/concatenate",
        "flash_fwd.1": step + "jvp(attn)/mla_core/pallas_call",
        "flash_fwd.2": step + "rematted_computation/attn/mla_core/pallas_call",
        "flash_dq.1": step + "transpose(jvp(attn))/mla_core/pallas_call",
        "flash_dkv.1": step + "transpose(jvp(attn))/mla_core/pallas_call",
        "fusion.3": step + "jvp(attn)/mla_out/dot_general",
        "fusion.4": step + "jvp(mlp)/moe_shared/dot_general",
        "fusion.5": step + "jvp(mlp)/moe_experts/mul",
        "fusion.6": step + "jvp(mtp)/attn/mla_q/dot_general",
        "fusion.7": step + "jvp(mtp)/lm_head_xent/dot_general",
    }}
    ops = {0: [
        ("fusion.1", 0.0, 1 * MS),              # proj 1
        ("fusion.2", 1 * MS, 2 * MS),           # proj 1
        ("flash_fwd.1", 2 * MS, 4 * MS),        # core 2
        ("fusion.3", 4 * MS, 5 * MS),           # proj 1
        ("fusion.4", 5 * MS, 6 * MS),           # moe_scopes': shared 1
        ("fusion.5", 6 * MS, 8 * MS),           # moe_scopes': experts 2
        ("fusion.6", 8 * MS, 9 * MS),           # proj 1 and mtp 1
        ("fusion.7", 9 * MS, 10 * MS),          # mtp 1
        ("flash_fwd.2", 10 * MS, 12 * MS),      # core 2, the remat's
        ("flash_dq.1", 12 * MS, 15 * MS),       # core 3
        ("flash_dkv.1", 15 * MS, 19 * MS),      # core 4
        ("copy.1", 19 * MS, 20 * MS),           # no path
        # a second step, cut by the window's edge after one forward call
        ("flash_fwd.1", 20 * MS, 24 * MS),      # core 4, in no whole step
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 20 * MS),
                   ("jit_tft_train_step", 20 * MS, 24 * MS)]}
    got = mla_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(24 * MS)
    assert got["shares"] == pytest.approx({
        "proj": 4 / 24, "core": 15 / 24, "mtp": 2 / 24})
    # the shared expert's share is served beside the other three, with
    # the same denominator
    assert moe_scopes.reduce(ops, modules, tables)["shares"] == pytest.approx({
        "router": 0.0, "dispatch": 0.0, "experts": 2 / 24, "shared": 1 / 24})
    assert [s["calls"] for s in got["steps"]] == [
        {"flash_fwd": 2, "flash_dq": 1, "flash_dkv": 1},
        {"flash_fwd": 1, "flash_dq": 0, "flash_dkv": 0},
    ]
    # one layer, 2 x 32 heads of 8192 at 192 / 128: what causal attention
    # needs of a call is 64 x 8192 x 8193 / 2 pairs x 2 x 320 = 1.3746 TFLOP,
    # 6.978 ms at the v5e's 197 TFLOP/s; the bytes take under a fifth of that
    shapes = {"batch_heads": 64, "seq_len": 8192, "d_qk": 192, "d_v": 128,
              "n_layers": 1}
    need = mla_flops.flash_flops_per_call(64, 8192, 192, 128)
    assert need == pytest.approx(1.3746e12, rel=1e-4)
    least_ms = need / 197e12 * 1e3
    for kernel in mla_scopes.KERNELS:
        assert mla_flops.flash_bytes_per_call(
            kernel, 64, 8192, 192, 128) / 819e9 < 0.2 * need / 197e12
    # the forward ran twice in the whole step (remat): 4 ms for one call's work
    assert mla_scopes.roofline(got, "flash_fwd", shapes, "TPU v5 lite") == \
        pytest.approx(100 * least_ms / 4, rel=1e-4)
    assert mla_scopes.roofline(got, "flash_dq", shapes, "TPU v5 lite") == \
        pytest.approx(100 * least_ms / 3, rel=1e-4)
    assert mla_scopes.roofline(got, "flash_dkv", shapes, "TPU v5 lite") == \
        pytest.approx(100 * least_ms / 4, rel=1e-4)
    # no whole step: nothing to report
    assert mla_scopes.roofline(
        got, "flash_dq", dict(shapes, n_layers=2), "TPU v5 lite") is None
    # a program without the scopes: nothing, though it has flash events
    assert mla_scopes.reduce(ops, modules, {}) is None
    gpt = {"jit_tft_train_step": {"flash_fwd.1": step + "jvp(attn)/pallas_call",
                                  "fusion.1": step + "jvp(attn)/dot_general"}}
    assert mla_scopes.reduce(ops, modules, gpt) is None


def test_byte_counts_of_the_flash_kernels() -> None:
    # [BH, S] = [2, 1024] rows of q, k 192 and v, o, dO 128 wide, bf16;
    # lse and delta one float32 a row
    rows = 2 * 1024
    qkv = rows * (192 + 192 + 128) * 2
    assert mla_flops.flash_bytes_per_call("flash_fwd", 2, 1024, 192, 128) == \
        qkv + rows * 128 * 2 + rows * 4
    assert mla_flops.flash_bytes_per_call("flash_dq", 2, 1024, 192, 128) == \
        qkv + rows * 128 * 2 + 2 * rows * 4 + rows * 192 * 2
    assert mla_flops.flash_bytes_per_call("flash_dkv", 2, 1024, 192, 128) == \
        qkv + rows * 128 * 2 + 2 * rows * 4 + rows * (192 + 128) * 2
