"""The Ouro family through the real ``run.py`` on the CPU at a tiny size
(``tiny-ouro.json``), the cell's shapes from the configuration's own keys,
the flash calls a step makes and the manifest's lists. The rehearsal is run
by hand with the other benchmark tests; the three cases without a run are
tier-1's too (``tests/test_ouro_family.py`` imports them):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import flash_flops, ouro_flops
from benchmark.tests import rehearse

CELL = "ouro-l8-solo-steady"
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(_BENCH, "configs", "ouro-2.6b-l8.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(os.path.dirname(_BENCH), "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_ouro_family_runs_the_steady_job_at_the_tiny_size(
        tmp_path, capsys, trace) -> None:
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-ouro",
        "traffic": "solo-steady", "chips": 1, "why": "test",
    }])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{
        "name": "tiny-ouro", "source": "test only",
        "file": "benchmark/tests/tiny-ouro.json", "reduced": [], "why": "t",
    }]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2147483659", "--seconds", "4",
        "--trace", str(trace),
    ])
    assert rc == 0 and line["failed"] == 0 and line["attempted"] > 0
    # every check but the reference's limits, which are set for sequences
    # of 8192 at the published widths (tests/test_ouro_family.py holds the
    # comparison at this size)
    checks = dict(
        l[len("check "):].split(": ", 1) for l in
        capsys.readouterr().err.splitlines() if l.startswith("check ")
    )
    for name in ("plain_worker", "steady", "losses_finite"):
        assert checks[name].startswith("ok"), (name, checks[name])
    reference = json.loads(checks["reference"].split(" ", 1)[1])
    assert reference["loss_abs_diff"] < 0.05
    assert reference["nll_abs_rms"] < 0.3 and reference["p_abs_max"] < 0.5
    assert (reference["tokens"], reference["passes"]) == (2 * 32, 4)
    assert sum(reference["mass"]) == pytest.approx(1.0, abs=2e-3)
    if not trace:
        assert set(line["metrics"]) == {"committed_tokens_per_s",
                                        "peak_hbm_gib", "setup_s"}
        return
    got = line["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    six = {s: got[f"{s}_device_share"]["value"] for s in
           ("xent", "attn", "mlp", "embed", "opt", "unnamed")}
    assert sum(six.values()) == pytest.approx(1.0)
    assert min(six[s] for s in ("xent", "attn", "mlp", "opt")) > 0
    # the flash call's scope is inside attn
    assert 0 < got["full_core_device_share"]["value"] < six["attn"]
    # every metric the cell lists: the 2 of set-up, the 15 solo ones, the
    # flash call's share and its two rooflines
    mine = rehearse.cell_metrics(CELL)
    assert len(mine) == 20
    missing = mine - set(got)
    # a 4 s window is all traced, so no rate of untraced steps; on the CPU
    # attention is an XLA path: no kernel event, so no roofline
    assert missing <= {"ft_over_bare", "window_over_blocks",
                       "full_flash_fwd_roofline",
                       "full_flash_bwd_roofline"}, missing


def test_the_cells_shapes_come_from_the_configurations_own_keys() -> None:
    """The loop's factor stands in the yardstick: ``T·L`` layer-steps and
    ``T`` heads a token, 15.50 GFLOP — NOT the 3.88 G ``flops.py``'s ``6·N
    + 6·L·d·S`` gives the cut's 511.7 M matmul parameters."""
    from benchmark import flops

    dims = ouro_flops.config_dims(CONFIG)
    assert dims == {"d_model": 2048, "n_layers": 8, "ut_steps": 4,
                    "n_heads": 16, "head_dim": 128, "d_ff": 5632,
                    "vocab": 49152, "seq_len": 8192}
    parts = ouro_flops.train_flops_per_token(**dims)
    assert parts["total"] == pytest.approx(15.50e9, rel=1e-3)
    assert parts["total"] == (
        6 * (4 * 8 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 4 * 2048 * 49152)
        + 6 * 4 * 8 * 2048 * 8192)
    assert parts["head"] / parts["total"] == pytest.approx(0.156, abs=1e-3)
    assert parts["attn_core"] / parts["total"] == pytest.approx(0.208,
                                                                abs=1e-3)
    matmul = 8 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 2048 * 49152
    assert matmul == 511_705_088
    unlooped = flops.train_flops_per_token(matmul, 8, 2048, 8192)
    assert unlooped == pytest.approx(3.88e9, rel=2e-3)
    assert parts["total"] / unlooped > 3.9
    # at the published depth the heads are 3.0 % of a token's work
    whole = ouro_flops.train_flops_per_token(**dict(dims, n_layers=48))
    assert whole["head"] / whole["total"] == pytest.approx(0.030, abs=1e-3)


def test_flash_calls_counts_a_call_a_layer_a_pass() -> None:
    calls = flash_flops.calls_of(CONFIG)
    assert calls == [flash_flops.Call(flash_flops.FULL, 8, 16, 16, 128, 128,
                                      calls_a_layer=4)]
    assert calls[0].calls == 32
    assert flash_flops.calls_of(dict(CONFIG, total_ut_steps=1))[0].calls == 8


def test_the_manifest_holds_the_cell_where_it_reads_something() -> None:
    """The two end-to-end lists, the flash call's share and its two
    rooflines, and nowhere else; the seventeen list-less entries apply by
    ``run.py``'s rule; the per-layer list stays full."""
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b-l8", "solo-steady", 1)
    entry = {c["name"]: c for c in MANIFEST["configs"]}["ouro-2.6b-l8"]
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers",
                                                     "layer_types"]
    assert entry["source"] == CONFIG["source"]
    listed = {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {"committed_tokens_per_s", "peak_hbm_gib",
                      "full_flash_fwd_roofline", "full_flash_bwd_roofline",
                      "full_core_device_share"}
    assert len(MANIFEST["per_layer"]) == 128
    assert len(rehearse.cell_metrics(CELL)) == 17 + 3
    assert MANIFEST["workloads"][-1] is cell


def test_the_notes_sum_the_loops_scopes_over_whole_step_programs() -> None:
    """``ouro_notes.scope_seconds`` on a small recorded table worked out by
    hand: three step programs of which the trace holds the middle one whole;
    an operation counts in every scope its path holds, and ``ut_pass`` alone
    is what stands under no inner scope."""
    from benchmark.tests import ouro_notes

    ms, step = 1e-3, "jit(tft_train_step)/"
    body = step + "jvp(ut_pass)/while/body/"
    tables = {"jit_tft_train_step": {
        "fusion.1": body + "checkpoint/attn/dot_general",
        "flash_fwd.1": body + "checkpoint/attn/full_core/pallas_call",
        "fusion.2": body + "checkpoint/mlp/dot_general",
        "fusion.3": body + "mul",                       # the final norm
        "fusion.4": body + "exit_gate/dot_general",
        "fusion.5": step + "jvp(lm_head_xent)/while/body/dot_general",
        "fusion.6": step + "jvp(exit_mix)/exp",
        "fusion.7": step + "transpose(jvp(ut_pass))/while/body/add_any",
        "fusion.8": step + "opt_update/mul",
    }}
    names = [("fusion.1", 4), ("flash_fwd.1", 3), ("fusion.2", 5),
             ("fusion.3", 1), ("fusion.4", 0.5), ("fusion.5", 6),
             ("fusion.6", 0.25), ("fusion.7", 2), ("fusion.8", 1)]

    def program(t0):
        out, t = [], t0
        for name, length in names:
            out.append((name, t, t + length * ms))
            t += length * ms
        return out, ("jit_tft_train_step", t0, t)

    events, modules = [], []
    for k in range(3):
        ops, module = program(k * 30 * ms)
        events += ops
        modules.append(module)
    got = ouro_notes.scope_seconds({0: events}, {0: modules}, tables)
    assert got["whole"] == 1
    assert got["lm_head_xent"] == pytest.approx(6 * ms)
    assert got["exit_gate"] == pytest.approx(0.5 * ms)
    assert got["exit_mix"] == pytest.approx(0.25 * ms)
    assert got["ut_pass"] == pytest.approx((4 + 3 + 5 + 1 + 0.5 + 2) * ms)
    assert got["ut_pass_alone"] == pytest.approx((1 + 2) * ms)
    assert ouro_notes.scope_seconds({0: events[:9]}, {0: modules[:1]},
                                    tables) is None
