"""The two readers of the program's own timeline, on numbers worked out
by hand: self time of nested device events, scope classification with
the forward / backward / recomputed split, and idle time put down to the
``tft.*`` spans of the replica living on the idlest chip."""

import pytest

from benchmark.readers import device_scopes, program_spans
from benchmark.tests import rehearse

MS = 1e-3


def test_self_time_of_a_while_holding_two_ops() -> None:
    # a while of 10 ms holding two ops of 3 ms: 4, 3, 3
    ops = [("while.4", 0.0, 10 * MS), ("fusion.1", 1 * MS, 4 * MS),
           ("fusion.2", 5 * MS, 8 * MS), ("copy.9", 12 * MS, 13 * MS)]
    got = {n: s for n, _a, s in device_scopes.self_times(ops)}
    assert got == pytest.approx({"while.4": 4 * MS, "fusion.1": 3 * MS,
                                 "fusion.2": 3 * MS, "copy.9": 1 * MS})
    # two levels: the outer loses only its direct child
    nested = [("call", 0.0, 10 * MS), ("while", 2 * MS, 8 * MS),
              ("dot", 3 * MS, 5 * MS)]
    got = {n: s for n, _a, s in device_scopes.self_times(nested)}
    assert got == pytest.approx({"call": 4 * MS, "while": 4 * MS,
                                 "dot": 2 * MS})
    # an event that only overlaps an earlier one is nobody's child
    overlap = [("a", 0.0, 4 * MS), ("b", 3 * MS, 6 * MS)]
    got = {n: s for n, _a, s in device_scopes.self_times(overlap)}
    assert got == pytest.approx({"a": 4 * MS, "b": 3 * MS})


@pytest.mark.parametrize("path,want", [
    ("jit(tft_train_step)/jvp(attn)/dot_general", ("attn", "forward")),
    ("jit(tft_train_step)/transpose(jvp(attn))/dot_general",
     ("attn", "backward")),
    ("jit(tft_train_step)/transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/mlp/dot_general", ("mlp", "recomputed")),
    ("jit(tft_train_step)/transpose(jvp(jvp()))/checkpoint/mlp/mul",
     ("mlp", "backward")),
    ("jit(tft_train_step)/jvp(lm_head_xent)/while/body/closed_call/exp",
     ("xent", "forward")),
    ("jit(tft_train_step)/opt_update/mul", ("opt", "forward")),
    ("jit(tft_grad_step)/transpose(jvp(embed))/scatter-add",
     ("embed", "backward")),
    # the outermost scope decides: the flash call sits inside attn
    ("jit(tft_train_step)/jvp(attn)/pallas_call[name=flash_fwd]/mlp",
     ("attn", "forward")),
    ("jit(tft_train_step)/jvp()/rsqrt", ("unnamed", "forward")),
    ("jit(tft_train_step)/jvp(dropout_attn)/mul", ("unnamed", "forward")),
    (None, ("unnamed", "forward")),
])
def test_scope_classification(path, want) -> None:
    assert device_scopes.classify(path) == want


def test_device_shares_on_a_small_recorded_table() -> None:
    tables = {
        "jit_tft_train_step": {
            "fusion.1": "jit(tft_train_step)/jvp(attn)/dot_general",
            "fusion.2": "jit(tft_train_step)/transpose(jvp(mlp))/dot_general",
            "while.4": "jit(tft_train_step)/jvp(lm_head_xent)/while",
            "fusion.7": "jit(tft_train_step)/jvp(lm_head_xent)/while/body/exp",
            "fusion.9": "jit(tft_train_step)/opt_update/mul",
        },
        # the same instruction name means something else in another program
        "jit_tft_grad_step": {
            "fusion.1": "jit(tft_grad_step)/jvp(mlp)/dot_general",
        },
    }
    ops = {0: [
        ("fusion.1", 0.0, 2 * MS),            # attn 2
        ("fusion.2", 2 * MS, 5 * MS),         # mlp backward 3
        ("while.4", 5 * MS, 9 * MS),          # xent: 4 - 3 = 1 of its own
        ("fusion.7", 6 * MS, 9 * MS),         # xent 3, inside the while
        ("fusion.9", 9 * MS, 10 * MS),        # opt 1
        ("copy.3", 10 * MS, 12 * MS),         # no path: unnamed 2
        ("fusion.1", 20 * MS, 24 * MS),       # the grad program's: mlp 4
        ("fusion.5", 30 * MS, 34 * MS),       # under no program: unnamed 4
    ]}
    modules = {0: [("jit_tft_train_step", 0.0, 12 * MS),
                   ("jit_tft_grad_step", 20 * MS, 25 * MS)]}
    got = device_scopes.reduce(ops, modules, tables)
    assert got["total_s"] == pytest.approx(20 * MS)
    assert got["shares"] == pytest.approx({
        "attn": 2 / 20, "mlp": 7 / 20, "xent": 4 / 20, "opt": 1 / 20,
        "embed": 0.0, "unnamed": 6 / 20,
    })
    assert sum(got["shares"].values()) == pytest.approx(1.0)
    assert got["seconds"][("mlp", "backward")] == pytest.approx(3 * MS)
    assert got["seconds"][("mlp", "forward")] == pytest.approx(4 * MS)
    assert got["by_program"] == pytest.approx({
        "jit_tft_train_step": 12 * MS, "jit_tft_grad_step": 4 * MS,
        "": 4 * MS,
    })
    assert got["no_path_s"] == pytest.approx(6 * MS)
    assert "xent [for 0.004]" in device_scopes._note(got)
    # no table (the parent's program): nothing to report, not "all unnamed"
    assert device_scopes.reduce(ops, modules, {}) is None


def test_idle_unexplained_share_two_replicas_on_two_chips() -> None:
    # chip 0 busy 0-10 and 90-100 ms; chip 1 is busy throughout
    ops = {0: [("a", 0.0, 10 * MS), ("b", 90 * MS, 100 * MS)],
           1: [("c", 0.0, 100 * MS)]}
    spans = [
        # replica of group 0 (chip 0): a quorum wait with the configure
        # inside it, and a later wire wait that ends in the busy stretch
        ("tft.quorum_wait", "bm_0_0_aa", 20 * MS, 50 * MS),
        ("tft.configure", "bm_0_0_aa", 30 * MS, 40 * MS),
        ("tft.wire_wait", "bm_0_0_aa", 60 * MS, 95 * MS),
        # its replacement lives on the same chip
        ("tft.heal_wire", "bm_0_1_bb", 52 * MS, 55 * MS),
        # group 1 lives on chip 1: its spans explain nothing on chip 0
        ("tft.quorum_wait", "bm_1_0_cc", 10 * MS, 90 * MS),
        # someone else's replica id: not placed
        ("tft.quorum_wait", "tl_a_dd", 10 * MS, 90 * MS),
    ]
    got = program_spans.reduce(ops, spans)
    assert got["chip"] == 0 and got["idle_s"] == pytest.approx(80 * MS)
    assert got["totals"] == pytest.approx({
        "tft.quorum_wait": 20 * MS,     # 20-30 and 40-50
        "tft.configure": 10 * MS,       # the innermost over 30-40
        "tft.heal_wire": 3 * MS,
        "tft.wire_wait": 30 * MS,       # 60-90: the rest ran under busy
        program_spans.NO_SPAN: 17 * MS,  # 10-20, 50-52, 55-60
    })
    assert got["unexplained_share"] == pytest.approx(17 / 80)
    assert program_spans.reduce(ops, []) is None     # the parent: no spans
    assert program_spans.chip_of("bm_6_1_x", 4) == 2
    assert program_spans.chip_of("tl_a_x", 4) is None


def test_innermost_segments_of_partly_overlapping_spans() -> None:
    spans = [("tft.quorum", 0.0, 10.0), ("tft.quorum_wait", 4.0, 12.0),
             ("tft.configure", 8.0, 9.0)]
    # over 4-10 the wait (8 long) is shorter than the RPC (10 long)
    assert program_spans.innermost(spans) == [
        ("tft.quorum", 0.0, 4.0), ("tft.quorum_wait", 4.0, 8.0),
        ("tft.configure", 8.0, 9.0), ("tft.quorum_wait", 9.0, 12.0),
    ]


def test_kill_cell_prints_every_new_metric_on_the_cpu(tmp_path) -> None:
    """The rehearsal: the kill job on four virtual devices, traced. Every
    new metric of the kill cell comes out. Since PR 32 the lighthouse
    knocks on the dead group's manager address and drops it at once, so
    the replacement joins in a later quorum: the survivors see a
    ``shrink`` and then a ``grow`` episode, as on the chip (PERF.md §5)."""
    root = rehearse.make_copy(str(tmp_path), [{
        "name": "tiny-cell", "config": "tiny-test", "traffic": "x4-kill60",
        "chips": 4, "why": "rehearsal",
    }])
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "3", "--seconds", "24",
        "--trace", "1",
    ])
    assert rc == 0 and line["correct"] and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"stall.gap_s", "stall.quorum_wait_s", "stall.wire_wait_s",
            "stall.other_s", "regrow.gap_s", "rejoin.gap_s", "rejoin.init_s",
            "rejoin.quorum_wait_s", "rejoin.first_step_s", "wire_d2h_ms",
            "wire_socket_ms", "wire_h2d_ms", "x4_rpcs_per_step",
            "idle_unexplained_share", "step_submit_ms", "land_queue_ms",
            "lane_cpu_ms", "lane_queue_ms"} <= set(got)
    # the survivors' longest stall as the step log has it is the longer of
    # the two episodes as the library counts them
    assert got["survivor_stall_s"] == pytest.approx(
        max(got["stall.gap_s"], got["regrow.gap_s"]), abs=0.5)
    assert got["stall.quorum_wait_s"] < 4.0  # no 5 s heartbeat runs out
    assert got["land_queue_ms"] <= got["land_queue_max_ms"]
    assert got["lane_cpu_ms"] <= got["lane_subop_ms"]
    assert 0.0 <= got["idle_unexplained_share"] <= 1.0
