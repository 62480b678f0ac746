"""The yardstick's own arithmetic: trace reduction, FLOPs, kill schedule."""

import json
import os

import pytest

from benchmark import flops, trace_reduce
from benchmark.traffic_gen import BatchSource, kill_schedule

_HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_gaps_by_hand() -> None:
    busy = trace_reduce.union([(3.0, 4.0), (0.0, 1.0), (0.5, 2.0), (2.0, 2.5)])
    assert busy == [(0.0, 2.5), (3.0, 4.0)]
    assert trace_reduce.gaps(busy, -1.0, 6.0) == [
        (-1.0, 0.0), (2.5, 3.0), (4.0, 6.0)
    ]
    assert trace_reduce.gaps(busy, 0.0, 4.0) == [(2.5, 3.0)]


def test_reduce_by_hand() -> None:
    # chip 0: ops cover [0,2] u [2,3] (overlapping) and [5,6]: busy 4 s,
    # gap [3,5]; chip 1: [1,2] and [9,10]: busy 2 s, gaps [0,1], [2,9]
    # (the common bounds are the first and last op of any chip: [0,10]),
    # and chip 0 has the trailing gap [6,10].
    ops = {
        0: [("fusion.1", 0.0, 2.0), ("fusion.2", 1.5, 3.0),
            ("custom-call.7", 5.0, 6.0)],
        1: [("fusion.1", 1.0, 2.0), ("fusion.1", 9.0, 10.0)],
    }
    spans = [
        ("bm.average", 0, 2.9, 5.1),   # covers chip 0's gap [3,5]
        ("bm.quorum", 0, 3.0, 3.5),    # inner, but covers less of it
        ("bm.quorum", 1, 2.0, 8.0),    # 6 s of chip 1's gap [2,9]
        ("bm.heal", 1, 0.0, 20.0),     # 7 s of it: the most, so it wins
        ("bm.input", None, 6.5, 7.0),  # no chip: offered to every chip
    ]
    out = trace_reduce.reduce(ops, spans, window_s=10.0)
    assert out["busy_by_chip"] == {0: 4.0, 1: 2.0}
    assert out["busy_s"] == 3.0
    assert out["idle_share"] == pytest.approx(0.8)  # chip 1, the idlest
    assert out["device_ops"][:3] == [
        ["fusion.1", 4.0], ["fusion.2", 1.5], ["custom-call.7", 1.0]
    ]
    assert out["idle_gaps"][:4] == [
        ["bm.heal chip1", 7.0], ["bm.input chip0", 4.0],
        ["bm.average chip0", 2.0], ["bm.heal chip1", 1.0],
    ]
    totals = dict(map(tuple, out["idle_gaps"][4:]))
    assert totals == {"total bm.heal": 8.0, "total bm.input": 4.0,
                      "total bm.average": 2.0}


def test_attribute_prefers_innermost_of_equals() -> None:
    spans = [("bm.heal", 0.0, 10.0), ("bm.quorum", 1.0, 5.0)]
    assert trace_reduce.attribute((2.0, 3.0), spans) == "bm.quorum"
    assert trace_reduce.attribute((20.0, 30.0), spans) == "(no span)"


def test_reduce_refuses_a_trace_without_device_operations() -> None:
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce({}, [], 1.0)


def test_recorded_trace() -> None:
    """A trace recorded on one v5e chip (my chip run, PR 22): three rounds
    of one 2048^3 bf16 matmul under ``bm.fused``, a 20 ms sleep under
    ``bm.quorum`` and a 10 ms sleep under ``bm.input``. The expected
    numbers were worked out by hand from its nine device events and nine
    host spans; ``small.expected.json`` shows the working."""
    path = os.path.join(_HERE, "data", "small.xplane.pb")
    with open(os.path.join(_HERE, "data", "small.expected.json")) as f:
        want = json.load(f)
    out = trace_reduce.reduce_file(path, want["window_s"])
    assert out["busy_s"] == pytest.approx(want["busy_s"], abs=1e-9)
    assert out["idle_share"] == pytest.approx(want["idle_share"], abs=1e-8)
    for got, (name, seconds) in zip(out["idle_gaps"], want["longest_gaps"]):
        assert got[0] == name and got[1] == pytest.approx(seconds, abs=1e-9)
    assert out["device_ops"][0][0] == want["top_op"]
    assert out["device_ops"][0][1] == pytest.approx(want["top_op_s"], abs=1e-9)


def test_flops_match_bench_on_the_125m_preset() -> None:
    import bench
    from torchft_tpu.models import CONFIGS

    cfg, n_params, tokens = CONFIGS["125m"], 123_456_789, 8 * 1024
    assert flops.train_flops_per_token(
        n_params, cfg.n_layers, cfg.d_model, cfg.max_seq_len
    ) * tokens == bench._flops_per_step(cfg, n_params, cfg.max_seq_len, tokens)


def test_peaks_unknown_kind_is_an_error() -> None:
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="not in"):
        flops.peaks("TPU v9 imaginary")


def test_kill_schedule_is_a_pure_function_of_seed_and_seconds() -> None:
    with open(os.path.join(_HERE, "..", "traffic", "x4-kill60.json")) as f:
        traffic = json.load(f)
    a = kill_schedule(7, 48, traffic, 4)
    assert a == kill_schedule(7, 48, traffic, 4)
    assert [t for t, _g in a] == [4.0]           # a kill a minute
    three = dict(traffic, kill_every_s=15)
    assert [t for t, _g in kill_schedule(7, 48, three, 4)] == [4.0, 19.0, 34.0]
    assert [t for t, _g in kill_schedule(7, 51, three, 4)] == [
        4.0, 19.0, 34.0, 49.0
    ]
    victims = [g for _t, g in kill_schedule(7, 48, three, 4)]
    assert len(set(victims)) == 3                # no group twice in four
    first = {kill_schedule(s, 48, traffic, 4)[0][1] for s in range(40)}
    assert first == {0, 1, 2, 3}                 # the seed picks the victim
    assert kill_schedule(7, 3, traffic, 4) == []


def test_commit_aligned_rate_by_hand() -> None:
    from benchmark import harness

    def rec(step, t1, committed=True):
        return {"step": step, "t1": t1, "committed": committed}

    # steps 1..4 commit at 1, 4, 9 (three groups; the slowest at 9) and 12;
    # a refused step in between; window [2, 11]: from the commit at 1 to
    # the commit at 9 there are steps 2 (two groups) and 3 (three groups):
    # 5 group-steps x 100 tokens in 8 s
    records = [rec(1, 0.9), rec(1, 1.0), rec(2, 3.5), rec(2, 4.0),
               rec(2, 5.0, committed=False), rec(3, 8.0), rec(3, 8.5),
               rec(3, 9.0), rec(4, 12.0)]
    out = harness.commit_aligned_rate(records, 2.0, 11.0, 100)
    assert out == {"tokens_per_s": 62.5, "steps": 2, "span_s": 8.0}
    assert harness.commit_aligned_rate(records, 0.5, 11.0, 100)[
        "tokens_per_s"] == 0.0                   # no commit before the window


def test_block_median_rate_by_hand() -> None:
    from benchmark import harness

    # 12 steps of 100 tokens, one a second, but the 6th takes 4 s: four
    # blocks of three steps last 3, 6, 3 and 3 s
    times = [0, 1, 2, 3, 4, 5, 9, 10, 11, 12, 13, 14, 15]
    out = harness.block_median_rate(times, 100, 4)
    assert out == {"tokens_per_s": 100.0, "whole": 80.0, "slowest": 50.0,
                   "blocks": 4}
    # more blocks asked for than steps run: one block a step
    assert harness.block_median_rate([0, 2, 3], 100, 12) == {
        "tokens_per_s": 75.0, "whole": 200 / 3, "slowest": 50.0, "blocks": 2}
    assert harness.block_median_rate([5.0], 100, 12)["tokens_per_s"] == 0.0


def test_completion_clock_keeps_the_order_watched() -> None:
    import jax.numpy as jnp

    from benchmark import harness

    clock = harness.CompletionClock()
    for i in range(5):
        clock.watch(jnp.ones(4) * i)
    times = clock.close()
    assert len(times) == 5 and times == sorted(times)


def test_batches_are_a_pure_function_of_the_seed() -> None:
    a = BatchSource(5, 1, 0, 2, 16, 500).host_batch(3)
    b = BatchSource(5, 1, 0, 2, 16, 500).host_batch(3)
    c = BatchSource(6, 1, 0, 2, 16, 500).host_batch(3)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (a[0] != c[0]).any()
    assert a[0].max() < 500 and (a[1][:, :-1] == a[0][:, 1:]).all()


def test_gpt_weights_come_from_any_seed_of_the_driver() -> None:
    """``--seed`` may pass 2**31 (and the kill job adds its poison offset
    to it); a seed below it gives the weights it gave as an ``int32``."""
    import jax
    import numpy as np

    from benchmark.families import gpt
    from torchft_tpu.models import init_params

    with open(os.path.join(_HERE, "tiny-test.json")) as f:
        model = gpt.build(json.load(f))
    device = jax.devices()[0]

    def head(seed):
        return np.asarray(jax.tree_util.tree_leaves(
            gpt.init_state(model, seed, device)["params"])[0])

    big, bigger = head(2**31 + 3), head(2**31 + 4)
    assert np.isfinite(big).all() and (big != bigger).any()
    old = np.asarray(jax.tree_util.tree_leaves(
        init_params(model.cfg, jax.random.key(np.int32(2147480101))))[0])
    assert (head(2147480101) == old).all()
