"""The harness takes a new configuration, traffic mix, job and per-layer
metric as new files plus manifest entries, with no edit to any file the
benchmark already has; and every cell's job runs end to end on the CPU at
the tiny test configuration through the real ``run.py``."""

import json
import os

import pytest

from benchmark.tests import rehearse


def _cell(name, config, traffic, chips=1):
    return {"name": name, "config": config, "traffic": traffic,
            "chips": chips, "why": "test"}


def test_new_files_are_found_without_editing_run_py(tmp_path) -> None:
    root = rehearse.make_copy(
        str(tmp_path), [_cell("new-cell", "tiny-b", "solo-short")],
        extra_metrics=[{
            "name": "steps_in_window", "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "step engine",
            "moves": "committed_tokens_per_s",
        }],
    )
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "run.py"), "rb") as f:
        run_py = f.read()
    # a configuration, a traffic mix, a job, a per-layer metric, a reader
    with open(os.path.join(bench, "tests", "tiny-test.json")) as f:
        config = dict(json.load(f), name="tiny-b", n_layer=1)
    with open(os.path.join(bench, "configs", "tiny-b.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "solo-steady.json")) as f:
        traffic = dict(json.load(f), job="steady_twice", trace_last_s=1,
                       bare_steps=3)
    with open(os.path.join(bench, "traffic", "solo-short.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "jobs", "steady_twice.py"), "w") as f:
        f.write(
            "from benchmark.jobs import steady\n"
            "def run(ctx):\n"
            "    record = steady.run(ctx)\n"
            "    record['came_through'] = 'steady_twice'\n"
            "    return record\n"
        )
    with open(os.path.join(bench, "layer_metrics",
                           "steps_in_window.json"), "w") as f:
        json.dump({"reader": "count_steps"}, f)
    with open(os.path.join(bench, "readers", "count_steps.py"), "w") as f:
        f.write(
            "def read(record, spec):\n"
            "    assert record['came_through'] == 'steady_twice'\n"
            "    return float(len(record['records']))\n"
        )
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-b", "source": "test only",
        "file": "benchmark/configs/tiny-b.json", "reduced": [], "why": "t",
    })
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    rc, line = rehearse.run_in_copy(root, [
        "--workload", "new-cell", "--seed", "1", "--seconds", "3",
        "--trace", "1",
    ])
    assert rc == 0 and line["correct"] and line["failed"] == 0
    assert line["metrics"]["steps_in_window"]["value"] == line["attempted"] > 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"}
    # each number compared beside its limit comes last in the line
    assert list(line)[-1] == "checks" and all(
        c["ok"] for c in line["checks"].values())
    assert line["checks"]["reference"]["abs_diff"] <= \
        line["checks"]["reference"]["atol"]
    assert line["device"]["busy_s"] > 0
    with open(os.path.join(bench, "run.py"), "rb") as f:
        assert f.read() == run_py


@pytest.mark.parametrize("traffic,chips,seconds,trace", [
    ("solo-steady", 1, 3, 0),
    ("x4-kill60", 4, 20, 0),
    ("x4-kill60", 4, 24, 1),
])
def test_every_job_runs_end_to_end_at_the_tiny_size(
        tmp_path, traffic, chips, seconds, trace) -> None:
    root = rehearse.make_copy(
        str(tmp_path), [_cell("tiny-cell", "tiny-test", traffic, chips)]
    )
    rc, line = rehearse.run_in_copy(root, [
        "--workload", "tiny-cell", "--seed", "2", "--seconds", str(seconds),
        "--trace", str(trace),
    ])
    assert rc == 0 and line["correct"] and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["count"] == chips
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    if trace:
        assert set(line["metrics"]) <= {m["name"] for m in manifest["per_layer"]}
        assert line["metrics"]["compiles_in_window"]["value"] > 0  # rejoins
        assert {"survivor_stall_s", "heal_s", "heal.recover_s",
                "wire_mb_per_step", "x4_device_idle_share"} <= set(
            line["metrics"])
    else:
        want = {"setup_s", "peak_hbm_gib"} | (
            {"committed_tokens_per_s"} if traffic == "solo-steady"
            else {"goodput_tokens_per_s"})
        assert set(line["metrics"]) == want
        assert all(v["value"] > 0 for k, v in line["metrics"].items()
                   if k != "peak_hbm_gib")       # the CPU reports no memory


def test_run_py_refuses_to_run_without_a_tpu() -> None:
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmark", "run.py"),
         "--workload", "c111m-solo-steady", "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=repo,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert not out.stdout.strip().endswith("}")
