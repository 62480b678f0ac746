"""bench.py measures a TPU and nothing else.

Every artifact the driver holds from the earlier rounds is a CPU run under
a chip metric's name: with no accelerator the bench used to re-run itself
on the CPU. These tests pin the contract that replaced that: without a TPU
the bench runs no phase, exits non-zero, and still ends its combined
stdout+stderr with ONE parseable JSON line (``bench_error``, naming the
missing TPU) — the way the driver reads it. What the bench computes on a
chip is checked on a chip (chip_smoke.py runs the same training loop there;
ROADMAP S1 gives the benchmark its own cells).
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench.py")


def _run_bench(extra_env, timeout):
    """Run bench.py as the driver does, merging stdout+stderr, on a
    machine whose only platform is the CPU."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "XLA_FLAGS")
    }
    env.update(JAX_PLATFORMS="cpu", **extra_env)
    return subprocess.run(
        [sys.executable, _BENCH],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,  # the driver greps a combined tail
        text=True,
        timeout=timeout,
    )


def _last_line_json(out):
    lines = [l for l in out.stdout.splitlines() if l.strip()]
    assert lines, "bench produced no output"
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError:
        pytest.fail(
            "bench tail is not JSON — the artifact would be lost. "
            f"Tail:\n{chr(10).join(lines[-15:])}"
        )


def test_bench_without_tpu_runs_nothing_and_says_why():
    out = _run_bench({"BENCH_MODEL": "tiny", "BENCH_STEPS": "2"}, timeout=120)
    payload = _last_line_json(out)
    assert out.returncode != 0
    assert payload["metric"] == "bench_error"
    assert "tpu" in payload["error"].lower()
    # no phase ran: nothing measured rides the error line
    assert payload["value"] == 0.0
    assert not any(k.startswith(("t0_", "t1_", "chaos_")) for k in payload)


def test_bench_error_path_still_emits_json():
    """Even a broken bench must leave a parseable tail for the driver."""
    out = _run_bench(
        {"BENCH_MODEL": "no_such_model", "BENCH_REPLICAS": "1",
         "BENCH_SYNC": "0"},
        timeout=120,
    )
    payload = _last_line_json(out)
    assert out.returncode != 0
    assert payload["metric"] == "bench_error"
    assert "value" in payload and "vs_baseline" in payload


def test_peak_flops_refuses_a_device_kind_it_does_not_know():
    sys.path.insert(0, _REPO)
    import bench

    class _Device:
        def __init__(self, kind):
            self.device_kind = kind

    assert bench._peak_flops(_Device("TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        bench._peak_flops(_Device("TPU v9 imaginary"))
    with pytest.raises(ValueError, match="cpu"):
        bench._peak_flops(_Device("cpu"))
