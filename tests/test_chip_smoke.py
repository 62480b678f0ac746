"""chip_smoke.py on the CPU: its scenario is importable and runs at ``tiny``
on the virtual devices (so the legs, the asserts and the device-residency
check are exercised by tier-1), while the script itself refuses to run
without a TPU."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def lighthouse():
    lh = chip_smoke.scenario_lighthouse()  # the chip run's own settings
    yield lh
    lh.shutdown()


@pytest.mark.parametrize("n", [2, 4])
def test_ft_scenario_each_group_on_its_own_device(lighthouse, n) -> None:
    # Group g lives on virtual device g. Before landings returned to the
    # sharding of the leaf they replace, every other group's averaged
    # gradients came back on device 0 and the residency assertion inside
    # every committed step failed here. Four groups is the four-chip
    # host's shape: three joiners are a majority that a short lighthouse
    # join timeout lets form a quorum without group 0.
    devices = jax.devices()[:n]
    summary = chip_smoke.run_ft_scenario(
        "tiny", devices, n_groups=n, batch=2, steps_per_leg=2,
        lighthouse_addr=lighthouse.address(), fence_window=2, timeout=20.0,
        log=lambda _msg: None,
    )
    assert [(leg["leg"], leg["participants"], leg["commits"])
            for leg in summary["legs"]] == [
        ("solo", 1, 2), ("all", n, 2), ("survivors", n - 1, 2),
        ("all_again", n, 2),
    ]
    assert summary["fused_steps"] > 0 and summary["classic_steps"] > 0
    assert [(h["leg"], h["group"]) for h in summary["heals"]] == [
        ("all", g) for g in range(1, n)
    ] + [("all_again", n - 1)]
    assert summary["loss_fixed_batch"][1] < summary["loss_fixed_batch"][0]
    assert {r["device"] for r in summary["reports"]} == {
        str(d) for d in devices
    }
    assert set(summary["fence_observation"]) == {
        "block_until_ready_s", "scalar_device_get_s",
    }


def test_residency_check_names_a_stray_leaf(lighthouse) -> None:
    group = chip_smoke.ReplicaGroup(
        0, "tiny", jax.devices()[1], 2, lighthouse.address(), timeout=10.0
    )
    try:
        group.check_resident()
        group.state["params"]["ln_f"]["bias"] = jax.device_put(
            group.state["params"]["ln_f"]["bias"], jax.devices()[0]
        )
        with pytest.raises(AssertionError, match=r"ln_f.*bias.*lives on"):
            group.check_resident()
    finally:
        group.shutdown()


_SMALL_TREE = {"wte": (96, 16), "h0": {"ln1": (2, 16), "qkv": (16, 48)},
               "lm_head": (16, 96)}


def test_landing_check_runs_and_counts_on_a_cpu_device() -> None:
    # the stage's body at a small tree: the arena poisoned the moment each
    # step resolves, the landed leaves still the reduced values. Here
    # every byte goes through the host copy; the stage itself asserts the
    # opposite on the chip.
    result = chip_smoke.run_landing_check(jax.devices()[1], _SMALL_TREE)
    assert result["copied_bytes"] == 3 * result["bytes_per_step"] > 0
    assert result["borrowed_bytes"] == 0
    assert result["land_workers"] >= 2


def test_landing_check_fails_on_a_landing_that_aliases_the_arena(
    monkeypatch,
) -> None:
    from torchft_tpu import ddp

    dev = jax.devices()[1]

    class _Alias:
        """A landed leaf that still is the arena's view."""

        def __init__(self, view) -> None:
            self.view = view

        def devices(self):
            return {dev}

        def __array__(self, dtype=None, copy=None):
            return self.view

    monkeypatch.setattr(
        ddp, "land_batch",
        lambda views, likes: ([_Alias(v) for v in views], 0, 0),
    )
    with pytest.raises(AssertionError, match="not the reduced value"):
        chip_smoke.run_landing_check(dev, _SMALL_TREE)


def test_chip_smoke_refuses_to_run_without_a_tpu() -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert "TPU" in out.stderr and "stage kernels failed" in out.stderr
    # no result: the last stdout line is not the {"ok": ...} object
    assert '"ok"' not in out.stdout
