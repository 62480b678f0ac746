"""Where compiled programs and the native library come from: the compile
cache is placed once per checkout (or from outside), and the native library
is rebuilt exactly when its sources' bytes change — never because a copy of
the tree scrambled file times — by one process at a time."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from torchft_tpu.control import _native

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------- compile cache


def _cache_dir_in_fresh_process(env_value):
    """What jax_compilation_cache_dir is after place_compile_cache() in a
    fresh interpreter (the config is process-global: never set it here)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    code = (
        "import json, jax\n"
        "from torchft_tpu.utils.device import place_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "ret = place_compile_cache()\n"
        "print(json.dumps([before, ret, jax.config.jax_compilation_cache_dir]))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=_REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_env_set_leaves_the_config_alone(tmp_path) -> None:
    outside = str(tmp_path / "cache_from_outside")
    before, ret, after = _cache_dir_in_fresh_process(outside)
    # jax read the variable itself, before our call; the call set nothing
    assert before == after == ret == outside


def test_compile_cache_unset_is_a_fixed_path_in_the_checkout() -> None:
    first = _cache_dir_in_fresh_process(None)
    second = _cache_dir_in_fresh_process(None)
    expected = os.path.join(_REPO, ".jax_cache")
    assert first == [None, expected, expected]
    assert second == first       # no pid, time or temp name in the path


# ------------------------------------------------------ native digest stamp


@pytest.fixture
def native_copy(tmp_path):
    """A private copy of native/ holding sources only — what a checkout of
    the committed files looks like."""
    dst = tmp_path / "native"
    dst.mkdir()
    src = os.path.join(_REPO, "native")
    for name in os.listdir(src):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            shutil.copy(os.path.join(src, name), dst / name)
    return str(dst)


def test_native_rebuilds_on_changed_bytes_not_on_changed_times(
    native_copy,
) -> None:
    assert _native.built_digest(native_copy) is None
    assert _native.ensure_built(native_copy) is True
    digest = _native.source_digest(native_copy)
    assert _native.built_digest(native_copy) == digest
    # a copy of the tree sets every time anew: sources now look NEWER than
    # the library, and nothing is rebuilt
    future = time.time() + 3600
    for name in os.listdir(native_copy):
        if name.endswith((".cc", ".h")):
            os.utime(os.path.join(native_copy, name), (future, future))
    assert _native.ensure_built(native_copy) is False
    # one changed byte in a header: rebuilt, and the stamp follows
    with open(os.path.join(native_copy, "quorum.h"), "a") as f:
        f.write("\n// changed\n")
    assert _native.source_digest(native_copy) != digest
    assert _native.ensure_built(native_copy) is True
    assert _native.built_digest(native_copy) == _native.source_digest(
        native_copy
    )
    # files that are not inputs of the library do not count
    with open(os.path.join(native_copy, "churn_stress.cc"), "a") as f:
        f.write("\n// changed\n")
    assert _native.ensure_built(native_copy) is False


def test_native_concurrent_importers_build_once(native_copy) -> None:
    code = (
        "import sys\n"
        "from torchft_tpu.control import _native\n"
        "print('BUILT' if _native.ensure_built(sys.argv[1]) else 'FOUND')\n"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code, native_copy], cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for _ in range(4)
    ]
    answers = []
    for proc in procs:
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err
        answers.append(out.strip())
    assert sorted(answers) == ["BUILT", "FOUND", "FOUND", "FOUND"]
    assert _native.built_digest(native_copy) == _native.source_digest(
        native_copy
    )


def test_loaded_library_carries_the_stamp_of_the_sources() -> None:
    _native.get_lib()
    assert _native.built_digest() == _native.source_digest()
