"""ctypes loader for the native control plane (libtorchft_tpu_native.so).

Builds the library from native/ on first use if it is missing or was not
built from the sources at hand (make is part of the baked toolchain). The
C ABI is defined in native/capi.cc; the reference achieves the same
Python↔native embedding with pyo3 (/root/reference/src/lib.rs) — pybind11
is unavailable here, so the ABI is plain C consumed via ctypes, which also
conveniently releases the GIL for every native call (parity with
py.allow_threads at ref lib.rs:54,98).

Staleness is decided by content, not by time: a sha256 over the
Makefile, its ``SRCS`` and every header is kept in a stamp beside the
library, and the library is rebuilt exactly when the stamp differs. File
times say nothing here — a checkout, an archive or a copy of the tree
sets them arbitrarily, and the objects are git-ignored, so a copied tree
can pair fresh-looking objects with other sources. The build is
serialised across processes by a lock file, so several workers started
at once on a fresh checkout run one ``make``.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import re
import subprocess
import threading
from typing import List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_NAME = "libtorchft_tpu_native.so"
_LIB_PATH = os.path.join(_NATIVE_DIR, _LIB_NAME)

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _build_inputs(native_dir: str) -> List[str]:
    """Every file the library is a function of: the Makefile, the .cc
    files on its ``SRCS`` line (the single source of truth —
    sanitizer-plane sources such as churn_stress.cc are NOT inputs and
    must not force rebuilds) and every header. If the Makefile cannot be
    parsed, every .cc counts."""
    with open(os.path.join(native_dir, "Makefile")) as f:
        m = re.search(r"^SRCS\s*=\s*(.+)$", f.read(), re.MULTILINE)
    lib_srcs = set(m.group(1).split()) if m else None
    names = ["Makefile"]
    for name in os.listdir(native_dir):
        if name.endswith(".h") or (
            name.endswith(".cc") and (lib_srcs is None or name in lib_srcs)
        ):
            names.append(name)
    return sorted(names)


def source_digest(native_dir: str = _NATIVE_DIR) -> str:
    """sha256 over the names and bytes of :func:`_build_inputs`."""
    h = hashlib.sha256()
    for name in _build_inputs(native_dir):
        with open(os.path.join(native_dir, name), "rb") as f:
            data = f.read()
        h.update(f"{name}:{len(data)}:".encode())
        h.update(data)
    return h.hexdigest()


def built_digest(native_dir: str = _NATIVE_DIR) -> Optional[str]:
    """The source digest the library on disk was built from (its stamp),
    or None if there is no library or no stamp."""
    lib = os.path.join(native_dir, _LIB_NAME)
    try:
        with open(lib + ".stamp") as f:
            stamp = f.read().strip()
    except OSError:
        return None
    return stamp if os.path.exists(lib) else None


def ensure_built(native_dir: str = _NATIVE_DIR) -> bool:
    """Make the library on disk match the sources; True if this call ran
    the build. Safe to call from many processes at once: the check is
    repeated under an exclusive lock, so whoever loses the race finds the
    winner's stamp and builds nothing."""
    digest = source_digest(native_dir)
    if built_digest(native_dir) == digest:
        return False
    lib = os.path.join(native_dir, _LIB_NAME)
    with open(os.path.join(native_dir, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if built_digest(native_dir) == digest:
            return False
        # -B: the objects on disk may come from other sources with newer
        # times (see module docstring), so make's own check is not trusted.
        result = subprocess.run(
            ["make", "-B", "-j", "-C", native_dir],
            capture_output=True,
            text=True,
        )
        if result.returncode != 0:
            raise RuntimeError(
                "failed to build native control plane:\n"
                f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
            )
        tmp = f"{lib}.stamp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(digest + "\n")
        os.replace(tmp, lib + ".stamp")
    return True


def _configure(lib: ctypes.CDLL) -> None:
    c_char_p = ctypes.c_char_p
    c_void_p = ctypes.c_void_p
    c_i64 = ctypes.c_int64
    c_u64 = ctypes.c_uint64
    c_int = ctypes.c_int
    err_p = ctypes.POINTER(c_char_p)

    lib.ft_free.argtypes = [c_void_p]
    lib.ft_free.restype = None

    lib.ft_lighthouse_new.argtypes = [
        c_char_p, c_int, c_char_p, c_u64, c_u64, c_u64, c_u64, c_char_p,
        err_p,
    ]
    lib.ft_lighthouse_new.restype = c_void_p
    lib.ft_lighthouse_address.argtypes = [c_void_p]
    lib.ft_lighthouse_address.restype = c_void_p  # char* we must free
    lib.ft_lighthouse_shutdown.argtypes = [c_void_p]
    lib.ft_lighthouse_shutdown.restype = None
    lib.ft_lighthouse_free.argtypes = [c_void_p]
    lib.ft_lighthouse_free.restype = None

    lib.ft_manager_new.argtypes = [
        c_char_p, c_char_p, c_char_p, c_char_p, c_int, c_char_p,
        c_u64, c_u64, c_u64, c_int, c_char_p, err_p,
    ]
    lib.ft_manager_new.restype = c_void_p
    lib.ft_manager_address.argtypes = [c_void_p]
    lib.ft_manager_address.restype = c_void_p
    lib.ft_manager_kill_requested.argtypes = [c_void_p]
    lib.ft_manager_kill_requested.restype = c_int
    lib.ft_manager_shutdown.argtypes = [c_void_p]
    lib.ft_manager_shutdown.restype = None
    lib.ft_manager_free.argtypes = [c_void_p]
    lib.ft_manager_free.restype = None

    lib.ft_manager_client_new.argtypes = [c_char_p, c_u64, err_p]
    lib.ft_manager_client_new.restype = c_void_p
    lib.ft_manager_client_quorum.argtypes = [
        c_void_p, c_i64, c_i64, c_char_p, c_int, c_int, c_i64, c_u64, err_p,
    ]
    lib.ft_manager_client_quorum.restype = c_void_p
    lib.ft_manager_client_epoch_watch.argtypes = [
        c_void_p, c_i64, c_u64, err_p,
    ]
    lib.ft_manager_client_epoch_watch.restype = c_void_p
    lib.ft_manager_client_checkpoint_metadata.argtypes = [
        c_void_p, c_i64, c_u64, err_p,
    ]
    lib.ft_manager_client_checkpoint_metadata.restype = c_void_p
    lib.ft_manager_client_should_commit.argtypes = [
        c_void_p, c_i64, c_i64, c_int, c_u64, err_p,
    ]
    lib.ft_manager_client_should_commit.restype = c_int
    lib.ft_manager_client_kill.argtypes = [c_void_p, c_char_p, c_u64, err_p]
    lib.ft_manager_client_kill.restype = c_int
    lib.ft_manager_client_free.argtypes = [c_void_p]
    lib.ft_manager_client_free.restype = None

    lib.ft_lighthouse_client_heartbeat.argtypes = [
        c_char_p, c_char_p, c_u64, err_p,
    ]
    lib.ft_lighthouse_client_heartbeat.restype = c_int
    lib.ft_lighthouse_client_quorum.argtypes = [
        c_char_p, c_char_p, c_u64, err_p,
    ]
    lib.ft_lighthouse_client_quorum.restype = c_void_p
    # Persistent lighthouse client handles (pooled keep-alive; the
    # one-shot functions above remain as thin compatibility wrappers).
    lib.ft_lighthouse_client_new.argtypes = [c_char_p, err_p]
    lib.ft_lighthouse_client_new.restype = c_void_p
    lib.ft_lighthouse_client_free.argtypes = [c_void_p]
    lib.ft_lighthouse_client_free.restype = None
    lib.ft_lighthouse_client_heartbeat2.argtypes = [
        c_void_p, c_char_p, c_u64, err_p,
    ]
    lib.ft_lighthouse_client_heartbeat2.restype = c_int
    lib.ft_lighthouse_client_quorum2.argtypes = [
        c_void_p, c_char_p, c_u64, err_p,
    ]
    lib.ft_lighthouse_client_quorum2.restype = c_void_p
    # Generic lighthouse POST (RegisterJob, raw EpochWatch, ...): the
    # escape hatch that keeps the ABI stable as control RPCs multiply.
    lib.ft_lighthouse_client_post.argtypes = [
        c_void_p, c_char_p, c_char_p, c_u64, err_p,
    ]
    lib.ft_lighthouse_client_post.restype = c_void_p

    lib.ft_quorum_compute.argtypes = [c_i64, c_char_p, c_char_p, err_p]
    lib.ft_quorum_compute.restype = c_void_p
    lib.ft_compute_quorum_results.argtypes = [c_char_p, c_i64, c_char_p, err_p]
    lib.ft_compute_quorum_results.restype = c_void_p
    lib.ft_json_roundtrip.argtypes = [c_char_p, err_p]
    lib.ft_json_roundtrip.restype = c_void_p

    # Incremental-quorum driver (property tests / bench_fleet oracle).
    lib.ft_iq_new.argtypes = [c_char_p, c_int, c_i64, err_p]
    lib.ft_iq_new.restype = c_void_p
    lib.ft_iq_free.argtypes = [c_void_p]
    lib.ft_iq_free.restype = None
    lib.ft_iq_heartbeat.argtypes = [c_void_p, c_char_p, c_i64]
    lib.ft_iq_heartbeat.restype = None
    lib.ft_iq_expire.argtypes = [c_void_p, c_char_p, c_i64]
    lib.ft_iq_expire.restype = c_int
    lib.ft_iq_join.argtypes = [c_void_p, c_i64, c_char_p, err_p]
    lib.ft_iq_join.restype = c_int
    lib.ft_iq_decision.argtypes = [c_void_p, c_i64, err_p]
    lib.ft_iq_decision.restype = c_void_p
    lib.ft_iq_install.argtypes = [c_void_p, c_i64, c_i64, err_p]
    lib.ft_iq_install.restype = c_void_p
    lib.ft_iq_state.argtypes = [c_void_p, err_p]
    lib.ft_iq_state.restype = c_void_p
    lib.ft_iq_counters.argtypes = [c_void_p, err_p]
    lib.ft_iq_counters.restype = c_void_p


def get_lib() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is None:
            ensure_built()
            lib = ctypes.CDLL(_LIB_PATH)
            _configure(lib)
            _lib = lib
    return _lib


def take_string(ptr: int) -> str:
    """Copy a malloc'd char* into a Python str and free it."""
    lib = get_lib()
    try:
        return ctypes.cast(ptr, ctypes.c_char_p).value.decode()  # type: ignore[union-attr]
    finally:
        lib.ft_free(ptr)


def check_error(err: "ctypes.c_char_p") -> None:
    """Raise from a `char** err` out-param; TIMEOUT: prefix → TimeoutError
    (the Status→PyErr mapping of ref lib.rs:321-339)."""
    if err.value is None:
        return
    msg = err.value.decode()
    get_lib().ft_free(err)  # the C side malloc'd the message
    if msg.startswith("TIMEOUT: "):
        raise TimeoutError(msg[len("TIMEOUT: "):])
    raise RuntimeError(msg)
