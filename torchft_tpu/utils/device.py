"""Where arrays and compiled programs live: the three device decisions
every entry point and every host-to-device landing shares.

* :func:`require_tpu` — an entry point that measures or smoke-tests the
  chip asks for the TPU by name, so jax's own fall-through to the CPU on a
  failed TPU start-up becomes an error instead of a CPU run.
* :func:`place_compile_cache` — one persistent compilation cache per
  checkout, placeable from outside through ``JAX_COMPILATION_CACHE_DIR``.
* :func:`land` / :func:`land_like` — a host array that replaces a device
  leaf returns to that leaf's dtype and sharding (its device, or its mesh
  layout), never to the process's default device. Always through a fresh
  host copy.
* :func:`land_batch` — the same for a whole bucket of staging-arena views,
  without the host copy where the target cannot alias host memory;
  :func:`placement` is where such a bucket lands.
"""

from __future__ import annotations

import os
from typing import Any, FrozenSet, Iterable, List, Sequence, Tuple

import numpy as np

__all__ = [
    "land", "land_batch", "land_like", "place_compile_cache", "placement",
    "require_tpu",
]

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def require_tpu() -> Sequence[Any]:
    """Initialise the TPU backend and nothing else; return its devices.
    Raises ``RuntimeError`` (jax's own, naming the TPU) when no chip can
    be claimed — including when ``JAX_PLATFORMS`` asked for the CPU."""
    import jax

    jax.config.update("jax_platforms", "tpu")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"expected a TPU backend, jax reports {devices[0].platform!r}"
        )
    return devices


def place_compile_cache() -> str:
    """Point jax's persistent compilation cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already places it (jax reads that
    variable itself, so nothing is set then). The path is part of nothing
    that moves — no temp name, pid or time — because a cache whose
    directory changes between runs never hits. Returns the directory in
    effect."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def land(host: np.ndarray, sharding: Any, dtype: Any = None) -> Any:
    """Copy ``host`` (cast to ``dtype`` if given) to the device(s) of
    ``sharding``; ``None`` — the leaf it stands for lives on the host —
    means the default device. Always a copy: callers land views of
    reusable staging arenas, and on the CPU backend a device buffer may
    alias the numpy memory it was put from."""
    import jax

    if sharding is None:
        return jax.numpy.array(host, dtype=dtype)
    return jax.device_put(np.array(host, dtype=dtype), sharding)


def land_like(host: np.ndarray, like: Any, dtype: Any = None) -> Any:
    """:func:`land` onto the placement of ``like``, the leaf that ``host``
    replaces or belongs to: ``like``'s sharding (none for a numpy leaf),
    and ``like``'s dtype unless ``dtype`` names another (optimizer
    moments, f32 pseudogradients)."""
    return land(
        host, getattr(like, "sharding", None),
        like.dtype if dtype is None else dtype,
    )


def placement(likes: Iterable[Any]) -> FrozenSet[Any]:
    """Where the leaves ``likes`` live: the union of their shardings'
    devices. A numpy leaf adds none (it lands on the default device)."""
    devices: set = set()
    for like in likes:
        sharding = getattr(like, "sharding", None)
        if sharding is not None:
            devices |= sharding.device_set
    return frozenset(devices)


def _may_alias_host(sharding: Any) -> bool:
    """Whether a buffer put to ``sharding`` may alias the numpy memory it
    was put from: the CPU backend's zero-copy ``device_put`` does, an
    accelerator's memory cannot. No sharding — the default device,
    whatever it is — counts as may."""
    return sharding is None or any(
        d.platform == "cpu" for d in sharding.device_set
    )


def land_batch(views: Sequence[np.ndarray],
               likes: Sequence[Any]) -> Tuple[List[Any], int, int]:
    """:func:`land_like` for one bucket: ``views[j]`` (a slice of a
    reusable staging arena) replaces the device leaf ``likes[j]``.
    Returns the device arrays and the bytes landed straight from the
    arena / through a host copy.

    Where no target device of a leaf can alias host memory
    (:func:`_may_alias_host`) and the view already has the leaf's dtype
    and is contiguous, the view itself is handed to ``device_put`` — no
    intermediate copy into fresh pages — and the bucket's transfers are
    issued back to back. The arena must then stay as it is until they have
    read it: this returns only once every such array is ready, so a
    caller that reports the bucket landed after this call (and repacks
    the arena only after that report) holds the lifetime. Everywhere
    else — a CPU device, a cast — the copy of :func:`land` stays (a
    cast is the one copy either way)."""
    import jax

    out: List[Any] = []
    lent: List[Any] = []
    borrowed = copied = 0
    try:
        for view, like in zip(views, likes):
            sharding = getattr(like, "sharding", None)
            if (not _may_alias_host(sharding) and view.dtype == like.dtype
                    and view.flags.c_contiguous):
                out.append(jax.device_put(view, sharding))
                lent.append(out[-1])
                borrowed += view.nbytes
            else:
                out.append(land(view, sharding, like.dtype))
                copied += view.nbytes
    finally:
        jax.block_until_ready(lent)
    return out, borrowed, copied
