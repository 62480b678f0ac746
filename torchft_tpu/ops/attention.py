"""Attention ops: pallas flash kernel on a TPU, XLA path elsewhere.

The local (per-device) causal attention used by models/transformer.py.
Off the TPU (the CPU tests) and as numerical reference, a plain
einsum-softmax that XLA fuses; on a TPU the pallas flash-attention kernel
(ops/flash.py) streams KV blocks through VMEM without materializing the
[S,S] score matrix.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["causal_attention", "reference_attention"]


def reference_attention(q, k, v, causal: bool = True,
                        scale: Optional[float] = None,
                        window: Optional[int] = None):
    """[B,S,H,D] einsum attention (fp32 softmax). ``window`` = W under
    the causal mask: position ``t`` sees keys ``t − (W − 1) … t``. ``k``
    and ``v`` may have fewer heads than ``q``, ``[B, S, KV, D]`` with ``H
    % KV == 0``: query head ``i`` reads key/value head ``i // (H / KV)``
    (the query heads are grouped in the two einsums; nothing is copied).
    Equal head counts trace to the program they always traced to."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    B, S, H, KV = *q.shape[:3], k.shape[2]
    if H % KV or v.shape[2] != KV:
        raise ValueError(
            f"attention: the {H} heads of q{tuple(q.shape)} must be a "
            f"multiple of the key/value heads of k{tuple(k.shape)} and "
            f"v{tuple(v.shape)}, which must be as many")
    if KV == H:
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
    else:
        s = jnp.einsum(
            "bqngd,bknd->bngqk",
            q.astype(jnp.float32).reshape(B, S, KV, H // KV, d),
            k.astype(jnp.float32),
        ).reshape(B, H, S, k.shape[1]) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((S, S), dtype=bool), -window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if KV == H:
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return jnp.einsum(
        "bngqk,bknd->bqngd",
        p.astype(v.dtype).reshape(B, KV, H // KV, S, k.shape[1]), v,
    ).reshape(B, S, H, v.shape[-1])


def causal_attention(q, k, v, scale: Optional[float] = None,
                     window: Optional[int] = None):
    """The local causal attention of the model zoo (``window``: of the
    last W keys only). The kernel is chosen
    from the backend alone: on a TPU the Mosaic flash kernel
    (ops/flash.py), everywhere else :func:`reference_attention`. A shape
    the kernel does not take raises its ``ValueError``, and a kernel the
    compiler refuses fails the enclosing jit's compile; neither is caught
    here, so the path that ran is never in doubt. ``k`` and ``v`` arrive
    at their own head count (``[B, S, KV, D]``, ``H % KV == 0``: query
    head ``i`` reads key/value head ``i // (H / KV)``) and mean the same on
    both backends: the kernels' index maps, the reference's grouped
    einsums."""
    if jax.default_backend() == "tpu":
        from torchft_tpu.ops.flash import flash_attention

        return flash_attention(q, k, v, causal=True, scale=scale,
                               window=window)
    return reference_attention(q, k, v, causal=True, scale=scale,
                               window=window)
