"""What more than one model file computes, in one place: a change here is
a change to every model that imports it, and says so. RMSNorm (``llama``,
``olmoe``, ``joyai``, ``nemotron_h``, ``lfm2``, ``kimi_linear``), the repeat of grouped
key/value heads (``nemotron_h``, ``lfm2``) and the router's balance bias — its
key in the parameter tree, the predicate ``optim.with_balance_bias``
partitions the leaves by, and the way a step's loads reach that rule in
the gradient tree at the bias's place (``joyai``, ``nemotron_h``,
``lfm2``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rms_norm", "repeat_kv", "BALANCE_BIAS", "is_balance_bias",
           "loads_as_gradient"]


def rms_norm(x, scale, eps: float):
    var = jnp.mean(
        jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True
    )
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def repeat_kv(kv, n_heads: int):
    """``[B, S, KV, D]`` -> ``[B, S, n_heads, D]``: query head ``i`` reads
    key/value head ``i // (n_heads / KV)``. The flash kernels take as
    many key/value heads as query heads; the sum over a key/value head's
    copies is the repeat's own transpose."""
    return jnp.repeat(kv, n_heads // kv.shape[2], axis=2)


# the key of a router's balance bias in the parameter tree
BALANCE_BIAS = "balance_bias"


def is_balance_bias(path) -> bool:
    """Whether a ``jax.tree_util`` key path ends at a balance bias: the
    predicate ``optim.with_balance_bias`` partitions the leaves by."""
    return getattr(path[-1], "key", None) == BALANCE_BIAS


# the function's own name stands in every program traced through it
# (``custom_vjp_call[name=...]``) and those programs are pinned by hash
# (tests/test_joyai.py, tests/test_nemotron_h.py): it keeps the name it
# was first traced under
@jax.custom_vjp
def _loads_as_gradient(bias, loads):
    """Adds 0 to the loss; its cotangent for ``bias`` is ``loads``. The
    balance bias has no gradient of its own (it only selects), so its
    place in the gradient tree carries what its update rule reads: the
    step's assignments per expert, averaged over replica groups with the
    gradients."""
    return jnp.zeros((), jnp.float32)


def _loads_fwd(bias, loads):
    return jnp.zeros((), jnp.float32), loads


def _loads_bwd(loads, g):
    return (g * loads).astype(loads.dtype), jnp.zeros_like(loads)


_loads_as_gradient.defvjp(_loads_fwd, _loads_bwd)
loads_as_gradient = _loads_as_gradient
