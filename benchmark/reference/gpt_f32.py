"""Plain reference of the GPT-2 architecture Cerebras-GPT uses (Dey et
al., arXiv:2304.03208, section 2 and Table 1; Radford et al. 2019):
learned absolute positions, pre-LayerNorm blocks of multi-head causal
attention and a 4x GELU MLP, a final LayerNorm, and a softmax over the
vocabulary. Straightforward ``jax.numpy`` in float32 with matmuls at
``highest`` precision: no flash kernel, no chunked cross entropy, no
recomputation, nothing imported from the program.

Departures from the published model, all forced by the code the model
runs through (``torchft_tpu/models/transformer.py``), which this
benchmark may not change; the configuration files list them too:

* no bias on the q/k/v/o and MLP projections (GPT-2 has them);
* the output head is its own matrix, not the transposed token embedding;
* GELU in its tanh form (``jax.nn.gelu``'s default; the published
  ``activation_function`` is the erf form);
* the softmax runs over every allocated row of the head (vocabulary
  padded to a multiple of 128), targets are drawn below the published
  vocabulary.

Parameter tree as ``models.transformer.init_params`` makes it.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _layer_norm(x: Any, p: Dict[str, Any], eps: float) -> Any:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x: Any) -> Any:
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)
    ))


def loss(params: Dict[str, Any], tokens: Any, targets: Any, *, n_layer: int,
         n_head: int, eps: float) -> Any:
    """Mean next-token cross entropy of ``tokens`` [B, S] against
    ``targets`` [B, S], float32 throughout."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: a.astype(jnp.float32), t
        )
        B, S = tokens.shape
        x = (f32(params["wte"]["embedding"])[tokens]
             + f32(params["wpe"]["embedding"])[:S][None])
        d = x.shape[-1]
        hd = d // n_head
        causal = jnp.tril(jnp.ones((S, S), dtype=bool))
        for i in range(n_layer):
            layer = f32(params[f"layers_{i}"])
            h = _layer_norm(x, layer["ln_1"], eps)
            q, k, v = (
                (h @ layer["attn"][n]["kernel"]).reshape(B, S, n_head, hd)
                for n in ("q_proj", "k_proj", "v_proj")
            )
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
            x = x + a.reshape(B, S, d) @ layer["attn"]["o_proj"]["kernel"]
            h = _layer_norm(x, layer["ln_2"], eps)
            h = _gelu_tanh(h @ layer["mlp"]["up_proj"]["kernel"])
            x = x + h @ layer["mlp"]["down_proj"]["kernel"]
        x = _layer_norm(x, f32(params["ln_f"]), eps)
        logits = x @ f32(params["lm_head"]["kernel"])
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(nll)
