"""Operations and bytes of the Olmo Hybrid family
(``torchft_tpu/models/olmo_hybrid.py``): part of the yardstick, beside
``flops.py``, ``kda_flops.py`` and their siblings.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus the two mixers' own work.
Recomputation (``jax.checkpoint`` of the layers, the chunked cross
entropy's second head matmul, what the backward kernels build again) is
hardware work the model does not require and is NOT credited. What a
token multiplies, by kind of layer — a layer is one mixer AND one MLP:

- a linear-attention mixer: ``d·(2HK + HV)`` (q, k, v), ``d·HV`` (the
  gate), ``2·d·H`` (the decay and the step), ``HV·d`` (the output); the
  delta rule itself as the RECURRENCE states it, whatever the chunk: a
  position of a head decays the state (``K·V``), reads it twice (``Sᵀk``,
  ``Sᵀq``: ``2·2KV``) and writes a rank-one update (``2KV``): ``7·K·V``
  forward, three times that forward and backward;
- a full-attention mixer: ``4·d²`` and causal attention over the ``(S +
  1) / 2`` keys a position sees on average, ``2·2·D`` operations a key a
  head forward;
- the SwiGLU MLP ``3·d·d_ff``;
- the head once (the table is gathered, not multiplied).

The chunked kernels (``ops/kda.py::gdn_scan``) spend many more
operations than the recurrence needs (the pair products, the triangular
inverse, the last group's two head places that lie outside the arrays):
a share of the roofline counts what the MODEL needs of the kernel, so
those are time and not work. **Bytes** of one
``gdn_scan`` call, the least it moves: every operand read once and every
result written once at its own width — ``gdn_fwd``: ``q, k`` (``K``
wide), ``v`` (``V`` wide) in the compute type, ``g`` and ``β`` (f32, one
a head) in, ``o`` (``V`` wide) out; ``gdn_bwd``: the same in with ``dO``,
and ``dq, dk, dv`` (compute type), ``dg, dβ`` (f32) out. The
chunk-boundary states the backward reads are the kernels' own choice and
count nothing. At 96 / 192 a position of a head needs 0.13 MFLOP and
1.16 KB forward: on a v5e **the bytes bind** (1.4 ns against 0.7).
"""

from __future__ import annotations

from typing import Any, Dict

KERNELS = ("gdn_fwd", "gdn_bwd")
LINEAR, FULL = "linear_attention", "full_attention"


def gdn_flops_per_token(kernel: str, *, n_heads: int, key_dim: int,
                        value_dim: int) -> float:
    """What the delta rule needs of ONE token in ``gdn_fwd`` or
    ``gdn_bwd`` (3.87 and 7.74 MFLOP at 30 heads of 96 x 192)."""
    return float(n_heads * 7 * key_dim * value_dim
                 * {"gdn_fwd": 1, "gdn_bwd": 2}[kernel])


def gdn_bytes_per_token(kernel: str, *, n_heads: int, key_dim: int,
                        value_dim: int, itemsize: int = 2) -> float:
    """The least one call moves for ONE token (34.8 and 58.1 KB at 30
    heads of 96 x 192 in bf16)."""
    qkv = (2 * key_dim + value_dim) * itemsize
    g_beta, out = 8, value_dim * itemsize
    fwd = qkv + g_beta + out
    return float(n_heads * {"gdn_fwd": fwd,
                            "gdn_bwd": fwd + qkv + g_beta}[kernel])


def linear_params(d_model: int, n_heads: int, key_dim: int,
                  value_dim: int) -> int:
    """Matmul weights of one linear-attention mixer (88.7 M as
    published)."""
    hk, hv = n_heads * key_dim, n_heads * value_dim
    return d_model * (2 * hk + hv) + d_model * hv + 2 * d_model * n_heads \
        + hv * d_model


def train_flops_per_token(*, d_model: int, n_heads: int, key_dim: int,
                          value_dim: int, d_ff: int, n_linear: int,
                          n_full: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (5.51 GFLOP at the cell's cut and S 8192)."""
    parts = {
        "gdn_proj": 6.0 * n_linear * linear_params(
            d_model, n_heads, key_dim, value_dim),
        "gdn_core": 3.0 * n_linear * gdn_flops_per_token(
            "gdn_fwd", n_heads=n_heads, key_dim=key_dim, value_dim=value_dim),
        "attn_proj": 6.0 * n_full * 4 * d_model * d_model,
        "attn_core": 3.0 * n_full * 2 * d_model * (seq_len + 1),
        "mlp": 6.0 * (n_linear + n_full) * 3 * d_model * d_ff,
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``olmo_hybrid`` family."""
    kinds = config["layer_types"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["linear_num_key_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        d_ff=config["intermediate_size"], n_linear=kinds.count(LINEAR),
        n_full=kinds.count(FULL), vocab=config["vocab_size"],
        seq_len=config["job"]["seq_len"],
    )
