"""Operations and bytes of the Kimi Linear family
(``torchft_tpu/models/kimi_linear.py``): part of the yardstick, beside
``flops.py``, ``moe_flops.py``, ``mla_flops.py``, ``ssd_flops.py`` and
``lfm2_flops.py``.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus the two mixers' own work.
Recomputation (``jax.checkpoint`` of the layers, the chunked cross
entropy's second head matmul, what the backward kernels build again) is
hardware work the model does not require and is NOT credited. What a
token multiplies, by kind of layer — a layer is one mixer AND one MLP:

- a KDA mixer: ``d·3HD`` (q, k, v), ``2·(d·r + r·HD)`` (the decay's and
  the gate's low-rank pairs), ``d·H`` (β), ``HD·d`` (the output); the
  delta rule itself as the RECURRENCE states it, whatever the chunk: a
  position of a head decays the state (``D²``), reads it twice (``Sᵀk``,
  ``Sᵀq``: ``2·2D²``) and writes a rank-one update (``2D²``): ``7·D²``
  forward, three times that forward and backward;
- an MLA mixer: ``mla_flops.mla_params`` without a q latent (``d·H·(nope
  + rope)`` in its place) and causal attention over the ``(S + 1) / 2``
  keys a position sees on average;
- a dense MLP ``3·d·d_ff``; an expert MLP: the router ``d·E_routed``, one
  shared expert ``3·d·f`` and the routed experts HELD HERE, ``top_k ·
  E_held / E_routed`` of them in expectation, ``3·d·f`` each;
- the head once (the table is gathered, not multiplied).

The chunked kernels (``ops/kda.py``) spend many more operations than the
recurrence needs (the pair sums, the triangular inverse): a share of the
roofline counts what the MODEL needs of the kernel, so those are time
and not work. **Bytes** of one ``kda_scan`` call, the least it moves:
every operand read once and every result written once — ``kda_fwd``:
``q, k, v`` (compute type), ``g`` (f32), ``β`` (f32) in, ``o`` out;
``kda_bwd``: the same in with ``dO``, and ``dq, dk, dv`` (compute type),
``dg, dβ`` (f32) out. The chunk-boundary states the backward reads are
the kernels' own choice and count nothing. At 128-wide heads a position
of a head needs 0.11 MFLOP and 1.5 KB forward: on a v5e **the bytes
bind** (1.9 ns against 0.6).
"""

from __future__ import annotations

from typing import Any, Dict

KERNELS = ("kda_fwd", "kda_bwd")


def kda_flops_per_token(kernel: str, *, n_heads: int, head_dim: int) -> float:
    """What the delta rule needs of ONE token in ``kda_fwd`` or
    ``kda_bwd`` (3.67 and 7.34 MFLOP at 32 heads of 128)."""
    return float(n_heads * 7 * head_dim * head_dim
                 * {"kda_fwd": 1, "kda_bwd": 2}[kernel])


def kda_bytes_per_token(kernel: str, *, n_heads: int, head_dim: int,
                        itemsize: int = 2) -> float:
    """The least one call moves for ONE token (49.3 and 90.4 KB at 32
    heads of 128 in bf16)."""
    qkv, g, beta = 3 * head_dim * itemsize, head_dim * 4, 4
    out = head_dim * itemsize
    fwd = qkv + g + beta + out
    return float(n_heads * {"kda_fwd": fwd,
                            "kda_bwd": fwd + qkv + g + beta}[kernel])


def kda_params(d_model: int, n_heads: int, head_dim: int, rank: int) -> int:
    """Matmul weights of one KDA mixer (39.4 M as published)."""
    hd = n_heads * head_dim
    return (d_model * 3 * hd + 2 * (d_model * rank + rank * hd)
            + d_model * n_heads + hd * d_model)


def mla_params(d_model: int, n_heads: int, kv_rank: int, nope: int,
               rope: int, v_dim: int) -> int:
    """Matmul weights of one MLA mixer without a q latent (29.1 M)."""
    return (d_model * n_heads * (nope + rope) + d_model * (kv_rank + rope)
            + kv_rank * n_heads * (nope + v_dim) + n_heads * v_dim * d_model)


def train_flops_per_token(*, d_model: int, n_heads: int, kda_head_dim: int,
                          kda_rank: int, kv_rank: int, nope: int, rope: int,
                          v_dim: int, d_ff: int, d_expert: int,
                          n_routed: int, n_held: int, top_k: int, n_kda: int,
                          n_mla: int, n_dense: int, n_expert_layers: int,
                          vocab: int, seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (2.36 GFLOP at the cell's cut and S 8192)."""
    parts = {
        "kda_proj": 6.0 * n_kda * kda_params(
            d_model, n_heads, kda_head_dim, kda_rank),
        "kda_core": 3.0 * n_kda * kda_flops_per_token(
            "kda_fwd", n_heads=n_heads, head_dim=kda_head_dim),
        "mla_proj": 6.0 * n_mla * mla_params(
            d_model, n_heads, kv_rank, nope, rope, v_dim),
        "mla_core": 3.0 * n_mla * n_heads * (nope + rope + v_dim)
        * (seq_len + 1),
        "dense_mlp": 6.0 * n_dense * 3 * d_model * d_ff,
        "router": 6.0 * n_expert_layers * d_model * n_routed,
        "shared": 6.0 * n_expert_layers * 3 * d_model * d_expert,
        "routed_held": 6.0 * n_expert_layers * (top_k * n_held / n_routed)
        * 3 * d_model * d_expert,
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``kimi_linear`` family."""
    linear = config["linear_attn_config"]
    n_dense = config["first_k_dense_replace"]
    return dict(
        d_model=config["hidden_size"], n_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"], kda_rank=linear["head_dim"],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_routed=config["published"]["num_experts"],
        n_held=config["num_experts"], top_k=config["num_experts_per_token"],
        n_kda=len(linear["kda_layers"]), n_mla=len(linear["full_attn_layers"]),
        n_dense=n_dense,
        n_expert_layers=config["num_hidden_layers"] - n_dense,
        vocab=config["vocab_size"], seq_len=config["job"]["seq_len"],
    )
