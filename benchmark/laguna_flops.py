"""Operations and bytes of the Laguna family
(``torchft_tpu/models/laguna.py``): part of the yardstick, beside
``flops.py``, ``moe_flops.py``, ``mla_flops.py``, ``ssd_flops.py``,
``lfm2_flops.py``, ``kda_flops.py``, ``phi4flash_flops.py``,
``smallthinker_flops.py`` and ``olmo_hybrid_flops.py``.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus attention. Recomputation
(``jax.checkpoint`` of the layers, the tiles the backward kernels build
again, a share's experts run forward twice) is hardware work the model
does not require and is NOT credited. What a token multiplies, LAYER BY
LAYER, because the query head count ``H_l`` follows the kind of layer:

- the attention projections ``d·D·(2·H_l + 2·KV)`` (q and o ``H_l·D``
  wide: 6144 or 8192 on a stream of 2048; k and v ``KV·D``) and the gate
  a head ``d·H_l``;
- the attention core, scores and ``P·V`` ``D`` wide over the keys a
  position SEES: in a full layer ``(S + 1) / 2`` on average under the
  causal mask; in a sliding layer the band's ``S·W − W(W − 1)/2`` live
  pairs a head, ``W − W(W − 1)/(2S)`` a position — never the tiles a
  kernel happens to compute. ``2·H_l·2D`` a pair forward, three times
  that forward and backward;
- a dense layer's MLP ``3·d·d_ff``; in a sparse layer the router
  ``d·E_routed``, the routed experts HELD HERE (``top_k · E_held /
  E_routed`` of them in expectation, ``3·d·f`` each) and the shared
  expert ``3·d·f_s``;
- the head once over the rows held (untied; the gather is not
  multiplied).

Of one flash call (``ops/flash.py``), kept for the roofline a later
``benchmark`` PR will read: the live pairs × ``2 (Dqk + Dv)`` operations a
kernel (forward: scores and P·V; dq: dP and dQ; dkv: dV and dK), and the
least bytes with K and V counted at THEIR OWN head count, ``B·KV`` heads
(the kernels read a key/value head where it lies since PR 55; the
accepted families' ``flash_bytes_per_call`` still counts ``B·H``). At
128-wide heads the operations bind in both calls: a banded forward at
W 512, [4 × 64 | 8, 8192]: 2.70 ms of operations against 1.49 ms of bytes
at a v5e's peaks; the causal one at 48 | 8 heads 16.75 ms against 1.15.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def live_pairs(seq_len: int, window: Optional[int] = None) -> float:
    """(query, key) pairs a head sees: ``Σ_t min(t + 1, W)`` under a
    window of ``window`` keys, ``S(S + 1)/2`` without."""
    w = seq_len if window is None else min(window, seq_len)
    return seq_len * w - w * (w - 1) / 2.0


def flash_flops_per_call(batch_heads: int, seq_len: int, d_qk: int, d_v: int,
                         window: Optional[int] = None) -> float:
    """What attention needs of ONE call of any of the three kernels, at
    ``batch_heads`` = batch × QUERY heads."""
    return batch_heads * live_pairs(seq_len, window) * 2.0 * (d_qk + d_v)


def flash_bytes_per_call(kernel: str, batch: int, n_heads: int,
                         n_kv_heads: int, seq_len: int, d_qk: int, d_v: int,
                         itemsize: int = 2) -> float:
    """The least one call moves: every operand read once and every result
    written once in the compute type, the statistics in float32, q / o /
    dO / dq at ``n_heads`` and k / v / dk / dv at ``n_kv_heads``.
    ``flash_fwd``: q, k, v -> o, lse. ``flash_dq``: q, k, v, dO, lse,
    delta -> dq. ``flash_dkv``: the same in, dk and dv out."""
    q_rows, kv_rows = batch * n_heads * seq_len, batch * n_kv_heads * seq_len
    read = (q_rows * d_qk + kv_rows * (d_qk + d_v)) * itemsize
    if kernel == "flash_fwd":
        return float(read + q_rows * d_v * itemsize + q_rows * 4)
    out = {"flash_dq": q_rows * d_qk,
           "flash_dkv": kv_rows * (d_qk + d_v)}[kernel]
    return float(read + q_rows * d_v * itemsize + 2 * q_rows * 4
                 + out * itemsize)


def train_flops_per_token(*, d_model: int, heads: Sequence[int],
                          windowed: Sequence[int], sparse: Sequence[int],
                          n_kv_heads: int, head_dim: int, window: int,
                          d_ff: int, d_expert: int, d_shared: int,
                          n_routed: int, n_held: int, top_k: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (2.405 GFLOP at the cell's cut and S 8192)."""
    n_sparse = sum(sparse)
    parts = {
        "gqa_proj": 6.0 * sum(
            d_model * head_dim * (2 * h + 2 * n_kv_heads) + d_model * h
            for h in heads),
        "full_core": 0.0, "swa_core": 0.0,
        "dense_mlp": 6.0 * (len(sparse) - n_sparse) * 3 * d_model * d_ff,
        "router": 6.0 * n_sparse * d_model * n_routed,
        "routed_held": 6.0 * n_sparse * (top_k * n_held / n_routed)
        * 3 * d_model * d_expert,
        "shared": 6.0 * n_sparse * 3 * d_model * d_shared,
        "head": 6.0 * d_model * vocab,
    }
    for h, is_windowed in zip(heads, windowed):
        pairs = live_pairs(seq_len, window if is_windowed else None)
        parts["swa_core" if is_windowed else "full_core"] += (
            3.0 * 2.0 * h * 2 * head_dim * pairs / seq_len)
    parts["total"] = sum(parts.values())
    return parts


def config_dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``laguna`` family."""
    return dict(
        d_model=config["hidden_size"],
        heads=config["num_attention_heads_per_layer"],
        windowed=[int(t == "sliding_attention")
                  for t in config["layer_types"]],
        sparse=[int(t == "sparse") for t in config["mlp_layer_types"]],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        n_routed=config["published"]["num_experts"],
        n_held=config["num_experts"],
        top_k=config["num_experts_per_tok"], vocab=config["vocab_size"],
        seq_len=config["job"]["seq_len"],
    )
