"""Operations of the Granite 4.0-H family
(``torchft_tpu/models/granite_hybrid.py``): part of the yardstick, beside
``flops.py`` and ``ssd_flops.py``.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus causal attention and the scan.
Recomputation (``jax.checkpoint`` of the layers, the chunked cross
entropy's second head matmul, the tiles the backward kernels build again)
is hardware work the model does not require and is NOT credited. What a
token multiplies, by part:

- ``ssm_proj``: a Mamba-2 mixer's ``d·(2I + 2GN + H)`` in and ``I·d``
  out;
- ``ssm_scan``: the scan, ``ssd_flops.ssd_flops_per_token`` AS IT STANDS
  (the chunked form at the chunk the configuration publishes, causal
  pairs only: the same work whatever grid computes it), forward and
  backward;
- ``gqa_proj``: ``d·HD + 2·d·KV·D + HD·d``; ``gqa_core``: scores and P·V
  ``D`` wide over the ``(S + 1) / 2`` keys a position sees on average,
  ``2·H·2D·(S + 1) / 2`` forward, three times that forward and backward;
- ``mlp``: the SwiGLU's three ``d × f`` matrices, in every layer;
- ``head``: the tied table read as the head, ``d × V`` over the rows held.
  The table is gathered, not multiplied; the four multipliers are
  element-wise and count nothing.

The scan's bytes are ``ssd_flops.ssd_bytes_per_token``'s: at one group of
64 heads of 64 and state 128, 17.2 and 26.1 KB a token against 9.55 MFLOP
forward + backward at chunk 256 — on a v5e the bytes bind both kernels.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.ssd_flops import ssd_flops_per_token

MAMBA, ATTENTION = "mamba", "attention"


def scan_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The scan's widths and the chunk counted, from a configuration file
    of the ``granite_hybrid`` family (``ssd_flops``' argument names)."""
    return dict(heads=config["mamba_n_heads"], head_dim=config["mamba_d_head"],
                groups=config["mamba_n_groups"], state=config["mamba_d_state"],
                chunk=config["mamba_chunk_size"])


def train_flops_per_token(*, d_model: int, ssm_heads: int, ssm_head_dim: int,
                          ssm_groups: int, ssm_state: int, chunk: int,
                          n_heads: int, n_kv_heads: int, head_dim: int,
                          d_ff: int, n_mamba: int, n_attn: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (4.82 GFLOP at the cell's cut and S 8192)."""
    inner = ssm_heads * ssm_head_dim
    scan = dict(heads=ssm_heads, head_dim=ssm_head_dim, groups=ssm_groups,
                state=ssm_state, chunk=chunk)
    parts = {
        "ssm_proj": 6.0 * n_mamba * d_model * (
            2 * inner + 2 * ssm_groups * ssm_state + ssm_heads + inner),
        "ssm_scan": n_mamba * (ssd_flops_per_token("ssd_fwd", **scan)
                               + ssd_flops_per_token("ssd_bwd", **scan)),
        "gqa_proj": 6.0 * n_attn * d_model * head_dim * (
            2 * n_heads + 2 * n_kv_heads),
        "gqa_core": 3.0 * n_attn * n_heads * 2 * head_dim * (seq_len + 1),
        "mlp": 6.0 * (n_mamba + n_attn) * 3 * d_model * d_ff,
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``granite_hybrid`` family."""
    kinds = config["layer_types"]
    scan = scan_dims(config)
    return dict(
        d_model=config["hidden_size"], ssm_heads=scan["heads"],
        ssm_head_dim=scan["head_dim"], ssm_groups=scan["groups"],
        ssm_state=scan["state"], chunk=scan["chunk"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["shared_intermediate_size"],
        n_mamba=kinds.count(MAMBA), n_attn=kinds.count(ATTENTION),
        vocab=config["vocab_size"], seq_len=config["job"]["seq_len"],
    )
