"""Operations and bytes of the sparse-expert layer's grouped matmuls
(``torchft_tpu/ops/moe.py``): part of the yardstick, beside ``flops.py``.

One assignment row (a token's copy at one of its experts) passes three
grouped matmuls — gate and up ``[d] x [d, f]``, down ``[f] x [f, d]`` —
forward, and each has two transposes backward (the gradient of the rows
and of the weights): ``3 x 3 x 2 x d x f = 18 x d x f`` operations a row
a step. Recomputation under ``jax.checkpoint`` is hardware work the
model does not require and is not credited. A padded or skipped tile
counts nothing: the count is what the algorithm needs, never what a
kernel happens to do.
"""

from __future__ import annotations


def expert_flops_per_row(d_model: int, d_expert: int) -> float:
    """Forward and backward, one assignment row, one layer."""
    return 18.0 * d_model * d_expert


def expert_flops_per_step(tokens: int, top_k: int, n_layers: int,
                          d_model: int, d_expert: int) -> float:
    """``top_k x tokens`` rows a layer: 7.42 TFLOP a layer at 24 576
    tokens x 8 of OLMoE's 2048 x 1024 experts."""
    return n_layers * tokens * top_k * expert_flops_per_row(d_model, d_expert)


def expert_bytes_per_step(tokens: int, top_k: int, n_layers: int,
                          n_experts: int, d_model: int, d_expert: int,
                          itemsize: int = 2) -> float:
    """The least the nine grouped matmuls move, forward and backward, in
    the compute type: each reads its row operand and its weights (or
    the two row operands, for a weight gradient) and writes its result
    once. Rows are ``[M, d]`` or ``[M, f]``, weights ``[E, d, f]``."""
    m = tokens * top_k
    rows_d, rows_f = m * d_model, m * d_expert
    w = n_experts * d_model * d_expert
    fwd = 2 * (rows_d + w + rows_f) + (rows_f + w + rows_d)
    # the row gradients mirror the forward; the weight gradients read
    # both row operands and write a weight-shaped result
    bwd_rows = fwd
    bwd_w = 3 * (rows_d + rows_f + w)
    return float(n_layers * itemsize * (fwd + bwd_rows + bwd_w))
