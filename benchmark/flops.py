"""The arithmetic of work and peaks: part of the yardstick, so it lives
here and not in the program.

``train_flops_per_token`` is a copy of ``bench.py::_flops_per_step``
(per token): 6·N for the forward and backward matmuls plus the causal
attention term 6·L·d·S. Recomputation (``jax.checkpoint`` of the blocks,
the chunked cross entropy's second head matmul) is hardware work the model
does not require and is NOT credited, so MFU dips when a job turns
recomputation on. ``N`` is whatever the family adapter hands in; the gpt
adapter counts the parameters that take part in matmuls and leaves out
the embedding tables, which are gathered, not multiplied.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def train_flops_per_token(n_params: int, n_layers: int, d_model: int,
                          seq_len: int) -> float:
    return 6.0 * n_params + 6.0 * n_layers * d_model * seq_len


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of ``device_kind``. A kind that is
    not in ``peaks.json`` is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {_PEAKS}; add it with "
            "its published source"
        )
    return table[device_kind]


def mfu(tokens_per_s_per_chip: float, flops_per_token: float,
        device_kind: str) -> float:
    """Model FLOP/s utilization of one chip: required operations per
    second over the chip's bf16 peak."""
    return tokens_per_s_per_chip * flops_per_token / peaks(device_kind)["bf16_flops"]
