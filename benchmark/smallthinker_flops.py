"""Operations and bytes of the SmallThinker family
(``torchft_tpu/models/smallthinker.py``): part of the yardstick, beside
``flops.py``, ``moe_flops.py``, ``mla_flops.py``, ``ssd_flops.py``,
``lfm2_flops.py``, ``kda_flops.py`` and ``phi4flash_flops.py``.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus attention. Recomputation
(``jax.checkpoint`` of the layers, the tiles the backward kernels build
again, a share's experts run forward twice) is hardware work the model
does not require and is NOT credited. What a token multiplies — every
layer is one attention mixer AND one expert MLP:

- the attention projections: ``d·HD + 2·d·KV·D + HD·d`` (``HD`` is not
  ``d``: 3584 on 2560);
- the attention core, scores and ``P·V`` ``D`` wide over the keys a
  position SEES: in a full layer ``(S + 1) / 2`` on average under the
  causal mask; in a windowed layer the band's ``S·W − W(W − 1)/2`` live
  pairs a head, ``W − W(W − 1)/(2S)`` a position — never the tiles a
  kernel happens to compute. ``2·H·2D`` a pair forward, three times that
  forward and backward;
- the router ``d·E_routed`` and the routed experts HELD HERE: ``top_k ·
  E_held / E_routed`` of them in expectation, ``3·d·f`` each (ReGLU: three
  matrices); no shared expert, no dense layer;
- the head once over the rows held (untied; the gather is not
  multiplied).

Of one flash call (``ops/flash.py``): the live pairs × ``2 (Dqk + Dv)``
operations a kernel (forward: scores and P·V; dq: dP and dQ; dkv: dV and
dK), and every operand and result once (``mla_flops.flash_bytes_per_call``).
At 128-wide heads the operations bind in both kinds of call (a windowed
forward at W 4096, [56, 16384]: 8.5 ms of operations against 1.2 ms of
bytes at a v5e's peaks; the full one 19.5 ms).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import mla_flops

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def live_pairs(seq_len: int, window: Optional[int] = None) -> float:
    """(query, key) pairs a head sees: ``Σ_t min(t + 1, W)`` under a
    window of ``window`` keys, ``S(S + 1)/2`` without."""
    w = seq_len if window is None else min(window, seq_len)
    return seq_len * w - w * (w - 1) / 2.0


def flash_flops_per_call(batch_heads: int, seq_len: int, d_qk: int, d_v: int,
                         window: Optional[int] = None) -> float:
    """What attention needs of ONE call of any of the three kernels."""
    return batch_heads * live_pairs(seq_len, window) * 2.0 * (d_qk + d_v)


# every operand read once and every result written once: one definition
flash_bytes_per_call = mla_flops.flash_bytes_per_call


def train_flops_per_token(*, d_model: int, n_heads: int, n_kv_heads: int,
                          head_dim: int, d_expert: int, n_routed: int,
                          n_held: int, top_k: int, n_full: int, n_swa: int,
                          window: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (2.12 GFLOP at the cell's cut and S 16384)."""
    layers = n_full + n_swa
    pair = 2.0 * n_heads * 2 * head_dim          # forward, one (q, k) pair
    parts = {
        "gqa_proj": 6.0 * layers * d_model * head_dim * (
            2 * n_heads + 2 * n_kv_heads),
        "full_core": 3.0 * n_full * pair * live_pairs(seq_len) / seq_len,
        "swa_core": 3.0 * n_swa * pair * live_pairs(seq_len, window) / seq_len,
        "router": 6.0 * layers * d_model * n_routed,
        "routed_held": 6.0 * layers * (top_k * n_held / n_routed)
        * 3 * d_model * d_expert,
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``smallthinker`` family."""
    windowed = config["sliding_window_layout"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_expert=config["moe_ffn_hidden_size"],
        n_routed=config["published"]["moe_num_primary_experts"],
        n_held=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        n_full=windowed.count(0), n_swa=windowed.count(1),
        window=config["sliding_window_size"], vocab=config["vocab_size"],
        seq_len=config["job"]["seq_len"],
    )
