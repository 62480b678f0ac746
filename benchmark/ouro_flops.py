"""Operations of the Ouro family (``torchft_tpu/models/ouro.py``): part of
the yardstick, beside ``flops.py`` and ``flash_flops.py``.

The stack runs ``T = total_ut_steps`` times on ONE set of weights, so a
token is multiplied by every layer's matrices ``T`` times and by the head
``T`` times: ``flops.py``'s ``6·N + 6·L·d·S`` is wrong here by the loop's
factor (3.88 GFLOP for the 511.7 M matmul parameters of the benchmark's
cut, against 15.50). ``train_flops_per_token``: 6 operations (forward and
backward) for every weight a token is multiplied by, EVERY TIME it is,
plus causal attention's core once a layer-step:

    6 · (T·L·(4·d·H·D + 3·d·ff) + T·d·V) + 6·T·L·H·D·S

Recomputation (``jax.checkpoint`` of the layer-steps, the tiles the
backward kernels build again) is hardware work the model does not require
and is NOT credited. The table is gathered, not multiplied; the gate's
``d -> 1`` product (``6·(T − 1)·d``), the norms and the mix count nothing.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark import flash_flops


def train_flops_per_token(*, d_model: int, n_layers: int, ut_steps: int,
                          n_heads: int, head_dim: int, d_ff: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (15.50 GFLOP at the cell's cut and S 8192)."""
    steps = ut_steps * n_layers         # layer-steps a token runs through
    parts = {
        "attn_proj": 6.0 * steps * 4 * d_model * n_heads * head_dim,
        "attn_core": 6.0 * steps * n_heads * head_dim * seq_len,
        "mlp": 6.0 * steps * 3 * d_model * d_ff,
        "head": head_flops_per_token(d_model=d_model, vocab=vocab,
                                     ut_steps=ut_steps),
    }
    parts["total"] = sum(parts.values())
    return parts


def head_flops_per_token(*, d_model: int, vocab: int, ut_steps: int) -> float:
    """The ``T`` heads a token: logits, ``dx`` and ``dW`` once a pass."""
    return 6.0 * ut_steps * d_model * vocab


def flash_calls(config: Dict[str, Any]) -> List[flash_flops.Call]:
    """``T`` causal calls a layer a step (``T·L`` in all), every one of the
    16 heads of 128 its own key and value (``benchmark/flash_flops.py``
    counts a call)."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return [flash_flops.Call(
        flash_flops.FULL, config["num_hidden_layers"], heads, kv,
        config["head_dim"], config["head_dim"],
        calls_a_layer=config["total_ut_steps"])]


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a configuration
    file of the ``ouro`` family."""
    return dict(
        d_model=config["hidden_size"], n_layers=config["num_hidden_layers"],
        ut_steps=config["total_ut_steps"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        seq_len=config["job"]["seq_len"],
    )
