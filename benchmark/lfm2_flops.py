"""Operations and bytes of the LFM2-MoE family
(``torchft_tpu/models/lfm2.py``): part of the yardstick, beside
``flops.py``, ``moe_flops.py``, ``mla_flops.py`` and ``ssd_flops.py``.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus causal attention. Recomputation
(``jax.checkpoint`` of the layers, the chunked cross entropy's second
head matmul, the tiles the backward kernels build again) is hardware
work the model does not require and is NOT credited. What a token
multiplies, by kind of layer — a layer is one mixer AND one MLP:

- a ``conv`` mixer: ``d·3d + d·d``; the convolution itself (3 taps, two
  gates: 8 operations a channel) is counted with the kernels' bytes
  below, not here: it is vector work of 16 KFLOP a token;
- a ``full_attention`` mixer: ``d·HD + 2·d·KV·D + HD·d`` and causal
  attention, scores and P·V ``D`` wide over the ``(S + 1) / 2`` keys a
  position sees on average: ``2·H·2D·(S + 1) / 2`` forward, three times
  that forward and backward;
- a dense MLP: ``3·d·d_ff``;
- an expert MLP: the router ``d·E_routed`` and the routed experts HELD
  HERE: ``top_k · E_held / E_routed`` of them in expectation, ``3·d·f``
  each (SwiGLU); no shared expert;
- the head once (the table, tied; the gather is not multiplied).

Bytes of one gated-convolution call (``ops/ssm_pointwise.py::
gated_conv``), the least it moves: every operand read once and every
result written once in the compute type. ``sconv_fwd``: ``[B ; C ; X]``
in (``3C``), the result out (``C``). ``sconv_bwd``: ``[B ; C ; X]`` and
the cotangent in, ``[dB ; dC ; dX]`` out. The taps and their gradient
count nothing. Operations: a channel of a token takes ``2K + 2`` forward
(``B ⊙ X``, ``K`` multiply-adds, ``C ⊙``) and ``6K + 5`` backward (the
convolution again, ``dC``, ``dconv``, the taps' ``K`` sums, ``du``'s
``K`` multiply-adds, ``dB``, ``dX``): the bytes bind.
"""

from __future__ import annotations

from typing import Any, Dict

KERNELS = ("sconv_fwd", "sconv_bwd")


def sconv_flops_per_token(kernel: str, *, channels: int, taps: int) -> float:
    """What the gated convolution needs of ONE token in ``sconv_fwd`` or
    ``sconv_bwd`` (16.4 and 47.1 KFLOP at 2048 channels, 3 taps)."""
    return float(channels * {"sconv_fwd": 2 * taps + 2,
                             "sconv_bwd": 6 * taps + 5}[kernel])


def sconv_bytes_per_token(kernel: str, *, channels: int,
                          itemsize: int = 2) -> float:
    """The least one call moves for ONE token (16.4 and 28.7 KB at 2048
    channels in bf16)."""
    return float(channels * itemsize * {"sconv_fwd": 3 + 1,
                                        "sconv_bwd": 3 + 1 + 3}[kernel])


def train_flops_per_token(*, d_model: int, n_heads: int, n_kv_heads: int,
                          head_dim: int, d_ff: int, d_expert: int,
                          n_routed: int, n_held: int, top_k: int,
                          n_conv: int, n_attn: int, n_dense: int,
                          n_expert_layers: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (1.69 GFLOP at the cell's cut and S 8192)."""
    parts = {
        "sconv_proj": 6.0 * n_conv * 4 * d_model * d_model,
        "gqa_proj": 6.0 * n_attn * d_model * head_dim * (
            2 * n_heads + 2 * n_kv_heads),
        "gqa_core": 3.0 * n_attn * n_heads * 2 * head_dim * (seq_len + 1),
        "dense_mlp": 6.0 * n_dense * 3 * d_model * d_ff,
        "router": 6.0 * n_expert_layers * d_model * n_routed,
        "routed_held": 6.0 * n_expert_layers * (top_k * n_held / n_routed)
        * 3 * d_model * d_expert,
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``lfm2`` family."""
    kinds = config["layer_types"]
    n_dense = config["num_dense_layers"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_routed=config["published"]["num_experts"],
        n_held=config["num_experts"], top_k=config["num_experts_per_tok"],
        n_conv=kinds.count("conv"), n_attn=kinds.count("full_attention"),
        n_dense=n_dense, n_expert_layers=len(kinds) - n_dense,
        vocab=config["vocab_size"], seq_len=config["job"]["seq_len"],
    )
