"""Operations and bytes of the Phi-4-mini-flash family
(``torchft_tpu/models/phi4flash.py``): part of the yardstick, beside
``flops.py``, ``moe_flops.py``, ``mla_flops.py``, ``ssd_flops.py``,
``lfm2_flops.py`` and ``kda_flops.py``.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus attention. Recomputation
(``jax.checkpoint`` of the layers, the chunked cross entropy's second
head matmul, the tiles the backward kernels build again, the chunk the
scan's backward runs forward again) is hardware work the model does not
require and is NOT credited. What a token multiplies, by kind of layer —
a layer is one mixer AND one SwiGLU MLP (``3·d·d_ff``):

- a Mamba-1 mixer: ``d·2d_i + d_i·(R + 2N) + R·d_i + d_i·d``. **The
  selective scan is vector work and counts ZERO matmul operations**: a
  state update is an exponential, two multiplies and an add a (channel,
  state index), ``9·d_i·N`` = 0.74 MFLOP a token forward on units
  ``peaks.json`` has no peak for, so its roofline below is its bytes;
- an attention mixer with its own keys and values (``swa``, ``full``):
  ``d·(HD + 2·KV·D) + HD·d``; a cross mixer ``d·HD + HD·d``;
- the attention core of any of the three: every one of the ``H`` (pair,
  half) heads takes scores ``D`` wide and ``P·V`` ``2D`` wide over the
  keys a position SEES: ``(S + 1) / 2`` on average under the causal mask,
  and under a window of ``W`` keys the band's ``S·W − W(W − 1)/2`` live
  pairs a head, ``W − W(W − 1)/(2S)`` a position — never the tiles a
  kernel happens to compute. ``2·H·3D`` a pair forward, three times that
  forward and backward;
- a gated memory unit: ``d·d_i + d_i·d``;
- the head once (the table, tied; the gather is not multiplied).

Bytes of one scan call (``ops/s6.py``), the least it moves: every
operand read once and every result written once — ``x``, ``B``, ``C``,
``y`` and their cotangents in the compute type, ``Δ`` and ``dΔ`` in
float32; ``A``, ``D``, their gradients and the chunk-boundary states
(the kernels' own choice) count nothing. ``s6_fwd``: ``8·d_i + 4N``
bytes a token; ``s6_bwd``: ``14·d_i + 8N``.

Of one windowed flash call (``ops/flash.py``, ``window=W``): the band's
live pairs × ``2 (Dqk + Dv)`` operations a kernel (forward: scores and
P·V; dq: dP and dQ; dkv: dV and dK), and every operand and result once
(``mla_flops.flash_bytes_per_call``: at 512 keys the two are of one
size, so both are taken).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import mla_flops

S6_KERNELS = ("s6_fwd", "s6_bwd")


def s6_bytes_per_token(kernel: str, *, channels: int, state: int,
                       itemsize: int = 2) -> float:
    """The least one scan call moves for ONE token (41.0 and 71.8 KB at
    5120 channels, 16 states, bf16)."""
    small = 2 * state * itemsize                      # B_t and C_t
    if kernel == "s6_fwd":
        return float(channels * (2 * itemsize + 4) + small)
    # x, Δ, dy in; dx, dΔ out; B, C in and dB, dC out
    return float(channels * (3 * itemsize + 2 * 4) + 2 * small)


def live_pairs(seq_len: int, window: int) -> float:
    """(query, key) pairs a head sees under a window of ``window`` keys:
    ``Σ_t min(t + 1, W)``."""
    w = min(window, seq_len)
    return seq_len * w - w * (w - 1) / 2.0


def swa_flash_flops_per_call(batch_heads: int, seq_len: int, window: int,
                             d_qk: int, d_v: int) -> float:
    """What windowed attention needs of ONE call of any of the three
    kernels."""
    return batch_heads * live_pairs(seq_len, window) * 2.0 * (d_qk + d_v)


def train_flops_per_token(*, d_model: int, n_heads: int, n_kv_heads: int,
                          head_dim: int, d_ff: int, d_inner: int, state: int,
                          dt_rank: int, window: int, n_mamba: int, n_swa: int,
                          n_full: int, n_cross: int, n_gmu: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (4.58 GFLOP at the cell's cut and S 8192)."""
    q, kv = n_heads * head_dim, n_kv_heads * head_dim
    pair = 2.0 * n_heads * 3 * head_dim          # forward, one (q, k) pair
    layers = n_mamba + n_swa + n_full + n_cross + n_gmu
    parts = {
        "ssm_proj": 6.0 * n_mamba * d_inner * (
            3 * d_model + 2 * dt_rank + 2 * state),
        "ssm_scan": 0.0,
        "diff_proj": 6.0 * ((n_swa + n_full) * d_model * 2 * (q + kv)
                            + n_cross * d_model * 2 * q),
        "swa_core": 3.0 * n_swa * pair * live_pairs(seq_len, window) / seq_len,
        "full_core": 3.0 * (n_full + n_cross) * pair * (seq_len + 1) / 2,
        "gmu": 6.0 * n_gmu * 2 * d_model * d_inner,
        "mlp": 6.0 * layers * 3 * d_model * d_ff,
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Layers of each kind in a configuration of the ``phi4flash`` family
    (``models/phi4flash.py::layer_kind`` on the published indices)."""
    n = config["published"]["num_hidden_layers"]
    half = n // 2
    ids = config["layer_ids"]
    return {
        "n_mamba": sum(i % 2 == 0 and i <= half for i in ids),
        "n_gmu": sum(i % 2 == 0 and i > half for i in ids),
        "n_swa": sum(i % 2 == 1 and i < half for i in ids),
        "n_full": sum(i == half + 1 for i in ids),
        "n_cross": sum(i % 2 == 1 and i > half + 1 for i in ids),
    }


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``phi4flash`` family."""
    ssm = config["mamba"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        d_inner=ssm["expand"] * config["hidden_size"],
        state=ssm["d_state"], dt_rank=ssm["dt_rank"],
        window=config["sliding_window"], vocab=config["vocab_size"],
        seq_len=config["job"]["seq_len"], **layer_counts(config),
    )


# the flash kernels' bytes are the two-width count of the latent-attention
# file: one definition
flash_bytes_per_call = mla_flops.flash_bytes_per_call
