"""Operations and bytes of the Qwen3-Next family
(``torchft_tpu/models/qwen3_next.py``): part of the yardstick, beside
``flops.py``, ``olmo_hybrid_flops.py``, ``smallthinker_flops.py``,
``laguna_flops.py`` and their siblings.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus the two mixers' own work.
Recomputation (``jax.checkpoint`` of the layers, the chunked cross
entropy's second head matmul, what the backward kernels build again, a
share's experts run forward twice) is hardware work the model does not
require and is NOT credited. What a token multiplies, by kind of layer —
a layer is one mixer AND one sparse sublayer:

- a linear-attention mixer: ``d·(2 H_k K + 2 H_v V)`` (q, k, v, z),
  ``2·d·H_v`` (the step and the decay), ``H_v V·d`` (the output); the
  delta rule itself as the RECURRENCE states it, whatever the chunk: a
  position of a STATE head (there are ``H_v``) decays the state
  (``K·V``), reads it twice (``Sᵀk``, ``Sᵀq``: ``2·2KV``) and writes a
  rank-one update (``2KV``): ``7·K·V`` forward, three times that forward
  and backward (``olmo_hybrid_flops``' convention);
- a full-attention mixer: ``d·2HD`` (q and its gate), ``2·d·KV·D`` (k,
  v), ``HD·d`` (the output) and causal attention over the ``(S + 1) / 2``
  keys a position sees on average, ``2·H·2D`` operations a key forward;
- the sparse sublayer: the router ``d·E_routed``, the routed experts
  HELD HERE (``top_k · E_held / E_routed`` of them in expectation,
  ``3·d·f`` each), the shared expert ``3·d·f_s`` and its gate ``d``;
- the head once over the rows held (untied; the gather is not
  multiplied).

**Of one ``gdn_scan`` call**, what the MODEL needs whatever the program
broadcasts: ``q`` and ``k`` at the ``H_k`` KEY heads (``K`` wide), ``v``
and ``o`` at the ``H_v`` value heads (``V`` wide) in the compute type,
``g`` and ``β`` (f32, one a state head) — ``gdn_fwd`` reads q, k, v, g, β
and writes o; ``gdn_bwd`` reads the same with ``dO`` and writes ``dq, dk``
(``H_k`` heads: the pair's sum), ``dv``, ``dg, dβ``. The program hands the
kernel q and k copied to ``H_v`` heads (``models/qwen3_next.py::
_value_groups``): those bytes are time and not work. At 16 | 32 heads of
128 a token needs 3.67 MFLOP and 24.8 KB forward: on a v5e **the bytes
bind** (30 ns against 19).

**Of one flash call** (``ops/flash.py`` at ``[rows·H, S, D]`` on
``[rows·KV, S, D]``): ``smallthinker_flops``' own functions, so that the
shares read beside ``full16k_*`` — the live pairs × ``2 (Dqk + Dv)``
operations a kernel and every operand and result once at the QUERY head
count (that convention's known undercount of ``dq`` / ``dkv``, PERF.md
section 7). At 256-wide heads the operations bind (a forward at [64, 8192]:
11.2 ms of operations against 0.8 ms of bytes at a v5e's peaks).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import smallthinker_flops

GDN_KERNELS = ("gdn_fwd", "gdn_bwd")
FLASH_KERNELS = smallthinker_flops.KERNELS
LINEAR, FULL = "linear_attention", "full_attention"

live_pairs = smallthinker_flops.live_pairs
flash_flops_per_call = smallthinker_flops.flash_flops_per_call
flash_bytes_per_call = smallthinker_flops.flash_bytes_per_call


def gdn_flops_per_token(kernel: str, *, n_value_heads: int, key_dim: int,
                        value_dim: int) -> float:
    """What the delta rule needs of ONE token in ``gdn_fwd`` or
    ``gdn_bwd`` (3.67 and 7.34 MFLOP at 32 state heads of 128 x 128)."""
    return float(n_value_heads * 7 * key_dim * value_dim
                 * {"gdn_fwd": 1, "gdn_bwd": 2}[kernel])


def gdn_bytes_per_token(kernel: str, *, n_key_heads: int, n_value_heads: int,
                        key_dim: int, value_dim: int,
                        itemsize: int = 2) -> float:
    """The least one call moves for ONE token, q and k at the KEY heads
    (24.8 and 41.5 KB at 16 | 32 heads of 128 in bf16)."""
    qkv = (n_key_heads * 2 * key_dim + n_value_heads * value_dim) * itemsize
    g_beta, out = 8 * n_value_heads, n_value_heads * value_dim * itemsize
    fwd = qkv + g_beta + out
    return float({"gdn_fwd": fwd, "gdn_bwd": fwd + qkv + g_beta}[kernel])


def linear_params(d_model: int, n_key_heads: int, n_value_heads: int,
                  key_dim: int, value_dim: int) -> int:
    """Matmul weights of one linear-attention mixer (33.69 M as
    published)."""
    hk, hv = n_key_heads * key_dim, n_value_heads * value_dim
    return d_model * (2 * hk + 2 * hv) + 2 * d_model * n_value_heads \
        + hv * d_model


def full_params(d_model: int, n_heads: int, n_kv_heads: int,
                head_dim: int) -> int:
    """Matmul weights of one full-attention mixer (27.26 M as
    published)."""
    return d_model * head_dim * (3 * n_heads + 2 * n_kv_heads)


def train_flops_per_token(*, d_model: int, n_key_heads: int,
                          n_value_heads: int, key_dim: int, value_dim: int,
                          n_heads: int, n_kv_heads: int, head_dim: int,
                          d_expert: int, d_shared: int, n_routed: int,
                          n_held: int, top_k: int, n_linear: int, n_full: int,
                          vocab: int, seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (1.385 GFLOP at the cell's cut and S 8192)."""
    layers = n_linear + n_full
    parts = {
        "gdn_proj": 6.0 * n_linear * linear_params(
            d_model, n_key_heads, n_value_heads, key_dim, value_dim),
        "gdn_core": 3.0 * n_linear * gdn_flops_per_token(
            "gdn_fwd", n_value_heads=n_value_heads, key_dim=key_dim,
            value_dim=value_dim),
        "gqa_proj": 6.0 * n_full * full_params(
            d_model, n_heads, n_kv_heads, head_dim),
        "full_core": 3.0 * n_full * 2.0 * n_heads * 2 * head_dim
        * live_pairs(seq_len) / seq_len,
        "router": 6.0 * layers * d_model * n_routed,
        "routed_held": 6.0 * layers * (top_k * n_held / n_routed)
        * 3 * d_model * d_expert,
        "shared": 6.0 * layers * (3 * d_model * d_shared + d_model),
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``qwen3_next`` family."""
    kinds = layer_types(config)
    return dict(
        d_model=config["hidden_size"],
        n_key_heads=config["linear_num_key_heads"],
        n_value_heads=config["linear_num_value_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        n_routed=config["share"]["router_width"],
        n_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        n_linear=kinds.count(LINEAR), n_full=kinds.count(FULL),
        vocab=config["vocab_size"], seq_len=config["job"]["seq_len"],
    )


def layer_types(config: Dict[str, Any]) -> list:
    """The kind of every layer held, as ``full_attention_interval`` says
    it: layer ``l`` is full attention where ``(l + 1) % interval == 0``."""
    interval = config["full_attention_interval"]
    return [FULL if (l + 1) % interval == 0 else LINEAR
            for l in range(config["num_hidden_layers"])]
