"""Operations and bytes of the latent-attention family
(``torchft_tpu/models/joyai.py``): part of the yardstick, beside
``flops.py`` (which assumes one head width for q, k and v and one kind of
layer) and ``moe_flops.py``.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus causal attention. Recomputation
(``jax.checkpoint`` of the blocks, the chunked cross entropy's second
head matmul, the score tile the backward kernels build again) is hardware
work the model does not require and is NOT credited. What a token
multiplies:

- every MLA sublayer (dense, expert and MTP layers): ``d·r_q + r_q·H·Dqk
  + d·(r_kv + D_rope) + r_kv·H·(D_nope + Dv) + H·Dv·d``;
- causal attention, a layer: scores ``Dqk`` and P·V ``Dv`` wide over the
  ``(S + 1) / 2`` keys a position sees on average: ``2·H·(Dqk + Dv)·(S +
  1) / 2`` forward, three times that forward and backward;
- the dense layers' SwiGLU ``3·d·d_ff``; an expert layer's router
  ``d·E_routed``, shared expert ``3·d·f`` and routed experts HELD HERE:
  ``top_k · E_held / E_routed`` of them in expectation (the count a step
  really computes is data; a balanced router's is this);
- the MTP module's ``2d·d`` projection, and the head once per cross
  entropy. The token table is gathered, not multiplied.

The flash kernels (``ops/flash.py``), a call: what causal attention needs
of each is ``BH · S(S + 1)/2`` query-key pairs times ``2·(Dqk + Dv)``
operations — the forward's scores and P·V, ``flash_dq``'s dP and dQ,
``flash_dkv``'s dV and dK. Tiles above the diagonal, the masked half of a
diagonal tile, padding, the score tile each backward kernel recomputes
and the dP that ``flash_dkv`` computes a second time count nothing.
"""

from __future__ import annotations

from typing import Any, Dict


def mla_params(d_model: int, n_heads: int, q_rank: int, kv_rank: int,
               nope: int, rope: int, v_dim: int) -> int:
    """Matmul weights of one MLA sublayer (26.34 M as published)."""
    return (d_model * q_rank + q_rank * n_heads * (nope + rope)
            + d_model * (kv_rank + rope) + kv_rank * n_heads * (nope + v_dim)
            + n_heads * v_dim * d_model)


def train_flops_per_token(*, d_model: int, n_heads: int, q_rank: int,
                          kv_rank: int, nope: int, rope: int, v_dim: int,
                          d_ff: int, d_expert: int, n_routed: int,
                          n_held: int, top_k: int, n_dense: int,
                          n_expert_layers: int, n_mtp: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (3.40 GFLOP at the cell's cut and S 8192). ``n_expert_layers``
    leaves the MTP module's out; ``n_mtp`` adds it."""
    layers = n_dense + n_expert_layers + n_mtp
    experts = n_expert_layers + n_mtp
    parts = {
        "mla_proj": 6.0 * layers * mla_params(
            d_model, n_heads, q_rank, kv_rank, nope, rope, v_dim),
        "mla_core": 3.0 * layers * n_heads * (nope + rope + v_dim)
        * (seq_len + 1),
        "dense_mlp": 6.0 * n_dense * 3 * d_model * d_ff,
        "router": 6.0 * experts * d_model * n_routed,
        "shared": 6.0 * experts * 3 * d_model * d_expert,
        "routed_held": 6.0 * experts * (top_k * n_held / n_routed)
        * 3 * d_model * d_expert,
        "mtp_proj": 6.0 * n_mtp * 2 * d_model * d_model,
        "heads": 6.0 * (1 + n_mtp) * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def flash_flops_per_call(batch_heads: int, seq_len: int, d_qk: int,
                         d_v: int) -> float:
    """What causal attention needs of ONE call of any of the three
    kernels (see the module's docstring)."""
    return batch_heads * (seq_len * (seq_len + 1) / 2) * 2.0 * (d_qk + d_v)


def flash_bytes_per_call(kernel: str, batch_heads: int, seq_len: int,
                         d_qk: int, d_v: int, itemsize: int = 2) -> float:
    """The least one call moves: every operand read once and every result
    written once in the compute type, the statistics in float32.
    ``flash_fwd``: q, k, v -> o, lse. ``flash_dq``: q, k, v, dO, lse,
    delta -> dq. ``flash_dkv``: the same in, dk and dv out."""
    rows = batch_heads * seq_len
    qkv = rows * (2 * d_qk + d_v) * itemsize
    if kernel == "flash_fwd":
        return float(qkv + rows * d_v * itemsize + rows * 4)
    out = {"flash_dq": d_qk, "flash_dkv": d_qk + d_v}[kernel]
    return float(qkv + rows * d_v * itemsize + 2 * rows * 4
                 + rows * out * itemsize)


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``joyai`` family."""
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_routed=config["published"]["n_routed_experts"],
        n_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        n_dense=config["first_k_dense_replace"],
        n_expert_layers=config["num_hidden_layers"]
        - config["first_k_dense_replace"],
        n_mtp=config["num_nextn_predict_layers"], vocab=config["vocab_size"],
        seq_len=config["job"]["seq_len"],
    )
