"""Operations and bytes of the Keye family
(``torchft_tpu/models/keye.py``, ``torchft_tpu/ops/dsa.py``): part of the
yardstick, beside ``flops.py``, ``moe_flops.py`` and the other families'.

What is counted is the PUBLISHED work, whatever a kernel computes to get
it: attention and the indexer's target over the CHOSEN pairs (``Σ_t min(t
+ 1, topk)`` a sequence and head), the index scores over EVERY causal
pair (``S(S + 1)/2``: to choose, every pair has to be scored). The
kernels here compute every causal tile of the core (four times the
chosen pairs at 16 384 positions); a later kernel that skips dead tiles
is read against the same work, and no share can pass 100 %.
Recomputation (``jax.checkpoint`` of the layers, the tiles the backward
kernels build again, the scores ``dsa_kl`` builds again) is hardware
work the model does not require and is NOT credited.

``train_flops_per_token``, a layer, forward and backward:

- the attention projections ``6 · d · D · (2H + 2KV)``;
- the indexer's projections ``4 · d · (HI·DI + DI + HI)``: its input is
  detached, so the backward holds the weights' products and none onto
  the stream;
- the index scores ``2 · HI · DI`` a causal pair forward, and backward
  (``L_I``'s gradient lives on the chosen pairs alone) ``4 · HI · DI`` a
  chosen pair;
- the core ``3 · 2 · H · 2D`` a chosen pair;
- the indexer's target ``p̄``: a second ``q·k``, ``2 · H · D`` a chosen
  pair, forward only (it is detached);
- the router ``6 · d · E_routed`` and the routed experts HELD HERE,
  ``top_k · E_held / E_routed`` of them in expectation, ``3 · d · f``
  each; the head once over the rows held.

Of one layer-step of a kernel of ``ops/dsa.py`` (:func:`kernel_flops`,
:func:`kernel_bytes`): ``dsa_select`` the scores of every causal pair;
``dsa_fwd`` / ``dsa_dq`` / ``dsa_dkv`` the chosen pairs × ``2 (Dqk +
Dv)`` each, as ``smallthinker_flops.flash_flops_per_call``; ``dsa_kl``
(both its calls of a step together) ``p̄`` and the scores' backward over
the chosen pairs. Bytes: every operand read and every result written
once, the packed sets among them.
"""

from __future__ import annotations

from typing import Any, Dict

KERNELS = ("dsa_select", "dsa_fwd", "dsa_dq", "dsa_dkv", "dsa_kl")


def causal_pairs(seq_len: int) -> float:
    return seq_len * (seq_len + 1) / 2.0


def chosen_pairs(seq_len: int, topk: int) -> float:
    """``Σ_t min(t + 1, topk)``."""
    k = min(topk, seq_len)
    return seq_len * k - k * (k - 1) / 2.0


def train_flops_per_token(*, d_model: int, n_heads: int, n_kv_heads: int,
                          head_dim: int, index_heads: int, index_dim: int,
                          topk: int, d_expert: int, n_routed: int,
                          n_held: int, top_k: int, n_layers: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum."""
    chosen = chosen_pairs(seq_len, topk) / seq_len
    causal = causal_pairs(seq_len) / seq_len
    hd = index_heads * index_dim
    parts = {
        "gqa_proj": 6.0 * n_layers * d_model * head_dim * (
            2 * n_heads + 2 * n_kv_heads),
        "index_proj": 4.0 * n_layers * d_model * (
            hd + index_dim + index_heads),
        "index_scores": n_layers * (2.0 * hd * causal + 4.0 * hd * chosen),
        "dsa_core": 3.0 * n_layers * 2.0 * n_heads * 2 * head_dim * chosen,
        "index_target": n_layers * 2.0 * n_heads * head_dim * chosen,
        "router": 6.0 * n_layers * d_model * n_routed,
        "routed_held": 6.0 * n_layers * (top_k * n_held / n_routed)
        * 3 * d_model * d_expert,
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def kernel_flops(kernel: str, *, batch: int, seq_len: int, n_heads: int,
                 head_dim: int, index_heads: int, index_dim: int,
                 topk: int, **_: Any) -> float:
    """What the model needs of ``kernel`` in ONE layer of one step."""
    chosen = batch * chosen_pairs(seq_len, topk)
    hd = index_heads * index_dim
    if kernel == "dsa_select":
        return batch * causal_pairs(seq_len) * 2.0 * hd
    if kernel in ("dsa_fwd", "dsa_dq", "dsa_dkv"):
        return chosen * n_heads * 2.0 * 2 * head_dim
    if kernel == "dsa_kl":
        return chosen * (2.0 * n_heads * head_dim + 4.0 * hd)
    raise KeyError(kernel)


def kernel_bytes(kernel: str, *, batch: int, seq_len: int, n_heads: int,
                 n_kv_heads: int, head_dim: int, index_heads: int,
                 index_dim: int, itemsize: int = 2, **_: Any) -> float:
    """The least ``kernel`` moves in one layer of one step."""
    rows = batch * seq_len
    q, kv = rows * n_heads * head_dim * itemsize, (
        rows * n_kv_heads * head_dim * itemsize)
    index = rows * (index_heads * index_dim + index_dim) * itemsize + (
        rows * index_heads * 4)
    sets, stat = rows * seq_len / 8.0, rows * 4.0
    return float({
        "dsa_select": index + sets + stat,
        "dsa_fwd": 2 * q + 2 * kv + sets + n_heads * stat,
        "dsa_dq": 3 * q + 2 * kv + sets + 2 * n_heads * stat,
        "dsa_dkv": 2 * q + 4 * kv + sets + 2 * n_heads * stat,
        # the loss's call reads; the gradient's reads again and writes
        "dsa_kl": 2 * (q + kv / 2 + index + sets + (n_heads + 2) * stat)
        + index,
    }[kernel])


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``keye`` family."""
    sa = config["sa_config"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], topk=sa["topk"],
        d_expert=config["moe_intermediate_size"],
        n_routed=config["published"]["num_experts"],
        n_held=config["num_experts"], top_k=config["num_experts_per_tok"],
        n_layers=config["num_hidden_layers"], vocab=config["vocab_size"],
        seq_len=config["job"]["seq_len"],
    )
