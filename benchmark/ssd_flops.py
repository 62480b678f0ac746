"""Operations and bytes of the Nemotron-H family
(``torchft_tpu/models/nemotron_h.py``): part of the yardstick, beside
``flops.py``, ``moe_flops.py`` and ``mla_flops.py``.

``train_flops_per_token``: 6 operations (forward and backward) for every
weight a token is multiplied by, plus causal attention and the scan.
Recomputation (``jax.checkpoint`` of the layers, the chunked cross
entropy's second head matmul, the tiles the backward kernels build
again) is hardware work the model does not require and is NOT credited.
What a token multiplies, by kind of layer:

- a Mamba-2 mixer: ``d·(2I + 2GN + H) + I·d`` and the scan (below);
- an attention mixer: ``d·HD + 2·d·KV·D + HD·d`` and causal attention,
  scores and P·V ``D`` wide over the ``(S + 1) / 2`` keys a position sees
  on average: ``2·H·2D·(S + 1) / 2`` forward, three times that forward
  and backward;
- an expert mixer: the router ``d·E_routed``, the shared expert
  ``2·d·f_s`` and the routed experts HELD HERE: ``top_k · E_held /
  E_routed`` of them in expectation, ``2·d·f`` each (two matrices, no
  gate);
- the head once. The token table is gathered, not multiplied.

The scan (``ops/ssd.py``), counted in the chunked form at the chunk the
configuration publishes (``chunk_size``: the released kernel's tile,
whatever the program's own), causal pairs only. Forward, a token: the
group's ``C·Bᵀ`` and each head's ``(C Bᵀ ∘ L)·u`` over the ``(Q + 1) / 2``
positions of its chunk at or before it — ``(Q + 1)·(G·N + H·P)`` — and
the chunk's state built and read, ``2·H·P·N`` each. Backward: ``Mᵀ·dy``,
``dy·uᵀ``, ``W·B`` and ``Wᵀ·C`` — twice the forward's pairs — and four
products with a state (``dy·Sᵀ``, ``u·dSᵀ``, ``Cᵀ·dy``, ``B·dS``). The
mask's dead half, padding, the tile each backward step builds again and
the decays' exponentials count nothing.

Bytes of one scan call, the least it moves: every operand read once and
every result written once in the compute type, ``Δ`` in float32.
``ssd_fwd``: ``x, B, C, Δ`` in, ``y`` out. ``ssd_bwd``: ``x, dy, B, C, Δ``
in, ``dx, dB, dC, dΔ`` out. The chunk-boundary states the forward writes
for the backward, and the backward reads, count nothing.
"""

from __future__ import annotations

from typing import Any, Dict


def ssd_flops_per_token(kernel: str, *, heads: int, head_dim: int,
                        groups: int, state: int, chunk: int) -> float:
    """What the scan needs of ONE token in ``ssd_fwd`` or ``ssd_bwd``
    (2.76 and 5.51 MFLOP at 64 x 64, 8 groups, state 128, chunk 128)."""
    pairs = (chunk + 1) * (groups * state + heads * head_dim)
    with_state = 2.0 * heads * head_dim * state
    return {"ssd_fwd": pairs + 2 * with_state,
            "ssd_bwd": 2 * pairs + 4 * with_state}[kernel]


def ssd_bytes_per_token(kernel: str, *, heads: int, head_dim: int,
                        groups: int, state: int, itemsize: int = 2) -> float:
    """The least one call moves for ONE token (20.7 and 33.3 KB)."""
    wide = heads * head_dim * itemsize           # x, y, dy, dx
    narrow = 2 * groups * state * itemsize       # B and C (dB and dC)
    delta = heads * 4
    return float({"ssd_fwd": 2 * wide + narrow + delta,
                  "ssd_bwd": 3 * wide + 2 * narrow + 2 * delta}[kernel])


def train_flops_per_token(*, d_model: int, ssm_heads: int, ssm_head_dim: int,
                          ssm_groups: int, ssm_state: int, chunk: int,
                          n_heads: int, n_kv_heads: int, head_dim: int,
                          d_expert: int, d_shared: int, n_routed: int,
                          n_held: int, top_k: int, n_mamba: int, n_attn: int,
                          n_expert_layers: int, vocab: int,
                          seq_len: int) -> Dict[str, float]:
    """Forward and backward operations of one token by part; ``total`` is
    their sum (2.17 GFLOP at the cell's cut and S 8192)."""
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
        "router": 6.0 * n_expert_layers * d_model * n_routed,
        "shared": 6.0 * n_expert_layers * 2 * d_model * d_shared,
        "routed_held": 6.0 * n_expert_layers * (top_k * n_held / n_routed)
        * 2 * d_model * d_expert,
        "head": 6.0 * d_model * vocab,
    }
    parts["total"] = sum(parts.values())
    return parts


def config_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The arguments of :func:`train_flops_per_token` from a
    configuration file of the ``nemotron_h`` family."""
    pattern = config["hybrid_override_pattern"]
    return dict(
        d_model=config["hidden_size"], ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"], ssm_groups=config["n_groups"],
        ssm_state=config["ssm_state_size"], chunk=config["chunk_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        n_routed=config["published"]["n_routed_experts"],
        n_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"], n_mamba=pattern.count("M"),
        n_attn=pattern.count("*"), n_expert_layers=pattern.count("E"),
        vocab=config["vocab_size"], seq_len=config["job"]["seq_len"],
    )
