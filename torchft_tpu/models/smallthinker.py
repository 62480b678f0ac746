"""SmallThinker decoder (``model_name`` ``smallthinker_21b_instruct``; the
benchmark's configuration is PowerInfer/SmallThinker-21BA3B-Instruct,
arXiv:2507.20984) as ONE CHIP'S SHARE of an expert-parallel layer. Every
layer is grouped-query attention and a sparse ReGLU expert MLP, each
behind its own RMSNorm, and **the router reads the stream BEFORE
attention**:

    n1 = RMSNorm(x; g1)
    z  = n1·W_r                 float32, over all routed experts
    E  = top-k of (z + b)       b the balance bias: selects, never weights
    w  = softmax(z[E])          = the softmax over all, renormalised
    h  = x + attention(n1)·W_o
    n2 = RMSNorm(h; g2)
    y  = h + Σ_{e in E and held} w_e · (relu(n2·W_g^e) ⊙ n2·W_u^e)·W_d^e

so the experts a token takes are known before its attention runs (the
published system fetches them from storage meanwhile; nothing here does).
TWO lists of the config say what a layer's attention is, independently:
``sliding_window_layout[l]`` — causal over all earlier keys, or over the
last ``window`` keys, itself among them — and ``rope_layout[l]`` —
RoPE ``rope_theta`` over the whole head (``rotate_half``:
``models/llama.py::_rope``) on q and k, or none (NoPE). As published the
two lists are one, ``[0, 1, 1, 1] × 13``: full unrotated layers between
threes of windowed rotated ones. ``n_heads`` × ``head_dim`` is NOT
``d_model`` (28 × 128 = 3584 on 2560); each of the ``n_kv_heads``
key/value heads serves ``n_heads / n_kv_heads`` consecutive query heads
(k and v reach the flash call at their own head count and its index maps
read head ``i // group`` for query head ``i``; nothing is copied: ``ops/
flash.py``). A final RMSNorm,
an untied ``lm_head``; no bias anywhere, no shared expert, no dense layer.

The layer is told which routed experts it holds (``first_expert``,
``n_experts_held``), routes over all ``n_routed_experts`` and computes
its own experts' part (``ops/moe.py::moe_mlp``, ``activation="reglu"``).
The balance bias ``b`` and the way its loads reach
``optim.with_balance_bias`` in the gradient tree are
``models/common.py``'s, and so is the sublayer
(``common.routed_sublayer(route_on=n1, score="softmax")``).

Conventions of ``models/lfm2.py``: float32 parameters, bf16 compute,
float32 norms / router, an explicit parameter tree with stable paths
``layers_<i>/{norm_1,norm_2}``, ``layers_<i>/attn/...``,
``layers_<i>/moe/...``, per-layer ``checkpoint_layer`` behind ``remat``,
and the step programs of ``transformer.make_train_step`` /
``make_grad_step`` (``loss=smallthinker.loss_fn``).

``checkpoint_layer`` (``models/common.py``) is ``jax.checkpoint`` that
keeps what a layer's router decided — the experts, their weights, the
chosen scores, the loads —, so the backward pass does not run the router
again (``common.routed_sublayer`` says why the weights are among them).

Device-trace scopes: ``embed``; ``attn`` with ``gqa_proj`` (the norm, q /
k / v, RoPE where the list says, the repeat, ``W_o``) and ``gqa_core``
(Nemotron-H's and LFM2's names), and inside ``gqa_core`` ``swa_core`` or
``full_core`` around the one flash call (Phi-4-mini-flash's names for the
two kinds of call); ``mlp`` with ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``; ``lm_head_xent``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.common import (
    BALANCE_BIAS,
    checkpoint_layer,
    embed,
    is_balance_bias,
    rms_norm,
    routed_sublayer,
    routing_record,
    share_loss_terms,
)
from torchft_tpu.models.llama import _rope
from torchft_tpu.models.transformer import ce_from_hidden
from torchft_tpu.ops.attention import causal_attention

__all__ = ["SmallThinkerConfig", "SMALLTHINKER_CONFIGS", "BALANCE_BIAS",
           "is_balance_bias", "init_params", "forward_hidden", "loss_terms",
           "loss_fn"]

_PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Defaults: PowerInfer/SmallThinker-21BA3B-Instruct as published,
    every expert held."""
    vocab_size: int = 151936
    d_model: int = 2560
    windowed: Tuple[int, ...] = _PERIOD * 13   # sliding_window_layout
    rotated: Tuple[int, ...] = _PERIOD * 13    # rope_layout
    init_depth: int = 52          # the PUBLISHED depth: residual outputs
                                  # are initialised / sqrt(init_depth)
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    window: int = 4096            # keys a windowed position sees, with itself
    rope_theta: float = 1.5e6
    d_expert: int = 768           # one routed expert's width
    n_routed_experts: int = 64    # the router's width
    first_expert: int = 0         # the share held here:
    n_experts_held: int = 64      # experts first .. first + held
    top_k: int = 6
    routed_scale: float = 1.0
    rms_eps: float = 1e-6
    init_std: float = 0.02
    embed_std: Optional[float] = None   # the table's; None: init_std
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0

    def __post_init__(self) -> None:
        assert self.windowed and len(self.windowed) == len(self.rotated)
        assert set(self.windowed) | set(self.rotated) <= {0, 1}
        assert self.n_heads % self.n_kv_heads == 0 and self.window >= 1
        assert 1 <= self.top_k <= self.n_routed_experts
        assert 0 <= self.first_expert
        assert 1 <= self.n_experts_held
        assert self.first_expert + self.n_experts_held <= self.n_routed_experts

    @property
    def n_layers(self) -> int:
        return len(self.windowed)


SMALLTHINKER_CONFIGS: Dict[str, SmallThinkerConfig] = {
    # the tests' size: two periods, so both kinds of layer repeat; 7
    # query heads a key/value head; a window shorter than the sequence
    # and no multiple of a tile edge; a share of 4 of 8 experts
    "smallthinker_tiny": SmallThinkerConfig(
        vocab_size=512, d_model=48, windowed=_PERIOD * 2,
        rotated=_PERIOD * 2, init_depth=8, n_heads=14, n_kv_heads=2,
        head_dim=8, window=20, d_expert=24, n_routed_experts=8,
        first_expert=0, n_experts_held=4, top_k=2, init_std=0.125,
    ),
}


def init_params(cfg: SmallThinkerConfig, key) -> Dict:
    """Matrices normal with ``init_std``, those onto the residual stream
    (``o_proj``, ``down_proj``) / sqrt(``init_depth``); the table normal
    with ``embed_std`` where a configuration gives it one of its own (the
    stream's scale beside what attention adds to it: a common component
    of the stream passes an attention layer with a gain of 0.17 /
    ``embed_std`` at the published widths, PERF.md section 4); norm
    weights one; the balance bias zero; the table and the head two
    leaves."""
    pd, d = cfg.param_dtype, cfg.d_model
    keys = jax.random.split(key, cfg.n_layers + 2)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def out(k, *shape):
        return normal(k, *shape) / math.sqrt(cfg.init_depth)

    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    held, f = cfg.n_experts_held, cfg.d_expert
    params: Dict[str, Any] = {
        "wte": {"embedding": jax.random.normal(
            keys[0], (cfg.vocab_size, d), pd) * (
                cfg.init_std if cfg.embed_std is None else cfg.embed_std)},
        "ln_f": {"scale": jnp.ones((d,), pd)},
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 8)
        params[f"layers_{i}"] = {
            "norm_1": {"scale": jnp.ones((d,), pd)},
            "norm_2": {"scale": jnp.ones((d,), pd)},
            "attn": {
                "q_proj": {"kernel": normal(k[0], d, q)},
                "k_proj": {"kernel": normal(k[1], d, kv)},
                "v_proj": {"kernel": normal(k[2], d, kv)},
                "o_proj": {"kernel": out(k[3], q, d)},
            },
            "moe": {
                "gate_proj": {"kernel": normal(k[4], held, d, f)},
                "up_proj": {"kernel": normal(k[5], held, d, f)},
                "down_proj": {"kernel": out(k[6], held, f, d)},
                "router": {"kernel": normal(k[7], d, cfg.n_routed_experts)},
                BALANCE_BIAS: jnp.zeros((cfg.n_routed_experts,), pd),
            },
        }
    return params


def repeat_kv(kv, n_heads: int):
    """``[B, S, KV, D]`` as it is: the place where a query head's
    key/value head is decided. That is the kernels' ``i // (n_heads /
    KV)`` now (``ops/flash.py``; off the TPU ``reference_attention``'s
    grouped einsums), so nothing is copied here. A seam by name: a fault
    (``benchmark/tests/smallthinker_faults.py::kv_heads_modulo``) puts an
    ``n_heads``-wide copy in another order in this function's place, and
    the call then runs at equal head counts on that copy."""
    return kv


@jax.named_scope("attn")
def _attn_mixer(cfg: SmallThinkerConfig, windowed: bool, rotated: bool,
                layer: Dict, x, *, attn_fn) -> Tuple[Any, Any]:
    """``(x + attention(n1)·W_o, n1 in float32)``: the second is what the
    layer's router reads."""
    a, dt = layer["attn"], cfg.dtype
    B, S, _ = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("gqa_proj"):
        n32 = rms_norm(x.astype(jnp.float32), layer["norm_1"]["scale"],
                       cfg.rms_eps)
        n = n32.astype(dt)
        q = (n @ a["q_proj"]["kernel"].astype(dt)).reshape(B, S, H, D)
        k = (n @ a["k_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
        v = (n @ a["v_proj"]["kernel"].astype(dt)).reshape(B, S, KV, D)
        if rotated:
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        k, v = repeat_kv(k, H), repeat_kv(v, H)
    with jax.named_scope("gqa_core"):
        with jax.named_scope("swa_core" if windowed else "full_core"):
            o = attn_fn(q, k, v, window=cfg.window if windowed else None)
    with jax.named_scope("gqa_proj"):
        return (x + o.reshape(B, S, H * D) @ a["o_proj"]["kernel"].astype(dt),
                n32)


def _moe_mlp(cfg: SmallThinkerConfig, layer: Dict, x, n1) -> Tuple[Any, Dict]:
    """``common.routed_sublayer`` on this model's terms: the router
    scores ``n1`` (the layer's INPUT norm), the weights are the softmax
    over the chosen logits, the experts ReGLU on ``RMSNorm(x; g2)``."""
    return routed_sublayer(cfg, x, layer["norm_2"]["scale"], layer["moe"],
                           route_on=n1, score="softmax", activation="reglu")


def _layer(cfg: SmallThinkerConfig, windowed: bool, rotated: bool,
           layer: Dict, x, *, attn_fn) -> Tuple[Any, Dict]:
    """One layer: ``(x, record)``."""
    h, n1 = _attn_mixer(cfg, windowed, rotated, layer, x, attn_fn=attn_fn)
    return _moe_mlp(cfg, layer, h, n1)


def forward_hidden(cfg: SmallThinkerConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None) -> Tuple[Any, Dict]:
    """tokens [B, S] -> (final-norm hidden states [B, S, d], record).
    ``attn_fn(q, k, v, window=None)`` is the local causal attention
    (``ops/attention.py::causal_attention`` by default: the flash kernels
    on a TPU). The record holds ``experts`` [L, N, top_k] and ``loads``
    [L, routed] of every layer in order, and ``carrier`` (zero; see
    ``common.loads_as_gradient``)."""
    if attn_fn is None:
        attn_fn = causal_attention
    x = embed(cfg, params, tokens)
    records = []
    for i, (windowed, rotated) in enumerate(zip(cfg.windowed, cfg.rotated)):
        run = functools.partial(_layer, cfg, bool(windowed), bool(rotated),
                                attn_fn=attn_fn)
        if cfg.remat:
            run = checkpoint_layer(run)
        x, rec = run(params[f"layers_{i}"], x)
        records.append(rec)
    out = routing_record(records)
    return rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps), out


def loss_terms(cfg: SmallThinkerConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``common.share_loss_terms`` of this model's forward pass, the cross
    entropy through the untied head."""
    h, rec = forward_hidden(cfg, params, tokens, attn_fn)
    return share_loss_terms(
        cfg, h, rec, ce_from_hidden(h, params["lm_head"]["kernel"], targets,
                                    cfg.xent_chunks))


def loss_fn(cfg: SmallThinkerConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of
    ``transformer.loss_fn``, for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
