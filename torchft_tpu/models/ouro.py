"""Ouro looped language model (Hugging Face ``model_type`` ``ouro``; the
benchmark's configuration is ByteDance/Ouro-2.6B; Zhu et al., "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): a dense
decoder whose WHOLE STACK is applied ``ut_steps`` times to the stream with
ONE set of weights, a head and a cross entropy after every pass, and an
exit gate whose distribution over the passes weighs the losses.

``T`` = ``ut_steps``, ``L`` layers, ``d`` = ``d_model``; no bias anywhere
but the gate's, no dropout, no multiplier on the embedding or the logits.

    x⁽⁰⁾ = E[tokens]
    pass t = 1 … T:  h ← x⁽ᵗ⁻¹⁾;  for l = 0 … L−1, THE SAME LEAVES EVERY t:
        h ← h + RMSNorm(Attn_l(RMSNorm(h; w¹_l)); w²_l)
        h ← h + RMSNorm(SwiGLU_l(RMSNorm(h; w³_l)); w⁴_l)
      x⁽ᵗ⁾ = RMSNorm(h; w_f)

Four norms a layer (each branch is normed going in and coming out: the
sandwich; ``common.dense_sublayer`` has no norm on the way out and is
eight other models', so the sublayers live here) and the ONE final norm
after every pass: the normed stream is what pass ``t + 1``, the head and
the gate read. ``Attn``: ``llama``'s — 16 heads of 128, as many key/value
heads, q and k turned by ``common.rotary`` over the whole head at
``θ^(−i/64)`` (``llama._rope``), causal softmax at ``128^−½`` through the
flash kernels. After every pass

    ℓ_t,i = lse(x⁽ᵗ⁾_i W_head) − (x⁽ᵗ⁾_i W_head)[target_i]
    λ_t,i = σ(x⁽ᵗ⁾_i · w_g + b_g)            for t < T
    S_0 = 1,  S_t = S_{t−1}(1 − λ_t);   p_t = λ_t S_{t−1} (t < T),
    p_T = S_{T−1}                        (the remainder: Σ_t p_t,i = 1)
    loss = mean_i [ Σ_t p_t,i ℓ_t,i + β Σ_t p_t,i log p_t,i ]

the expected task loss under the exit distribution less ``β`` times its
entropy (the paper's Stage-I objective under a uniform prior). ``p`` is
differentiated: it is the gate's only gradient, and reaches it through
``∂loss/∂p_t,i = (ℓ_t,i + β(1 + log p_t,i)) / N``.

THE LOOP. The ``T`` passes are one ``lax.scan`` whose body is the stack
(``L`` layer-steps, each under ``common.checkpoint_layer`` behind
``remat``) and closes over the weights: no pass copies them, the scan's
backward adds a layer's ``T`` gradients into ONE f32 accumulator a leaf
(live from the last pass's backward to the first's: the whole stack's
parameters × 4 B, which no other family's fused step holds), and the
checkpoints are a layer-STEP's, ``T·L`` of ``[B, S, d]``. The scan hands
back the ``T`` normed streams stacked and the gate's logits; the head and
the mix read them outside it. (The scan unrolled — ``lax.scan``'s
``unroll`` — ran the cell's step no faster on the chip, 1.2315 s against
1.2288, and compiled 2.4 times as long, 97.7 s against 40.0: PERF.md
section 6, PR 73. It is not an option.)

THE HEAD is ONE call of ``ops/xent.py::weighted_cross_entropy`` over the
``T·N`` stacked rows with ``weights = p / N`` stacked with them: the sum
``Σ p ℓ / N`` and its three gradients in one sweep over ≤ 4 096-row
tiles, one f32 ``dW`` residual (``T`` calls would keep ``T``), the rows'
own ``ℓ`` back for the gate's gradient and the gauges.

Precision as every family here: float32 parameters, bf16 stream and
matmul operands with float32 accumulation; norms, softmax statistics,
``lse``, the gate's logit from its accumulator on, ``log σ``, the survival
sum, ``p``, ``log p`` and the mix in float32. The survival product is
taken in logs (``log p_t = log σ(g_t) + Σ_{s<t} log σ(−g_s)``), so ``p
log p`` is finite where a ``λ`` saturates. The stream between passes is
the final norm's output rounded once to bf16, as between layers.

THE GAUGES. Where the probability mass sits over the passes is this
model's routing. Three statistics of a step — ``EXIT_GAUGES``: the mean
exit pass ``mean_i Σ_t t·p_t,i``, the mean entropy of ``p_·,i`` (nats),
the last pass's mean ``ℓ_T`` (the full-depth model's plain loss) — ride
the gradient tree at the leaf ``exit_stats`` (``common.loads_as_gradient``:
adds 0 to the loss, its cotangent is the statistics) into the optimizer's
state (``optim.with_step_stats``), which ``OptimizerWrapper`` reads a
commit later without a wait. The leaf itself stays zero.

Parameter tree, stable paths: ``wte/embedding``, ``lm_head/kernel``,
``ln_f/scale``, ``exit_gate/{kernel [d, 1], bias [1]}``, ``exit_stats``
[3], ``layers_<i>/{attn_norm, attn_out_norm, mlp_norm,
mlp_out_norm}/scale``, ``layers_<i>/attn/{q,k,v,o}_proj/kernel``,
``layers_<i>/mlp/{gate,up,down}_proj/kernel``. The step programs are
``transformer.make_train_step`` / ``make_grad_step`` (``loss=
ouro.loss_fn``).

Device-trace scopes: ``embed``; ``ut_pass`` (one pass over the stack) and
inside it ``attn`` (``full_core`` around the flash call), ``mlp`` and
``exit_gate``, the pass's final norm under ``ut_pass`` alone;
``lm_head_xent``; ``exit_mix`` (the survival sum, the entropy, the
gauges).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from torchft_tpu.models.common import (
    checkpoint_layer,
    embed,
    loads_as_gradient,
    rms_norm,
    swiglu,
)
from torchft_tpu.models.llama import _rope
from torchft_tpu.models.transformer import _local_causal_attention
from torchft_tpu.ops.xent import weighted_cross_entropy

__all__ = ["OuroConfig", "OURO_CONFIGS", "EXIT_STATS", "EXIT_GAUGES",
           "is_exit_stats", "publish_exit_gauges", "init_params",
           "forward_hidden",
           "exit_distribution", "loss_terms", "loss_fn"]

# the key of the leaf whose place in the gradient tree carries a step's
# statistics, and the gauges they become, in the leaf's order
EXIT_STATS = "exit_stats"
EXIT_GAUGES = ("ut_exit_mean_pass", "ut_exit_entropy", "ut_last_pass_loss")


def is_exit_stats(path) -> bool:
    """Whether a ``jax.tree_util`` key path ends at :data:`EXIT_STATS`:
    the predicate ``optim.with_step_stats`` partitions the leaves by."""
    return getattr(path[-1], "key", None) == EXIT_STATS


def publish_exit_gauges(metrics, stats) -> None:
    """A step's three statistics onto a ``Metrics`` sink under
    :data:`EXIT_GAUGES`' names: what ``optim.with_step_stats`` is handed
    beside :func:`is_exit_stats`."""
    mean_pass, entropy, last_pass_loss = stats
    metrics.gauge("ut_exit_mean_pass", mean_pass)
    metrics.gauge("ut_exit_entropy", entropy)
    metrics.gauge("ut_last_pass_loss", last_pass_loss)


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Defaults: ByteDance/Ouro-2.6B as published."""
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 16             # and key/value heads
    head_dim: int = 128
    d_ff: int = 5632
    ut_steps: int = 4             # passes over the stack, one set of weights
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-6
    exit_entropy_weight: float = 0.05     # β
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    xent_chunks: int = 0          # tiles the head's sweep takes at least

    def __post_init__(self) -> None:
        assert self.ut_steps >= 1 and self.head_dim % 2 == 0


OURO_CONFIGS: Dict[str, OuroConfig] = {
    # the tests' size: two layers four times
    "ouro_tiny": OuroConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=96, init_std=0.125,
    ),
}


def init_params(cfg: OuroConfig, key) -> Dict:
    """Every matrix, the table and ``w_g`` normal with ``init_std``, every
    norm weight one, ``b_g`` and the statistics' leaf zero."""
    pd, d, hd = cfg.param_dtype, cfg.d_model, cfg.n_heads * cfg.head_dim
    keys = jax.random.split(key, cfg.n_layers + 3)

    def normal(k, *shape):
        return jax.random.normal(k, shape, pd) * cfg.init_std

    def ones():
        return {"scale": jnp.ones((d,), pd)}

    params: Dict[str, Any] = {
        "wte": {"embedding": normal(keys[0], cfg.vocab_size, d)},
        "ln_f": ones(),
        "lm_head": {"kernel": normal(keys[1], d, cfg.vocab_size)},
        "exit_gate": {"kernel": normal(keys[2], d, 1),
                      "bias": jnp.zeros((1,), pd)},
        EXIT_STATS: jnp.zeros((len(EXIT_GAUGES),), pd),
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[3 + i], 7)
        params[f"layers_{i}"] = {
            "attn_norm": ones(), "attn_out_norm": ones(),
            "mlp_norm": ones(), "mlp_out_norm": ones(),
            "attn": {"q_proj": {"kernel": normal(k[0], d, hd)},
                     "k_proj": {"kernel": normal(k[1], d, hd)},
                     "v_proj": {"kernel": normal(k[2], d, hd)},
                     "o_proj": {"kernel": normal(k[3], hd, d)}},
            "mlp": {"gate_proj": {"kernel": normal(k[4], d, cfg.d_ff)},
                    "up_proj": {"kernel": normal(k[5], d, cfg.d_ff)},
                    "down_proj": {"kernel": normal(k[6], cfg.d_ff, d)}},
        }
    return params


@jax.named_scope("attn")
def _attn_sublayer(cfg: OuroConfig, layer: Dict, h, *, attn_fn):
    a, dt, eps = layer["attn"], cfg.dtype, cfg.rms_eps
    B, S, _ = h.shape
    heads = (B, S, cfg.n_heads, cfg.head_dim)
    n = rms_norm(h, layer["attn_norm"]["scale"], eps)
    q = _rope((n @ a["q_proj"]["kernel"].astype(dt)).reshape(heads),
              cfg.rope_theta)
    k = _rope((n @ a["k_proj"]["kernel"].astype(dt)).reshape(heads),
              cfg.rope_theta)
    v = (n @ a["v_proj"]["kernel"].astype(dt)).reshape(heads)
    with jax.named_scope("full_core"):
        o = attn_fn(q, k, v)
    y = o.reshape(B, S, -1) @ a["o_proj"]["kernel"].astype(dt)
    return h + rms_norm(y, layer["attn_out_norm"]["scale"], eps)


@jax.named_scope("mlp")
def _mlp_sublayer(cfg: OuroConfig, layer: Dict, h):
    n = rms_norm(h, layer["mlp_norm"]["scale"], cfg.rms_eps)
    return h + rms_norm(swiglu(n, layer["mlp"], cfg.dtype),
                        layer["mlp_out_norm"]["scale"], cfg.rms_eps)


# ``_mlp_sublayer``, ``_layer``, ``_pass_end``, ``exit_distribution`` and
# ``_mix`` are looked up on this module at call time:
# ``benchmark/tests/ouro_faults.py`` puts its stand-ins in their places
def _layer(cfg: OuroConfig, layer: Dict, h, *, attn_fn):
    return _mlp_sublayer(cfg, layer, _attn_sublayer(cfg, layer, h,
                                                    attn_fn=attn_fn))


def _pass_end(cfg: OuroConfig, params: Dict, h):
    """``(x, (x, g))`` from the end of the stack: the final norm's output
    ``x`` is what the next pass is handed and what the head reads, and the
    gate's logit ``g = x · w_g + b_g`` [B, S] f32 is taken from it (of
    every pass: the published code computes ``λ_T`` too and reads it
    nowhere, and so does :func:`exit_distribution`)."""
    x = rms_norm(h, params["ln_f"]["scale"], cfg.rms_eps)
    with jax.named_scope("exit_gate"):
        gate = params["exit_gate"]
        g = jnp.dot(x, gate["kernel"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32)[..., 0]
        return x, (x, g + gate["bias"].astype(jnp.float32))


def forward_hidden(cfg: OuroConfig, params: Dict, tokens,
                   attn_fn: Optional[Callable] = None):
    """tokens [B, S] -> ``(streams, gate)``: the ``T`` normed streams
    ``x⁽¹⁾ … x⁽ᵀ⁾`` stacked [T, B, S, d] and the gate's logits [T, B, S]
    f32."""
    if attn_fn is None:
        attn_fn = _local_causal_attention

    @jax.named_scope("ut_pass")
    def one_pass(x, _):
        run = functools.partial(_layer, cfg, attn_fn=attn_fn)
        if cfg.remat:
            run = checkpoint_layer(run)
        for i in range(cfg.n_layers):
            x = run(params[f"layers_{i}"], x)
        return _pass_end(cfg, params, x)

    return jax.lax.scan(one_pass, embed(cfg, params, tokens), None,
                        length=cfg.ut_steps)[1]


def exit_distribution(gate):
    """``(p, log p)``, each [T, N] f32, from the gate's logits [T, N]
    (the last row is not read): ``p_t = λ_t S_{t−1}`` for ``t < T`` and
    the remainder ``p_T = S_{T−1}``, in logs."""
    gate = gate[:-1]
    log_exit = jax.nn.log_sigmoid(gate)                     # log λ_t
    log_stay = jnp.cumsum(jax.nn.log_sigmoid(-gate), axis=0)    # log S_t
    zero = jnp.zeros((1,) + gate.shape[1:], gate.dtype)
    log_p = jnp.concatenate([log_exit, zero]) + jnp.concatenate(
        [zero, log_stay])
    return jnp.exp(log_p), log_p


def _pass_losses(cfg: OuroConfig, params: Dict, streams, targets, weights):
    """``(Σ weights ℓ, ℓ [T, N])`` of the ``T`` stacked streams through the
    one head."""
    T = cfg.ut_steps
    total, nll = weighted_cross_entropy(
        streams.astype(jnp.float32).reshape(-1, cfg.d_model),
        params["lm_head"]["kernel"].astype(jnp.float32),
        jnp.tile(targets.reshape(-1), T), weights.reshape(-1),
        max(cfg.xent_chunks, 1))
    return total, nll.reshape(T, -1)


def _mix(cfg: OuroConfig, p, log_p, weighted, nll):
    """The objective from its parts, and the three statistics:
    ``weighted`` = ``Σ p ℓ / N``."""
    neg_entropy = jnp.mean(jnp.sum(p * log_p, axis=0))
    passes = jnp.arange(1, cfg.ut_steps + 1, dtype=jnp.float32)[:, None]
    stats = jnp.stack([jnp.mean(jnp.sum(passes * p, axis=0)), -neg_entropy,
                       jnp.mean(nll[-1])])
    return weighted + cfg.exit_entropy_weight * neg_entropy, stats


def loss_terms(cfg: OuroConfig, params, tokens, targets,
               attn_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """``loss`` (the objective), ``nll`` [T, N] (every pass's per-token
    cross entropy), ``p`` [T, N] (the exit distribution), ``stats`` (the
    three of :data:`EXIT_GAUGES`) and ``hidden``, the last pass's normed
    stream [B, S, d]."""
    streams, gate = forward_hidden(cfg, params, tokens, attn_fn)
    n = targets.size
    with jax.named_scope("exit_mix"):
        p, log_p = exit_distribution(gate.reshape(cfg.ut_steps, n))
    with jax.named_scope("lm_head_xent"):
        weighted, nll = _pass_losses(cfg, params, streams, targets, p / n)
    with jax.named_scope("exit_mix"):
        loss, stats = _mix(cfg, p, log_p, weighted, nll)
        loss = loss + loads_as_gradient(
            params[EXIT_STATS],
            jax.lax.stop_gradient(stats).astype(params[EXIT_STATS].dtype))
    return {"loss": loss, "nll": nll, "p": p, "stats": stats,
            "hidden": streams[-1]}


def loss_fn(cfg: OuroConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """The scalar training loss: the signature of ``transformer.loss_fn``,
    for the one step maker."""
    return loss_terms(cfg, params, tokens, targets, attn_fn)["loss"]
