"""GPT-style decoder-only transformer — the framework's flagship model.

Pure-jax (explicit param pytree, no module framework): parameter paths are
stable strings, which the TP/FSDP sharding rules key on
(parallel/sharding.py tp_rules_gpt), and everything the train step touches
is visible in one place. Design choices are TPU-first:

- bf16 activations/matmuls (MXU-native), f32 params + optimizer state
- all shapes static; per-layer ``jax.checkpoint`` (remat) to trade HBM for
  FLOPs at long sequence lengths
- attention pluggable: local causal attention (fused by XLA) or ring
  attention over a ``seq`` mesh axis for long-context (parallel/ring.py)

The reference framework has no model zoo (its examples train torchvision
models); the BASELINE configs require a 125M/1B transformer family, defined
here via ``TransformerConfig`` presets.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TransformerConfig", "init_params", "forward", "loss_fn",
           "make_train_step", "count_params", "CONFIGS"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16   # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    attention: str = "local"    # "local" | "ring"
    seq_axis: str = "seq"       # mesh axis for ring attention
    # >0: loss_fn uses ops/xent.py's fused sweep, the logits cut into (at
    # least) this many tiles of rows instead of materializing [B, S, V]
    # (the logits tensor is the single largest HBM consumer at
    # small-d_model/large-vocab shapes). 0 = dense log_softmax.
    xent_chunks: int = 0

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


CONFIGS: Dict[str, TransformerConfig] = {
    "tiny": TransformerConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, d_ff=256,
        max_seq_len=128, remat=False,
    ),
    # remat off: at this size the full activation set fits one chip's HBM
    # with room to spare, and the recompute is then pure cost (the
    # benchmark's 111m cell runs without it too: PERF.md §4). 350m/1b keep
    # remat — 350m without it did not fit at bench.py's shape.
    "125m": TransformerConfig(
        vocab_size=32768, d_model=768, n_layers=12, n_heads=12, d_ff=3072,
        max_seq_len=1024, xent_chunks=8, remat=False,
    ),
    "350m": TransformerConfig(
        vocab_size=32768, d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
        max_seq_len=1024, xent_chunks=8,
    ),
    "1b": TransformerConfig(
        vocab_size=32768, d_model=2048, n_layers=24, n_heads=16, d_ff=8192,
        max_seq_len=2048, xent_chunks=8,
    ),
}


def init_params(cfg: TransformerConfig, key) -> Dict:
    """Initialize the parameter pytree. Path names (wte/wpe, layers_i/attn/
    {q,k,v,o}_proj, mlp/{up,down}_proj, ln_f) are load-bearing: the TP rules
    in parallel/sharding.py match on them."""
    keys = jax.random.split(key, cfg.n_layers + 3)
    pd = cfg.param_dtype
    d, h, f = cfg.d_model, cfg.n_heads, cfg.d_ff

    def dense(k, fan_in, fan_out):
        scale = 1.0 / np.sqrt(fan_in)
        return (jax.random.normal(k, (fan_in, fan_out), pd) * scale)

    params: Dict[str, Any] = {
        "wte": {"embedding": jax.random.normal(
            keys[0], (cfg.vocab_size, d), pd) * 0.02},
        "wpe": {"embedding": jax.random.normal(
            keys[1], (cfg.max_seq_len, d), pd) * 0.02},
        "ln_f": {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)},
        "lm_head": {"kernel": dense(keys[2], d, cfg.vocab_size)},
    }
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[3 + i], 6)
        params[f"layers_{i}"] = {
            "ln_1": {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)},
            "attn": {
                "q_proj": {"kernel": dense(lk[0], d, d)},
                "k_proj": {"kernel": dense(lk[1], d, d)},
                "v_proj": {"kernel": dense(lk[2], d, d)},
                "o_proj": {"kernel": dense(lk[3], d, d)},
            },
            "ln_2": {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)},
            "mlp": {
                "up_proj": {"kernel": dense(lk[4], d, f)},
                "down_proj": {"kernel": dense(lk[5], f, d)},
            },
        }
    return params


def count_params(params) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))


def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(
        x.dtype
    )


def _local_causal_attention(q, k, v):
    """[B,S,H,D] in, XLA-fused causal softmax attention (flash-pattern is
    handled by ops/attention.py's pallas path on real TPU)."""
    from torchft_tpu.ops.attention import causal_attention

    return causal_attention(q, k, v)


@jax.named_scope("attn")
def _attn_sublayer(cfg, layer: Dict, x, *, attn_fn):
    """ln_1 + multi-head causal attention + residual. ``cfg`` is duck-typed
    (needs dtype/n_heads/head_dim/d_model) so MoE and other families reuse
    the exact dense attention path. Scope ``attn`` on the device trace:
    projections and the flash call inside (docs/operations.md §8)."""
    dt = cfg.dtype
    h = _layer_norm(x, layer["ln_1"]["scale"], layer["ln_1"]["bias"])
    B, S, _ = h.shape
    q = (h @ layer["attn"]["q_proj"]["kernel"].astype(dt)).reshape(
        B, S, cfg.n_heads, cfg.head_dim
    )
    k = (h @ layer["attn"]["k_proj"]["kernel"].astype(dt)).reshape(
        B, S, cfg.n_heads, cfg.head_dim
    )
    v = (h @ layer["attn"]["v_proj"]["kernel"].astype(dt)).reshape(
        B, S, cfg.n_heads, cfg.head_dim
    )
    a = attn_fn(q, k, v).reshape(B, S, cfg.d_model)
    return x + a @ layer["attn"]["o_proj"]["kernel"].astype(dt)


def _block(cfg: TransformerConfig, layer: Dict, x, *, attn_fn):
    dt = cfg.dtype
    x = _attn_sublayer(cfg, layer, x, attn_fn=attn_fn)

    with jax.named_scope("mlp"):
        h = _layer_norm(x, layer["ln_2"]["scale"], layer["ln_2"]["bias"])
        h = h @ layer["mlp"]["up_proj"]["kernel"].astype(dt)
        h = jax.nn.gelu(h)
        x = x + h @ layer["mlp"]["down_proj"]["kernel"].astype(dt)
    return x


@jax.named_scope("embed")
def _embed(cfg, params: Dict, tokens):
    """Token + learned-position embeddings in the compute dtype. ``cfg`` is
    duck-typed (needs dtype) so other families share the preamble."""
    dt = cfg.dtype
    S = tokens.shape[1]
    x = params["wte"]["embedding"].astype(dt)[tokens]
    return x + params["wpe"]["embedding"].astype(dt)[jnp.arange(S)][None, :, :]


@jax.named_scope("lm_head_xent")
def ce_from_hidden(h, lm_head_kernel, targets, xent_chunks: int = 0):
    """Mean next-token cross entropy from final-norm hidden states.
    ``xent_chunks`` > 0 routes through ops/xent.py's fused sweep over row
    tiles (loss and gradients in one scan), so the [B, S, V] logits tensor
    is never materialized (exact up to fp reassociation); 0 = dense
    log_softmax. Assumes a replicated lm head —
    under TP (vocab-sharded head) use
    ops/xent.py make_vocab_parallel_cross_entropy instead."""
    if xent_chunks > 0:
        from torchft_tpu.ops.xent import hidden_cross_entropy

        return hidden_cross_entropy(h, lm_head_kernel, targets, xent_chunks)
    logits = h.astype(jnp.float32) @ lm_head_kernel.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def forward_hidden(
    cfg: TransformerConfig,
    params: Dict,
    tokens,
    attn_fn: Optional[Callable] = None,
) -> Any:
    """tokens [B,S] int32 -> final-norm hidden states [B,S,d_model]
    (pre-lm-head), so losses can fuse the vocab projection."""
    if attn_fn is None:
        attn_fn = _local_causal_attention
    x = _embed(cfg, params, tokens)

    block = functools.partial(_block, cfg, attn_fn=attn_fn)
    if cfg.remat:
        block = jax.checkpoint(block)
    for i in range(cfg.n_layers):
        x = block(params[f"layers_{i}"], x)

    return _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])


def forward(
    cfg: TransformerConfig,
    params: Dict,
    tokens,
    attn_fn: Optional[Callable] = None,
) -> Any:
    """tokens [B,S] int32 -> logits [B,S,vocab] (f32)."""
    x = forward_hidden(cfg, params, tokens, attn_fn)
    logits = x.astype(jnp.float32) @ params["lm_head"]["kernel"].astype(
        jnp.float32
    )
    return logits


def loss_fn(cfg: TransformerConfig, params, tokens, targets,
            attn_fn: Optional[Callable] = None):
    """Mean next-token cross entropy (see ce_from_hidden for the
    chunked-vs-dense and TP caveats; __graft_entry__.dryrun_multichip §1b
    shows the vocab-parallel TP loss)."""
    h = forward_hidden(cfg, params, tokens, attn_fn)
    return ce_from_hidden(
        h, params["lm_head"]["kernel"], targets, cfg.xent_chunks
    )


def make_train_step(cfg: Any, tx,
                    attn_fn: Optional[Callable] = None,
                    donate: bool = True, loss: Callable = loss_fn):
    """Jitted (params, opt_state, tokens, targets) -> (params, opt_state,
    loss). The replica dimension does not exist here — cross-replica
    averaging happens outside on the grad pytree (ddp.py) so quorum changes
    never recompile this function.

    The one step maker of every model family: ``loss(cfg, params, tokens,
    targets, attn_fn) -> scalar`` is the family's training loss (this
    file's ``loss_fn``, ``models/olmoe.py::loss_fn``) and ``cfg`` its
    config; the program's name, the ``opt_update`` scope, ``StepProgram``
    and donation are the same for all.

    Equal arguments return THE SAME ``StepProgram`` within a process
    (``utils/profiling.step_program``): ``cfg`` is compared by value (a
    frozen dataclass), ``tx``, ``attn_fn`` and ``loss`` by identity. The
    program outlives whoever asked first, so a replica group rebuilt in
    this process with the same model runs the executables already loaded
    — no trace, no compile. For a private program pass a private ``tx``
    or ``loss``; an unhashable argument always gets a fresh one."""
    import optax

    from torchft_tpu.utils.profiling import step_program

    # the function's name is the program's on the trace's XLA Modules line
    def tft_train_step(params, opt_state, tokens, targets):
        value, grads = jax.value_and_grad(
            lambda p: loss(cfg, p, tokens, targets, attn_fn)
        )(params)
        with jax.named_scope("opt_update"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, value

    return step_program(
        tft_train_step, (cfg, tx, attn_fn, loss), (0, 1) if donate else ()
    )[0]


def make_grad_step(cfg: Any,
                   attn_fn: Optional[Callable] = None,
                   microbatches: int = 1, loss: Callable = loss_fn):
    """Jitted (params, tokens, targets) -> (loss, grads): the FT-DDP path
    computes grads on-device, averages them across replica groups over DCN,
    then applies the optimizer behind the commit gate.

    ``microbatches`` > 1 accumulates gradients over that many equal
    slices of the batch via lax.scan — one compiled program, activation
    memory of a single slice, identical mean-loss semantics (each slice
    is the same size, so averaging slice means equals the full-batch
    mean). The knob large effective batches need under a fixed HBM
    budget; the batch dim must divide evenly.

    As ``make_train_step``: equal ``(cfg, attn_fn, microbatches, loss)``
    return the same ``StepProgram`` within a process."""

    from torchft_tpu.utils.profiling import step_program

    def tft_grad_step(params, tokens, targets):
        if microbatches <= 1:
            return jax.value_and_grad(
                lambda p: loss(cfg, p, tokens, targets, attn_fn)
            )(params)
        b = tokens.shape[0]
        if b % microbatches:
            raise ValueError(
                f"batch {b} not divisible by microbatches {microbatches}"
            )
        mb = b // microbatches
        tok_mb = tokens.reshape(microbatches, mb, *tokens.shape[1:])
        tgt_mb = targets.reshape(microbatches, mb, *targets.shape[1:])

        def body(carry, xs):
            loss_acc, grad_acc = carry
            tok, tgt = xs
            value, grads = jax.value_and_grad(
                lambda p: loss(cfg, p, tok, tgt, attn_fn)
            )(params)
            return (
                loss_acc + value,
                jax.tree_util.tree_map(jnp.add, grad_acc, grads),
            ), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss_sum, grad_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), (tok_mb, tgt_mb)
        )
        inv = 1.0 / microbatches
        # accumulate f32 regardless of param dtype; hand back param-dtype
        # grads so both microbatch settings feed ddp/optim identically
        return loss_sum * inv, jax.tree_util.tree_map(
            lambda g, p: (g * inv).astype(p.dtype), grad_sum, params
        )

    return step_program(
        tft_grad_step, (cfg, attn_fn, microbatches, loss)
    )[0]
