"""Adapter for the GPT-2 family (``torchft_tpu/models/transformer.py``):
everything the harness needs of a model family, and nothing of any one
configuration. A configuration is its ``configs/<name>.json``; another
family is another file beside this one.

The interface a job uses: ``build``, ``init_state``, ``make_train_step``,
``make_grad_step``, ``flops_per_token``, ``check_reference``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

# |system loss - f32 reference loss| on the same weights and sequences.
# The system computes in bf16 (8 mantissa bits, relative rounding 2^-9 =
# 2e-3 per operation) with f32 accumulation, LayerNorm, softmax and
# logsumexp; the loss is a mean over 4096 positions of values near
# ln(50304) = 10.8, so per-position rounding of a few 1e-3 averages down
# and what is left is the systematic part. Measured on the v5e (PERF.md,
# PR 22; 43 runs of both configurations): at most 3.4e-4 absolute. About
# five times that is the bound. A step to a
# coarser compute type (fp8: relative rounding 6e-2, thirty times bf16's)
# moves the loss by more than 1e-2 and fails; so does dropping a block,
# the positions or the causal mask, which move it by 1e-1 and more.
REFERENCE_LOSS_ATOL = 1.5e-3
REFERENCE_SEQUENCES = 2


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any                # the program's TransformerConfig
    tx: Any                 # the optax transformation the job trains with
    seq_len: int
    vocab_draw: int         # token ids are drawn below this
    rows: int               # sequences per step and group, from the sizing
    layer_norm_eps: float
    n_matmul_params: int


def build(config: Dict[str, Any]) -> Model:
    import optax

    from torchft_tpu.models import TransformerConfig

    job, opt = config["job"], config["optimizer"]
    d, layers, ff = config["n_embd"], config["n_layer"], config["n_inner"]
    rows_alloc = config["vocab_rows_allocated"]
    cfg = TransformerConfig(
        vocab_size=rows_alloc, d_model=d, n_layers=layers,
        n_heads=config["n_head"], d_ff=ff,
        max_seq_len=config["n_positions"], remat=bool(job["remat"]),
        xent_chunks=int(job["xent_chunks"]),
    )
    tx = optax.adamw(
        opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"],
    )
    # four d x d and two d x ff matrices a block, and the head; the token
    # and position tables are gathered, not multiplied
    n_matmul = layers * (4 * d * d + 2 * d * ff) + d * rows_alloc
    return Model(
        cfg=cfg, tx=tx, seq_len=config["n_positions"],
        vocab_draw=config["vocab_size"], rows=int(job["rows"]),
        layer_norm_eps=float(config["layer_norm_epsilon"]),
        n_matmul_params=n_matmul,
    )


def init_state(model: Model, seed: int, device: Any) -> Dict[str, Any]:
    """Weights and optimizer state from ``seed``, made on ``device`` in
    one jitted call, in the types they are trained in (f32)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from torchft_tpu.models import init_params

    def make(s):
        params = init_params(model.cfg, jax.random.key(s))
        return {"params": params, "opt": model.tx.init(params)}

    import numpy as np

    # --seed may pass 2**31; below it the key is the one np.int32(seed) gave
    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        np.uint32(seed & 0xFFFFFFFF)
    )


def make_train_step(model: Model) -> Callable:
    from torchft_tpu.models import make_train_step as make

    return make(model.cfg, model.tx, donate=True)


def make_grad_step(model: Model) -> Callable:
    from torchft_tpu.models import make_grad_step as make

    return make(model.cfg)


def flops_per_token(model: Model) -> float:
    from benchmark.flops import train_flops_per_token

    return train_flops_per_token(
        model.n_matmul_params, model.cfg.n_layers, model.cfg.d_model,
        model.seq_len,
    )


def check_reference(model: Model, params: Any, seed: int,
                    device: Any) -> Dict[str, Any]:
    """The system's ``loss_fn`` against ``reference/gpt_f32.py`` on the
    same weights and ``REFERENCE_SEQUENCES`` seeded sequences, at the
    configuration's widths and the cell's depth."""
    import functools

    import jax

    from benchmark.reference import gpt_f32
    from benchmark.traffic_gen import BatchSource
    from torchft_tpu.models import loss_fn

    tokens, targets = BatchSource(
        seed, 0x7265, 0, REFERENCE_SEQUENCES, model.seq_len, model.vocab_draw
    ).device_batch(0, device)
    system = jax.jit(functools.partial(loss_fn, model.cfg))
    reference = jax.jit(functools.partial(
        gpt_f32.loss, n_layer=model.cfg.n_layers, n_head=model.cfg.n_heads,
        eps=model.layer_norm_eps,
    ))
    got = float(system(params, tokens, targets))
    want = float(reference(params, tokens, targets))
    return {
        "ok": abs(got - want) <= REFERENCE_LOSS_ATOL,
        "system_loss": got, "reference_loss": want,
        "abs_diff": abs(got - want), "atol": REFERENCE_LOSS_ATOL,
    }
