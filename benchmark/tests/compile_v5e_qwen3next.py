"""Sizing rehearsal for the Qwen3-Next family (on-chip-measurement guide,
section 2; sibling of ``compile_v5e_smallthinker.py``): compile the
donated fused step, the grad step and the update at the published widths
and the configuration's share for a described v5e chip, with
``memory_analysis()``, before any chip call. Run by hand, one candidate
an argument, ``rows:seq_len[:remat]`` (the depth is fixed: one whole
period L L L F):

    JAX_PLATFORMS=cpu python benchmark/tests/compile_v5e_qwen3next.py \
        4:8192:1 3:8192:1 2:8192:1

The rule (ISSUE 63, as PRs 31 - 59): sequences of 8192 with remat, the
largest of 4 / 3 / 2 rows whose donated fused step plans <= 15.0 GiB.
Nothing runs and nothing here is a measurement: the numbers are the
compiler's plan for one program at a time. ``causal_attention``,
``ops/moe.py``, ``ops/kda.py`` and ``ops/ssm_pointwise.py`` pick their
kernels from ``jax.default_backend()``, which is the CPU here, so this
script (not the program) points the model at the Mosaic kernels the chip
would run. It also prints what ``ops/flash.py`` picks at the 256-wide
call (tiles, the forward's chunk, both estimates) and, of the fused
step's text, the ``copy(`` instructions as large as q or o (PR 62's rule
(a), PERF.md section 7: a layout copy beside the flash call).
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))


def _layouts(rows: int, seq_len: int, heads: int, head: int):
    """The layouts q and o take between the projections and the flash
    call, as they stand in the compiled text."""
    return [f"[{','.join(map(str, shape))}]" for shape in (
        (rows, seq_len, heads * head), (rows, seq_len, heads, head),
        (rows, heads, seq_len, head), (rows * heads, seq_len, head))]


def main() -> int:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import torchft_tpu.models.qwen3_next as J
    import torchft_tpu.ops.flash as flash_ops
    import torchft_tpu.ops.kda as kda_ops
    import torchft_tpu.ops.moe as moe_ops
    import torchft_tpu.ops.ssm_pointwise as pointwise_ops
    from benchmark.families import qwen3_next as family
    from torchft_tpu.ops.flash import flash_attention

    jax.config.update("jax_enable_compilation_cache", False)
    J.causal_attention = lambda q, k, v: flash_attention(q, k, v, causal=True)
    for ops in (moe_ops, kda_ops, pointwise_ops):
        ops._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree,
        )

    with open(os.path.join(_BENCH, "configs", "qwen3-next-80b-a3b-ep16.json")) as f:
        base = json.load(f)
    for spec in sys.argv[1:]:
        rows, seq_len, *rest = spec.split(":")
        config = json.loads(json.dumps(base))
        config["job"].update(rows=int(rows), seq_len=int(seq_len),
                             remat=bool(not rest or int(rest[0])))
        model = family.build(config)
        params = jax.eval_shape(
            lambda: J.init_params(model.cfg, jax.random.key(0))
        )
        opt = jax.eval_shape(model.tx.init, params)
        batch = jax.ShapeDtypeStruct((model.rows, model.seq_len), jnp.int32)
        n = sum(x.size for x in jax.tree_util.tree_leaves(params))
        state_gb = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves((params, opt))
        ) / 1e9

        def update(grads, opt_state, p):
            updates, new = model.tx.update(grads, opt_state, p)
            return optax.apply_updates(p, updates), new

        programs = {
            "fused": (family.make_train_step(model), (params, opt, batch, batch)),
            "grad": (family.make_grad_step(model), (params, batch, batch)),
            "update": (jax.jit(update), (params, opt, params)),
        }
        print(f"qwen3-next-80b-a3b-ep16 layers={model.cfg.n_layers} rows={model.rows} "
              f"seq={model.seq_len} remat={model.cfg.remat} "
              f"params={n / 1e6:.1f}M params+adam={state_gb:.2f}GB "
              f"flops/token={model.flops_per_token / 1e9:.3f}G", flush=True)
        S, D = model.seq_len, model.cfg.head_dim
        bq, bk = flash_ops._choose_blocks(S, D, 2)
        chunk = flash_ops._choose_chunk(S, D, 2, bq, bk)
        print(f"  flash at D {D}: tiles {bq} x {bk} (estimate "
              f"{flash_ops._vmem_estimate(S, D, 2, bq, bk) / 2**20:.2f} MiB), "
              f"forward chunk {chunk} (estimate "
              f"{flash_ops._forward_vmem_estimate(D, D, 2, bq, bk, chunk) / 2**20:.2f}"
              f" MiB) of a budget of {flash_ops._VMEM_BUDGET / 2**20:.0f}",
              flush=True)
        for label, (fn, args) in programs.items():
            try:
                compiled = fn.lower(*on_chip(args)).compile()
                mem = compiled.memory_analysis()
            except Exception as e:  # noqa: BLE001 — the compiler's refusal is the result
                print(f"  {label:6s} REFUSED {str(e)[:300]}", flush=True)
                continue
            total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
            print(f"  {label:6s} args {mem.argument_size_in_bytes / 1e9:6.2f} "
                  f"out {mem.output_size_in_bytes / 1e9:6.2f} "
                  f"alias {mem.alias_size_in_bytes / 1e9:6.2f} "
                  f"temp {mem.temp_size_in_bytes / 1e9:6.2f} "
                  f"-> {total / 1e9:6.2f} GB = {total / 2**30:6.2f} GiB",
                  flush=True)
            if label == "fused":
                layouts = _layouts(model.rows, S, model.cfg.n_heads, D)
                copies = [line.strip()[:160] for line in
                          compiled.as_text().splitlines()
                          if " copy(" in line
                          and any(shape in line for shape in layouts)]
                print(f"  copy( of q's or o's size ({layouts[0]}): "
                      f"{len(copies)}", flush=True)
                for line in copies:
                    print("    " + line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
