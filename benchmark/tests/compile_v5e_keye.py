"""Sizing rehearsal for the Keye family (on-chip-measurement guide,
section 2; sibling of ``compile_v5e_smallthinker.py``): compile the
donated fused step, the grad step and the update at the published widths
and the configuration's share for a described v5e chip, with
``memory_analysis()``, before any chip call. Run by hand, one candidate
an argument, ``layers:rows[:seq_len[:remat]]``:

    JAX_PLATFORMS=cpu python benchmark/tests/compile_v5e_keye.py \
        6:2 5:2 4:2 4:1

The rule (ISSUE 66): sequences of 16 384 with remat, the largest of 6 / 5
/ 4 layers whose donated fused step at 2 rows plans <= 15.0 GiB; rows
fall to 1 only if depth 4 does not plan at 2. Nothing runs and nothing
here is a measurement: the numbers are the compiler's plan for one
program at a time. ``ops/dsa.py``, ``ops/moe.py`` and
``ops/ssm_pointwise.py`` pick their kernels from
``jax.default_backend()``, which is the CPU here, so this script (not
the program) points the model at the Mosaic kernels the chip would run.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import torchft_tpu.models.keye as J
    import torchft_tpu.ops.dsa as dsa_ops
    import torchft_tpu.ops.moe as moe_ops
    import torchft_tpu.ops.ssm_pointwise as pointwise_ops
    from benchmark.families import keye as family

    jax.config.update("jax_enable_compilation_cache", False)
    dsa_ops._use_kernels = lambda interpret: (True, False)
    for ops in (moe_ops, pointwise_ops):
        ops._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree,
        )

    with open(os.path.join(_BENCH, "configs",
                           "keye-vl-2.0-30b-a3b-ep8.json")) as f:
        base = json.load(f)
    for spec in sys.argv[1:]:
        layers, rows, *rest = spec.split(":")
        config = json.loads(json.dumps(base))
        config["num_hidden_layers"] = int(layers)
        config["job"].update(
            rows=int(rows), seq_len=int(rest[0]) if rest else 16384,
            remat=bool(len(rest) < 2 or int(rest[1])))
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
        print(f"keye-vl-2.0-30b-a3b-ep8 layers={model.cfg.n_layers} "
              f"rows={model.rows} seq={model.seq_len} "
              f"remat={model.cfg.remat} params={n / 1e6:.1f}M "
              f"params+adam={state_gb:.2f}GB "
              f"flops/token={model.flops_per_token / 1e9:.3f}G", flush=True)
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
