"""Third rehearsal (on-chip-measurement guide, section 2): compile each
configuration's fused, grad and update programs at the real size for a
described v5e chip, with ``memory_analysis()``, before any chip call.
Run by hand:

    JAX_PLATFORMS=cpu python benchmark/tests/compile_v5e.py \
        cerebras-gpt-111m:16 cerebras-gpt-1.3b:4 [name:rows[:n_layer]]

Nothing runs and nothing here is a measurement: the numbers are the
compiler's plan for one program at a time, not what else the process
keeps on the device. ``causal_attention`` picks its kernel from
``jax.default_backend()``, which is the CPU here, so this script (not the
program) points the model at the flash kernel the chip would run.
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

    import torchft_tpu.models.transformer as T
    from benchmark.families import gpt
    from torchft_tpu.ops.flash import flash_attention

    jax.config.update("jax_enable_compilation_cache", False)
    T._local_causal_attention = lambda q, k, v: flash_attention(
        q, k, v, causal=True
    )
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree,
        )

    for spec in sys.argv[1:]:
        name, rows, *depth = spec.split(":")
        with open(os.path.join(_BENCH, "configs", name + ".json")) as f:
            config = json.load(f)
        config["job"]["rows"] = int(rows)
        if depth:
            config["n_layer"] = int(depth[0])
        model = gpt.build(config)
        params = jax.eval_shape(
            lambda: T.init_params(model.cfg, jax.random.key(0))
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
            "fused": (gpt.make_train_step(model), (params, opt, batch, batch)),
            "grad": (gpt.make_grad_step(model), (params, batch, batch)),
            "update": (jax.jit(update), (params, opt, params)),
        }
        print(f"{name} n_layer={model.cfg.n_layers} rows={model.rows} "
              f"params={n / 1e6:.1f}M params+adam={state_gb:.2f}GB", flush=True)
        for label, (fn, args) in programs.items():
            mem = fn.lower(*on_chip(args)).compile().memory_analysis()
            total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
            print(f"  {label:6s} args {mem.argument_size_in_bytes / 1e9:6.2f} "
                  f"out {mem.output_size_in_bytes / 1e9:6.2f} "
                  f"alias {mem.alias_size_in_bytes / 1e9:6.2f} "
                  f"temp {mem.temp_size_in_bytes / 1e9:6.2f} "
                  f"-> {total / 1e9:6.2f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
