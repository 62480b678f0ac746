"""Sizing rehearsal for the Ouro family (on-chip-measurement guide,
section 2; after ``compile_v5e_granite.py``): compile the donated fused
step, the grad step and the update at the published widths for a described
v5e chip, with ``memory_analysis()``, before any chip call. Run by hand,
one candidate an argument, ``rows:seq_len[:remat[:layers]]``:

    JAX_PLATFORMS=cpu python benchmark/tests/compile_v5e_ouro.py \
        1:8192:1 2:8192:1

The rule (ISSUE 73): ``total_ut_steps`` 4 and every width as published,
one row of 8192, remat, depth 8; if the donated fused step plans over 15.0
GiB, depth 7, then 6 (never under 4); then the chip's reading decides
(``peak_hbm_gib`` under 15.0). Two rows are planned for the record and not
taken (17 - 19 steps a window). Nothing runs and nothing here is a
measurement: the numbers are the compiler's plan for one program at a
time. ``causal_attention`` picks its kernel from ``jax.default_backend()``,
which is the CPU here, so this script (not the program) points the model at
the Mosaic kernels the chip would run.
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

    import torchft_tpu.models.ouro as J
    from benchmark.families import ouro as family
    from torchft_tpu.ops.flash import flash_attention

    jax.config.update("jax_enable_compilation_cache", False)
    J._local_causal_attention = lambda q, k, v: flash_attention(
        q, k, v, causal=True)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree,
        )

    with open(os.path.join(
            _BENCH, "configs", "ouro-2.6b-l8.json")) as f:
        base = json.load(f)
    for spec in sys.argv[1:]:
        rows, seq_len, *rest = spec.split(":")
        config = json.loads(json.dumps(base))
        config["job"].update(rows=int(rows), seq_len=int(seq_len),
                             remat=bool(not rest or int(rest[0])))
        if len(rest) > 1:
            config["num_hidden_layers"] = int(rest[1])
            config["layer_types"] = config["layer_types"][:int(rest[1])]
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
        print(f"ouro-2.6b layers={model.cfg.n_layers} x "
              f"{model.cfg.ut_steps} passes "
              f"rows={model.rows} seq={model.seq_len} remat={model.cfg.remat} "
              f"params={n} ({n / 1e6:.1f}M) params+adam={state_gb:.2f}GB "
              f"flops/token={model.flops_per_token / 1e9:.3f}G", flush=True)
        for label, (fn, args) in programs.items():
            try:
                mem = fn.lower(*on_chip(args)).compile().memory_analysis()
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
