"""Sizing rehearsal for the Granite 4.0-H family (on-chip-measurement
guide, section 2; after ``compile_v5e_olmo_hybrid.py``): compile the
donated fused step, the grad step and the update at the published widths
and the configuration's share for a described v5e chip, with
``memory_analysis()``, before any chip call. Run by hand, one candidate
an argument, ``rows:seq_len[:remat]``:

    JAX_PLATFORMS=cpu python benchmark/tests/compile_v5e_granite.py \
        2:8192:1 1:8192:1

The rule (ISSUE 68, the repo's since ISSUE 33): depth 10 fixed (one whole
period, mamba x 5, attention, mamba x 4), sequences of 8192, remat; the
larger of 2 rows and 1 row whose donated fused step plans <= 15.0 GiB;
then the chip's reading decides (``peak_hbm_gib`` under 15.75). Nothing
runs and nothing here is a measurement: the numbers are the compiler's
plan for one program at a time. ``causal_attention``,
``ops/ssm_pointwise.py`` and ``ops/ssd.py`` pick their kernels from
``jax.default_backend()``, which is the CPU here, so this script (not the
program) points the model at the Mosaic kernels the chip would run.
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

    import torchft_tpu.models.granite_hybrid as J
    import torchft_tpu.ops.ssd as ssd_ops
    import torchft_tpu.ops.ssm_pointwise as pointwise_ops
    from benchmark.families import granite_hybrid as family
    from torchft_tpu.ops.flash import flash_attention

    jax.config.update("jax_enable_compilation_cache", False)
    J.causal_attention = lambda q, k, v, scale=None: flash_attention(
        q, k, v, causal=True, scale=scale)
    ssd_ops._interpret = lambda: False
    pointwise_ops._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree,
        )

    with open(os.path.join(
            _BENCH, "configs", "granite-4.0-h-micro-vp8.json")) as f:
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
        print(f"granite-4.0-h-micro-vp8 layers={model.cfg.n_layers} "
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
