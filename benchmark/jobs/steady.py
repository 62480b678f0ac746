"""Job ``steady``: one replica group on one chip, a closed loop running
free for the window, a new seeded batch every step. What one group pays
per step for being fault tolerant when the wire is free: a lighthouse, a
manager, a quorum and a two-phase commit around the path
``OptimizerWrapper.can_fuse()`` picks."""

from __future__ import annotations

import time
from typing import Any, Dict

from benchmark import harness
from benchmark.group import ReplicaGroup
from benchmark.traffic_gen import BatchSource

_WARM_STEPS = 3   # also the steps compared with the plain worker


def run(ctx: harness.Context) -> Dict[str, Any]:
    import jax

    from torchft_tpu.control import Lighthouse

    fam, traffic = ctx.family, ctx.traffic
    model = fam.build(ctx.config)
    rows = int(traffic.get("rows") or model.rows)
    device = ctx.devices[0]
    source = BatchSource(ctx.seed, 0, 0, rows, model.seq_len, model.vocab_draw)
    checks: Dict[str, Any] = {}

    # -- the plain single worker, and the reference, on the seed's weights
    state = fam.init_state(model, ctx.seed, device)
    checks["reference"] = fam.check_reference(
        model, state["params"], ctx.seed, device
    )
    train_step = fam.make_train_step(model)
    plain = harness.bare_step_loop(
        train_step, state, source, device, 0, _WARM_STEPS
    )
    first_step_s = plain["step_s"][0]   # compiles, or loads from the cache
    plain_losses, state = plain["losses"], plain["state"]
    bare = None
    if ctx.trace:  # the bare step is a per-layer number: traced run only
        bare = harness.bare_step_loop(
            train_step, state, source, device, _WARM_STEPS,
            int(traffic["bare_steps"]),
        )
        state = bare.pop("state")
    harness.free(state)
    del state, plain

    # -- the same worker inside the fault-tolerant loop
    lighthouse = Lighthouse(**traffic["lighthouse"])
    group = None
    try:
        group = ReplicaGroup(
            0, 0, model, fam, device, 0, lighthouse.address(), ctx.seed,
            source, train_step=train_step,
        )
        for i in range(_WARM_STEPS):
            group.step(*source.device_batch(i, device))
        jax.block_until_ready(group.state)
        ft_losses = [float(x) for x in
                     jax.device_get([r["loss"] for r in group.records])]
        checks["plain_worker"] = {
            # the FT loop dispatches the very program the plain worker
            # ran, on the same weights and batches: equal to the bit
            "ok": ft_losses == plain_losses
            and all(r["committed"] for r in group.records),
            "plain": plain_losses, "ft": ft_losses,
        }
        group.records = []
        group.reset_timings()
        at_start = group.snapshots()
        compiles0 = ctx.counter.compiles

        # -- the window
        clock = harness.CompletionClock()

        def ran(rec: Dict[str, Any]) -> None:
            if rec["committed"]:
                clock.watch(rec["loss"])

        traced_s = float(traffic["trace_last_s"]) if ctx.trace else 0.0
        setup_s = time.perf_counter() - ctx.t_start
        t0 = time.perf_counter()
        t_mid = t0 + max(ctx.seconds - traced_s, 0.0)
        group.run(lambda g: time.perf_counter() < t_mid,
                  first_batch=_WARM_STEPS, on_step=ran)
        jax.block_until_ready(group.state)
        t_untraced_end = time.perf_counter()
        n_untraced = len(group.records)
        if ctx.trace:
            ctx.start_trace()
            t_end = time.perf_counter() + traced_s
            group.run(lambda g: time.perf_counter() < t_end,
                      first_batch=_WARM_STEPS + n_untraced, on_step=ran)
            jax.block_until_ready(group.state)
            ctx.stop_trace()
        t1 = time.perf_counter()
        done = clock.close()
        if group.error is not None:
            raise group.error

        records = group.records
        # the rate of the steps that ran untraced: all of them in an
        # untraced run, whose end-to-end number this is
        n_ran = sum(1 for r in records[:n_untraced] if r["committed"])
        rate = harness.block_median_rate(
            [t0] + done[:n_ran], source.tokens_per_batch,
            int(traffic["rate_blocks"]),
        )
        compiles = ctx.counter.compiles - compiles0
        checks["steady"] = {
            "ok": compiles == 0 and all(r["path"] == "fused" for r in records),
            "compiles_in_window": compiles,
        }
        checks["losses_finite"] = {"ok": harness.losses_finite(records)}
        gaps = sorted(b["t0"] - a["t0"] for a, b in zip(records, records[1:]))
        return {
            "checks": checks,
            "notes": [
                f"{n_untraced} steps in {t_untraced_end - t0:.3f}s untraced, "
                f"{len(records) - n_untraced} in {t1 - t_untraced_end:.3f}s "
                f"traced; host step-to-step median "
                f"{harness.median(gaps) or 0:.4f}s, longest "
                f"{gaps[-1] if gaps else 0:.4f}s",
                f"tokens/s of the untraced steps: median of "
                f"{rate['blocks']} blocks {rate['tokens_per_s']:.1f}, "
                f"whole {rate['whole']:.1f}, slowest block "
                f"{rate['slowest']:.1f}; longest completion-to-completion "
                f"{max(b - a for a, b in zip([t0] + done, done)):.4f}s"
                if done else "no step ran",
            ],
            "attempted": len(records),
            "failed": sum(1 for r in records if not r["committed"]),
            "end_to_end": {
                "committed_tokens_per_s": rate["tokens_per_s"] / ctx.chips,
                "peak_hbm_gib": harness.peak_hbm_bytes(ctx.devices) / 2**30,
                "setup_s": setup_s,
            },
            # raw observations for the per-layer readers
            "records": records,
            "ft_tokens_per_s_per_chip": rate["tokens_per_s"] / ctx.chips,
            "window_over_blocks": rate["whole"] / rate["tokens_per_s"]
            if rate["tokens_per_s"] else None,
            "bare": bare,
            "boot_s": ctx.boot_s,
            "first_step_s": first_step_s,
            "compiles_in_window": compiles,
            "sinks": [dict(group.snapshots(), start=at_start,
                           replacement=False)],
            "flops_per_token": fam.flops_per_token(model),
            "kills": [],
        }
    finally:
        if group is not None:
            group.teardown()
        lighthouse.shutdown()
