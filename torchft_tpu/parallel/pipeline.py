"""Pipeline parallelism: GPipe-style microbatched stages over a mesh axis.

The reference has no PP (SURVEY.md §2c). TPU-native addition completing the
axis set (dp / fsdp / tp / seq / expert / stage). The design leans on jax's
autodiff instead of hand-scheduling: the forward pipeline is an ordinary
``lax.fori_loop`` of compute + ``ppermute`` hops under ``shard_map``, so
``jax.grad`` through it yields the reverse pipeline automatically (the
transpose of ppermute is the reverse rotation). Activations for the
backward are rematerialized per the surrounding ``jax.checkpoint`` policy.

Layout: every parameter leaf is stacked with a leading ``num_stages`` dim
sharded over the ``stage`` axis; microbatches flow stage 0 → S-1 with a
(M + S - 1)-tick schedule; outputs surface on the last stage and are
psum-broadcast back.

    mesh = ft_mesh({"stage": 4})
    stacked = stack_stage_params([p0, p1, p2, p3])
    pp = make_pipeline(mesh, stage_fn)     # stage_fn(stage_params, h) -> h
    out = pp(stacked, microbatches)        # [M, mb, ...] -> [M, mb, ...]
"""

from __future__ import annotations

from typing import Any, Callable, Optional

__all__ = ["make_pipeline", "make_pipeline_1f1b",
           "make_pipeline_interleaved_1f1b", "stack_stage_params",
           "stack_interleaved_params", "split_microbatches",
           "merge_microbatches"]


def stack_stage_params(stage_params_list) -> Any:
    """Stack per-stage param pytrees into one pytree with a leading
    num_stages dim (shard it over the stage axis with
    PartitionSpec(('stage',), ...))."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params_list
    )


def stack_interleaved_params(stage_params_list, num_stages: int,
                             interleave: int):
    """Stack V*S virtual-stage param pytrees (virtual-stage order: list
    index v, where v = chunk*S + device) into one pytree with a leading
    [S*V] dim in DEVICE-MAJOR order (row s*V + c = virtual stage c*S + s),
    so sharding the leading dim over the stage axis hands device s exactly
    its V chunks, c-indexed."""
    import jax
    import jax.numpy as jnp

    S, V = num_stages, interleave
    assert len(stage_params_list) == S * V, (len(stage_params_list), S * V)
    device_major = [
        stage_params_list[c * S + s] for s in range(S) for c in range(V)
    ]
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *device_major
    )


def split_microbatches(x, num_microbatches: int):
    """[B, ...] -> [M, B/M, ...]"""
    b = x.shape[0]
    assert b % num_microbatches == 0, (
        f"batch {b} not divisible by {num_microbatches} microbatches"
    )
    return x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])


def merge_microbatches(x):
    """[M, mb, ...] -> [M*mb, ...]"""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def make_pipeline(mesh, stage_fn: Callable[[Any, Any], Any],
                  axis: str = "stage",
                  embed_fn: Optional[Callable[[Any], Any]] = None,
                  readout_fn: Optional[Callable[[Any], Any]] = None):
    """Build a jittable pipelined apply: (stacked_params, microbatches) ->
    outputs, where ``stage_fn(params_for_one_stage, h)`` is one stage's
    compute and microbatches is [M, mb, ...].

    ``embed_fn`` (applied on stage 0 only) maps a raw input microbatch to
    the hidden representation, and ``readout_fn`` (last stage only) maps
    the final hidden state to the pipeline output — lifting the round-1
    restriction that inputs/outputs share the hidden shape (e.g. int32
    token ids in, logits out, [mb, d_model] flowing between stages).
    ``stage_fn`` itself must still map hidden -> hidden (the inter-stage
    channel is one SPMD-uniform buffer)."""
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    num_stages = mesh.shape[axis]

    def _body(stacked_params, x):
        stage = lax.axis_index(axis)
        # shard_map hands each device its [1, ...] slice of the stack
        params = jax.tree_util.tree_map(lambda l: l[0], stacked_params)
        num_mb = x.shape[0]
        ticks = num_mb + num_stages - 1

        def _embed(mb):
            return embed_fn(mb) if embed_fn is not None else mb

        def _readout(h):
            return readout_fn(h) if readout_fn is not None else h

        hidden_sds = jax.eval_shape(_embed, jax.eval_shape(lambda: x[0]))
        state0 = jnp.zeros(hidden_sds.shape, hidden_sds.dtype)
        out0 = jnp.zeros((num_mb,) + hidden_sds.shape, hidden_sds.dtype)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        # Embed once, outside the tick loop (stage 0 is the only consumer;
        # one batched application instead of one per tick).
        x_emb = jax.vmap(_embed)(x)

        def tick(t, carry):
            state, out = carry
            # stage 0 ingests microbatch t (clamped reads past the end are
            # discarded by the schedule)
            mb_in = lax.dynamic_index_in_dim(
                x_emb, jnp.clip(t, 0, num_mb - 1), axis=0, keepdims=False
            )
            h = stage_fn(
                params, jnp.where(stage == 0, mb_in, state)
            )
            # the last stage completes microbatch t-(S-1) at this tick
            mb_done = t - (num_stages - 1)
            write_idx = jnp.clip(mb_done, 0, num_mb - 1)
            should_write = (stage == num_stages - 1) & (mb_done >= 0)
            current = lax.dynamic_index_in_dim(
                out, write_idx, axis=0, keepdims=False
            )
            out = lax.dynamic_update_index_in_dim(
                out,
                jnp.where(should_write, h, current),
                write_idx,
                axis=0,
            )
            state = lax.ppermute(h, axis, perm)
            return state, out

        _, out = lax.fori_loop(0, ticks, tick, (state0, out0))
        # outputs live on the last stage; zero elsewhere, psum-broadcast,
        # then one batched readout (not one per tick per stage)
        out = jnp.where(stage == num_stages - 1, out, jnp.zeros_like(out))
        out = lax.psum(out, axis)
        return jax.vmap(_readout)(out)

    return shard_map(
        _body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def make_pipeline_1f1b(mesh, stage_fn: Callable[[Any, Any], Any],
                       loss_fn: Callable[[Any, Any], Any],
                       num_microbatches: int,
                       axis: str = "stage",
                       embed_fn: Optional[Callable[[Any], Any]] = None):
    """Explicit 1F1B training pipeline: (stacked_params, x_mb, y_mb) ->
    (mean_loss, stacked_param_grads).

    Unlike ``make_pipeline`` + jax.grad (which replays the whole forward
    schedule before any backward), this follows the 1F1B schedule
    (schedule.py): each stage starts backwards as soon as its first
    microbatch returns from the last stage, so peak in-flight activations
    are bounded by the stage count S instead of the microbatch count M.
    Per-tick actions come from static schedule tables; idle/active is
    gated with lax.cond so bubble ticks skip the stage compute.

    ``loss_fn(h_last, y_mb) -> scalar`` plays the readout role on the
    last stage (its VJP seeds the backward cotangent);
    ``embed_fn`` (stage 0) lifts raw inputs to the hidden shape.
    Backward recomputes each stage's forward from the stored stage INPUT
    (remat-style), so only inputs are buffered."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from torchft_tpu.parallel.schedule import one_f_one_b_schedule

    S = mesh.shape[axis]
    M = num_microbatches

    sched = one_f_one_b_schedule(S, M)
    T = len(sched)
    f_tbl = np.full((T, S), -1, np.int32)
    b_tbl = np.full((T, S), -1, np.int32)
    for t, row in enumerate(sched):
        for s, action in enumerate(row):
            if action is None:
                continue
            phase, mb, _ = action
            (f_tbl if phase == "F" else b_tbl)[t, s] = mb

    def _body(stacked_params, x, y):
        stage = lax.axis_index(axis)
        params = jax.tree_util.tree_map(lambda l: l[0], stacked_params)
        assert x.shape[0] == M, (x.shape, M)

        def _embed(mb):
            return embed_fn(mb) if embed_fn is not None else mb

        hidden_sds = jax.eval_shape(_embed, jax.eval_shape(lambda: x[0]))
        zeros_hidden = jnp.zeros(hidden_sds.shape, hidden_sds.dtype)
        ftbl = jnp.asarray(f_tbl)
        btbl = jnp.asarray(b_tbl)
        perm_fwd = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [(i, (i - 1) % S) for i in range(S)]
        zero_pgrads = jax.tree_util.tree_map(jnp.zeros_like, params)

        def tick(t, carry):
            h_chan, g_chan, acts, pgrads, loss_acc = carry
            f_mb = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(ftbl, t, axis=0, keepdims=False),
                stage, axis=0, keepdims=False,
            )
            b_mb = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(btbl, t, axis=0, keepdims=False),
                stage, axis=0, keepdims=False,
            )

            # ---- forward slot --------------------------------------
            mb_in = lax.dynamic_index_in_dim(
                x, jnp.clip(f_mb, 0, M - 1), axis=0, keepdims=False
            )
            h_in = jnp.where(stage == 0, _embed(mb_in), h_chan)

            def do_fwd(_):
                return stage_fn(params, h_in)

            h_out = lax.cond(f_mb >= 0, do_fwd,
                             lambda _: zeros_hidden, operand=None)
            # stash the stage INPUT for backward recompute; in-flight
            # count is bounded by S so slot = mb % S never collides
            slot = jnp.clip(f_mb, 0, M - 1) % S
            stored = lax.dynamic_index_in_dim(
                acts, slot, axis=0, keepdims=False
            )
            acts = lax.dynamic_update_index_in_dim(
                acts,
                jnp.where(f_mb >= 0, h_in, stored),
                slot, axis=0,
            )

            # ---- backward slot -------------------------------------
            b_slot = jnp.clip(b_mb, 0, M - 1) % S
            a_in = lax.dynamic_index_in_dim(
                acts, b_slot, axis=0, keepdims=False
            )
            y_mb = lax.dynamic_index_in_dim(
                y, jnp.clip(b_mb, 0, M - 1), axis=0, keepdims=False
            )

            def do_bwd(_):
                def last_stage(_):
                    def fwd_loss(p, a):
                        return loss_fn(stage_fn(p, a), y_mb)

                    loss_k, vjp = jax.vjp(fwd_loss, params, a_in)
                    pg, ag = vjp(jnp.ones_like(loss_k))
                    return loss_k, pg, ag

                def mid_stage(_):
                    _, vjp = jax.vjp(stage_fn, params, a_in)
                    pg, ag = vjp(g_chan)
                    return jnp.zeros(()), pg, ag

                return lax.cond(stage == S - 1, last_stage, mid_stage,
                                operand=None)

            def no_bwd(_):
                return jnp.zeros(()), zero_pgrads, zeros_hidden

            loss_k, pg, ag = lax.cond(b_mb >= 0, do_bwd, no_bwd,
                                      operand=None)
            pgrads = jax.tree_util.tree_map(
                lambda acc, g: acc + g, pgrads, pg
            )
            loss_acc = loss_acc + loss_k

            h_chan = lax.ppermute(h_out, axis, perm_fwd)
            g_chan = lax.ppermute(ag, axis, perm_bwd)
            return h_chan, g_chan, acts, pgrads, loss_acc

        acts0 = jnp.zeros((S,) + hidden_sds.shape, hidden_sds.dtype)
        carry0 = (zeros_hidden, zeros_hidden, acts0, zero_pgrads,
                  jnp.zeros(()))
        _, _, _, pgrads, loss_acc = lax.fori_loop(0, T, tick, carry0)

        mean_loss = lax.psum(loss_acc, axis) / M
        pgrads = jax.tree_util.tree_map(
            lambda l: (l / M)[None], pgrads
        )
        return mean_loss, pgrads

    return shard_map(
        _body,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )


def make_pipeline_interleaved_1f1b(
    mesh, stage_fn: Callable[[Any, Any], Any],
    loss_fn: Callable[[Any, Any], Any],
    num_microbatches: int,
    interleave: int,
    axis: str = "stage",
    embed_fn: Optional[Callable[[Any], Any]] = None,
):
    """Executable interleaved 1F1B (Megatron-style virtual stages):
    (stacked_params, x_mb, y_mb) -> (mean_loss, stacked_param_grads).

    Each device owns ``interleave`` model chunks (params stacked
    device-major via stack_interleaved_params); microbatches traverse all
    V*S virtual stages, which maps onto the physical ring because
    virtual-stage hop v -> v+1 is always device s -> (s+1) % S. The greedy
    interleaved schedule does not align a producer's send with its
    consumer's fire tick, so values park in per-device buffers whose slot
    assignments are computed statically (schedule.interleaved_tables) and
    driven by per-tick index tables inside the fori_loop. Bubble fraction
    drops toward (S-1)/V of GPipe's (see schedule.py); in exchange every
    microbatch makes V times the p2p hops.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from torchft_tpu.parallel.schedule import interleaved_tables

    S = mesh.shape[axis]
    M = num_microbatches
    V = interleave

    tbl = interleaved_tables(S, M, V)
    T = tbl["ticks"]
    names = ("f_mb", "f_chunk", "f_src", "f_act", "f_stash",
             "b_mb", "b_chunk", "b_act", "b_gsrc", "b_stash")
    np_tables = {
        n: np.asarray(tbl[n], np.int32) for n in names
    }

    def _body(stacked_params, x, y):
        stage = lax.axis_index(axis)
        # local slice after shard_map: [V, ...] per leaf (device-major)
        params = stacked_params
        assert x.shape[0] == M, (x.shape, M)

        def _embed(mb):
            return embed_fn(mb) if embed_fn is not None else mb

        hidden_sds = jax.eval_shape(_embed, jax.eval_shape(lambda: x[0]))
        zeros_hidden = jnp.zeros(hidden_sds.shape, hidden_sds.dtype)
        tabs = {n: jnp.asarray(a) for n, a in np_tables.items()}

        def pick_chunk(c):
            return jax.tree_util.tree_map(
                lambda l: lax.dynamic_index_in_dim(
                    l, c, axis=0, keepdims=False
                ),
                params,
            )

        zero_chunk_grads = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape[1:], l.dtype), params
        )
        perm_fwd = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [(i, (i - 1) % S) for i in range(S)]

        def tick(t, carry):
            (h_chan, g_chan, fwd_buf, bwd_buf, acts, pgrads,
             loss_acc) = carry

            def cell(name):
                return lax.dynamic_index_in_dim(
                    lax.dynamic_index_in_dim(
                        tabs[name], t, axis=0, keepdims=False
                    ),
                    stage, axis=0, keepdims=False,
                )

            f_mb, f_chunk, f_src, f_act, f_stash = (
                cell("f_mb"), cell("f_chunk"), cell("f_src"),
                cell("f_act"), cell("f_stash"),
            )
            b_mb, b_chunk, b_act, b_gsrc, b_stash = (
                cell("b_mb"), cell("b_chunk"), cell("b_act"),
                cell("b_gsrc"), cell("b_stash"),
            )

            # ---- stash arriving channel values -----------------------
            def masked_store(buf, slot, value):
                idx = jnp.clip(slot, 0, buf.shape[0] - 1)
                current = lax.dynamic_index_in_dim(
                    buf, idx, axis=0, keepdims=False
                )
                return lax.dynamic_update_index_in_dim(
                    buf, jnp.where(slot >= 0, value, current), idx, axis=0,
                )

            fwd_buf = masked_store(fwd_buf, f_stash, h_chan)
            bwd_buf = masked_store(bwd_buf, b_stash, g_chan)

            # ---- forward slot ----------------------------------------
            mb_in = lax.dynamic_index_in_dim(
                x, jnp.clip(f_mb, 0, M - 1), axis=0, keepdims=False
            )
            src = lax.dynamic_index_in_dim(
                fwd_buf, jnp.clip(f_src, 0, fwd_buf.shape[0] - 1),
                axis=0, keepdims=False,
            )
            h_in = jnp.where(f_src < 0, _embed(mb_in), src)
            p_f = pick_chunk(jnp.clip(f_chunk, 0, V - 1))
            h_out = lax.cond(
                f_mb >= 0,
                lambda _: stage_fn(p_f, h_in),
                lambda _: zeros_hidden,
                operand=None,
            )
            acts = masked_store(acts, f_act, h_in)

            # ---- backward slot ---------------------------------------
            a_in = lax.dynamic_index_in_dim(
                acts, jnp.clip(b_act, 0, acts.shape[0] - 1),
                axis=0, keepdims=False,
            )
            y_mb = lax.dynamic_index_in_dim(
                y, jnp.clip(b_mb, 0, M - 1), axis=0, keepdims=False
            )
            g_in = lax.dynamic_index_in_dim(
                bwd_buf, jnp.clip(b_gsrc, 0, bwd_buf.shape[0] - 1),
                axis=0, keepdims=False,
            )
            p_b = pick_chunk(jnp.clip(b_chunk, 0, V - 1))

            def do_bwd(_):
                def last_virtual(_):
                    def fwd_loss(p, a):
                        return loss_fn(stage_fn(p, a), y_mb)

                    loss_k, vjp = jax.vjp(fwd_loss, p_b, a_in)
                    pg, ag = vjp(jnp.ones_like(loss_k))
                    return loss_k, pg, ag

                def mid_virtual(_):
                    _, vjp = jax.vjp(stage_fn, p_b, a_in)
                    pg, ag = vjp(g_in)
                    return jnp.zeros(()), pg, ag

                return lax.cond(
                    b_gsrc < 0, last_virtual, mid_virtual, operand=None
                )

            def no_bwd(_):
                return jnp.zeros(()), zero_chunk_grads, zeros_hidden

            loss_k, pg, ag = lax.cond(b_mb >= 0, do_bwd, no_bwd,
                                      operand=None)
            c_idx = jnp.clip(b_chunk, 0, V - 1)
            pgrads = jax.tree_util.tree_map(
                lambda acc, g: lax.dynamic_update_index_in_dim(
                    acc,
                    lax.dynamic_index_in_dim(
                        acc, c_idx, axis=0, keepdims=False
                    ) + g,
                    c_idx, axis=0,
                ),
                pgrads, pg,
            )
            loss_acc = loss_acc + loss_k

            h_chan = lax.ppermute(h_out, axis, perm_fwd)
            g_chan = lax.ppermute(ag, axis, perm_bwd)
            return (h_chan, g_chan, fwd_buf, bwd_buf, acts, pgrads,
                    loss_acc)

        fwd_buf0 = jnp.zeros(
            (tbl["n_fwd_slots"],) + hidden_sds.shape, hidden_sds.dtype
        )
        bwd_buf0 = jnp.zeros(
            (tbl["n_bwd_slots"],) + hidden_sds.shape, hidden_sds.dtype
        )
        acts0 = jnp.zeros(
            (tbl["n_act_slots"],) + hidden_sds.shape, hidden_sds.dtype
        )
        pgrads0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        carry0 = (zeros_hidden, zeros_hidden, fwd_buf0, bwd_buf0, acts0,
                  pgrads0, jnp.zeros(()))
        out = lax.fori_loop(0, T, tick, carry0)
        pgrads, loss_acc = out[5], out[6]

        mean_loss = lax.psum(loss_acc, axis) / M
        pgrads = jax.tree_util.tree_map(lambda l: l / M, pgrads)
        return mean_loss, pgrads

    return shard_map(
        _body,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )
