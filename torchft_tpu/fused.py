"""Fused single-executable training step over the 2-D (replica, model) mesh.

This is the raw-speed plane ISSUE 16 adds on top of the PR 6/8/11
collectives: the HSDP step (params allgather over the model axis →
per-microbatch grad → grad reduce-scatter back onto the model axis →
codec-encoded cross-replica exchange → sharded optimizer update →
params allgather over the replica axis) compiled into ONE cached
executable, so a training step is one device dispatch with zero host
round-trips between stages. The staged arm keeps the SAME four local
stage bodies as four separate executables with real host round-trips in
between — the live A/B lever and the bitwise oracle (PR 3/5/8 pattern):
``_hardround`` fences at every stage boundary in both arms make
fused↔staged a bit-for-bit identity, not a numeric envelope.

Counter contract (the sandbox-pinnable win, ROADMAP item 3):

- ``step_dispatch_count``    +1 per compiled-executable invocation —
                             exactly 1/step fused, 4/step staged
- ``step_host_hops``         +1 per intermediate device↔host transfer
                             between dispatches — 0 fused, 6 staged
                             (gm, h, new_sub each cross twice)
- ``step_executable_count``  gauge: distinct executables the last step
                             used (1 fused / 4 staged — fleet_top's
                             mode signal)
- ``mesh_shape``             label ``"{replicas}x{model_shards}"``
- ``fused_step``             event per fused dispatch (mesh shape,
                             codec, counts, compile-cache state)

Compile behaviour rides the MeshManager executable cache: first sight
of a (mesh shape, codec, layouts) compiles once per program; any later
step at a seen shape — including after a kill→shrink→rejoin cycle — is
a cache lookup, never a retrace (``MeshManager.compile_count`` /
``trace_count`` pin this in tests).
"""

from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from torchft_tpu.comm.xla_backend import (
    MeshManager,
    _FusedSpec,
    _build_fused_step,
    _build_step_stage,
    _fused_avals,
)
from torchft_tpu.utils.metrics import Metrics

__all__ = ["FusedStepEngine"]

_STAGES = ("grad", "exchange", "update", "gather")


class FusedStepEngine:
    """Owns the device-resident training state of one replica-group
    fleet laid out on a ``replicas x model_shards`` mesh and steps it
    through either arm of the A/B.

    Layout (``d = r * model_shards + m`` row-major over the mesh):
    device ``(r, m)`` holds params shard ``m`` (replicated over the
    replica axis), the error-feedback residual for ITS OWN encoded
    contribution, and optimizer state for the sub-shard
    ``shard_m[r*q_len : (r+1)*q_len]`` it updates — the PR 8 sharded
    update, on-device. ``params`` is any flat float32 vector; it is
    zero-padded to the mesh-divisible length internally and truncated
    on the way out.

    ``loss_fn(flat_params, microbatch) -> scalar`` and the optax-style
    ``tx`` are traced into the executables; ``fn_key`` names their
    identity in the executable cache key (two engines with different
    losses must use different keys).
    """

    def __init__(
        self,
        mesh_manager: MeshManager,
        replicas: int,
        model_shards: int,
        params: np.ndarray,
        batch_size: int,
        loss_fn: Any,
        tx: Any,
        codec: str = "none",
        chunk_bytes: int = 1 << 16,
        error_feedback: Optional[bool] = None,
        metrics: Optional[Metrics] = None,
        events: Any = None,
        fn_key: str = "default",
    ) -> None:
        if codec not in ("none", "bf16", "fp16", "int8"):
            raise ValueError(f"unknown step codec {codec!r}")
        self.mesh_mgr = mesh_manager
        self.replicas = int(replicas)
        self.model_shards = max(1, int(model_shards))
        self.codec = codec
        self.tx = tx
        self.loss_fn = loss_fn
        self.metrics = metrics if metrics is not None else Metrics()
        self.events = events
        self.step_count = 0
        if error_feedback is None:
            error_feedback = codec == "int8"
        params = np.asarray(params, dtype=np.float32).ravel()
        spec_opt = self._opt_template(params.size, chunk_bytes)
        treedef, leaf_shapes, leaf_dtypes = spec_opt
        self.spec = _FusedSpec(
            replicas=self.replicas,
            model_shards=self.model_shards,
            param_size=params.size,
            batch_size=int(batch_size),
            codec_name=codec,
            chunk_bytes=int(chunk_bytes),
            error_feedback=bool(error_feedback),
            loss_fn=loss_fn,
            tx=tx,
            opt_treedef=treedef,
            opt_leaf_shapes=leaf_shapes,
            opt_leaf_dtypes=leaf_dtypes,
            fn_key=fn_key,
        )
        self._init_device_state(params)
        self.metrics.label(
            "mesh_shape", f"{self.replicas}x{self.model_shards}"
        )

    # ------------------------------------------------------------ state

    def _opt_template(
        self, param_size: int, chunk_bytes: int
    ) -> Tuple[Any, List[Tuple[int, ...]], List[Any]]:
        """Flatten ``tx.init`` on a q_len-shaped zero vector once to
        learn the optimizer state's treedef and per-leaf layouts (the
        executable cache key pins them)."""
        import jax
        import jax.numpy as jnp

        q_len = max(
            1, -(-param_size // (self.replicas * self.model_shards))
        )
        state = self.tx.init(jnp.zeros((q_len,), jnp.float32))
        leaves, treedef = jax.tree_util.tree_flatten(state)
        shapes = [tuple(np.shape(leaf)) for leaf in leaves]
        dtypes = [np.asarray(leaf).dtype for leaf in leaves]
        return treedef, shapes, dtypes

    def _init_device_state(self, params: np.ndarray) -> None:
        """Pad + replicate the flat param vector into the device-stacked
        layout and commit every state array to its mesh sharding, so
        step outputs (same shardings by construction) feed straight back
        in without implicit transfers."""
        import jax
        import jax.numpy as jnp

        spec = self.spec
        R, M, D = self.replicas, self.model_shards, self.world_devices
        padded = np.zeros((spec.s_len,), np.float32)
        padded[: spec.param_size] = params
        shards = padded.reshape(M, spec.p_len)
        p_rows = np.stack([shards[d % M] for d in range(D)])
        e_rows = np.zeros((D, spec.p_len), np.float32)
        opt_rows: List[np.ndarray] = []
        per_dev: List[List[np.ndarray]] = []
        for d in range(D):
            r, m = divmod(d, M)
            sub = padded[
                m * spec.p_len + r * spec.q_len:
                m * spec.p_len + (r + 1) * spec.q_len
            ]
            state = self.tx.init(jnp.asarray(sub))
            leaves = jax.tree_util.tree_leaves(state)
            per_dev.append([np.asarray(leaf) for leaf in leaves])
        for i in range(len(per_dev[0])):
            opt_rows.append(
                np.stack([per_dev[d][i] for d in range(D)]).astype(
                    spec.opt_leaf_dtypes[i]
                )
            )
        rep, row, _ = _fused_avals(self.mesh_mgr, spec)
        self._rep, self._row = rep, row
        self._z = jax.device_put(np.int32(0), rep)
        self._p = jax.device_put(p_rows, row)
        self._e = jax.device_put(e_rows, row)
        self._opt = [jax.device_put(a, row) for a in opt_rows]

    @property
    def world_devices(self) -> int:
        return self.replicas * self.model_shards

    def params(self) -> np.ndarray:
        """The full (unpadded) flat param vector, read from the rank-0
        replica row of each model shard."""
        p = np.asarray(self._p)
        full = np.concatenate(
            [p[m] for m in range(self.model_shards)]
        )
        return full[: self.spec.param_size]

    def digest(self) -> str:
        """sha256 over ALL device-resident state (params, EF residual,
        optimizer leaves) — the staged↔fused bitwise oracle."""
        h = hashlib.sha256()
        h.update(np.ascontiguousarray(np.asarray(self._p)).tobytes())
        h.update(np.ascontiguousarray(np.asarray(self._e)).tobytes())
        for leaf in self._opt:
            h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
        return h.hexdigest()

    def verify_replicas(self) -> None:
        """Cross-rank check: every replica row of a model shard must
        hold bitwise-identical params (the replica-axis allgather ships
        raw bytes, so divergence means a broken exchange)."""
        p = np.asarray(self._p)
        M = self.model_shards
        for m in range(M):
            base = p[m]
            for r in range(1, self.replicas):
                got = p[r * M + m]
                if base.tobytes() != got.tobytes():
                    raise AssertionError(
                        f"replica divergence at model shard {m}: "
                        f"replica 0 vs replica {r}"
                    )

    # ------------------------------------------------------------ steps

    def _exe(self, kind: str) -> Any:
        spec = self.spec
        if kind == "fused":
            build = lambda: _build_fused_step(self.mesh_mgr, spec)  # noqa: E731
        else:
            build = lambda: _build_step_stage(self.mesh_mgr, spec, kind)  # noqa: E731
        exe, _shardings = self.mesh_mgr.executable(
            spec.exec_key(kind), build
        )
        return exe

    def _batch_rows(self, batch: np.ndarray) -> Any:
        import jax

        b = np.asarray(batch, dtype=np.float32)
        want = (self.world_devices, self.spec.batch_size)
        if b.shape != want:
            raise ValueError(
                f"batch shape {b.shape} != (devices, batch_size) {want}"
            )
        return jax.device_put(b, self._row)

    def step_fused(self, batch: np.ndarray) -> float:
        """ONE device dispatch: the whole step, intermediates never
        leave HBM."""
        exe = self._exe("fused")
        b = self._batch_rows(batch)
        outs = exe(self._z, self._p, b, self._e, *self._opt)
        self.metrics.incr("step_dispatch_count")
        self.metrics.gauge("step_executable_count", 1)
        self._p, loss_row, self._e = outs[0], outs[1], outs[2]
        self._opt = list(outs[3:])
        self.step_count += 1
        loss = float(np.asarray(loss_row)[0])
        ev = self.events
        if ev:
            ev.emit(
                "fused_step",
                step=self.step_count,
                mesh_shape=f"{self.replicas}x{self.model_shards}",
                codec=self.codec,
                dispatches=1,
                executables=1,
                compile_count=self.mesh_mgr.compile_count,
                trace_count=self.mesh_mgr.trace_count,
                cache_hits=self.mesh_mgr.hit_count,
            )
        return loss

    def step_staged(self, batch: np.ndarray) -> float:
        """FOUR dispatches composing the SAME stage bodies, with every
        intermediate (gm, h, new_sub) taking a real device→host→device
        round-trip between them — the A/B baseline whose outputs must
        match :meth:`step_fused` bit for bit."""
        import jax

        exes = {s: self._exe(s) for s in _STAGES}
        b = self._batch_rows(batch)

        def hop(x: Any) -> Any:
            # d2h + h2d: two host hops per intermediate, f32-lossless
            host = np.asarray(x)
            self.metrics.incr("step_host_hops", 2)
            return jax.device_put(host, self._row)

        gm, loss_row = exes["grad"](self._z, self._p, b)
        gm = hop(gm)
        h, new_e = exes["exchange"](self._z, gm, self._e)
        h = hop(h)
        upd = exes["update"](self._z, h, self._p, *self._opt)
        new_sub = hop(upd[0])
        (new_p,) = exes["gather"](new_sub)
        self.metrics.incr("step_dispatch_count", len(_STAGES))
        self.metrics.gauge("step_executable_count", len(_STAGES))
        self._p, self._e = new_p, new_e
        self._opt = list(upd[1:])
        self.step_count += 1
        return float(np.asarray(loss_row)[0])

    def step(self, batch: np.ndarray, fused: bool = True) -> float:
        return self.step_fused(batch) if fused else self.step_staged(batch)

    # --------------------------------------------------------- topology

    def reshape_mesh(self, replicas: int,
                     model_shards: Optional[int] = None) -> None:
        """Re-lay the SAME logical model onto a new mesh shape (the
        heal/churn path): params are read back once, the device layout
        (and optimizer template) is rebuilt for the new shape, and the
        executables for the new shape come from the MeshManager cache —
        a previously-seen shape costs zero compiles and zero retraces.

        The EF residual is intentionally dropped (it is layout-local
        compensation state, exactly like the host arena across a wire
        world change); optimizer state is re-initialised here — the
        Manager-integrated path redistributes it through the PR 14
        planner instead (optim.py)."""
        params = self.params()
        self.replicas = int(replicas)
        if model_shards is not None:
            self.model_shards = max(1, int(model_shards))
        old = self.spec
        spec_opt = self._opt_template(old.param_size, old.chunk_bytes)
        treedef, leaf_shapes, leaf_dtypes = spec_opt
        self.spec = _FusedSpec(
            replicas=self.replicas,
            model_shards=self.model_shards,
            param_size=old.param_size,
            batch_size=old.batch_size,
            codec_name=old.codec_name,
            chunk_bytes=old.chunk_bytes,
            error_feedback=old.error_feedback,
            loss_fn=old.loss_fn,
            tx=old.tx,
            opt_treedef=treedef,
            opt_leaf_shapes=leaf_shapes,
            opt_leaf_dtypes=leaf_dtypes,
            fn_key=old.fn_key,
        )
        self._init_device_state(params)
        self.metrics.label(
            "mesh_shape", f"{self.replicas}x{self.model_shards}"
        )

    def counters(self) -> Dict[str, Any]:
        """The counter-oracle snapshot tests and the bench pin."""
        snap = self.metrics.snapshot()
        return {
            "step_dispatch_count": snap.get("step_dispatch_count", 0),
            "step_host_hops": snap.get("step_host_hops", 0),
            "step_executable_count": snap.get(
                "step_executable_count", 0
            ),
            "mesh_shape": snap.get("mesh_shape", ""),
            "compile_count": self.mesh_mgr.compile_count,
            "trace_count": self.mesh_mgr.trace_count,
            "cache_hits": self.mesh_mgr.hit_count,
        }
