"""Zero-copy streaming heal plane tests (ISSUE 4).

Pins the pipeline's contracts: BITWISE heal identity on every default
path, zero full-array copies on the donor serve path, lazy staging that
serves the first leaf before the tree finishes staging (and priority-
bumps requested leaves), bounded Content-Length reads with prescriptive
errors, multi-donor striped fetches, donor death mid-stream failover,
and the heal_* metric surface.
"""

import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from torchft_tpu.checkpointing import (
    CheckpointServer,
    fetch_leaf,
    fetch_manifest,
    recv_checkpoint_sharded,
    serve_copy_stats,
)
from torchft_tpu.utils.metrics import Metrics


def _state(dtype_name: str):
    import jax.numpy as jnp

    if dtype_name == "fp32":
        w = jnp.asarray(
            np.random.default_rng(7).standard_normal(8192),
            dtype=jnp.float32,
        )
        b = jnp.asarray(
            np.random.default_rng(8).standard_normal((33, 17)),
            dtype=jnp.float32,
        )
    else:  # bf16 params (ml_dtypes-backed extension dtype on host)
        w = jnp.asarray(
            np.random.default_rng(7).standard_normal(8192),
            dtype=jnp.bfloat16,
        )
        b = jnp.asarray(
            np.random.default_rng(8).standard_normal((33, 17)),
            dtype=jnp.bfloat16,
        )
    return {
        "params": {"w": w, "b": b},
        "torchft": {"step": 3, "batches_committed": 9},
    }


def _assert_bitwise(got, src) -> None:
    import jax

    g_flat, g_def = jax.tree_util.tree_flatten(got)
    s_flat, s_def = jax.tree_util.tree_flatten(src)
    assert len(g_flat) == len(s_flat)
    for g, s in zip(g_flat, s_flat):
        if hasattr(s, "dtype"):
            ga, sa = np.asarray(g), np.asarray(s)
            assert ga.dtype == sa.dtype and ga.shape == sa.shape
            assert ga.tobytes() == sa.tobytes()  # BITWISE
        else:
            assert g == s


@pytest.mark.parametrize("dtype_name", ["fp32", "bf16"])
@pytest.mark.parametrize(
    "mode", ["full_stream", "chunked", "sharded", "striped"]
)
def test_bitwise_heal_identity(mode: str, dtype_name: str) -> None:
    # The default heal paths must be BITWISE identical to the donor's
    # state — trajectory oracles depend on it (docs/architecture.md).
    state = _state(dtype_name)
    donor = CheckpointServer(timeout=10.0)
    donor.send_checkpoint([1], step=3, state_dict=state, timeout=10.0)
    if mode == "full_stream":
        healer = CheckpointServer(timeout=10.0)
    elif mode == "chunked":
        healer = CheckpointServer(timeout=10.0, num_chunks=3)
    elif mode == "sharded":
        healer = CheckpointServer(
            timeout=10.0, template_fn=lambda: state
        )
    else:  # striped: force multi-connection striping on the big leaf
        healer = CheckpointServer(
            timeout=10.0, template_fn=lambda: state,
            stripe_bytes=2048,
        )
    try:
        got = healer.recv_checkpoint(0, donor.metadata(), 3, 10.0)
        _assert_bitwise(got, state)
    finally:
        donor.shutdown()
        healer.shutdown()


def test_sharded_multi_donor_bitwise() -> None:
    # Two donor hosts each holding HALF the pieces (the multi-host
    # simulation seam): the healer routes each region to the owning host
    # and the result is bitwise identical.
    import jax
    import jax.numpy as jnp

    from tests.test_integration_hsdp import group_mesh, shard_group_params

    mesh = group_mesh(0)
    params = shard_group_params(
        {"w": jnp.arange(16 * 32, dtype=jnp.float32).reshape(16, 32)},
        mesh,
    )
    host_a = CheckpointServer(timeout=10.0)
    host_b = CheckpointServer(timeout=10.0)
    try:
        host_a._shard_filter = lambda path, b: b[0][0] < 8
        host_b._shard_filter = lambda path, b: b[0][0] >= 8
        host_a.set_peers([host_b.metadata()])
        host_a.send_checkpoint([], 7, params, 10.0)
        host_b.send_checkpoint([], 7, params, 10.0)
        got = recv_checkpoint_sharded(
            host_a.metadata(), 7, params, timeout=10.0
        )
        assert np.asarray(got["w"]).tobytes() == np.asarray(
            params["w"]
        ).tobytes()
    finally:
        host_a.shutdown()
        host_b.shutdown()


def test_donor_zero_copy_serve() -> None:
    # Acceptance: serving a C-contiguous non-ml_dtypes leaf performs ZERO
    # full-array copies (memoryview straight off the staged array).
    import jax.numpy as jnp

    state = {
        "w": jnp.arange(4096, dtype=jnp.float32),
        "host": np.arange(512, dtype=np.float64),
    }
    donor = CheckpointServer(timeout=10.0)
    try:
        donor.send_checkpoint([], 1, state, 10.0)
        # stage fully first so the serve path is isolated from staging
        donor._staged.finish_staging(10.0)
        serve_copy_stats(reset=True)
        # jax flattens dict keys sorted: leaf 0 = "host", leaf 1 = "w"
        got_h = fetch_leaf(donor.metadata(), 1, 0)
        got_w = fetch_leaf(donor.metadata(), 1, 1)
        np.testing.assert_array_equal(got_h, state["host"])
        np.testing.assert_array_equal(got_w, np.asarray(state["w"]))
        stats = serve_copy_stats()
        assert stats["full_array_copies"] == 0, stats
        assert stats["zero_copy_serves"] == 2, stats
    finally:
        donor.shutdown()


def test_lazy_staging_first_leaf_before_last_staged() -> None:
    # Event-order acceptance: the healer's first leaf lands BEFORE the
    # donor's full-tree staging completes, and a requested leaf is
    # priority-bumped past leaves the background stager is stuck on.
    import jax.numpy as jnp

    gate = threading.Event()
    staged_idx: list = []

    def hook(idx: int, path: str) -> None:
        staged_idx.append(idx)
        if idx == 0:
            # the background stager (leaf order) wedges here; requested
            # leaves must not wait behind it
            gate.wait(10.0)

    state = {
        "a": jnp.zeros(64, jnp.float32),
        "b": jnp.arange(64, dtype=jnp.float32),
        "c": jnp.ones(64, jnp.float32),
    }
    donor = CheckpointServer(timeout=10.0)
    donor._stage_hook = hook
    try:
        donor.send_checkpoint([], 2, state, 10.0)
        # send_checkpoint returned while staging is wedged on leaf 0
        assert not donor._staged.all_staged.done()
        got = fetch_leaf(donor.metadata(), 2, 1)  # priority bump
        np.testing.assert_array_equal(
            got, np.arange(64, dtype=np.float32)
        )
        assert not donor._staged.all_staged.done()  # tree still staging
        assert 1 in staged_idx  # leaf 1 staged by the REQUEST, early
        gate.set()
        donor._staged.all_staged.result(10.0)  # stager drains the rest
    finally:
        gate.set()
        donor.shutdown()


def test_disallow_finishes_residual_staging() -> None:
    # Gate-close must drain lazy staging (the trainer may donate device
    # buffers right after), not strand claimed-but-unstarted slots.
    import jax.numpy as jnp

    state = {"w": jnp.arange(128, dtype=jnp.float32)}
    donor = CheckpointServer(timeout=10.0)
    try:
        donor.send_checkpoint([], 4, state, 10.0)
        staged = donor._staged
        donor.disallow_checkpoint()
        assert staged.all_staged.done()
    finally:
        donor.shutdown()


def test_wire_bf16_opt_in_roundtrip() -> None:
    # Opt-in lossy wire precision: values exactly representable in bf16
    # roundtrip exactly; the healed dtype is the TEMPLATE dtype (fp32).
    import jax.numpy as jnp

    w = jnp.asarray(np.arange(256, dtype=np.float32))  # bf16-exact
    state = {"w": w}
    donor = CheckpointServer(timeout=10.0)
    healer = CheckpointServer(
        timeout=10.0, num_chunks=2, heal_wire_dtype="bf16"
    )
    try:
        donor.send_checkpoint([], 5, state, 10.0)
        got = healer.recv_checkpoint(0, donor.metadata(), 5, 10.0)
        assert np.asarray(got["w"]).dtype == np.float32
        np.testing.assert_array_equal(got["w"], np.asarray(w))
        # direct fetch: wire dtype headers honored, fewer wire bytes
        leaf = fetch_leaf(donor.metadata(), 5, 0, wire_dtype="bf16")
        assert leaf.dtype == np.float32
        np.testing.assert_array_equal(leaf, np.asarray(w))
    finally:
        donor.shutdown()
        healer.shutdown()


def test_unknown_wire_dtype_rejected() -> None:
    with pytest.raises(ValueError, match="heal_wire_dtype"):
        CheckpointServer(timeout=1.0, heal_wire_dtype="fp4")


class _LyingHandler(BaseHTTPRequestHandler):
    """Donor that advertises a Content-Length inconsistent with its
    dtype/shape headers (version skew), or truncates the body (death
    mid-stream)."""

    mode = "mismatch"

    def log_message(self, *a) -> None:  # noqa: A002
        pass

    def do_GET(self) -> None:  # noqa: N802
        body = np.arange(4, dtype=np.float32).tobytes()
        self.send_response(200)
        self.send_header("X-Kind", "ndarray")
        self.send_header("X-Dtype", "float32")
        self.send_header("X-Shape", "4")
        if self.mode == "mismatch":
            self.send_header("Content-Length", str(len(body) + 12))
            self.end_headers()
            self.wfile.write(body + b"\x00" * 12)
        else:  # short body, honest headers
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body[:8])
            self.wfile.flush()
            self.connection.close()


@pytest.mark.parametrize("mode", ["mismatch", "short"])
def test_fetch_leaf_bounded_and_prescriptive(mode: str) -> None:
    # Satellite: fetch_leaf must bound reads to the advertised length and
    # reject mismatched/short bodies with a prescriptive error, never a
    # downstream frombuffer shape crash.
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _LyingHandler)
    _LyingHandler.mode = mode
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    addr = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        # crc=False: this stub donor predates the CRC frames; the test
        # exercises the length-bounding contract, not the checksum one
        with pytest.raises(ConnectionError) as exc_info:
            fetch_leaf(addr, 1, 0, timeout=5.0, crc=False)
        msg = str(exc_info.value)
        if mode == "mismatch":
            assert "Content-Length" in msg and "version skew" in msg
        else:
            assert "truncated" in msg
    finally:
        srv.shutdown()
        srv.server_close()


class _DieAfterManifestProxy:
    """TCP proxy standing in for a donor that dies mid-stream: manifest
    requests are relayed to the real donor; every later connection is
    closed without a response (the healer sees a hard network error, not
    an HTTP error)."""

    def __init__(self, upstream: str) -> None:
        from urllib.parse import urlparse

        u = urlparse(upstream)
        self._up = (u.hostname, u.port)
        self._sock = socket.socket()
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(16)
        self.addr = f"http://127.0.0.1:{self._sock.getsockname()[1]}"
        self._stop = False
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self) -> None:
        while not self._stop:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            try:
                req = conn.recv(65536)
                if b"/manifest" in req.split(b"\r\n", 1)[0]:
                    up = socket.create_connection(self._up, timeout=5)
                    up.sendall(req)
                    up.shutdown(socket.SHUT_WR)
                    while True:
                        chunk = up.recv(65536)
                        if not chunk:
                            break
                        conn.sendall(chunk)
                    up.close()
                # anything else: close abruptly — donor died
            except OSError:
                pass
            finally:
                conn.close()

    def close(self) -> None:
        self._stop = True
        try:
            self._sock.close()
        except OSError:
            pass


def test_donor_death_mid_stream_retries_surviving_peer() -> None:
    # The primary donor serves the manifest then dies; its manifest
    # advertises a surviving peer with full coverage. The healer must
    # fail over and heal bitwise — and with NO survivor, raise instead
    # of committing partial state.
    import jax.numpy as jnp

    state = {"w": jnp.arange(2048, dtype=jnp.float32),
             "b": jnp.ones((7, 5), jnp.float32)}
    survivor = CheckpointServer(timeout=10.0)
    primary = CheckpointServer(timeout=10.0)
    proxy = _DieAfterManifestProxy(primary.metadata())
    try:
        primary._peers = [survivor.metadata()]
        primary.send_checkpoint([], 9, state, 10.0)
        survivor.send_checkpoint([], 9, state, 10.0)
        got = recv_checkpoint_sharded(
            proxy.addr, 9, state, timeout=10.0, parallel=2
        )
        _assert_bitwise(got, state)
    finally:
        proxy.close()

    # no surviving peer -> the heal RAISES; nothing partial is returned
    lonely = CheckpointServer(timeout=10.0)
    proxy2 = _DieAfterManifestProxy(lonely.metadata())
    try:
        lonely.send_checkpoint([], 9, state, 10.0)
        with pytest.raises(Exception) as exc_info:
            recv_checkpoint_sharded(
                proxy2.addr, 9, state, timeout=5.0, parallel=2
            )
        assert not isinstance(exc_info.value, AssertionError)
    finally:
        proxy2.close()
        lonely.shutdown()
        primary.shutdown()
        survivor.shutdown()


def test_heal_metrics_surface() -> None:
    # The heal round must land heal_stage / heal_wire spans and the
    # heal_fetch_ms / heal_bytes_per_s gauges in the shared sink;
    # heal_wall_ms is the Manager's (assignment → applied), and a
    # transport on its own writes none.
    import jax.numpy as jnp

    state = {"w": jnp.arange(4096, dtype=jnp.float32)}
    donor = CheckpointServer(timeout=10.0)
    healer = CheckpointServer(timeout=10.0, num_chunks=2)
    donor_metrics, healer_metrics = Metrics(), Metrics()
    donor.set_metrics(donor_metrics)
    healer.set_metrics(healer_metrics)
    try:
        donor.send_checkpoint([], 6, state, 10.0)
        got = healer.recv_checkpoint(0, donor.metadata(), 6, 10.0)
        np.testing.assert_array_equal(got["w"], np.asarray(state["w"]))
        donor._staged.all_staged.result(10.0)
        d = donor_metrics.snapshot()
        h = healer_metrics.snapshot()
        assert d.get("heal_stage_avg_ms", -1) >= 0.0, sorted(d)
        assert h.get("heal_wire_avg_ms", -1) >= 0.0, sorted(h)
        assert h.get("heal_fetch_ms", -1) > 0.0, sorted(h)
        assert "heal_wall_ms" not in h and "heal_wall_ms" not in d
        assert h.get("heal_bytes_per_s", -1) > 0.0, sorted(h)
        for v in (h["heal_fetch_ms"], h["heal_bytes_per_s"]):
            assert np.isfinite(v)
        # the fetch's inside: bytes and leaves as the manifest has them
        # (one CRC frame a leaf rides the raw stream), the workers'
        # seconds by phase, and the donor's side of the same wire
        assert (h["heal_bytes"], h["heal_leaves"]) == (4096 * 4 + 4, 1)
        for phase in ("wait", "read", "crc"):
            assert h[f"heal_wire_{phase}_s"] > 0.0
            assert h[f"heal_wire_{phase}_avg_ms"] > 0.0
        assert 0.0 <= h["heal_donor_wait_share"] <= 1.0
        assert d["heal_serve_crc_s"] > 0.0
        assert {"heal_gate_avg_ms", "heal_serve_avg_ms"} <= set(d)
    finally:
        donor.shutdown()
        healer.shutdown()


@pytest.mark.parametrize("mode", ["sharded", "chunked"])
def test_donor_wait_share_is_this_heals_and_rises_with_a_slow_stage(
        mode: str) -> None:
    # Heals into ONE sink: plain, staging delayed by the _stage_hook
    # seam, plain. Each heal's share is the ratio of ITS deltas of the
    # three counters (read here around the call), not the run's. On the
    # sharded plane a delayed donor holds a worker before the response's
    # headers; the raw stream sends its headers from metadata and stages
    # behind the wire, so there a slow stage reads as a slow body.
    import jax.numpy as jnp

    # leaves large enough that reading and checking them outweighs a
    # request's round trip, so the plain heals read low
    n, side = 3, 1448
    state = {f"l{i}": jnp.full((side, side), float(i), jnp.float32)
             for i in range(n)}
    sharded = mode == "sharded"
    healer = CheckpointServer(
        timeout=10.0, **({"template_fn": lambda: state} if sharded
                         else {"num_chunks": 2}))
    sink = Metrics()
    healer.set_metrics(sink)
    phases = ("heal_wire_wait_s", "heal_wire_read_s", "heal_wire_crc_s")
    shares = []
    try:
        for step, delay in enumerate(
                (0.0, 0.15, 0.0) if sharded else (0.0, 0.0)):
            donor = CheckpointServer(timeout=10.0)
            donor._stage_hook = lambda idx, path, d=delay: time.sleep(d)
            try:
                before = [sink.count(p) for p in phases]
                donor.send_checkpoint([], step, state, 10.0)
                got = healer.recv_checkpoint(0, donor.metadata(), step, 10.0)
                _assert_bitwise(got, state)
            finally:
                donor.shutdown()
            wait, read, crc = (sink.count(p) - b
                               for p, b in zip(phases, before))
            snap = sink.snapshot()
            assert snap["heal_donor_wait_share"] == pytest.approx(
                wait / (wait + read + crc), rel=1e-9)
            assert 0.0 <= snap["heal_donor_wait_share"] <= 1.0
            assert snap["heal_leaves"] == n
            assert snap["heal_bytes"] == n * side * side * 4 + (
                0 if sharded else n * 4)  # the raw stream's CRC frames
            if delay:
                assert wait >= delay  # a worker sat out one stage at least
            shares.append(snap["heal_donor_wait_share"])
        if sharded:
            assert shares[1] > max(shares[0], shares[2]), shares
    finally:
        healer.shutdown()


def test_striped_fetch_into_out_buffer() -> None:
    # readinto contract: a striped sharded fetch lands bytes in the
    # healer's preallocated buffers; out= misuse fails loudly.
    import jax.numpy as jnp

    donor = CheckpointServer(timeout=10.0)
    w = np.arange(1024, dtype=np.float32)
    try:
        donor.send_checkpoint([], 8, {"w": jnp.asarray(w)}, 10.0)
        out = np.empty(1024, np.float32)
        got = fetch_leaf(donor.metadata(), 8, 0, out=out)
        assert got is out
        np.testing.assert_array_equal(out, w)
        with pytest.raises(ValueError, match="does not match"):
            fetch_leaf(
                donor.metadata(), 8, 0,
                out=np.empty(7, np.float32),
            )
        with pytest.raises(ValueError, match="contiguous"):
            fetch_leaf(
                donor.metadata(), 8, 0,
                out=np.empty((1024, 2), np.float32)[:, 0],
            )
    finally:
        donor.shutdown()
