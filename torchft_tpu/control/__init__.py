"""Python surface of the native control plane.

API parity target: the reference pyo3 classes in
/root/reference/torchft/torchft.pyi (Manager/ManagerClient/Lighthouse/
QuorumResult). Server objects own native threads; every RPC call releases
the GIL for its full duration (ctypes calls drop the GIL).
"""

from __future__ import annotations

import ctypes
import json
from dataclasses import dataclass, field
from datetime import timedelta
from typing import List, Optional

from torchft_tpu.control._native import check_error, get_lib, take_string

__all__ = [
    "IncrementalQuorum",
    "Lighthouse",
    "LighthouseClient",
    "ManagerServer",
    "ManagerClient",
    "QuorumResult",
    "lighthouse_heartbeat",
    "lighthouse_quorum",
    "quorum_compute_raw",
]


def _ms(t: "float | timedelta", default_ms: int = 60000) -> int:
    if t is None:
        return default_ms
    if isinstance(t, timedelta):
        return max(1, int(t.total_seconds() * 1000))
    return max(1, int(float(t) * 1000))


def _split_bind(bind: str) -> "tuple[str, int]":
    """Accept 'host:port', ':port', '[::]:port'."""
    host, _, port = bind.rpartition(":")
    if host in ("", "[::]", "::"):
        host = "0.0.0.0"
    if host.startswith("[") and host.endswith("]"):
        host = host[1:-1]
    return host, int(port or "0")


@dataclass
class QuorumResult:
    """Per-rank quorum view (proto ManagerQuorumResponse; ref torchft.pyi:23-34)."""

    quorum_id: int = 0
    replica_rank: int = 0
    replica_world_size: int = 1
    recover_src_manager_address: str = ""
    recover_src_rank: Optional[int] = None
    recover_dst_ranks: List[int] = field(default_factory=list)
    store_address: str = ""
    max_step: int = 0
    max_rank: Optional[int] = None
    max_world_size: int = 1
    # Sorted replica_ids of the max-step cohort (diagnostics/labeling).
    max_replica_ids: List[str] = field(default_factory=list)
    # Data-plane transport membership: quorum participants that did not
    # opt out of the gradient wire (observer replicas are excluded).
    # transport_rank is None when this replica itself opted out.
    transport_rank: Optional[int] = None
    transport_world_size: int = 0
    transport_replica_ids: List[str] = field(default_factory=list)
    heal: bool = False
    # Epoch lease (steady-state fast path): the membership epoch this
    # quorum was announced at and the lease duration the lighthouse
    # grants (0 = leases disabled / pre-lease lighthouse). While an
    # EpochWatch sees the epoch unchanged and the lease is live, the
    # manager steps with zero control RPCs.
    membership_epoch: int = 0
    lease_ms: int = 0
    # Prescriptive eviction (multi-tenant priority preemption): the
    # lighthouse answered the group's quorum request with an eviction
    # decision instead of a member list. No other field is meaningful;
    # the trainer should exit cleanly while the job's survivors shrink.
    evicted: bool = False

    @staticmethod
    def from_json(payload: str) -> "QuorumResult":
        d = json.loads(payload)
        if d.get("evicted"):
            return QuorumResult(
                evicted=True,
                membership_epoch=d.get("membership_epoch", 0),
                lease_ms=0,
            )
        return QuorumResult(
            quorum_id=d["quorum_id"],
            replica_rank=d["replica_rank"],
            replica_world_size=d["replica_world_size"],
            recover_src_manager_address=d["recover_src_manager_address"],
            recover_src_rank=d.get("recover_src_rank"),
            recover_dst_ranks=list(d.get("recover_dst_ranks") or []),
            store_address=d["store_address"],
            max_step=d["max_step"],
            max_rank=d.get("max_rank"),
            max_world_size=d["max_world_size"],
            max_replica_ids=list(d.get("max_replica_ids") or []),
            transport_rank=d.get("transport_rank"),
            transport_world_size=d.get("transport_world_size", 0),
            transport_replica_ids=list(
                d.get("transport_replica_ids") or []
            ),
            heal=d["heal"],
            membership_epoch=d.get("membership_epoch", 0),
            lease_ms=d.get("lease_ms", 0),
        )


class Lighthouse:
    """In-process lighthouse server (ref lib.rs:266-319 pyclass).

    Note the embedded default join_timeout_ms=100 matches the reference
    pyclass default (lib.rs:285); the CLI default is 60000.

    Fleet-scale options (PR 10):

    - ``cache_quorum``: serve epoch-cached quorum decisions (default).
      ``False`` runs the pure decision kernel on every evaluation — the
      always-recompute arm of ``scripts/bench_fleet.py``'s A/B.
    - ``prune_after_ms``: heartbeat/participant entries dead longer than
      this are pruned (default 12x heartbeat_timeout_ms).
    - ``upstream_addr``/``domain``/``tier``: constructing with an
      upstream address makes this lighthouse a tier-1 aggregator for a
      domain (rack/ICI) of replica groups — it holds that domain's
      quorum and posts one membership summary upstream to the root every
      ``upstream_report_interval_ms``; the root renders the summaries
      under ``/status.json`` ``domains`` with report staleness.
    """

    def __init__(
        self,
        bind: str = "0.0.0.0:0",
        min_replicas: int = 1,
        join_timeout_ms: Optional[int] = None,
        quorum_tick_ms: Optional[int] = None,
        heartbeat_timeout_ms: Optional[int] = None,
        hostname: str = "127.0.0.1",
        cache_quorum: bool = True,
        prune_after_ms: Optional[int] = None,
        tier: Optional[int] = None,
        domain: Optional[str] = None,
        upstream_addr: Optional[str] = None,
        upstream_report_interval_ms: Optional[int] = None,
        lease_ms: Optional[int] = None,
        fleet_capacity: Optional[int] = None,
    ) -> None:
        host, port = _split_bind(bind)
        lib = get_lib()
        err = ctypes.c_char_p()
        extra = {"cache_quorum": bool(cache_quorum)}
        if prune_after_ms is not None:
            extra["prune_after_ms"] = int(prune_after_ms)
        if tier is not None:
            extra["tier"] = int(tier)
        if domain is not None:
            extra["domain"] = domain
        if upstream_addr is not None:
            extra["upstream_addr"] = upstream_addr
        if upstream_report_interval_ms is not None:
            extra["upstream_report_interval_ms"] = int(
                upstream_report_interval_ms
            )
        if lease_ms is not None:
            extra["lease_ms"] = int(lease_ms)
        if fleet_capacity is not None:
            # Admission capacity in replica groups summed across jobs;
            # above it, higher-priority quorum requests preempt groups
            # from the lowest-priority over-budget job.
            extra["fleet_capacity"] = int(fleet_capacity)
        self._handle = lib.ft_lighthouse_new(
            host.encode(),
            port,
            hostname.encode(),
            min_replicas,
            join_timeout_ms if join_timeout_ms is not None else 100,
            quorum_tick_ms if quorum_tick_ms is not None else 100,
            heartbeat_timeout_ms if heartbeat_timeout_ms is not None else 5000,
            json.dumps(extra).encode(),
            ctypes.byref(err),
        )
        check_error(err)
        if not self._handle:
            raise RuntimeError("failed to create lighthouse")

    def address(self) -> str:
        return take_string(get_lib().ft_lighthouse_address(self._handle))

    def shutdown(self) -> None:
        if self._handle:
            get_lib().ft_lighthouse_shutdown(self._handle)

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle:
            try:
                get_lib().ft_lighthouse_free(handle)
            except Exception:
                pass  # interpreter teardown


class ManagerServer:
    """Native per-replica-group manager server, embedded in the rank-0
    trainer process (ref lib.rs:33-86 `Manager` pyclass)."""

    def __init__(
        self,
        replica_id: str,
        lighthouse_addr: str,
        hostname: Optional[str] = None,
        bind: str = "0.0.0.0:0",
        store_addr: str = "",
        world_size: int = 1,
        heartbeat_interval: "float | timedelta" = 0.1,
        connect_timeout: "float | timedelta" = 10.0,
        exit_on_kill: bool = True,
        job_id: str = "default",
    ) -> None:
        if hostname is None:
            # The advertised address crosses hosts (it becomes peers'
            # recover_src_manager_address).
            from torchft_tpu.utils.net import advertised_host

            hostname = advertised_host()
        host, port = _split_bind(bind)
        lib = get_lib()
        err = ctypes.c_char_p()
        self._handle = lib.ft_manager_new(
            replica_id.encode(),
            lighthouse_addr.encode(),
            hostname.encode(),
            host.encode(),
            port,
            store_addr.encode(),
            world_size,
            _ms(heartbeat_interval, 100),
            _ms(connect_timeout, 10000),
            1 if exit_on_kill else 0,
            json.dumps({"job_id": job_id or "default"}).encode(),
            ctypes.byref(err),
        )
        check_error(err)
        if not self._handle:
            raise RuntimeError("failed to create manager server")

    def address(self) -> str:
        return take_string(get_lib().ft_manager_address(self._handle))

    def kill_requested(self) -> bool:
        return bool(get_lib().ft_manager_kill_requested(self._handle))

    def shutdown(self) -> None:
        if self._handle:
            get_lib().ft_manager_shutdown(self._handle)

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle:
            try:
                get_lib().ft_manager_free(handle)
            except Exception:
                pass  # interpreter teardown


class ManagerClient:
    """Blocking client to a ManagerServer (ref lib.rs:88-197; API shape
    torchft.pyi:4-21). Every call carries an explicit timeout that is also
    enforced server-side via the x-timeout-ms header."""

    def __init__(
        self, addr: str, connect_timeout: "float | timedelta" = 10.0
    ) -> None:
        lib = get_lib()
        err = ctypes.c_char_p()
        self._handle = lib.ft_manager_client_new(
            addr.encode(), _ms(connect_timeout, 10000), ctypes.byref(err)
        )
        check_error(err)
        if not self._handle:
            raise RuntimeError("failed to create manager client")

    def quorum(
        self,
        rank: int,
        step: int,
        checkpoint_metadata: str,
        shrink_only: bool,
        timeout: "float | timedelta",
        data_plane: bool = True,
        comm_epoch: int = 0,
    ) -> QuorumResult:
        err = ctypes.c_char_p()
        ptr = get_lib().ft_manager_client_quorum(
            self._handle,
            rank,
            step,
            checkpoint_metadata.encode(),
            1 if shrink_only else 0,
            1 if data_plane else 0,
            comm_epoch,
            _ms(timeout),
            ctypes.byref(err),
        )
        check_error(err)
        return QuorumResult.from_json(take_string(ptr))

    def epoch_watch(
        self, epoch: int, timeout: "float | timedelta"
    ) -> "tuple[int, bool]":
        """Park on the manager's EpochWatch proxy until the membership
        epoch moves off ``epoch`` or ~timeout elapses. Returns
        ``(current_epoch, changed)`` — ``changed=False`` at the deadline
        is a lease renewal; ``changed=True`` means the fleet moved and
        any lease granted at ``epoch`` is dead."""
        err = ctypes.c_char_p()
        ptr = get_lib().ft_manager_client_epoch_watch(
            self._handle, epoch, _ms(timeout), ctypes.byref(err)
        )
        check_error(err)
        d = json.loads(take_string(ptr))
        return int(d.get("epoch", 0)), bool(d.get("changed", False))

    def checkpoint_metadata(
        self, rank: int, timeout: "float | timedelta"
    ) -> str:
        err = ctypes.c_char_p()
        ptr = get_lib().ft_manager_client_checkpoint_metadata(
            self._handle, rank, _ms(timeout), ctypes.byref(err)
        )
        check_error(err)
        return take_string(ptr)

    def should_commit(
        self,
        rank: int,
        step: int,
        should_commit: bool,
        timeout: "float | timedelta",
    ) -> bool:
        err = ctypes.c_char_p()
        result = get_lib().ft_manager_client_should_commit(
            self._handle,
            rank,
            step,
            1 if should_commit else 0,
            _ms(timeout),
            ctypes.byref(err),
        )
        check_error(err)
        return result == 1

    def kill(self, msg: str = "", timeout: "float | timedelta" = 10.0) -> None:
        err = ctypes.c_char_p()
        get_lib().ft_manager_client_kill(
            self._handle, msg.encode(), _ms(timeout), ctypes.byref(err)
        )
        check_error(err)

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle:
            try:
                get_lib().ft_manager_client_free(handle)
            except Exception:
                pass  # interpreter teardown


class LighthouseClient:
    """Persistent client to a lighthouse: heartbeat (single or batched)
    and quorum RPCs over pooled keep-alive connections. At fleet scale
    this is the client the tier-1 aggregator / bench harness holds per
    lighthouse instead of paying a connect per heartbeat; the module-level
    ``lighthouse_heartbeat``/``lighthouse_quorum`` one-shots remain as
    thin wrappers for compatibility."""

    def __init__(self, addr: str) -> None:
        lib = get_lib()
        err = ctypes.c_char_p()
        self._handle = lib.ft_lighthouse_client_new(
            addr.encode(), ctypes.byref(err)
        )
        check_error(err)
        if not self._handle:
            raise RuntimeError("failed to create lighthouse client")

    def heartbeat(
        self,
        replica_id: "str | List[str]",
        timeout: "float | timedelta" = 5.0,
        job_id: Optional[str] = None,
    ) -> None:
        """Heartbeat one replica id, or a whole batch in ONE RPC (a list
        posts the ``replica_ids`` wire form — the per-domain aggregation
        that cuts steady-state heartbeat RPCs ~len(batch)x). ``job_id``
        routes the heartbeat to that job's shard (absent → "default")."""
        if job_id is not None:
            body: dict = (
                {"replica_ids": replica_id}
                if isinstance(replica_id, list)
                else {"replica_id": replica_id}
            )
            body["job_id"] = job_id
            payload = json.dumps(body)
        else:
            payload = json.dumps(replica_id)
        err = ctypes.c_char_p()
        get_lib().ft_lighthouse_client_heartbeat2(
            self._handle,
            payload.encode(),
            _ms(timeout),
            ctypes.byref(err),
        )
        check_error(err)

    def quorum(
        self,
        requester: dict,
        timeout: "float | timedelta" = 60.0,
        job_id: Optional[str] = None,
        extra: Optional[dict] = None,
    ) -> dict:
        """Lighthouse quorum long-poll. ``job_id`` lands the request on
        that job's shard; ``extra`` merges additional top-level request
        fields (e.g. ``priority``/``group_budget`` riding the request)."""
        if job_id is not None or extra:
            body = {"requester": requester}
            if job_id is not None:
                body["job_id"] = job_id
            if extra:
                body.update(extra)
            payload = json.dumps(body)
        else:
            payload = json.dumps(requester)
        err = ctypes.c_char_p()
        ptr = get_lib().ft_lighthouse_client_quorum2(
            self._handle,
            payload.encode(),
            _ms(timeout),
            ctypes.byref(err),
        )
        check_error(err)
        return json.loads(take_string(ptr))

    def post(self, path: str, body: dict, timeout: "float | timedelta" = 10.0) -> dict:
        """Generic lighthouse POST (RegisterJob, raw EpochWatch, ...)."""
        err = ctypes.c_char_p()
        ptr = get_lib().ft_lighthouse_client_post(
            self._handle,
            path.encode(),
            json.dumps(body).encode(),
            _ms(timeout),
            ctypes.byref(err),
        )
        check_error(err)
        return json.loads(take_string(ptr))

    def register_job(
        self,
        job_id: str,
        priority: Optional[int] = None,
        group_budget: Optional[int] = None,
        rpc_budget: Optional[int] = None,
        timeout: "float | timedelta" = 10.0,
    ) -> dict:
        """Admission registration for one job shard: priority class plus
        group/RPC budgets (last writer wins; raising or unlimiting the
        group budget re-admits previously evicted groups)."""
        body: dict = {"job_id": job_id}
        if priority is not None:
            body["priority"] = int(priority)
        if group_budget is not None:
            body["group_budget"] = int(group_budget)
        if rpc_budget is not None:
            body["rpc_budget"] = int(rpc_budget)
        return self.post(
            "/torchft.LighthouseService/RegisterJob", body, timeout
        )

    def epoch_watch(
        self,
        replica_id: str,
        epoch: int,
        timeout: "float | timedelta" = 10.0,
        job_id: Optional[str] = None,
    ) -> "tuple[int, bool]":
        """Raw lighthouse EpochWatch long-poll on the JOB's membership
        epoch (bench/test path; managers use ManagerClient.epoch_watch).
        Returns ``(current_epoch, changed)``."""
        body: dict = {"replica_id": replica_id, "epoch": int(epoch)}
        if job_id is not None:
            body["job_id"] = job_id
        d = self.post("/torchft.LighthouseService/EpochWatch", body, timeout)
        return int(d.get("epoch", 0)), bool(d.get("changed", False))

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle:
            try:
                get_lib().ft_lighthouse_client_free(handle)
            except Exception:
                pass  # interpreter teardown


def quorum_compute_raw(now_ms: int, state_json: str, opts: dict) -> str:
    """Run the pure decision kernel over a dumped QuorumState, returning
    the RAW decision JSON string — the byte-identity oracle against
    ``IncrementalQuorum.decision``."""
    err = ctypes.c_char_p()
    ptr = get_lib().ft_quorum_compute(
        now_ms,
        state_json.encode(),
        json.dumps(opts).encode(),
        ctypes.byref(err),
    )
    check_error(err)
    return take_string(ptr)


class IncrementalQuorum:
    """Driver over the native incremental quorum evaluator
    (ftquorum::IncrementalQuorum) — the epoch-cached decision plane the
    lighthouse serves at fleet scale. Exposed so property tests and
    ``scripts/bench_fleet.py`` can replay arbitrary heartbeat/join/
    expiry/install sequences and pin ``decision()`` byte-identical to a
    from-scratch ``quorum_compute_raw`` over ``state()``.

    ``now_ms`` arguments must be non-decreasing across calls (the
    lighthouse feeds a monotonic clock)."""

    def __init__(
        self,
        opts: Optional[dict] = None,
        incremental: bool = True,
        prune_after_ms: int = 0,
    ) -> None:
        lib = get_lib()
        err = ctypes.c_char_p()
        self._handle = lib.ft_iq_new(
            json.dumps(opts or {}).encode(),
            1 if incremental else 0,
            prune_after_ms,
            ctypes.byref(err),
        )
        check_error(err)
        if not self._handle:
            raise RuntimeError("failed to create incremental quorum")

    def heartbeat(self, replica_id: str, now_ms: int) -> None:
        get_lib().ft_iq_heartbeat(self._handle, replica_id.encode(), now_ms)

    def expire(self, replica_id: str, now_ms: int) -> bool:
        """The door-knock's early expiry: the alive->dead edge the sweep
        takes at heartbeat_timeout_ms, taken at ``now_ms``. False (and
        nothing changes) if the replica is not healthy."""
        return bool(
            get_lib().ft_iq_expire(self._handle, replica_id.encode(), now_ms)
        )

    def join(self, joined_ms: int, member: dict) -> None:
        err = ctypes.c_char_p()
        get_lib().ft_iq_join(
            self._handle, joined_ms, json.dumps(member).encode(),
            ctypes.byref(err),
        )
        check_error(err)

    def decision(self, now_ms: int) -> str:
        """RAW decision JSON ({"quorum": [...]|null, "reason": ...}) —
        returned unparsed so byte-level comparison is possible."""
        err = ctypes.c_char_p()
        ptr = get_lib().ft_iq_decision(
            self._handle, now_ms, ctypes.byref(err)
        )
        check_error(err)
        return take_string(ptr)

    def install(self, now_ms: int, wall_ms: int = 0) -> dict:
        """Install the current decision as prev_quorum when ready (the
        lighthouse announcement step). {"installed": bool, "quorum_id"}."""
        err = ctypes.c_char_p()
        ptr = get_lib().ft_iq_install(
            self._handle, now_ms, wall_ms, ctypes.byref(err)
        )
        check_error(err)
        return json.loads(take_string(ptr))

    def state(self) -> str:
        """RAW QuorumState JSON in the shape quorum_compute_raw consumes."""
        err = ctypes.c_char_p()
        ptr = get_lib().ft_iq_state(self._handle, ctypes.byref(err))
        check_error(err)
        return take_string(ptr)

    def counters(self) -> dict:
        err = ctypes.c_char_p()
        ptr = get_lib().ft_iq_counters(self._handle, ctypes.byref(err))
        check_error(err)
        return json.loads(take_string(ptr))

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle:
            try:
                get_lib().ft_iq_free(handle)
            except Exception:
                pass  # interpreter teardown


def lighthouse_heartbeat(
    lighthouse_addr: str, replica_id: str, timeout: "float | timedelta" = 5.0
) -> None:
    """One-shot heartbeat (thin wrapper; prefer LighthouseClient for
    long-lived callers)."""
    err = ctypes.c_char_p()
    get_lib().ft_lighthouse_client_heartbeat(
        lighthouse_addr.encode(), replica_id.encode(), _ms(timeout),
        ctypes.byref(err),
    )
    check_error(err)


def lighthouse_quorum(
    lighthouse_addr: str,
    requester: dict,
    timeout: "float | timedelta" = 60.0,
) -> dict:
    """Direct lighthouse quorum RPC (one-shot thin wrapper; used by
    tests/tools)."""
    err = ctypes.c_char_p()
    ptr = get_lib().ft_lighthouse_client_quorum(
        lighthouse_addr.encode(),
        json.dumps(requester).encode(),
        _ms(timeout),
        ctypes.byref(err),
    )
    check_error(err)
    return json.loads(take_string(ptr))
