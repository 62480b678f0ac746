// torchft_tpu native control plane — pure quorum decision kernels.
//
// Semantics match the reference's decision logic (quorum_compute at
// /root/reference/src/lighthouse.rs:113-241, compute_quorum_results at
// /root/reference/src/manager.rs:357-480) but are a fresh C++ design:
// the kernels are pure functions over value types so they can be unit-tested
// (from Python via the C API) without any server running.
#pragma once

#include <cstdint>
#include <map>
#include <optional>
#include <set>
#include <string>
#include <vector>

#include "ftjson.h"

namespace ftquorum {

// proto/torchft_tpu.proto QuorumMember.
struct Member {
  std::string replica_id;
  std::string address;        // manager control address (http://host:port)
  std::string store_address;  // rendezvous store address
  int64_t step = 0;
  uint64_t world_size = 1;
  bool shrink_only = false;
  // false = observer replica: joins the quorum and the commit barrier but
  // opts out of the gradient data plane (e.g. monitoring probes, bench
  // echo replicas on a host that cannot absorb the wire). Data-plane
  // members must never wait on an observer's transport.
  bool data_plane = true;
  // Monotonic per-replica data-plane incarnation. A replica bumps this
  // when its transport latched an error that membership change alone
  // would not clear (e.g. a timed-out collective with a stable quorum):
  // any epoch change makes quorum_changed() true, so the lighthouse
  // issues a fresh quorum_id and EVERY wire member reconfigures onto a
  // fresh rendezvous prefix together — the coordinated recovery a
  // member-local reconfigure cannot achieve. (The reference gets the
  // equivalent only via process restart: a relaunched replica's changed
  // address bumps its quorum, ref lighthouse.rs:272-283.)
  int64_t comm_epoch = 0;

  ftjson::Value to_json() const;
  static Member from_json(const ftjson::Value& v);
};

struct QuorumInfo {
  int64_t quorum_id = 0;
  std::vector<Member> participants;
  int64_t created_ms = 0;  // wall-clock epoch millis

  ftjson::Value to_json() const;
  static QuorumInfo from_json(const ftjson::Value& v);
};

struct ParticipantDetails {
  int64_t joined_ms = 0;  // monotonic ms when the replica requested quorum
  Member member;
};

// Inputs to the quorum decision, extracted from lighthouse state.
struct QuorumState {
  std::map<std::string, ParticipantDetails> participants;
  std::map<std::string, int64_t> heartbeats;  // replica_id -> monotonic ms
  std::optional<QuorumInfo> prev_quorum;
};

struct QuorumOpts {
  uint64_t min_replicas = 1;
  uint64_t join_timeout_ms = 60000;
  uint64_t quorum_tick_ms = 100;
  uint64_t heartbeat_timeout_ms = 5000;
};

struct QuorumDecision {
  std::optional<std::vector<Member>> quorum;  // nullopt = not ready
  std::string reason;
  // How many replicas are healthy by heartbeat and have not asked, when
  // they are what holds the quorum up: the straggler wait, and the
  // split-brain guard (the same absentees, counted against a majority).
  // 0 for every other decision. The holds the lighthouse's door-knock
  // acts on; not part of decision_to_json.
  size_t absent = 0;
};

// Membership (replica-id set) comparison: a quorum "changed" only when the
// ordered id list differs (ref lighthouse.rs:105-110).
bool quorum_changed(const std::vector<Member>& a, const std::vector<Member>& b);

// Reason-string builders shared by the batch kernel and the incremental
// evaluator so both planes emit byte-identical QuorumDecision JSON — the
// fleet bench's decision-equality oracle depends on this sharing, not on
// two format strings staying in sync by hand.
std::string quorum_meta(size_t healthy_participants, size_t participants,
                        size_t healthy_replicas, bool shrink_only);
std::string reason_fast(const std::string& meta);
std::string reason_min_replicas(size_t healthy_participants,
                                uint64_t min_replicas,
                                const std::string& meta);
std::string reason_split_brain(size_t healthy_participants,
                               size_t healthy_replicas,
                               const std::string& meta);
std::string reason_stragglers(size_t healthy_participants,
                              size_t stragglers, const std::string& meta);
std::string reason_valid(const std::string& meta);

// {"quorum": [members]|null, "reason": str} — one serializer for the
// kernel C API and the incremental driver (oracle byte-identity).
std::string decision_to_json(const QuorumDecision& d);

// The decision kernel. Healthy = heartbeat younger than heartbeat_timeout;
// fast-quorum when every prev-quorum member is a healthy participant;
// min_replicas floor; split-brain guard (participants must exceed half the
// healthy heartbeaters); join timeout holds the quorum open for healthy
// stragglers; shrink_only drops non-prev-members from the candidate set.
QuorumDecision quorum_compute(int64_t now_ms, const QuorumState& state,
                              const QuorumOpts& opts);

// Incrementally maintained quorum evaluator — the fleet-scale hot path.
//
// The pure kernel rescans every participant + heartbeat per evaluation, so
// one quorum round at n replica groups (n RPCs, each proactively
// re-evaluating) costs O(n^2). This class maintains the decision inputs as
// aggregates updated on state EDGES (heartbeat dead->alive, expiry
// alive->dead, participant join, quorum install) — each O(log n) — and
// caches the QuorumDecision keyed by a membership epoch that bumps only on
// those edges. Evaluations with an unchanged epoch are cache hits;
// recompute count becomes O(membership changes) instead of O(RPCs), and a
// recompute is O(1) aggregate checks unless a quorum actually materializes
// (O(n), once per round).
//
// Decisions are byte-identical to quorum_compute over the same state (the
// reason strings come from the shared builders above; candidate order is
// the participant map's key order, which IS the kernel's sorted order).
// `incremental=false` disables both the cache and the aggregate fast path
// — every decision() runs the pure kernel — which is the always-recompute
// arm of scripts/bench_fleet.py's A/B.
//
// Time handling: decision(now)/sweep(now) expect non-decreasing now_ms
// (the lighthouse feeds a monotonic clock). Expiry (a heartbeat aging
// past heartbeat_timeout_ms) and join-timeout maturation are the only
// time-driven decision changes; sweep() detects the former lazily via a
// conservative next-expiry watermark, and the cache stores an expiry
// deadline for the latter — so steady-state heartbeat refreshes never
// invalidate anything.
//
// Pruning: heartbeats dead for longer than prune_after_ms (default
// 12x heartbeat_timeout; <=0 keeps the default) are erased together with
// their stale participant entries during sweep(), with counters — the
// fix for the monotonic growth of state_.heartbeats across churn.
class IncrementalQuorum {
 public:
  explicit IncrementalQuorum(QuorumOpts opts, bool incremental = true,
                             int64_t prune_after_ms = 0);

  // -- state edges (each bumps the epoch when decision-relevant) --
  void heartbeat(const std::string& replica_id, int64_t now_ms);
  void join(int64_t joined_ms, const Member& m);
  // Expire stale heartbeats (alive->dead edges) + prune long-dead
  // entries. Cheap no-op until the conservative next-expiry/next-prune
  // watermarks pass. Called internally by decision().
  void sweep(int64_t now_ms);
  // Install a formed quorum as prev_quorum (bumping quorum_id iff
  // membership changed), clear participants for the next round.
  const QuorumInfo& install(const std::vector<Member>& members,
                            int64_t created_wall_ms);
  // Administrative removal (priority preemption): erase the replica's
  // heartbeat + participant entries in one edge. Returns true (and bumps
  // the epoch — breaking every lease on it) iff anything was erased.
  // prev_quorum is left intact: the next round simply forms without the
  // evicted member (not a fast quorum, but hp==hb once the survivors
  // rejoin, so no join-timeout stall).
  bool evict(const std::string& replica_id);
  // Early expiry (the lighthouse's door-knock saw the replica's manager
  // address refuse a connection): the alive->dead edge sweep() takes at
  // heartbeat_timeout_ms, taken at now_ms. The heartbeat entry is aged
  // to exactly the timeout, so quorum_compute over state() and
  // evaluate() keep agreeing, /status.json shows it dead, and pruning
  // counts from here. A later heartbeat() revives it like any dead id.
  // Returns false (nothing changes) if the replica is not healthy.
  bool expire(const std::string& replica_id, int64_t now_ms);

  // The decision at now_ms, served from cache when the epoch is
  // unchanged and no time deadline passed.
  const QuorumDecision& decision(int64_t now_ms);

  const QuorumState& state() const { return state_; }
  int64_t quorum_id() const { return quorum_id_; }
  bool is_healthy(const std::string& replica_id) const {
    return healthy_.count(replica_id) > 0;
  }
  size_t healthy_count() const { return healthy_.size(); }

  // -- counters (all monotonic; surfaced in /status.json "control") --
  uint64_t epoch() const { return epoch_; }
  uint64_t compute_count() const { return compute_count_; }
  uint64_t cache_hits() const { return cache_hits_; }
  uint64_t pruned_heartbeats() const { return pruned_heartbeats_; }
  uint64_t pruned_participants() const { return pruned_participants_; }
  bool incremental() const { return incremental_; }

 private:
  // A participant entered/left the healthy set, or its member payload
  // changed: fold it into (or out of) the healthy-participant aggregates.
  void add_healthy_participant(const ParticipantDetails& d);
  void remove_healthy_participant(const ParticipantDetails& d);
  // The alive->dead edge shared by sweep(), expire() and evict(): out of
  // the healthy set and, if it had asked, out of the aggregates. False if
  // it was not healthy. The caller bumps the epoch.
  bool drop_healthy(const std::string& replica_id);
  int64_t first_joined(int64_t now_ms);
  std::vector<Member> materialize(bool shrink_filter) const;
  void evaluate(int64_t now_ms);

  QuorumOpts opts_;
  bool incremental_;
  int64_t prune_after_ms_;

  QuorumState state_;
  int64_t quorum_id_ = 0;

  // Healthy = fresh heartbeat; maintained by heartbeat()/sweep().
  std::set<std::string> healthy_;
  // Aggregates over (participants ∩ healthy).
  size_t hp_count_ = 0;
  size_t hp_shrink_count_ = 0;
  int64_t hp_first_joined_ = 0;  // min joined_ms; valid iff !first_dirty_
  bool first_dirty_ = true;
  // prev-quorum presence: ids of prev members + how many of them are
  // currently healthy participants (fast-quorum = all present).
  std::set<std::string> prev_ids_;
  size_t prev_present_ = 0;

  // Conservative time watermarks (sweep is a no-op before them).
  int64_t next_expiry_ms_ = 0;
  int64_t next_prune_ms_ = 0;

  // Decision cache.
  QuorumDecision cached_;
  bool cache_valid_ = false;
  uint64_t cache_epoch_ = 0;
  int64_t cache_deadline_ms_ = 0;  // join-timeout maturation

  uint64_t epoch_ = 0;
  uint64_t compute_count_ = 0;
  uint64_t cache_hits_ = 0;
  uint64_t pruned_heartbeats_ = 0;
  uint64_t pruned_participants_ = 0;
};

// Per-rank view of an announced quorum (proto ManagerQuorumResponse).
struct QuorumResults {
  int64_t quorum_id = 0;
  std::string recover_src_manager_address;
  std::optional<int64_t> recover_src_rank;
  std::vector<int64_t> recover_dst_ranks;
  std::string store_address;
  int64_t max_step = 0;
  std::optional<int64_t> max_rank;
  int64_t max_world_size = 0;
  // Sorted replica_ids of the max-step cohort (diagnostics/labeling).
  std::vector<std::string> max_replica_ids;
  // Data-plane transport membership: the quorum participants that did not
  // opt out of the gradient wire (Member.data_plane). Healing replicas
  // stay members (they must RECEIVE the cohort average in their heal
  // step); observers are excluded so the wire never waits on them.
  // transport_rank is nullopt when this replica itself opted out.
  std::optional<int64_t> transport_rank;
  int64_t transport_world_size = 0;
  std::vector<std::string> transport_replica_ids;
  int64_t replica_rank = 0;
  int64_t replica_world_size = 0;
  bool heal = false;

  ftjson::Value to_json() const;
};

// Recovery-assignment kernel: sorts participants by replica_id, derives the
// caller's replica_rank, the max-step cohort, the primary store, and the
// round-robin mapping of recovering replicas onto up-to-date sources offset
// by the caller's local rank (so different local ranks pull from different
// donors). Throws std::runtime_error if replica_id is absent from quorum.
QuorumResults compute_quorum_results(const std::string& replica_id,
                                     int64_t rank, const QuorumInfo& quorum);

}  // namespace ftquorum
