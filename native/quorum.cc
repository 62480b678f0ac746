#include "quorum.h"

#include <algorithm>
#include <set>
#include <sstream>
#include <stdexcept>

namespace ftquorum {

ftjson::Value Member::to_json() const {
  ftjson::Object o;
  o["replica_id"] = replica_id;
  o["address"] = address;
  o["store_address"] = store_address;
  o["step"] = step;
  o["world_size"] = static_cast<int64_t>(world_size);
  o["shrink_only"] = shrink_only;
  o["data_plane"] = data_plane;
  o["comm_epoch"] = comm_epoch;
  return ftjson::Value(std::move(o));
}

Member Member::from_json(const ftjson::Value& v) {
  Member m;
  m.replica_id = v.get_str("replica_id");
  m.address = v.get_str("address");
  m.store_address = v.get_str("store_address");
  m.step = v.get_int("step");
  m.world_size = static_cast<uint64_t>(v.get_int("world_size", 1));
  m.shrink_only = v.get_bool("shrink_only");
  m.data_plane = v.get_bool("data_plane", true);
  m.comm_epoch = v.get_int("comm_epoch", 0);
  return m;
}

ftjson::Value QuorumInfo::to_json() const {
  ftjson::Object o;
  o["quorum_id"] = quorum_id;
  ftjson::Array parts;
  for (const auto& p : participants) parts.push_back(p.to_json());
  o["participants"] = ftjson::Value(std::move(parts));
  o["created_ms"] = created_ms;
  return ftjson::Value(std::move(o));
}

QuorumInfo QuorumInfo::from_json(const ftjson::Value& v) {
  QuorumInfo q;
  q.quorum_id = v.get_int("quorum_id");
  q.created_ms = v.get_int("created_ms");
  for (const auto& p : v.get("participants").as_array()) {
    q.participants.push_back(Member::from_json(p));
  }
  return q;
}

bool quorum_changed(const std::vector<Member>& a,
                    const std::vector<Member>& b) {
  if (a.size() != b.size()) return true;
  for (size_t i = 0; i < a.size(); i++) {
    if (a[i].replica_id != b[i].replica_id) return true;
    // A bumped data-plane incarnation is a membership change for
    // transport purposes: the fresh quorum_id it forces is what makes
    // every wire member reconfigure together (see Member::comm_epoch).
    if (a[i].comm_epoch != b[i].comm_epoch) return true;
  }
  return false;
}

std::string quorum_meta(size_t healthy_participants, size_t participants,
                        size_t healthy_replicas, bool shrink_only) {
  std::ostringstream meta;
  meta << "[" << healthy_participants << "/" << participants
       << " participants healthy][" << healthy_replicas
       << " heartbeating][shrink_only=" << (shrink_only ? "true" : "false")
       << "]";
  return meta.str();
}

std::string reason_fast(const std::string& meta) {
  return "Fast quorum found! " + meta;
}

std::string reason_min_replicas(size_t healthy_participants,
                                uint64_t min_replicas,
                                const std::string& meta) {
  std::ostringstream r;
  r << "New quorum not ready, only have " << healthy_participants
    << " participants, need min_replicas " << min_replicas << " " << meta;
  return r.str();
}

std::string reason_split_brain(size_t healthy_participants,
                               size_t healthy_replicas,
                               const std::string& meta) {
  std::ostringstream r;
  r << "New quorum not ready, only have " << healthy_participants
    << " participants, need at least half of " << healthy_replicas
    << " healthy workers " << meta;
  return r.str();
}

std::string reason_stragglers(size_t healthy_participants, size_t stragglers,
                              const std::string& meta) {
  std::ostringstream r;
  r << "Valid quorum with " << healthy_participants
    << " participants, waiting for " << stragglers
    << " healthy but not participating stragglers due to join timeout "
    << meta;
  return r.str();
}

std::string reason_valid(const std::string& meta) {
  return "Valid quorum found " + meta;
}

std::string decision_to_json(const QuorumDecision& d) {
  ftjson::Object out;
  if (d.quorum.has_value()) {
    ftjson::Array members;
    for (const auto& m : *d.quorum) members.push_back(m.to_json());
    out["quorum"] = ftjson::Value(std::move(members));
  } else {
    out["quorum"] = ftjson::Value(nullptr);
  }
  out["reason"] = d.reason;
  return ftjson::Value(std::move(out)).dump();
}

QuorumDecision quorum_compute(int64_t now_ms, const QuorumState& state,
                              const QuorumOpts& opts) {
  // A replica is healthy iff its last heartbeat is fresh.
  std::set<std::string> healthy_replicas;
  for (const auto& hb : state.heartbeats) {
    if (now_ms - hb.second <
        static_cast<int64_t>(opts.heartbeat_timeout_ms)) {
      healthy_replicas.insert(hb.first);
    }
  }

  // Participants (replicas that actually requested a quorum) that are healthy.
  std::vector<const ParticipantDetails*> healthy_participants;
  for (const auto& kv : state.participants) {
    if (healthy_replicas.count(kv.first)) {
      healthy_participants.push_back(&kv.second);
    }
  }

  std::vector<Member> candidates;
  candidates.reserve(healthy_participants.size());
  for (const auto* d : healthy_participants) candidates.push_back(d->member);
  std::sort(candidates.begin(), candidates.end(),
            [](const Member& a, const Member& b) {
              return a.replica_id < b.replica_id;
            });

  bool shrink_only = false;
  for (const auto* d : healthy_participants) {
    if (d->member.shrink_only) shrink_only = true;
  }

  std::string meta =
      quorum_meta(healthy_participants.size(), state.participants.size(),
                  healthy_replicas.size(), shrink_only);

  if (state.prev_quorum.has_value()) {
    const QuorumInfo& prev = *state.prev_quorum;
    std::set<std::string> prev_ids;
    for (const auto& p : prev.participants) prev_ids.insert(p.replica_id);

    if (shrink_only) {
      std::vector<Member> filtered;
      for (auto& c : candidates) {
        if (prev_ids.count(c.replica_id)) filtered.push_back(c);
      }
      candidates = std::move(filtered);
    }

    // Fast quorum: every member of the previous quorum is a healthy
    // participant again, so no need to wait out the join timeout.
    std::set<std::string> healthy_participant_ids;
    for (const auto* d : healthy_participants) {
      healthy_participant_ids.insert(d->member.replica_id);
    }
    bool is_fast = true;
    for (const auto& p : prev.participants) {
      if (!healthy_participant_ids.count(p.replica_id)) {
        is_fast = false;
        break;
      }
    }
    if (is_fast) {
      return {candidates, reason_fast(meta)};
    }
  }

  if (healthy_participants.size() < opts.min_replicas) {
    return {std::nullopt,
            reason_min_replicas(healthy_participants.size(),
                                opts.min_replicas, meta)};
  }

  // Split-brain guard: require a strict majority of the healthy heartbeaters
  // to be participating before forming a quorum without them.
  if (healthy_participants.size() <= healthy_replicas.size() / 2) {
    return {std::nullopt,
            reason_split_brain(healthy_participants.size(),
                               healthy_replicas.size(), meta),
            healthy_replicas.size() - healthy_participants.size()};
  }

  bool all_healthy_joined =
      healthy_participants.size() == healthy_replicas.size();
  int64_t first_joined = now_ms;
  for (const auto* d : healthy_participants) {
    first_joined = std::min(first_joined, d->joined_ms);
  }
  if (!all_healthy_joined &&
      now_ms - first_joined < static_cast<int64_t>(opts.join_timeout_ms)) {
    size_t absent = healthy_replicas.size() - healthy_participants.size();
    return {std::nullopt,
            reason_stragglers(healthy_participants.size(), absent, meta),
            absent};
  }

  return {candidates, reason_valid(meta)};
}

// ------------------------------------------------------ IncrementalQuorum

namespace {
constexpr int64_t kNever = INT64_MAX;
}  // namespace

IncrementalQuorum::IncrementalQuorum(QuorumOpts opts, bool incremental,
                                     int64_t prune_after_ms)
    : opts_(opts),
      incremental_(incremental),
      prune_after_ms_(
          prune_after_ms > 0
              ? prune_after_ms
              : 12 * static_cast<int64_t>(opts.heartbeat_timeout_ms)) {}

void IncrementalQuorum::add_healthy_participant(
    const ParticipantDetails& d) {
  hp_count_ += 1;
  if (d.member.shrink_only) hp_shrink_count_ += 1;
  if (prev_ids_.count(d.member.replica_id)) prev_present_ += 1;
  if (!first_dirty_) {
    hp_first_joined_ = std::min(hp_first_joined_, d.joined_ms);
  }
}

void IncrementalQuorum::remove_healthy_participant(
    const ParticipantDetails& d) {
  hp_count_ -= 1;
  if (d.member.shrink_only) hp_shrink_count_ -= 1;
  if (prev_ids_.count(d.member.replica_id)) prev_present_ -= 1;
  // Removing the min holder invalidates the maintained min; removals are
  // membership-change edges (rare), so the lazy O(n) recompute on the
  // next decision is bounded by the same edge count as the recompute
  // itself.
  if (!first_dirty_ && d.joined_ms == hp_first_joined_) first_dirty_ = true;
}

bool IncrementalQuorum::drop_healthy(const std::string& replica_id) {
  if (!healthy_.erase(replica_id)) return false;
  auto pit = state_.participants.find(replica_id);
  if (pit != state_.participants.end()) {
    remove_healthy_participant(pit->second);
  }
  return true;
}

int64_t IncrementalQuorum::first_joined(int64_t now_ms) {
  if (first_dirty_) {
    hp_first_joined_ = kNever;
    for (const auto& kv : state_.participants) {
      if (healthy_.count(kv.first)) {
        hp_first_joined_ = std::min(hp_first_joined_, kv.second.joined_ms);
      }
    }
    first_dirty_ = false;
  }
  return std::min(now_ms, hp_first_joined_);
}

void IncrementalQuorum::heartbeat(const std::string& replica_id,
                                  int64_t now_ms) {
  state_.heartbeats[replica_id] = now_ms;
  // Keep the expiry watermark conservative: this entry expires at
  // now+timeout, which may be earlier than whatever the last sweep saw
  // (in particular after a sweep over an empty/fully-pruned table).
  next_expiry_ms_ = std::min(
      next_expiry_ms_,
      now_ms + static_cast<int64_t>(opts_.heartbeat_timeout_ms));
  if (healthy_.insert(replica_id).second) {
    // dead->alive (or first sighting): a decision input changed.
    epoch_ += 1;
    auto it = state_.participants.find(replica_id);
    if (it != state_.participants.end()) add_healthy_participant(it->second);
  }
  // alive->alive refresh: no epoch bump — the decision is a function of
  // the healthy SET, not of heartbeat ages.
}

void IncrementalQuorum::join(int64_t joined_ms, const Member& m) {
  auto it = state_.participants.find(m.replica_id);
  bool healthy = healthy_.count(m.replica_id) > 0;
  if (it != state_.participants.end()) {
    if (healthy) remove_healthy_participant(it->second);
    it->second.joined_ms = joined_ms;
    it->second.member = m;
    if (healthy) add_healthy_participant(it->second);
  } else {
    ParticipantDetails d;
    d.joined_ms = joined_ms;
    d.member = m;
    auto ins = state_.participants.emplace(m.replica_id, std::move(d));
    if (healthy) add_healthy_participant(ins.first->second);
  }
  // The member payload (step, shrink_only, comm_epoch...) rides into the
  // decision's candidate list, so every (re)join is decision-relevant.
  epoch_ += 1;
}

void IncrementalQuorum::sweep(int64_t now_ms) {
  if (now_ms < next_expiry_ms_ && now_ms < next_prune_ms_) return;
  const int64_t hb_timeout =
      static_cast<int64_t>(opts_.heartbeat_timeout_ms);
  next_expiry_ms_ = kNever;
  next_prune_ms_ = kNever;
  for (auto it = state_.heartbeats.begin();
       it != state_.heartbeats.end();) {
    int64_t age = now_ms - it->second;
    if (age < hb_timeout) {
      next_expiry_ms_ = std::min(next_expiry_ms_, it->second + hb_timeout);
      ++it;
      continue;
    }
    if (drop_healthy(it->first)) epoch_ += 1;  // alive->dead edge
    if (age >= prune_after_ms_) {
      // Long-dead: drop the heartbeat entry AND any stale participant
      // record so neither the decision scan nor /status.json grows
      // monotonically across churn. A pruned replica that comes back
      // simply re-registers via heartbeat + join.
      auto pit = state_.participants.find(it->first);
      if (pit != state_.participants.end()) {
        state_.participants.erase(pit);
        pruned_participants_ += 1;
        // participants.size() appears in the decision meta string.
        epoch_ += 1;
      }
      pruned_heartbeats_ += 1;
      it = state_.heartbeats.erase(it);
    } else {
      next_prune_ms_ = std::min(next_prune_ms_, it->second + prune_after_ms_);
      ++it;
    }
  }
}

std::vector<Member> IncrementalQuorum::materialize(
    bool shrink_filter) const {
  std::vector<Member> out;
  out.reserve(hp_count_);
  // The participant map iterates in replica_id order — exactly the
  // kernel's sorted candidate order.
  for (const auto& kv : state_.participants) {
    if (!healthy_.count(kv.first)) continue;
    if (shrink_filter && !prev_ids_.count(kv.first)) continue;
    out.push_back(kv.second.member);
  }
  return out;
}

void IncrementalQuorum::evaluate(int64_t now_ms) {
  const size_t hp = hp_count_;
  const size_t hb = healthy_.size();
  const bool shrink = hp_shrink_count_ > 0;
  const bool has_prev = state_.prev_quorum.has_value();
  std::string meta =
      quorum_meta(hp, state_.participants.size(), hb, shrink);
  cache_deadline_ms_ = kNever;

  if (has_prev && prev_present_ == prev_ids_.size()) {
    cached_ = {materialize(shrink), reason_fast(meta)};
    return;
  }
  if (hp < opts_.min_replicas) {
    cached_ = {std::nullopt, reason_min_replicas(hp, opts_.min_replicas,
                                                 meta)};
    return;
  }
  if (hp <= hb / 2) {
    cached_ = {std::nullopt, reason_split_brain(hp, hb, meta), hb - hp};
    return;
  }
  if (hp != hb) {
    int64_t first = first_joined(now_ms);
    int64_t matures = first + static_cast<int64_t>(opts_.join_timeout_ms);
    if (now_ms < matures) {
      cached_ = {std::nullopt, reason_stragglers(hp, hb - hp, meta),
                 hb - hp};
      // The only decision transition driven purely by time passing with
      // no state edge: the join timeout maturing.
      cache_deadline_ms_ = matures;
      return;
    }
  }
  cached_ = {materialize(shrink && has_prev), reason_valid(meta)};
}

const QuorumDecision& IncrementalQuorum::decision(int64_t now_ms) {
  sweep(now_ms);  // may bump epoch_ on expiry/prune edges
  if (cache_valid_ && cache_epoch_ == epoch_ &&
      now_ms < cache_deadline_ms_) {
    cache_hits_ += 1;
    return cached_;
  }
  compute_count_ += 1;
  if (incremental_) {
    evaluate(now_ms);
  } else {
    cached_ = quorum_compute(now_ms, state_, opts_);
    cache_deadline_ms_ = 0;  // always-recompute arm: never serve cached
  }
  cache_valid_ = incremental_;
  cache_epoch_ = epoch_;
  return cached_;
}

bool IncrementalQuorum::evict(const std::string& replica_id) {
  bool erased = drop_healthy(replica_id);
  if (state_.participants.erase(replica_id)) {
    // participants.size() appears in the decision meta string.
    erased = true;
  }
  if (state_.heartbeats.erase(replica_id)) erased = true;
  if (erased) epoch_ += 1;
  return erased;
}

bool IncrementalQuorum::expire(const std::string& replica_id,
                               int64_t now_ms) {
  if (!drop_healthy(replica_id)) return false;
  int64_t stamp = now_ms - static_cast<int64_t>(opts_.heartbeat_timeout_ms);
  state_.heartbeats[replica_id] = stamp;
  next_prune_ms_ = std::min(next_prune_ms_, stamp + prune_after_ms_);
  epoch_ += 1;
  return true;
}

const QuorumInfo& IncrementalQuorum::install(
    const std::vector<Member>& members, int64_t created_wall_ms) {
  if (!state_.prev_quorum.has_value() ||
      quorum_changed(members, state_.prev_quorum->participants)) {
    quorum_id_ += 1;
  }
  QuorumInfo q;
  q.quorum_id = quorum_id_;
  q.participants = members;
  q.created_ms = created_wall_ms;
  state_.prev_quorum = std::move(q);

  prev_ids_.clear();
  for (const auto& p : state_.prev_quorum->participants) {
    prev_ids_.insert(p.replica_id);
  }
  // Each round requires a fresh request from every replica.
  state_.participants.clear();
  hp_count_ = 0;
  hp_shrink_count_ = 0;
  prev_present_ = 0;
  first_dirty_ = true;
  epoch_ += 1;
  return *state_.prev_quorum;
}

ftjson::Value QuorumResults::to_json() const {
  ftjson::Object o;
  o["quorum_id"] = quorum_id;
  o["recover_src_manager_address"] = recover_src_manager_address;
  o["recover_src_rank"] = recover_src_rank.has_value()
                              ? ftjson::Value(*recover_src_rank)
                              : ftjson::Value(nullptr);
  ftjson::Array dst;
  for (int64_t r : recover_dst_ranks) dst.push_back(r);
  o["recover_dst_ranks"] = ftjson::Value(std::move(dst));
  o["store_address"] = store_address;
  o["max_step"] = max_step;
  o["max_rank"] = max_rank.has_value() ? ftjson::Value(*max_rank)
                                       : ftjson::Value(nullptr);
  o["max_world_size"] = max_world_size;
  ftjson::Array ids;
  for (const auto& id : max_replica_ids) ids.push_back(id);
  o["max_replica_ids"] = ftjson::Value(std::move(ids));
  o["transport_rank"] = transport_rank.has_value()
                            ? ftjson::Value(*transport_rank)
                            : ftjson::Value(nullptr);
  o["transport_world_size"] = transport_world_size;
  ftjson::Array tids;
  for (const auto& id : transport_replica_ids) tids.push_back(id);
  o["transport_replica_ids"] = ftjson::Value(std::move(tids));
  o["replica_rank"] = replica_rank;
  o["replica_world_size"] = replica_world_size;
  o["heal"] = heal;
  return ftjson::Value(std::move(o));
}

QuorumResults compute_quorum_results(const std::string& replica_id,
                                     int64_t rank, const QuorumInfo& quorum) {
  std::vector<Member> participants = quorum.participants;
  std::sort(participants.begin(), participants.end(),
            [](const Member& a, const Member& b) {
              return a.replica_id < b.replica_id;
            });

  int64_t replica_rank = -1;
  for (size_t i = 0; i < participants.size(); i++) {
    if (participants[i].replica_id == replica_id) {
      replica_rank = static_cast<int64_t>(i);
      break;
    }
  }
  if (replica_rank < 0) {
    throw std::runtime_error("replica " + replica_id +
                             " not participating in returned quorum");
  }

  // Observers (data_plane=false) are invisible to all step/recovery
  // logic: they are not electable as primary/donor, never recovery
  // destinations, don't define max_step, and are not counted in the
  // participating cohort — they join only the quorum and the commit
  // barrier. (A degenerate all-observer quorum falls back to treating
  // everyone as data-plane so the kernel stays total.)
  std::vector<size_t> dp_indices;
  for (size_t i = 0; i < participants.size(); i++) {
    if (participants[i].data_plane) dp_indices.push_back(i);
  }
  if (dp_indices.empty()) {
    for (size_t i = 0; i < participants.size(); i++) dp_indices.push_back(i);
  }

  int64_t max_step = 0;
  for (size_t i : dp_indices) {
    max_step = std::max(max_step, participants[i].step);
  }

  // Index list of the up-to-date ("max step") data-plane cohort.
  std::vector<size_t> max_indices;
  for (size_t i : dp_indices) {
    if (participants[i].step == max_step) max_indices.push_back(i);
  }

  std::optional<int64_t> max_rank;
  for (size_t mi = 0; mi < max_indices.size(); mi++) {
    if (participants[max_indices[mi]].replica_id == replica_id) {
      max_rank = static_cast<int64_t>(mi);
      break;
    }
  }

  // Primary store for this local rank, spread over the max-step cohort.
  const Member& primary =
      participants[max_indices[static_cast<size_t>(rank) %
                               max_indices.size()]];

  // Recovering replicas: behind max_step, or (step 0 bootstrap) everyone but
  // the primary so that all replicas sync identical initial state.
  // Observers are excluded: assigning one as a perpetual recover_dst would
  // make every donor restage a full checkpoint each quorum round.
  std::vector<size_t> recover_dst;
  std::set<size_t> recover_dst_set;
  for (size_t i : dp_indices) {
    if (participants[i].step != max_step ||
        (max_step == 0 && primary.replica_id != participants[i].replica_id)) {
      recover_dst.push_back(i);
      recover_dst_set.insert(i);
    }
  }
  std::vector<size_t> up_to_date;
  for (size_t i : dp_indices) {
    if (!recover_dst_set.count(i)) up_to_date.push_back(i);
  }

  // Round-robin recovering→source assignment, offset by the local rank so
  // that different local ranks of the same healing replica pull from
  // different donor replicas.
  std::map<size_t, std::vector<int64_t>> assignments;
  std::optional<int64_t> recover_src_rank;
  for (size_t i = 0; i < recover_dst.size(); i++) {
    size_t src =
        up_to_date[(i + static_cast<size_t>(rank)) % up_to_date.size()];
    assignments[src].push_back(static_cast<int64_t>(recover_dst[i]));
    if (static_cast<int64_t>(recover_dst[i]) == replica_rank) {
      recover_src_rank = static_cast<int64_t>(src);
    }
  }

  QuorumResults out;
  out.quorum_id = quorum.quorum_id;
  out.recover_src_rank = recover_src_rank;
  out.heal = recover_src_rank.has_value();
  if (recover_src_rank.has_value()) {
    out.recover_src_manager_address =
        participants[static_cast<size_t>(*recover_src_rank)].address;
  }
  auto it = assignments.find(static_cast<size_t>(replica_rank));
  if (it != assignments.end()) out.recover_dst_ranks = it->second;
  out.store_address = primary.store_address;
  out.max_step = max_step;
  out.max_rank = max_rank;
  out.max_world_size = static_cast<int64_t>(max_indices.size());
  for (size_t mi : max_indices) {
    out.max_replica_ids.push_back(participants[mi].replica_id);
  }
  // Data-plane membership: everyone who did not opt out, in sorted order
  // (so all members derive identical transport ranks). Uses dp_indices,
  // not the per-member flag, so the all-observer degenerate fallback
  // (dp_indices = full membership above) emits a coherent wire instead of
  // electing observer primaries/donors while leaving the transport empty
  // for Python's legacy-control-plane branch to guess at.
  for (size_t i : dp_indices) {
    const auto& p = participants[i];
    if (p.replica_id == replica_id) {
      out.transport_rank =
          static_cast<int64_t>(out.transport_replica_ids.size());
    }
    out.transport_replica_ids.push_back(p.replica_id);
  }
  out.transport_world_size =
      static_cast<int64_t>(out.transport_replica_ids.size());
  out.replica_rank = replica_rank;
  out.replica_world_size = static_cast<int64_t>(participants.size());
  return out;
}

}  // namespace ftquorum
