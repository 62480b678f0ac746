#include "lighthouse.h"

#include <sys/socket.h>
#include <unistd.h>

#include <cerrno>
#include <chrono>
#include <cstdio>
#include <sstream>

namespace ftlighthouse {

using fthttp::Request;
using fthttp::Response;
using ftquorum::Member;
using ftquorum::QuorumInfo;

namespace {
int64_t wall_ms() {
  return std::chrono::duration_cast<std::chrono::milliseconds>(
             std::chrono::system_clock::now().time_since_epoch())
      .count();
}

std::string html_escape(const std::string& s) {
  std::string out;
  for (char c : s) {
    switch (c) {
      case '<':
        out += "&lt;";
        break;
      case '>':
        out += "&gt;";
        break;
      case '&':
        out += "&amp;";
        break;
      case '"':
        out += "&quot;";
        break;
      case '\'':
        out += "&#39;";
        break;
      default:
        out += c;
    }
  }
  return out;
}

std::string normalize_job(const std::string& job_id) {
  return job_id.empty() ? std::string("default") : job_id;
}

// The prescriptive eviction decision: an evicted group learns its fate
// in a quorum response body — never by watching its RPCs time out. The
// body is shaped like a lease-less quorum reply minus the member list,
// plus `evicted:true`; the manager surfaces it to every rank so the
// job's survivors shrink through the redistribution planner while the
// victim exits cleanly.
Response eviction_response(const std::string& job_id, JobState& job) {
  ftjson::Object o;
  o["evicted"] = true;
  o["job_id"] = job_id;
  o["reason"] = std::string("evicted: preempted by higher-priority job");
  o["membership_epoch"] = static_cast<int64_t>(job.iq.epoch());
  o["lease_ms"] = static_cast<int64_t>(0);
  return Response{200, "application/json", ftjson::Value(std::move(o)).dump()};
}
}  // namespace

Lighthouse::Lighthouse(LighthouseOpts opts)
    : opts_(std::move(opts)), server_(opts_.bind_host, opts_.port) {
  if (opts_.tier < 0) opts_.tier = opts_.upstream_addr.empty() ? 0 : 1;
  if (opts_.domain.empty() && opts_.tier > 0) {
    opts_.domain = "domain:" + std::to_string(server_.port());
  }
  // The default shard exists from birth so pre-multi-tenant clients and
  // status payloads never observe a jobless lighthouse.
  jobs_.emplace("default", std::make_unique<JobState>(opts_));
  server_.set_handler([this](const Request& req) { return handle(req); });
}

Lighthouse::~Lighthouse() { shutdown(); }

void Lighthouse::start() {
  server_.start();
  tick_thread_ = std::thread([this] { tick_loop(); });
}

void Lighthouse::shutdown() {
  {
    std::lock_guard<std::mutex> lk(mu_);
    if (stopping_) return;
    stopping_ = true;
  }
  cv_.notify_all();
  if (tick_thread_.joinable()) tick_thread_.join();
  server_.shutdown();
}

std::string Lighthouse::address() const {
  std::string host = opts_.hostname;
  if (host.empty()) {
    if (!opts_.bind_host.empty() && opts_.bind_host != "0.0.0.0" &&
        opts_.bind_host != "[::]") {
      host = opts_.bind_host;
    } else {
      char buf[256];
      host = (gethostname(buf, sizeof(buf)) == 0) ? buf : "127.0.0.1";
    }
  }
  return "http://" + host + ":" + std::to_string(server_.port());
}

JobState& Lighthouse::job_locked(const std::string& job_id) {
  std::string key = normalize_job(job_id);
  auto it = jobs_.find(key);
  if (it == jobs_.end()) {
    it = jobs_.emplace(key, std::make_unique<JobState>(opts_)).first;
  }
  return *it->second;
}

bool Lighthouse::rate_limited_locked(JobState& job, int64_t now_ms) {
  if (job.rpc_budget <= 0) return false;
  if (now_ms - job.rpc_window_start_ms >= 1000) {
    job.rpc_window_start_ms = now_ms;
    job.rpc_window_count = 0;
  }
  if (job.rpc_window_count >= job.rpc_budget) {
    job.rate_limit_drops += 1;
    return true;
  }
  job.rpc_window_count += 1;
  return false;
}

void Lighthouse::maybe_preempt_locked(const std::string& claimant_id,
                                      JobState& claimant) {
  if (opts_.fleet_capacity <= 0) return;
  int64_t total = 0;
  for (const auto& kv : jobs_) {
    total += static_cast<int64_t>(kv.second->iq.healthy_count());
  }
  // Minimal preemption: evict exactly one group per capacity overrun,
  // never below capacity, and only from jobs that are BOTH over their
  // own group budget and strictly lower-priority than the claimant.
  while (total > opts_.fleet_capacity) {
    JobState* victim = nullptr;
    std::string victim_name;
    for (const auto& kv : jobs_) {
      JobState* j = kv.second.get();
      if (j == &claimant) continue;
      if (j->priority >= claimant.priority) continue;
      if (j->group_budget <= 0) continue;  // unlimited budget: not evictable
      if (static_cast<int64_t>(j->iq.healthy_count()) <= j->group_budget) {
        continue;
      }
      if (!victim || j->priority < victim->priority ||
          (j->priority == victim->priority && kv.first < victim_name)) {
        victim = j;
        victim_name = kv.first;
      }
    }
    if (!victim) return;
    // Evict the max replica_id among the victim's healthy members: a
    // deterministic choice both sides can reconstruct from status alone.
    std::string evict_id;
    for (const auto& hb : victim->iq.state().heartbeats) {
      if (victim->iq.is_healthy(hb.first)) evict_id = hb.first;
    }
    if (evict_id.empty()) return;
    victim->iq.evict(evict_id);
    victim->evicted.insert(evict_id);
    victim->preemptions += 1;
    total -= 1;
    // The epoch bump breaks the victim job's leases: parked EpochWatch
    // waiters wake with changed=true, survivors fall back to the full
    // Quorum path and re-form, and the evicted member's own Quorum RPC
    // returns the prescriptive body above.
    cv_.notify_all();
  }
  (void)claimant_id;
}

std::vector<std::string> Lighthouse::build_domain_reports_locked(
    int64_t now_ms) {
  std::vector<std::string> bodies;
  for (const auto& kv : jobs_) {
    const JobState& job = *kv.second;
    // Silent shards (no members ever) would only add noise upstream.
    if (job.iq.state().heartbeats.empty() &&
        !job.iq.state().prev_quorum.has_value() && kv.first != "default") {
      continue;
    }
    ftjson::Object o;
    o["domain"] = kv.first == "default"
                      ? opts_.domain
                      : opts_.domain + "/job:" + kv.first;
    o["tier"] = static_cast<int64_t>(opts_.tier);
    o["address"] = address();
    o["job_id"] = kv.first;
    o["healthy"] = static_cast<int64_t>(job.iq.healthy_count());
    o["participants"] =
        static_cast<int64_t>(job.iq.state().participants.size());
    int64_t quorum_id = 0;
    int64_t max_step = 0;
    if (job.iq.state().prev_quorum.has_value()) {
      const auto& q = *job.iq.state().prev_quorum;
      quorum_id = q.quorum_id;
      for (const auto& p : q.participants)
        max_step = std::max(max_step, p.step);
    }
    o["quorum_id"] = quorum_id;
    o["max_step"] = max_step;
    o["report_interval_ms"] =
        static_cast<int64_t>(opts_.upstream_report_interval_ms);
    bodies.push_back(ftjson::Value(std::move(o)).dump());
  }
  (void)now_ms;
  return bodies;
}

void Lighthouse::tick_loop() {
  std::unique_lock<std::mutex> lk(mu_);
  int64_t last_report_ms = 0;
  std::string up_host;
  int up_port = 0;
  bool up_ok = !opts_.upstream_addr.empty() &&
               fthttp::parse_http_addr(opts_.upstream_addr, &up_host,
                                       &up_port);
  while (!stopping_) {
    // One pass over every shard: a stable job's decision() is an epoch
    // cache hit, so the per-tick cost of quiet tenants is O(1) each.
    for (auto& kv : jobs_) tick_job_locked(*kv.second);
    std::vector<DoorKnock> knocks = collect_knocks_locked(fthttp::now_ms());
    if (!knocks.empty()) {
      std::vector<std::string> addrs;
      for (const auto& k : knocks) addrs.push_back(k.address);
      // Never connect while holding the state lock: a listener that
      // accepts nothing must not delay a heartbeat or a quorum RPC.
      lk.unlock();
      std::vector<fthttp::Knock> found = fthttp::knock(
          addrs, fthttp::now_ms() +
                     static_cast<int64_t>(opts_.quorum.quorum_tick_ms));
      lk.lock();
      if (stopping_) break;
      apply_knocks_locked(knocks, found, fthttp::now_ms());
    }
    // Evict domain rows silent far past their own advertised interval
    // (well after the 3x staleness flag, so operators see the STALE row
    // first): an aggregator restarting under a fresh generated domain
    // name must not grow the root's map forever — the same monotonic-
    // growth hygiene sweep() applies to heartbeats.
    if (!domains_.empty()) {
      int64_t now = fthttp::now_ms();
      for (auto it = domains_.begin(); it != domains_.end();) {
        int64_t expire =
            std::max<int64_t>(20 * it->second.report_interval_ms, 3000);
        if (now - it->second.received_ms > expire) {
          it = domains_.erase(it);
          domains_pruned_ += 1;
        } else {
          ++it;
        }
      }
    }
    if (up_ok) {
      int64_t now = fthttp::now_ms();
      int64_t interval =
          static_cast<int64_t>(opts_.upstream_report_interval_ms);
      if (now - last_report_ms >= interval) {
        last_report_ms = now;
        std::vector<std::string> bodies = build_domain_reports_locked(now);
        // Never post while holding the state lock; a slow/dead root
        // must not block heartbeats or quorum RPCs.
        lk.unlock();
        for (const auto& body : bodies) {
          fthttp::http_post(up_host, up_port,
                            "/torchft.LighthouseService/DomainReport", body,
                            fthttp::now_ms() + interval);
        }
        lk.lock();
        if (stopping_) break;
      }
    }
    cv_.wait_for(lk, std::chrono::milliseconds(opts_.quorum.quorum_tick_ms),
                 [this] { return stopping_; });
  }
}

std::vector<Lighthouse::DoorKnock> Lighthouse::collect_knocks_locked(
    int64_t now_ms) {
  std::vector<DoorKnock> knocks;
  const int64_t two_ticks =
      2 * static_cast<int64_t>(opts_.quorum.quorum_tick_ms);
  for (const auto& kv : jobs_) {
    const JobState& job = *kv.second;
    if (job.held_since_ms < 0 || now_ms - job.held_since_ms < two_ticks) {
      continue;
    }
    const auto& state = job.iq.state();
    if (!state.prev_quorum.has_value() || state.participants.empty()) {
      continue;
    }
    // Only a member of the last quorum has given an address; a replica
    // known by heartbeat alone is left to the timeout.
    for (const auto& m : state.prev_quorum->participants) {
      if (!job.iq.is_healthy(m.replica_id)) continue;
      if (state.participants.count(m.replica_id)) continue;
      knocks.push_back({kv.first, m.replica_id, m.address,
                        state.heartbeats.at(m.replica_id)});
    }
  }
  return knocks;
}

void Lighthouse::apply_knocks_locked(const std::vector<DoorKnock>& knocks,
                                     const std::vector<fthttp::Knock>& found,
                                     int64_t now_ms) {
  std::set<JobState*> moved;
  for (size_t i = 0; i < knocks.size(); i++) {
    const DoorKnock& k = knocks[i];
    if (found[i] == fthttp::Knock::kNoAddress) continue;
    JobState& job = *jobs_.at(k.job_id);  // shards are never erased
    job.door_knocks += 1;
    if (found[i] != fthttp::Knock::kRefused) continue;
    // It beat or asked while the tick thread knocked: it lives.
    const auto& state = job.iq.state();
    auto hb = state.heartbeats.find(k.replica_id);
    if (hb == state.heartbeats.end() || hb->second != k.heartbeat_ms ||
        state.participants.count(k.replica_id)) {
      continue;
    }
    if (!job.iq.expire(k.replica_id, now_ms)) continue;
    job.refused_expiries += 1;
    moved.insert(&job);
    fprintf(stderr,
            "[torchft_tpu lighthouse] job %s: replica %s expired, its "
            "manager address %s refused a connection\n",
            k.job_id.c_str(), k.replica_id.c_str(), k.address.c_str());
  }
  for (JobState* job : moved) tick_job_locked(*job);
}

void Lighthouse::tick_job_locked(JobState& job) {
  int64_t now_ms = fthttp::now_ms();
  const auto& decision = job.iq.decision(now_ms);
  job.last_reason = decision.reason;
  if (decision.absent == 0) {
    job.held_since_ms = -1;
  } else if (job.held_since_ms < 0 || job.held_epoch != job.iq.epoch()) {
    job.held_since_ms = now_ms;
    job.held_epoch = job.iq.epoch();
  }
  // Epoch-watch wakeup: decision()'s sweep (expiry/prune), any join since
  // the last tick, and evictions may have bumped THIS job's membership
  // epoch without an announcement. Parked EpochWatch waiters key their
  // lease validity on exactly this edge, so notify them here — detection
  // latency is then bounded by quorum_tick_ms instead of the watch
  // re-stamp interval. The cv is shared across shards; a foreign job's
  // waiters re-check their own epoch/seq and park again, counters
  // untouched.
  if (job.iq.epoch() != job.watched_epoch) {
    job.watched_epoch = job.iq.epoch();
    cv_.notify_all();
  }
  if (!decision.quorum.has_value()) return;

  // install() bumps the quorum id only when membership changed (ref
  // lighthouse.rs 272-283); the id is what triggers transport
  // reconfiguration downstream. It also clears participants — each
  // quorum round requires a fresh request from every replica.
  const QuorumInfo& q = job.iq.install(*decision.quorum, wall_ms());
  // Serialize the announcement ONCE; each of the n waiters ships these
  // bytes verbatim instead of re-rendering an O(n) member list per RPC.
  ftjson::Object reply;
  reply["quorum"] = q.to_json();
  // Epoch lease (sampled AFTER install's epoch bump, so the granted
  // epoch is exactly the one a stable fleet keeps): while a manager's
  // EpochWatch sees this epoch unchanged and the lease window has not
  // expired, it may step with zero control RPCs. Any join / expiry /
  // announcement bumps the epoch and invalidates every outstanding
  // lease — the full Quorum path below is the always-correct fallback.
  reply["membership_epoch"] = static_cast<int64_t>(job.iq.epoch());
  reply["lease_ms"] = opts_.lease_ms;
  job.watched_epoch = job.iq.epoch();
  job.latest_quorum_body = ftjson::Value(std::move(reply)).dump();
  job.latest_quorum_ids.clear();
  for (const auto& p : q.participants) {
    job.latest_quorum_ids.insert(p.replica_id);
  }
  job.quorum_seq += 1;
  cv_.notify_all();
}

Response Lighthouse::handle(const Request& req) {
  if (req.path == "/torchft.LighthouseService/Quorum" &&
      req.method == "POST") {
    return handle_quorum(req);
  }
  if (req.path == "/torchft.LighthouseService/EpochWatch" &&
      req.method == "POST") {
    return handle_epoch_watch(req);
  }
  if (req.path == "/torchft.LighthouseService/Heartbeat" &&
      req.method == "POST") {
    return handle_heartbeat(req);
  }
  if (req.path == "/torchft.LighthouseService/DomainReport" &&
      req.method == "POST") {
    return handle_domain_report(req);
  }
  if (req.path == "/torchft.LighthouseService/RegisterJob" &&
      req.method == "POST") {
    return handle_register_job(req);
  }
  if (req.path == "/status" && req.method == "GET") {
    return handle_status();
  }
  if (req.path == "/status.json" && req.method == "GET") {
    return handle_status_json();
  }
  if (req.path == "/statsz" && req.method == "GET") {
    // Transport-level stats (JSON): with client connection pooling the
    // accepted count stays near the number of distinct clients instead of
    // growing with every heartbeat (keep-alive parity, ref src/net.rs).
    std::ostringstream js;
    js << "{\"http_conns_accepted\":" << server_.total_accepted() << "}";
    return Response{200, "application/json", js.str()};
  }
  if (req.path == "/" && req.method == "GET") {
    // Dashboard shell: vanilla-JS 1s polling of /status (the reference uses
    // htmx for the same cadence, templates/index.html).
    static const char* kIndex = R"html(<!DOCTYPE html>
<html><head><title>torchft_tpu lighthouse</title>
<style>
body { font-family: monospace; margin: 2em; background: #101418; color: #d8e0e8; }
h1 { color: #7fd4ff; } table { border-collapse: collapse; }
td, th { border: 1px solid #3a4654; padding: 4px 10px; text-align: left; }
.recovering { color: #ffb347; } .dead { color: #ff6b6b; }
button { background: #ff6b6b; border: none; padding: 3px 8px; cursor: pointer; }
</style></head>
<body><h1>torchft_tpu lighthouse</h1><div id="status">loading…</div>
<script>
async function poll() {
  try {
    const r = await fetch('/status');
    document.getElementById('status').innerHTML = await r.text();
  } catch (e) {}
}
poll(); setInterval(poll, 1000);
async function killReplica(id) { await fetch('/replica/' + id + '/kill', {method: 'POST'}); }
</script></body></html>)html";
    return Response{200, "text/html", kIndex};
  }
  // POST /replica/{id}/kill
  const std::string kKillPrefix = "/replica/";
  if (req.method == "POST" && req.path.rfind(kKillPrefix, 0) == 0) {
    std::string rest = req.path.substr(kKillPrefix.size());
    size_t slash = rest.find('/');
    if (slash != std::string::npos && rest.substr(slash) == "/kill") {
      return handle_kill(rest.substr(0, slash));
    }
  }
  return Response{404, "text/plain", "not found"};
}

Response Lighthouse::handle_quorum(const Request& req) {
  Member requester;
  std::string job_id = "default";
  bool has_priority = false, has_group_budget = false, has_rpc_budget = false;
  int64_t priority = 0, group_budget = 0, rpc_budget = 0;
  try {
    auto body = ftjson::Value::parse(req.body);
    if (!body.has("requester")) {
      return Response{400, "application/json",
                      "{\"error\":\"missing requester\"}"};
    }
    requester = Member::from_json(body.get("requester"));
    job_id = normalize_job(body.get_str("job_id", "default"));
    // Registration fields may ride the quorum request (a manager that
    // was started with a priority re-asserts it on every round, so a
    // lighthouse restart can't silently forget admissions).
    if (body.has("priority")) {
      has_priority = true;
      priority = body.get_int("priority");
    }
    if (body.has("group_budget")) {
      has_group_budget = true;
      group_budget = body.get_int("group_budget");
    }
    if (body.has("rpc_budget")) {
      has_rpc_budget = true;
      rpc_budget = body.get_int("rpc_budget");
    }
  } catch (const std::exception& e) {
    return Response{400, "application/json",
                    std::string("{\"error\":\"bad request: ") + e.what() +
                        "\"}"};
  }

  std::unique_lock<std::mutex> lk(mu_);
  JobState& job = job_locked(job_id);
  job.quorum_rpcs += 1;
  if (has_priority) job.priority = priority;
  if (has_group_budget) {
    // Raising (or unlimiting) the budget is the re-admission edge.
    if (group_budget <= 0 || group_budget > job.group_budget) {
      job.evicted.clear();
    }
    job.group_budget = group_budget;
  }
  if (has_rpc_budget) job.rpc_budget = rpc_budget;
  // Prescriptive eviction: an evicted group's quorum request is answered
  // immediately with the decision body — it must NEVER park (a timeout
  // is exactly the failure mode the decision body exists to prevent) and
  // must NEVER heartbeat/join (that would re-register it as healthy and
  // hold the survivors' quorum hostage via the split-brain guard).
  if (job.evicted.count(requester.replica_id)) {
    return eviction_response(job_id, job);
  }
  int64_t now = fthttp::now_ms();
  // Implicit heartbeat + join (ref lighthouse.rs:455-478).
  job.iq.heartbeat(requester.replica_id, now);
  job.iq.join(now, requester);
  maybe_preempt_locked(job_id, job);
  uint64_t seen = job.quorum_seq;
  tick_job_locked(job);  // proactive evaluation (cache hit unless state moved)

  // While parked, wake periodically to re-stamp our own heartbeat: a
  // live long-poll IS a liveness signal, which is what lets the manager
  // suppress separate heartbeat RPCs while its quorum request is in
  // flight (the piggyback contract, native/manager.cc heartbeat_loop).
  // The interval must stay safely below the heartbeat timeout — never
  // stretched by a coarse quorum_tick_ms — or a parked waiter would
  // expire between its own re-stamps.
  const int64_t stamp_interval = std::max<int64_t>(
      1, static_cast<int64_t>(opts_.quorum.heartbeat_timeout_ms) / 4);

  while (true) {
    while (job.quorum_seq == seen && !stopping_ &&
           !job.evicted.count(requester.replica_id)) {
      int64_t now2 = fthttp::now_ms();
      int64_t wake = std::min(req.deadline_ms, now2 + stamp_interval);
      auto deadline =
          std::chrono::steady_clock::now() +
          std::chrono::milliseconds(std::max<int64_t>(1, wake - now2));
      if (cv_.wait_until(lk, deadline) == std::cv_status::timeout &&
          job.quorum_seq == seen) {
        if (fthttp::now_ms() >= req.deadline_ms) {
          return Response{504, "application/json",
                          "{\"error\":\"quorum deadline exceeded\"}"};
        }
        if (job.evicted.count(requester.replica_id)) break;
        // A DEAD long-poll is not a liveness signal: peek the serving
        // socket before stamping — a parked handler never reads it, so
        // a SIGKILLed client would otherwise look alive until the RPC
        // deadline instead of expiring after heartbeat_timeout.
        if (req.client_fd >= 0) {
          char probe;
          ssize_t pr = ::recv(req.client_fd, &probe, 1,
                              MSG_PEEK | MSG_DONTWAIT);
          if (pr == 0 || (pr < 0 && errno != EAGAIN &&
                          errno != EWOULDBLOCK && errno != EINTR)) {
            // Client vanished; stop stamping and let its heartbeat age
            // out. The response write will fail harmlessly.
            return Response{503, "application/json",
                            "{\"error\":\"client disconnected\"}"};
          }
        }
        job.iq.heartbeat(requester.replica_id, fthttp::now_ms());
      }
    }
    if (stopping_) {
      return Response{503, "application/json",
                      "{\"error\":\"lighthouse shutting down\"}"};
    }
    if (job.evicted.count(requester.replica_id)) {
      return eviction_response(job_id, job);
    }
    seen = job.quorum_seq;
    if (job.latest_quorum_ids.count(requester.replica_id)) break;
    // Announced quorum doesn't include us: rejoin and wait for the next one
    // (ref lighthouse.rs:480-501).
    int64_t now2 = fthttp::now_ms();
    job.iq.heartbeat(requester.replica_id, now2);
    job.iq.join(now2, requester);
  }

  if (opts_.lease_ms > 0) job.lease_grants += 1;
  return Response{200, "application/json", job.latest_quorum_body};
}

Response Lighthouse::handle_epoch_watch(const Request& req) {
  // Lease renewal long-poll: park while the JOB's membership epoch equals
  // the watched one, re-stamping the requester's heartbeat (same liveness
  // piggyback as handle_quorum — a parked watch IS the replica's
  // heartbeat, native/manager.cc heartbeat_loop). Returns
  // {epoch, changed}: changed=false at the deadline is a lease renewal;
  // changed=true means the job moved and the caller's lease is dead.
  // Sharding is the lease-isolation guarantee: a foreign job's churn
  // bumps a different shard's epoch, so it can never break this lease.
  std::string replica_id;
  std::string job_id = "default";
  uint64_t watched = 0;
  try {
    auto body = ftjson::Value::parse(req.body);
    replica_id = body.get_str("replica_id");
    watched = static_cast<uint64_t>(body.get_int("epoch"));
    job_id = normalize_job(body.get_str("job_id", "default"));
  } catch (const std::exception& e) {
    return Response{400, "application/json",
                    std::string("{\"error\":\"bad request: ") + e.what() +
                        "\"}"};
  }

  std::unique_lock<std::mutex> lk(mu_);
  JobState& job = job_locked(job_id);
  job.epoch_watch_rpcs += 1;
  // An evicted member's lease is dead by decree: answer immediately
  // (never park, never stamp — stamping would re-register it).
  if (job.evicted.count(replica_id)) {
    job.lease_breaks += 1;
    ftjson::Object out;
    out["epoch"] = static_cast<int64_t>(job.iq.epoch());
    out["changed"] = true;
    out["evicted"] = true;
    return Response{200, "application/json",
                    ftjson::Value(std::move(out)).dump()};
  }
  int64_t entry = fthttp::now_ms();
  job.iq.heartbeat(replica_id, entry);
  const int64_t stamp_interval = std::max<int64_t>(
      1, static_cast<int64_t>(opts_.quorum.heartbeat_timeout_ms) / 4);
  // Return a margin BEFORE the RPC deadline: the renewal response must
  // clear the proxy hop and the client's socket guard, or every renewal
  // would race its own timeout and read as a lease break.
  const int64_t window = req.deadline_ms - entry;
  const int64_t watch_deadline =
      req.deadline_ms -
      std::min<int64_t>(1000, std::max<int64_t>(20, window / 10));

  while (job.iq.epoch() == watched && !stopping_ &&
         fthttp::now_ms() < watch_deadline) {
    int64_t now = fthttp::now_ms();
    int64_t wake = std::min(watch_deadline, now + stamp_interval);
    auto deadline =
        std::chrono::steady_clock::now() +
        std::chrono::milliseconds(std::max<int64_t>(1, wake - now));
    if (cv_.wait_until(lk, deadline) == std::cv_status::timeout &&
        job.iq.epoch() == watched) {
      // Run the (cached) decision so expiry edges are observed even if
      // the tick thread is briefly behind; a dead member must break
      // leases from the watch itself, not only from the next tick.
      (void)job.iq.decision(fthttp::now_ms());
      if (job.iq.epoch() != watched) break;
      if (fthttp::now_ms() >= watch_deadline) break;
      if (job.evicted.count(replica_id)) break;
      // Dead-client probe, as in handle_quorum: a SIGKILLed watcher
      // must expire after heartbeat_timeout, not look alive until the
      // RPC deadline.
      if (req.client_fd >= 0) {
        char probe;
        ssize_t pr = ::recv(req.client_fd, &probe, 1,
                            MSG_PEEK | MSG_DONTWAIT);
        if (pr == 0 || (pr < 0 && errno != EAGAIN &&
                        errno != EWOULDBLOCK && errno != EINTR)) {
          return Response{503, "application/json",
                          "{\"error\":\"client disconnected\"}"};
        }
      }
      job.iq.heartbeat(replica_id, fthttp::now_ms());
    }
  }
  if (stopping_) {
    return Response{503, "application/json",
                    "{\"error\":\"lighthouse shutting down\"}"};
  }
  bool changed = job.iq.epoch() != watched;
  if (changed) job.lease_breaks += 1;
  ftjson::Object out;
  out["epoch"] = static_cast<int64_t>(job.iq.epoch());
  out["changed"] = changed;
  if (job.evicted.count(replica_id)) out["evicted"] = true;
  return Response{200, "application/json",
                  ftjson::Value(std::move(out)).dump()};
}

Response Lighthouse::handle_heartbeat(const Request& req) {
  try {
    auto body = ftjson::Value::parse(req.body);
    std::string job_id = normalize_job(body.get_str("job_id", "default"));
    int64_t now = fthttp::now_ms();
    std::lock_guard<std::mutex> lk(mu_);
    JobState& job = job_locked(job_id);
    job.heartbeat_rpcs += 1;
    // Admission rate limit: heartbeats over the job's rpc_budget are
    // dropped (429) — quorum/watch RPCs are never dropped, they carry
    // liveness and decisions.
    if (rate_limited_locked(job, now)) {
      return Response{429, "application/json",
                      "{\"error\":\"rate limited\",\"job_id\":\"" + job_id +
                          "\"}"};
    }
    if (body.has("replica_ids")) {
      // Batched form: one RPC carries a whole domain's heartbeats (the
      // tier-1 aggregator path; proto LighthouseHeartbeatRequest).
      for (const auto& v : body.get("replica_ids").as_array()) {
        if (!job.evicted.count(v.as_str())) job.iq.heartbeat(v.as_str(), now);
        job.heartbeat_ids += 1;
      }
    } else {
      std::string rid = body.get_str("replica_id");
      // Evicted members' heartbeats are ignored, not errors: the member
      // learns its fate from its next quorum/watch RPC, and meanwhile it
      // must not re-enter the healthy set.
      if (!job.evicted.count(rid)) job.iq.heartbeat(rid, now);
      job.heartbeat_ids += 1;
    }
  } catch (const std::exception& e) {
    return Response{400, "application/json",
                    std::string("{\"error\":\"") + e.what() + "\"}"};
  }
  return Response{200, "application/json", "{}"};
}

Response Lighthouse::handle_domain_report(const Request& req) {
  try {
    auto body = ftjson::Value::parse(req.body);
    DomainSummary s;
    std::string domain = body.get_str("domain");
    s.tier = body.get_int("tier", 1);
    s.address = body.get_str("address", "");
    s.job_id = normalize_job(body.get_str("job_id", "default"));
    s.healthy = body.get_int("healthy", 0);
    s.participants = body.get_int("participants", 0);
    s.quorum_id = body.get_int("quorum_id", 0);
    s.max_step = body.get_int("max_step", 0);
    s.report_interval_ms = body.get_int("report_interval_ms", 0);
    s.received_ms = fthttp::now_ms();
    std::lock_guard<std::mutex> lk(mu_);
    domain_reports_ += 1;
    domains_[domain] = std::move(s);
  } catch (const std::exception& e) {
    return Response{400, "application/json",
                    std::string("{\"error\":\"") + e.what() + "\"}"};
  }
  return Response{200, "application/json", "{}"};
}

Response Lighthouse::handle_register_job(const Request& req) {
  // Admission registration: priority class + group/RPC budgets for one
  // job shard. Registering is idempotent and last-writer-wins; raising
  // (or unlimiting) the group budget clears the shard's evicted set —
  // the operator-driven re-admission edge after a preemption.
  std::string job_id;
  try {
    auto body = ftjson::Value::parse(req.body);
    job_id = normalize_job(body.get_str("job_id", "default"));
    std::lock_guard<std::mutex> lk(mu_);
    JobState& job = job_locked(job_id);
    if (body.has("priority")) job.priority = body.get_int("priority");
    if (body.has("group_budget")) {
      int64_t nb = body.get_int("group_budget");
      if (nb <= 0 || nb > job.group_budget) job.evicted.clear();
      job.group_budget = nb;
    }
    if (body.has("rpc_budget")) job.rpc_budget = body.get_int("rpc_budget");
    ftjson::Object out;
    out["job_id"] = job_id;
    out["priority"] = job.priority;
    out["group_budget"] = job.group_budget;
    out["rpc_budget"] = job.rpc_budget;
    return Response{200, "application/json",
                    ftjson::Value(std::move(out)).dump()};
  } catch (const std::exception& e) {
    return Response{400, "application/json",
                    std::string("{\"error\":\"") + e.what() + "\"}"};
  }
}

Response Lighthouse::handle_status() {
  std::ostringstream html;
  {
    std::lock_guard<std::mutex> lk(mu_);
    JobState& dj = job_locked("default");
    const auto& decision = dj.iq.decision(fthttp::now_ms());
    html << "<p>tier " << opts_.tier;
    if (!opts_.domain.empty()) {
      html << " &middot; domain " << html_escape(opts_.domain);
    }
    html << "</p><p>quorum status: " << html_escape(decision.reason)
         << "</p>";
    const auto& state = dj.iq.state();
    if (state.prev_quorum.has_value()) {
      const auto& q = *state.prev_quorum;
      int64_t max_step = 0;
      for (const auto& p : q.participants)
        max_step = std::max(max_step, p.step);
      html << "<p>quorum id: " << q.quorum_id << " &middot; "
           << q.participants.size() << " participants &middot; age "
           << (wall_ms() - q.created_ms) / 1000 << "s &middot; max step "
           << max_step << "</p><table><tr><th>replica</th><th>step</th>"
           << "<th>manager address</th><th>store</th><th></th></tr>";
      for (const auto& p : q.participants) {
        bool recovering = p.step != max_step;
        html << "<tr class=\"" << (recovering ? "recovering" : "") << "\"><td>"
             << html_escape(p.replica_id) << "</td><td>" << p.step
             << (recovering ? " (recovering)" : "") << "</td><td>"
             << html_escape(p.address) << "</td><td>"
             << html_escape(p.store_address) << "</td><td><button "
             << "onclick=\"killReplica('" << html_escape(p.replica_id)
             << "')\">kill</button></td></tr>";
      }
      html << "</table>";
    } else {
      html << "<p>no quorum formed yet</p>";
    }
    html << "<h3>heartbeats</h3><table><tr><th>replica</th><th>age</th></tr>";
    int64_t now = fthttp::now_ms();
    for (const auto& hb : state.heartbeats) {
      bool dead = now - hb.second >=
                  static_cast<int64_t>(opts_.quorum.heartbeat_timeout_ms);
      html << "<tr class=\"" << (dead ? "dead" : "") << "\"><td>"
           << html_escape(hb.first) << "</td><td>" << (now - hb.second)
           << "ms</td></tr>";
    }
    html << "</table>";
    if (jobs_.size() > 1) {
      html << "<h3>jobs</h3><table><tr><th>job</th><th>priority</th>"
           << "<th>healthy/budget</th><th>epoch</th><th>preemptions</th>"
           << "<th>door knocks (refused)</th></tr>";
      for (const auto& kv : jobs_) {
        const JobState& j = *kv.second;
        html << "<tr><td>" << html_escape(kv.first) << "</td><td>"
             << j.priority << "</td><td>" << j.iq.healthy_count() << "/"
             << (j.group_budget > 0 ? std::to_string(j.group_budget)
                                    : std::string("∞"))
             << "</td><td>" << j.iq.epoch() << "</td><td>" << j.preemptions
             << "</td><td>" << j.door_knocks << " (" << j.refused_expiries
             << ")</td></tr>";
      }
      html << "</table>";
    }
    if (!domains_.empty()) {
      html << "<h3>domains</h3><table><tr><th>domain</th><th>healthy</th>"
           << "<th>quorum id</th><th>report age</th></tr>";
      for (const auto& kv : domains_) {
        html << "<tr><td>" << html_escape(kv.first) << "</td><td>"
             << kv.second.healthy << "</td><td>" << kv.second.quorum_id
             << "</td><td>" << (now - kv.second.received_ms)
             << "ms</td></tr>";
      }
      html << "</table>";
    }
  }
  return Response{200, "text/html", html.str()};
}

Response Lighthouse::handle_status_json() {
  // Machine-readable twin of /status: the fleet discovery root. The
  // root-level shape (reason / quorum / heartbeats / control) renders the
  // DEFAULT job exactly as the single-tenant lighthouse did — with
  // control counters summed across shards, so a single-job deployment is
  // byte-compatible and a multi-job one still satisfies "per-job
  // counters sum to root totals". The per-job truth lives under "jobs".
  ftjson::Object o;
  {
    std::lock_guard<std::mutex> lk(mu_);
    int64_t now = fthttp::now_ms();
    JobState& dj = job_locked("default");
    const auto& decision = dj.iq.decision(now);
    o["reason"] = decision.reason;
    o["now_ms"] = now;
    const auto& state = dj.iq.state();
    if (state.prev_quorum.has_value()) {
      const auto& q = *state.prev_quorum;
      o["quorum"] = q.to_json();
      o["quorum_age_ms"] = wall_ms() - q.created_ms;
      int64_t max_step = 0;
      for (const auto& p : q.participants)
        max_step = std::max(max_step, p.step);
      o["max_step"] = max_step;
    }
    ftjson::Object hb;
    for (const auto& h : state.heartbeats) {
      ftjson::Object entry;
      entry["age_ms"] = now - h.second;
      entry["dead"] =
          now - h.second >=
          static_cast<int64_t>(opts_.quorum.heartbeat_timeout_ms);
      hb[h.first] = ftjson::Value(std::move(entry));
    }
    o["heartbeats"] = ftjson::Value(std::move(hb));

    // Cross-shard sums for the root "control" object.
    uint64_t sum_compute = 0, sum_cache_hits = 0, sum_epoch = 0;
    uint64_t sum_hb_rpcs = 0, sum_hb_ids = 0, sum_q_rpcs = 0;
    uint64_t sum_hb_pruned = 0, sum_part_pruned = 0;
    uint64_t sum_lease_grants = 0, sum_lease_breaks = 0, sum_watch_rpcs = 0;
    uint64_t sum_preemptions = 0, sum_rl_drops = 0, sum_healthy = 0;
    uint64_t sum_knocks = 0, sum_refused = 0;
    for (const auto& kv : jobs_) {
      const JobState& j = *kv.second;
      sum_compute += j.iq.compute_count();
      sum_cache_hits += j.iq.cache_hits();
      sum_epoch += j.iq.epoch();
      sum_hb_rpcs += j.heartbeat_rpcs;
      sum_hb_ids += j.heartbeat_ids;
      sum_q_rpcs += j.quorum_rpcs;
      sum_hb_pruned += j.iq.pruned_heartbeats();
      sum_part_pruned += j.iq.pruned_participants();
      sum_lease_grants += j.lease_grants;
      sum_lease_breaks += j.lease_breaks;
      sum_watch_rpcs += j.epoch_watch_rpcs;
      sum_preemptions += j.preemptions;
      sum_rl_drops += j.rate_limit_drops;
      sum_healthy += j.iq.healthy_count();
      sum_knocks += j.door_knocks;
      sum_refused += j.refused_expiries;
    }

    // Control-plane scaling counters (PR 10): the evidence surface for
    // "recompute count is O(membership changes), not O(RPCs)".
    ftjson::Object ctl;
    ctl["quorum_compute_count"] = static_cast<int64_t>(sum_compute);
    ctl["quorum_cache_hits"] = static_cast<int64_t>(sum_cache_hits);
    ctl["membership_epoch"] = static_cast<int64_t>(sum_epoch);
    ctl["cache_enabled"] = opts_.cache_quorum;
    ctl["heartbeat_rpcs"] = static_cast<int64_t>(sum_hb_rpcs);
    ctl["heartbeat_ids"] = static_cast<int64_t>(sum_hb_ids);
    ctl["quorum_rpcs"] = static_cast<int64_t>(sum_q_rpcs);
    ctl["domain_reports"] = static_cast<int64_t>(domain_reports_);
    ctl["domains_pruned"] = static_cast<int64_t>(domains_pruned_);
    ctl["heartbeats_pruned"] = static_cast<int64_t>(sum_hb_pruned);
    ctl["participants_pruned"] = static_cast<int64_t>(sum_part_pruned);
    ctl["door_knocks"] = static_cast<int64_t>(sum_knocks);
    ctl["refused_expiries"] = static_cast<int64_t>(sum_refused);
    ctl["lease_grants"] = static_cast<int64_t>(sum_lease_grants);
    ctl["lease_breaks"] = static_cast<int64_t>(sum_lease_breaks);
    ctl["epoch_watch_rpcs"] = static_cast<int64_t>(sum_watch_rpcs);
    ctl["lease_ms"] = opts_.lease_ms;
    ctl["healthy_replicas"] = static_cast<int64_t>(sum_healthy);
    ctl["preemptions"] = static_cast<int64_t>(sum_preemptions);
    ctl["rate_limit_drops"] = static_cast<int64_t>(sum_rl_drops);
    ctl["fleet_capacity"] = opts_.fleet_capacity;
    ctl["jobs"] = static_cast<int64_t>(jobs_.size());
    ctl["tier"] = static_cast<int64_t>(opts_.tier);
    ctl["domain"] = opts_.domain;
    ctl["upstream"] = opts_.upstream_addr;
    o["control"] = ftjson::Value(std::move(ctl));

    // Per-job shard truth: one entry per job, counters UNsummed. The
    // isolation oracle (scripts/bench_fleet.py --jobs) reads exactly
    // these — churn in job A must leave every other entry's
    // quorum_compute_count / membership_epoch / lease_breaks untouched.
    ftjson::Object jobs;
    for (const auto& kv : jobs_) {
      const JobState& j = *kv.second;
      ftjson::Object e;
      e["priority"] = j.priority;
      e["group_budget"] = j.group_budget;
      e["rpc_budget"] = j.rpc_budget;
      e["healthy"] = static_cast<int64_t>(j.iq.healthy_count());
      e["participants"] =
          static_cast<int64_t>(j.iq.state().participants.size());
      e["membership_epoch"] = static_cast<int64_t>(j.iq.epoch());
      e["quorum_compute_count"] = static_cast<int64_t>(j.iq.compute_count());
      e["quorum_cache_hits"] = static_cast<int64_t>(j.iq.cache_hits());
      e["quorum_rpcs"] = static_cast<int64_t>(j.quorum_rpcs);
      e["heartbeat_rpcs"] = static_cast<int64_t>(j.heartbeat_rpcs);
      e["heartbeat_ids"] = static_cast<int64_t>(j.heartbeat_ids);
      e["lease_grants"] = static_cast<int64_t>(j.lease_grants);
      e["lease_breaks"] = static_cast<int64_t>(j.lease_breaks);
      e["epoch_watch_rpcs"] = static_cast<int64_t>(j.epoch_watch_rpcs);
      e["preemptions"] = static_cast<int64_t>(j.preemptions);
      e["rate_limit_drops"] = static_cast<int64_t>(j.rate_limit_drops);
      e["door_knocks"] = static_cast<int64_t>(j.door_knocks);
      e["refused_expiries"] = static_cast<int64_t>(j.refused_expiries);
      e["reason"] = j.last_reason;
      if (!j.evicted.empty()) {
        ftjson::Array ev;
        for (const auto& id : j.evicted) ev.push_back(ftjson::Value(id));
        e["evicted"] = ftjson::Value(std::move(ev));
      }
      if (j.iq.state().prev_quorum.has_value()) {
        const auto& q = *j.iq.state().prev_quorum;
        e["quorum_id"] = q.quorum_id;
        e["quorum_age_ms"] = wall_ms() - q.created_ms;
        int64_t max_step = 0;
        for (const auto& p : q.participants)
          max_step = std::max(max_step, p.step);
        e["max_step"] = max_step;
        ftjson::Array ids;
        for (const auto& p : q.participants)
          ids.push_back(ftjson::Value(p.replica_id));
        e["quorum_replica_ids"] = ftjson::Value(std::move(ids));
        // Full installed quorum (participants with address/store_address/
        // step), same shape as the default job's top-level "quorum": the
        // fleet poller walks non-default jobs — serving cohorts above all
        // — to their replicas' telemetry endpoints through exactly this.
        e["quorum"] = q.to_json();
      }
      jobs[kv.first] = ftjson::Value(std::move(e));
    }
    o["jobs"] = ftjson::Value(std::move(jobs));

    // Root side of the two-level tree: one summary row per reporting
    // domain aggregator, with report staleness derived from the
    // aggregator's own advertised interval.
    if (!domains_.empty()) {
      ftjson::Object doms;
      for (const auto& kv : domains_) {
        const DomainSummary& s = kv.second;
        ftjson::Object d;
        d["tier"] = s.tier;
        d["address"] = s.address;
        d["job_id"] = s.job_id;
        d["healthy"] = s.healthy;
        d["participants"] = s.participants;
        d["quorum_id"] = s.quorum_id;
        d["max_step"] = s.max_step;
        d["report_interval_ms"] = s.report_interval_ms;
        int64_t age = now - s.received_ms;
        d["report_age_ms"] = age;
        d["stale"] =
            s.report_interval_ms > 0 && age > 3 * s.report_interval_ms;
        doms[kv.first] = ftjson::Value(std::move(d));
      }
      o["domains"] = ftjson::Value(std::move(doms));
    }
  }
  return Response{200, "application/json", ftjson::Value(std::move(o)).dump()};
}

Response Lighthouse::handle_kill(const std::string& replica_id) {
  std::string manager_addr;
  {
    std::lock_guard<std::mutex> lk(mu_);
    for (const auto& kv : jobs_) {
      const auto& state = kv.second->iq.state();
      if (!state.prev_quorum.has_value()) continue;
      for (const auto& m : state.prev_quorum->participants) {
        if (m.replica_id == replica_id) {
          manager_addr = m.address;
          break;
        }
      }
      if (!manager_addr.empty()) break;
    }
  }
  if (manager_addr.empty()) {
    return Response{500, "text/plain", "failed to find replica"};
  }
  std::string host;
  int port = 0;
  if (!fthttp::parse_http_addr(manager_addr, &host, &port)) {
    return Response{500, "text/plain", "bad manager address"};
  }
  ftjson::Object body;
  body["msg"] = std::string("killed from dashboard");
  auto res =
      fthttp::http_post(host, port, "/torchft.ManagerService/Kill",
                        ftjson::Value(body).dump(), fthttp::now_ms() + 10000);
  if (!res.error.empty()) {
    return Response{500, "text/plain", "kill failed: " + res.error};
  }
  return Response{200, "text/plain", "ok"};
}

}  // namespace ftlighthouse
