// torchft_tpu native control plane — Lighthouse server.
//
// Global quorum service (reference: /root/reference/src/lighthouse.rs).
// Serves, on one port:
//   POST /torchft.LighthouseService/Quorum       (long-poll until quorum)
//   POST /torchft.LighthouseService/Heartbeat    (single id or batched
//                                                 replica_ids list)
//   POST /torchft.LighthouseService/DomainReport (tier-1 aggregator ->
//                                                 root membership summary)
//   POST /torchft.LighthouseService/RegisterJob  (admission: priority
//                                                 class + group/RPC budgets)
//   GET  /            dashboard HTML
//   GET  /status      dashboard fragment (polled by the dashboard JS)
//   GET  /status.json machine-readable fleet status (quorum members with
//                     manager/store addresses + per-replica heartbeat
//                     ages + "control" counters + "jobs" map + "domains"
//                     tree) — the discovery root for scripts/fleet_top.py
//   POST /replica/{id}/kill   proxies a Kill RPC to that replica's manager
//
// Design: one mutex + condition_variable guard all state; the quorum RPC
// long-polls on a monotonically increasing quorum sequence number (the
// C++ rendering of the reference's tokio broadcast channel); a tick thread
// re-evaluates the decision every quorum_tick_ms.
//
// Fleet scale (PR 10): quorum state lives in an IncrementalQuorum —
// decisions are cached per membership epoch so a round at n replica
// groups costs O(n) recomputes (one per join edge) instead of O(n^2)
// full scans, the announced quorum's response JSON and id-set are
// serialized once per announcement and served verbatim to every waiter,
// and a parked long-poll waiter is periodically re-stamped as alive so
// managers can suppress their separate heartbeat RPCs while a quorum
// request is in flight (the piggyback path, native/manager.cc).
//
// Multi-tenant (PR 19): ONE lighthouse multiplexes many jobs. Every RPC
// carries an optional `job_id` (absent -> job "default", so pre-PR
// clients keep byte-identical behavior) and lands on that job's SHARD —
// its own IncrementalQuorum, announcement body/seq, epoch-watch state,
// and counters. A quorum recompute is therefore O(that job's membership
// changes): job A's churn causes exactly 0 recomputes, 0 membership-
// epoch bumps, and 0 lease breaks in job B. Jobs register a priority
// class plus group/RPC budgets (RegisterJob, or the same fields riding a
// Quorum request); when the fleet is over `fleet_capacity`, a quorum
// request from a higher-priority job PREEMPTS one group from the
// lowest-priority over-budget job — the evicted group learns it from a
// prescriptive `evicted:true` quorum decision body (never a timeout),
// and the victim job's epoch bump breaks its leases so the survivors
// re-form and shrink live through the redistribution planner.
//
// Door-knock (PR 32): a replica killed on a live host leaves evidence
// the heartbeat timeout ignores — the manager address it registered in
// the last quorum refuses a connection from that instant. While members
// that have asked are parked and only replicas healthy by heartbeat that
// have not asked hold their quorum up (the straggler wait, or the
// split-brain guard counting the same absentees against a majority) and
// nothing has moved for two ticks, the tick thread connects, off the
// state lock, to each such absentee that was a member of the previous
// quorum. A connection
// REFUSED expires that replica's heartbeat now — the edge sweep() takes
// at heartbeat_timeout_ms, taken early; every other outcome changes
// nothing and the timeout decides. The decision rules are untouched:
// they act on the state after the expiry as they would act on it a
// timeout later. Only what the lighthouse sees first-hand counts — no
// survivor's report, no goodbye from the victim.
//
// Two-level tree: a lighthouse constructed with an upstream address is a
// tier-1 aggregator for a domain (rack/ICI) of replica groups — it holds
// the quorum for that domain and reports ONE membership summary upstream
// per report interval; the root renders the summaries in /status.json
// ("domains", with report staleness) without tracking any per-replica
// state for foreign domains.
#pragma once

#include <condition_variable>
#include <map>
#include <memory>
#include <mutex>
#include <optional>
#include <set>
#include <string>
#include <thread>
#include <vector>

#include "httpx.h"
#include "quorum.h"

namespace ftlighthouse {

struct LighthouseOpts {
  std::string bind_host = "0.0.0.0";
  int port = 0;                  // 0 = ephemeral
  std::string hostname = "";     // advertised host; "" = bind_host or 127.0.0.1
  ftquorum::QuorumOpts quorum;
  // -- fleet-scale options --
  // Serve epoch-cached decisions (true) or run the pure kernel on every
  // evaluation (false — the always-recompute A/B arm of bench_fleet.py).
  bool cache_quorum = true;
  // Heartbeat/participant entries dead for longer than this are pruned
  // (<=0: IncrementalQuorum's default of 12x heartbeat_timeout_ms).
  int64_t prune_after_ms = 0;
  // Topology tier label: 0 = root, 1 = domain aggregator. Derived from
  // upstream_addr when left at -1.
  int tier = -1;
  std::string domain = "";         // domain (rack/ICI) name, "" = unnamed
  std::string upstream_addr = "";  // root lighthouse; "" = this IS the root
  uint64_t upstream_report_interval_ms = 500;
  // Epoch-lease duration granted with every Quorum response (<=0: leases
  // disabled). A manager holding a live lease steps without control RPCs
  // and renews it off the step path via the EpochWatch long-poll; any
  // membership-epoch bump observed by a watch breaks the lease.
  int64_t lease_ms = 0;
  // Admission capacity in replica groups, summed over every job's
  // healthy set (<=0: unlimited, preemption never triggers). While the
  // fleet is above capacity, a quorum request from a higher-priority job
  // evicts one group from the lowest-priority over-budget job.
  int64_t fleet_capacity = 0;
};

// One aggregator's latest upstream summary, as stored by the root.
struct DomainSummary {
  int64_t tier = 1;
  std::string address;
  std::string job_id = "default";
  int64_t healthy = 0;
  int64_t participants = 0;
  int64_t quorum_id = 0;
  int64_t max_step = 0;
  int64_t report_interval_ms = 0;
  int64_t received_ms = 0;  // monotonic, root's clock
};

// One job's shard of the control plane: its own incremental quorum,
// announcement state, lease/watch bookkeeping, admission registration,
// and counters. Guarded by the Lighthouse's mu_ (shards are about
// recompute/epoch isolation, not lock granularity). Held by unique_ptr
// and never erased, so JobState& references stay valid across cv waits.
struct JobState {
  explicit JobState(const LighthouseOpts& opts)
      : iq(opts.quorum, opts.cache_quorum, opts.prune_after_ms) {}

  ftquorum::IncrementalQuorum iq;
  uint64_t quorum_seq = 0;
  // Serialized once per announcement (the installed quorum itself lives
  // in iq.state().prev_quorum); every waiter ships these bytes verbatim
  // instead of re-serializing an O(n) member list per RPC.
  std::string latest_quorum_body;
  std::set<std::string> latest_quorum_ids;
  std::string last_reason;
  // Last epoch tick_locked saw: an epoch edge from ANY source (join,
  // expiry sweep, install, evict) wakes parked EpochWatch waiters within
  // one tick instead of their next re-stamp interval.
  uint64_t watched_epoch = 0;
  // Since when (monotonic ms) absentees have held the quorum up
  // (QuorumDecision::absent) with nothing moving: every membership edge
  // — a member asking, a heartbeat's first sighting, an expiry — starts
  // the count again (held_epoch); -1 = they do not hold it. The
  // door-knock waits two ticks of this: in a steady step the members ask
  // within milliseconds of each other and a live manager is owed no
  // connection a step.
  int64_t held_since_ms = -1;
  uint64_t held_epoch = 0;

  // Admission registration (RegisterJob, or fields riding a Quorum
  // request body; last writer wins).
  int64_t priority = 0;      // higher preempts lower
  int64_t group_budget = 0;  // healthy groups above this are evictable; 0 = unlimited
  int64_t rpc_budget = 0;    // heartbeat RPCs per second; 0 = unlimited
  // Rate-limit window (1s tumbling) for rpc_budget.
  int64_t rpc_window_start_ms = 0;
  int64_t rpc_window_count = 0;

  // Groups evicted from this job by preemption. A member on this list
  // gets a prescriptive `evicted:true` decision from every Quorum RPC,
  // its heartbeats are ignored (so it can't hold the survivors' quorum
  // hostage via the split-brain guard), and its EpochWatch returns
  // changed immediately. Cleared by a RegisterJob that raises the
  // group budget (operator-driven re-admission).
  std::set<std::string> evicted;

  // Per-job RPC counters (monotonic; surfaced under /status.json
  // "jobs"; the root "control" object carries their cross-job sums).
  uint64_t heartbeat_rpcs = 0;
  uint64_t heartbeat_ids = 0;  // replica ids carried by those RPCs
  uint64_t quorum_rpcs = 0;
  uint64_t lease_grants = 0;
  uint64_t epoch_watch_rpcs = 0;
  uint64_t lease_breaks = 0;
  uint64_t preemptions = 0;       // groups evicted FROM this job
  uint64_t rate_limit_drops = 0;  // heartbeats dropped over rpc_budget
  uint64_t door_knocks = 0;       // connections tried to a straggler's manager
  uint64_t refused_expiries = 0;  // heartbeats expired early on a refusal
};

class Lighthouse {
 public:
  explicit Lighthouse(LighthouseOpts opts);
  ~Lighthouse();

  void start();
  void shutdown();
  std::string address() const;  // http://host:port
  int port() const { return server_.port(); }

 private:
  fthttp::Response handle(const fthttp::Request& req);
  fthttp::Response handle_quorum(const fthttp::Request& req);
  fthttp::Response handle_epoch_watch(const fthttp::Request& req);
  fthttp::Response handle_heartbeat(const fthttp::Request& req);
  fthttp::Response handle_domain_report(const fthttp::Request& req);
  fthttp::Response handle_register_job(const fthttp::Request& req);
  fthttp::Response handle_status();
  fthttp::Response handle_status_json();
  fthttp::Response handle_kill(const std::string& replica_id);
  // Get-or-create the shard for a job id ("" -> "default"). Caller must
  // hold mu_.
  JobState& job_locked(const std::string& job_id);
  // Runs the (cached) decision for one job; on success publishes a new
  // quorum — one serialization, one id-set — and wakes waiters. Caller
  // must hold mu_.
  void tick_job_locked(JobState& job);
  void tick_loop();
  // The door-knock, in three steps around the tick thread's unlocked
  // connects. collect: every job held up by absentees for two ticks
  // gives the previous-quorum members that are healthy and have not
  // asked, with the heartbeat stamp seen. apply: a refusal expires the
  // replica unless it has beaten or asked meanwhile, and the job is
  // ticked again so its parked members get their quorum in this tick.
  // Both hold mu_.
  struct DoorKnock {
    std::string job_id;
    std::string replica_id;
    std::string address;
    int64_t heartbeat_ms;
  };
  std::vector<DoorKnock> collect_knocks_locked(int64_t now_ms);
  void apply_knocks_locked(const std::vector<DoorKnock>& knocks,
                           const std::vector<fthttp::Knock>& found,
                           int64_t now_ms);
  // Admission check after `claimant` gained a member: while the fleet is
  // over capacity, evict one group from the lowest-priority over-budget
  // job with priority strictly below the claimant's. Caller holds mu_.
  void maybe_preempt_locked(const std::string& claimant_id,
                            JobState& claimant);
  // Build the upstream DomainReport bodies — one per job shard, keyed
  // "<domain>" for the default job and "<domain>/job:<id>" otherwise so
  // the root's domains map stays one row per (domain, job). Holds mu_.
  std::vector<std::string> build_domain_reports_locked(int64_t now_ms);
  // True when the heartbeat should be dropped for exceeding the job's
  // rpc_budget (counts the drop). Caller holds mu_.
  bool rate_limited_locked(JobState& job, int64_t now_ms);

  LighthouseOpts opts_;
  fthttp::HttpServer server_;
  std::thread tick_thread_;

  mutable std::mutex mu_;
  std::condition_variable cv_;
  // job_id -> shard. The "default" job is every pre-multi-tenant
  // client's home and is created eagerly so legacy status payloads
  // render identically.
  std::map<std::string, std::unique_ptr<JobState>> jobs_;
  bool stopping_ = false;

  // Whole-lighthouse counters (not attributable to one job).
  uint64_t domain_reports_ = 0;
  uint64_t domains_pruned_ = 0;

  // Root side of the two-level tree: domain name -> latest summary.
  // Rows silent for far longer than their advertised interval are
  // evicted by the tick loop (counted above) so aggregator restarts
  // under generated domain names can't grow this map forever.
  std::map<std::string, DomainSummary> domains_;
};

}  // namespace ftlighthouse
