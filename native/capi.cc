// torchft_tpu native control plane — C ABI for Python (ctypes).
//
// The reference binds its Rust control plane into Python with pyo3
// (/root/reference/src/lib.rs); here we expose a plain C ABI consumed via
// ctypes (pybind11 is not in this image). All returned strings are malloc'd
// and must be freed with ft_free(). Errors are returned through `char** err`
// (malloc'd message, NULL on success); timeout errors are prefixed
// "TIMEOUT: " so the Python layer can raise TimeoutError, mirroring the
// Status→PyErr mapping at reference lib.rs:321-339.
#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <random>
#include <string>

#include "ftjson.h"
#include "httpx.h"
#include "lighthouse.h"
#include "manager.h"
#include "quorum.h"

namespace {

char* dup_string(const std::string& s) {
  char* out = static_cast<char*>(malloc(s.size() + 1));
  memcpy(out, s.data(), s.size() + 1);
  return out;
}

void set_err(char** err, const std::string& msg) {
  if (err != nullptr) *err = dup_string(msg);
}

struct ClientHandle {
  std::string host;
  int port;
  std::string addr;
  // Per-logical-RPC attempt ids for the ShouldCommit barrier: attached
  // ONCE per call (before the pooled-connection send/retry loop), so a
  // transport-level resend carries the SAME id and the server can replay
  // the decided round's answer instead of counting a duplicate vote.
  // Random base so a recreated client can't collide with its ancestor.
  int64_t attempt_base = []() {
    std::random_device rd;
    return (static_cast<int64_t>(rd()) << 20) & 0x7fffffffffffff00LL;
  }();
  std::atomic<int64_t> attempt_seq{0};
};

// POST helper that converts HTTP/transport failures into err strings.
bool client_post(ClientHandle* c, const std::string& path,
                 const std::string& body, int64_t timeout_ms,
                 std::string* out, char** err) {
  auto res = fthttp::http_post(c->host, c->port, path, body,
                               fthttp::now_ms() + timeout_ms);
  if (!res.error.empty()) {
    set_err(err, (res.timed_out ? std::string("TIMEOUT: ") : std::string()) +
                     "rpc to " + c->addr + path + " failed: " + res.error);
    return false;
  }
  if (res.status == 504) {
    set_err(err, "TIMEOUT: " + path + ": " + res.body);
    return false;
  }
  if (res.status != 200) {
    set_err(err, path + " failed with status " +
                     std::to_string(res.status) + ": " + res.body);
    return false;
  }
  *out = res.body;
  return true;
}

}  // namespace

extern "C" {

void ft_free(char* p) { free(p); }

// ---------------------------------------------------------------- lighthouse

// `extra_json` carries the fleet-scale options as an optional JSON blob
// so the ABI stays stable as options grow:
//   {"cache_quorum": bool, "prune_after_ms": int, "tier": int,
//    "domain": str, "upstream_addr": str,
//    "upstream_report_interval_ms": int, "lease_ms": int,
//    "fleet_capacity": int}
// NULL or "" keeps every default (cached decisions, root tier).
void* ft_lighthouse_new(const char* bind_host, int port, const char* hostname,
                        uint64_t min_replicas, uint64_t join_timeout_ms,
                        uint64_t quorum_tick_ms, uint64_t heartbeat_timeout_ms,
                        const char* extra_json, char** err) {
  try {
    ftlighthouse::LighthouseOpts opts;
    opts.bind_host = bind_host ? bind_host : "0.0.0.0";
    opts.port = port;
    opts.hostname = hostname ? hostname : "";
    opts.quorum.min_replicas = min_replicas;
    opts.quorum.join_timeout_ms = join_timeout_ms;
    opts.quorum.quorum_tick_ms = quorum_tick_ms;
    opts.quorum.heartbeat_timeout_ms = heartbeat_timeout_ms;
    if (extra_json != nullptr && extra_json[0] != '\0') {
      auto extra = ftjson::Value::parse(extra_json);
      opts.cache_quorum = extra.get_bool("cache_quorum", true);
      opts.prune_after_ms = extra.get_int("prune_after_ms", 0);
      opts.tier = static_cast<int>(extra.get_int("tier", -1));
      opts.domain = extra.get_str("domain", "");
      opts.upstream_addr = extra.get_str("upstream_addr", "");
      opts.upstream_report_interval_ms = static_cast<uint64_t>(
          extra.get_int("upstream_report_interval_ms", 500));
      opts.lease_ms = extra.get_int("lease_ms", 0);
      opts.fleet_capacity = extra.get_int("fleet_capacity", 0);
    }
    auto lh = std::make_unique<ftlighthouse::Lighthouse>(std::move(opts));
    lh->start();
    return lh.release();
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

char* ft_lighthouse_address(void* handle) {
  return dup_string(static_cast<ftlighthouse::Lighthouse*>(handle)->address());
}

void ft_lighthouse_shutdown(void* handle) {
  static_cast<ftlighthouse::Lighthouse*>(handle)->shutdown();
}

void ft_lighthouse_free(void* handle) {
  delete static_cast<ftlighthouse::Lighthouse*>(handle);
}

// ------------------------------------------------------------------- manager

// `extra_json` (optional, NULL/"" = defaults) carries growth options:
//   {"job_id": str}  — multi-tenant job this replica group belongs to.
void* ft_manager_new(const char* replica_id, const char* lighthouse_addr,
                     const char* hostname, const char* bind_host, int port,
                     const char* store_addr, uint64_t world_size,
                     uint64_t heartbeat_interval_ms,
                     uint64_t connect_timeout_ms, int exit_on_kill,
                     const char* extra_json, char** err) {
  try {
    ftmanager::ManagerOpts opts;
    opts.replica_id = replica_id;
    opts.lighthouse_addr = lighthouse_addr;
    if (extra_json != nullptr && extra_json[0] != '\0') {
      auto extra = ftjson::Value::parse(extra_json);
      std::string job = extra.get_str("job_id", "default");
      opts.job_id = job.empty() ? "default" : job;
    }
    opts.hostname = hostname ? hostname : "127.0.0.1";
    opts.bind_host = bind_host ? bind_host : "0.0.0.0";
    opts.port = port;
    opts.store_addr = store_addr ? store_addr : "";
    opts.world_size = world_size;
    opts.heartbeat_interval_ms = heartbeat_interval_ms;
    opts.connect_timeout_ms = connect_timeout_ms;
    opts.exit_on_kill = exit_on_kill != 0;
    auto m = std::make_unique<ftmanager::ManagerServer>(std::move(opts));
    m->start();
    return m.release();
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

char* ft_manager_address(void* handle) {
  return dup_string(static_cast<ftmanager::ManagerServer*>(handle)->address());
}

int ft_manager_kill_requested(void* handle) {
  return static_cast<ftmanager::ManagerServer*>(handle)->kill_requested() ? 1
                                                                          : 0;
}

void ft_manager_shutdown(void* handle) {
  static_cast<ftmanager::ManagerServer*>(handle)->shutdown();
}

void ft_manager_free(void* handle) {
  delete static_cast<ftmanager::ManagerServer*>(handle);
}

// ------------------------------------------------------------ manager client

void* ft_manager_client_new(const char* addr, uint64_t connect_timeout_ms,
                            char** err) {
  auto* c = new ClientHandle();
  c->addr = addr;
  if (!fthttp::parse_http_addr(addr, &c->host, &c->port)) {
    set_err(err, std::string("bad manager address: ") + addr);
    delete c;
    return nullptr;
  }
  (void)connect_timeout_ms;  // connections are per-request with retry
  return c;
}

char* ft_manager_client_quorum(void* handle, int64_t rank, int64_t step,
                               const char* checkpoint_metadata,
                               int shrink_only, int data_plane,
                               int64_t comm_epoch,
                               uint64_t timeout_ms, char** err) {
  auto* c = static_cast<ClientHandle*>(handle);
  ftjson::Object req;
  req["rank"] = rank;
  req["step"] = step;
  req["checkpoint_metadata"] = std::string(checkpoint_metadata);
  req["shrink_only"] = shrink_only != 0;
  req["data_plane"] = data_plane != 0;
  req["comm_epoch"] = comm_epoch;
  std::string out;
  if (!client_post(c, "/torchft.ManagerService/Quorum",
                   ftjson::Value(req).dump(),
                   static_cast<int64_t>(timeout_ms), &out, err)) {
    return nullptr;
  }
  return dup_string(out);
}

// Epoch-lease renewal long-poll: parks on the manager's EpochWatch proxy
// (which carries one lighthouse EpochWatch for the whole group) until
// the membership epoch moves off `epoch` or ~timeout_ms elapses. Returns
// the JSON body {"epoch": int, "changed": bool} — changed=false at the
// deadline IS the renewal.
char* ft_manager_client_epoch_watch(void* handle, int64_t epoch,
                                    uint64_t timeout_ms, char** err) {
  auto* c = static_cast<ClientHandle*>(handle);
  ftjson::Object req;
  req["epoch"] = epoch;
  std::string out;
  if (!client_post(c, "/torchft.ManagerService/EpochWatch",
                   ftjson::Value(req).dump(),
                   static_cast<int64_t>(timeout_ms), &out, err)) {
    return nullptr;
  }
  return dup_string(out);
}

char* ft_manager_client_checkpoint_metadata(void* handle, int64_t rank,
                                            uint64_t timeout_ms, char** err) {
  auto* c = static_cast<ClientHandle*>(handle);
  ftjson::Object req;
  req["rank"] = rank;
  std::string out;
  if (!client_post(c, "/torchft.ManagerService/CheckpointMetadata",
                   ftjson::Value(req).dump(),
                   static_cast<int64_t>(timeout_ms), &out, err)) {
    return nullptr;
  }
  try {
    return dup_string(
        ftjson::Value::parse(out).get_str("checkpoint_metadata"));
  } catch (const std::exception& e) {
    set_err(err, std::string("bad response: ") + e.what());
    return nullptr;
  }
}

int ft_manager_client_should_commit(void* handle, int64_t rank, int64_t step,
                                    int should_commit, uint64_t timeout_ms,
                                    char** err) {
  auto* c = static_cast<ClientHandle*>(handle);
  ftjson::Object req;
  req["rank"] = rank;
  req["step"] = step;
  req["should_commit"] = should_commit != 0;
  req["attempt"] = c->attempt_base + c->attempt_seq.fetch_add(1);
  std::string out;
  if (!client_post(c, "/torchft.ManagerService/ShouldCommit",
                   ftjson::Value(req).dump(),
                   static_cast<int64_t>(timeout_ms), &out, err)) {
    return -1;
  }
  try {
    return ftjson::Value::parse(out).get_bool("should_commit") ? 1 : 0;
  } catch (const std::exception& e) {
    set_err(err, std::string("bad response: ") + e.what());
    return -1;
  }
}

int ft_manager_client_kill(void* handle, const char* msg, uint64_t timeout_ms,
                           char** err) {
  auto* c = static_cast<ClientHandle*>(handle);
  ftjson::Object req;
  req["msg"] = std::string(msg);
  // The far side may _exit(1) before replying, so post-send transport
  // errors are expected and ignored — but a connect failure means the kill
  // never reached anything and must surface.
  auto res = fthttp::http_post(c->host, c->port,
                               "/torchft.ManagerService/Kill",
                               ftjson::Value(req).dump(),
                               fthttp::now_ms() +
                                   static_cast<int64_t>(timeout_ms));
  if (!res.error.empty() &&
      res.error.rfind("connect deadline exceeded", 0) == 0) {
    set_err(err, "TIMEOUT: kill rpc could not connect to " + c->addr + ": " +
                     res.error);
    return -1;
  }
  return 0;
}

void ft_manager_client_free(void* handle) {
  delete static_cast<ClientHandle*>(handle);
}

// --------------------------------------------------------- lighthouse client
//
// Persistent client handles: connections ride the process-wide keep-alive
// pool (httpx.cc ConnPool) keyed by endpoint, so a long-lived handle's
// heartbeats/quorums reuse one socket instead of reconnecting per call.
// The one-shot ft_lighthouse_client_heartbeat/_quorum functions below are
// kept as thin wrappers over a transient handle for compatibility.

void* ft_lighthouse_client_new(const char* addr, char** err) {
  auto* c = new ClientHandle();
  c->addr = addr;
  if (!fthttp::parse_http_addr(addr, &c->host, &c->port)) {
    set_err(err, std::string("bad lighthouse address: ") + addr);
    delete c;
    return nullptr;
  }
  return c;
}

void ft_lighthouse_client_free(void* handle) {
  delete static_cast<ClientHandle*>(handle);
}

// `ids_json`: a JSON string ("replica_0") for the single-id form, a JSON
// array (["a","b",...]) for one batched RPC carrying a whole domain's
// heartbeats, or a JSON object passed through as the full request body
// (the multi-tenant form: {"replica_id": ..., "job_id": ...}).
int ft_lighthouse_client_heartbeat2(void* handle, const char* ids_json,
                                    uint64_t timeout_ms, char** err) {
  auto* c = static_cast<ClientHandle*>(handle);
  try {
    auto ids = ftjson::Value::parse(ids_json);
    ftjson::Object req;
    if (ids.is_object()) {
      req = std::move(ids.as_object());
    } else if (ids.is_string()) {
      req["replica_id"] = ids.as_str();
    } else {
      req["replica_ids"] = std::move(ids);
    }
    std::string out;
    return client_post(c, "/torchft.LighthouseService/Heartbeat",
                       ftjson::Value(req).dump(),
                       static_cast<int64_t>(timeout_ms), &out, err)
               ? 0
               : -1;
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return -1;
  }
}

char* ft_lighthouse_client_quorum2(void* handle, const char* requester_json,
                                   uint64_t timeout_ms, char** err) {
  auto* c = static_cast<ClientHandle*>(handle);
  try {
    auto parsed = ftjson::Value::parse(requester_json);
    ftjson::Object req;
    if (parsed.is_object() && parsed.has("requester")) {
      // Full-body passthrough (the multi-tenant form: the caller already
      // wrapped the member and added job_id / registration fields).
      req = std::move(parsed.as_object());
    } else {
      req["requester"] = std::move(parsed);
    }
    std::string out;
    if (!client_post(c, "/torchft.LighthouseService/Quorum",
                     ftjson::Value(req).dump(),
                     static_cast<int64_t>(timeout_ms), &out, err)) {
      return nullptr;
    }
    return dup_string(out);
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

// Generic POST against the lighthouse: `path` is the RPC path (e.g.
// "/torchft.LighthouseService/RegisterJob") and `body_json` the raw
// request body. Returns the malloc'd response body. This is how Python
// reaches RPCs that have no bespoke wrapper (RegisterJob, raw
// EpochWatch) without an ABI bump per endpoint.
char* ft_lighthouse_client_post(void* handle, const char* path,
                                const char* body_json, uint64_t timeout_ms,
                                char** err) {
  auto* c = static_cast<ClientHandle*>(handle);
  std::string out;
  if (!client_post(c, path, body_json ? body_json : "{}",
                   static_cast<int64_t>(timeout_ms), &out, err)) {
    return nullptr;
  }
  return dup_string(out);
}

int ft_lighthouse_client_heartbeat(const char* lighthouse_addr,
                                   const char* replica_id,
                                   uint64_t timeout_ms, char** err) {
  ClientHandle c;
  c.addr = lighthouse_addr;
  if (!fthttp::parse_http_addr(lighthouse_addr, &c.host, &c.port)) {
    set_err(err, std::string("bad lighthouse address: ") + lighthouse_addr);
    return -1;
  }
  // JSON-encode the bare id into heartbeat2's single-id form so the
  // Heartbeat wire shape lives in exactly one place.
  std::string id_json = ftjson::Value(std::string(replica_id)).dump();
  return ft_lighthouse_client_heartbeat2(&c, id_json.c_str(), timeout_ms,
                                         err);
}

char* ft_lighthouse_client_quorum(const char* lighthouse_addr,
                                  const char* requester_json,
                                  uint64_t timeout_ms, char** err) {
  ClientHandle c;
  c.addr = lighthouse_addr;
  if (!fthttp::parse_http_addr(lighthouse_addr, &c.host, &c.port)) {
    set_err(err, std::string("bad lighthouse address: ") + lighthouse_addr);
    return nullptr;
  }
  return ft_lighthouse_client_quorum2(&c, requester_json, timeout_ms, err);
}

// ------------------------------------------------------------- pure kernels
// Exposed so the Python test suite can drive the decision kernels directly
// (the reference tests its Rust kernels in-file; we test from pytest).

static ftquorum::QuorumOpts parse_quorum_opts(const char* opts_json);

char* ft_quorum_compute(int64_t now_ms, const char* state_json,
                        const char* opts_json, char** err) {
  try {
    auto state_v = ftjson::Value::parse(state_json);
    ftquorum::QuorumState state;
    for (const auto& p : state_v.get("participants").as_array()) {
      ftquorum::ParticipantDetails d;
      d.joined_ms = p.get_int("joined_ms");
      d.member = ftquorum::Member::from_json(p.get("member"));
      state.participants[d.member.replica_id] = d;
    }
    if (state_v.has("heartbeats")) {
      for (const auto& kv : state_v.get("heartbeats").as_object()) {
        state.heartbeats[kv.first] = kv.second.as_int();
      }
    }
    if (state_v.has("prev_quorum") && !state_v.get("prev_quorum").is_null()) {
      state.prev_quorum =
          ftquorum::QuorumInfo::from_json(state_v.get("prev_quorum"));
    }
    auto opts = parse_quorum_opts(opts_json);
    auto decision = ftquorum::quorum_compute(now_ms, state, opts);
    // decision_to_json is shared with ft_iq_decision: the byte-identity
    // oracle between the incremental and from-scratch planes.
    return dup_string(ftquorum::decision_to_json(decision));
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

char* ft_compute_quorum_results(const char* replica_id, int64_t rank,
                                const char* quorum_json, char** err) {
  try {
    auto quorum =
        ftquorum::QuorumInfo::from_json(ftjson::Value::parse(quorum_json));
    auto results = ftquorum::compute_quorum_results(replica_id, rank, quorum);
    return dup_string(results.to_json().dump());
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

// ------------------------------------------------- incremental quorum driver
// Drives ftquorum::IncrementalQuorum directly from Python so the property
// tests can replay arbitrary heartbeat/join/expiry/install sequences and
// pin the incremental plane's decision JSON byte-identical to a
// from-scratch ft_quorum_compute over the dumped state.

static ftquorum::QuorumOpts parse_quorum_opts(const char* opts_json) {
  auto opts_v = ftjson::Value::parse(opts_json);
  ftquorum::QuorumOpts opts;
  opts.min_replicas =
      static_cast<uint64_t>(opts_v.get_int("min_replicas", 1));
  opts.join_timeout_ms =
      static_cast<uint64_t>(opts_v.get_int("join_timeout_ms", 60000));
  opts.heartbeat_timeout_ms =
      static_cast<uint64_t>(opts_v.get_int("heartbeat_timeout_ms", 5000));
  return opts;
}

void* ft_iq_new(const char* opts_json, int incremental,
                int64_t prune_after_ms, char** err) {
  try {
    return new ftquorum::IncrementalQuorum(parse_quorum_opts(opts_json),
                                           incremental != 0, prune_after_ms);
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

void ft_iq_free(void* handle) {
  delete static_cast<ftquorum::IncrementalQuorum*>(handle);
}

void ft_iq_heartbeat(void* handle, const char* replica_id, int64_t now_ms) {
  static_cast<ftquorum::IncrementalQuorum*>(handle)->heartbeat(replica_id,
                                                               now_ms);
}

// The door-knock's early expiry; 1 iff the replica was healthy.
int ft_iq_expire(void* handle, const char* replica_id, int64_t now_ms) {
  return static_cast<ftquorum::IncrementalQuorum*>(handle)->expire(replica_id,
                                                                   now_ms);
}

int ft_iq_join(void* handle, int64_t joined_ms, const char* member_json,
               char** err) {
  try {
    auto m = ftquorum::Member::from_json(ftjson::Value::parse(member_json));
    static_cast<ftquorum::IncrementalQuorum*>(handle)->join(joined_ms, m);
    return 0;
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return -1;
  }
}

// Same {"quorum": [...]|null, "reason": str} shape (and bytes) as
// ft_quorum_compute — decision_to_json is shared.
char* ft_iq_decision(void* handle, int64_t now_ms, char** err) {
  try {
    auto* iq = static_cast<ftquorum::IncrementalQuorum*>(handle);
    return dup_string(ftquorum::decision_to_json(iq->decision(now_ms)));
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

// Install the current decision as prev_quorum when ready (what the
// lighthouse tick does on announcement). Returns
// {"installed": bool, "quorum_id": int}.
char* ft_iq_install(void* handle, int64_t now_ms, int64_t wall_ms,
                    char** err) {
  try {
    auto* iq = static_cast<ftquorum::IncrementalQuorum*>(handle);
    auto decision = iq->decision(now_ms);  // copy: install mutates state
    ftjson::Object out;
    if (decision.quorum.has_value()) {
      const auto& q = iq->install(*decision.quorum, wall_ms);
      out["installed"] = true;
      out["quorum_id"] = q.quorum_id;
    } else {
      out["installed"] = false;
      out["quorum_id"] = iq->quorum_id();
    }
    return dup_string(ftjson::Value(std::move(out)).dump());
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

// Dump the live QuorumState in exactly the shape ft_quorum_compute
// parses, so the oracle recompute runs over the same inputs.
char* ft_iq_state(void* handle, char** err) {
  try {
    auto* iq = static_cast<ftquorum::IncrementalQuorum*>(handle);
    const auto& state = iq->state();
    ftjson::Object o;
    ftjson::Array parts;
    for (const auto& kv : state.participants) {
      ftjson::Object p;
      p["joined_ms"] = kv.second.joined_ms;
      p["member"] = kv.second.member.to_json();
      parts.push_back(ftjson::Value(std::move(p)));
    }
    o["participants"] = ftjson::Value(std::move(parts));
    ftjson::Object hbs;
    for (const auto& kv : state.heartbeats) hbs[kv.first] = kv.second;
    o["heartbeats"] = ftjson::Value(std::move(hbs));
    o["prev_quorum"] = state.prev_quorum.has_value()
                           ? state.prev_quorum->to_json()
                           : ftjson::Value(nullptr);
    return dup_string(ftjson::Value(std::move(o)).dump());
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

char* ft_iq_counters(void* handle, char** err) {
  try {
    auto* iq = static_cast<ftquorum::IncrementalQuorum*>(handle);
    ftjson::Object o;
    o["epoch"] = static_cast<int64_t>(iq->epoch());
    o["compute_count"] = static_cast<int64_t>(iq->compute_count());
    o["cache_hits"] = static_cast<int64_t>(iq->cache_hits());
    o["pruned_heartbeats"] =
        static_cast<int64_t>(iq->pruned_heartbeats());
    o["pruned_participants"] =
        static_cast<int64_t>(iq->pruned_participants());
    o["healthy"] = static_cast<int64_t>(iq->healthy_count());
    return dup_string(ftjson::Value(std::move(o)).dump());
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

// JSON round-trip helper for ftjson unit tests.
char* ft_json_roundtrip(const char* text, char** err) {
  try {
    return dup_string(ftjson::Value::parse(text).dump());
  } catch (const std::exception& e) {
    set_err(err, e.what());
    return nullptr;
  }
}

}  // extern "C"
