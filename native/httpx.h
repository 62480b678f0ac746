// torchft_tpu native control plane — minimal HTTP/1.1 server + client.
//
// Transport for the control-plane services (Lighthouse/Manager, see
// proto/torchft_tpu.proto). Thread-per-connection with keep-alive; client
// timeouts ride an `x-timeout-ms` request header which the server converts
// into an absolute deadline so *server-side* waits honor client deadlines
// (the role grpc-timeout parsing plays in the reference, src/timeout.rs).
// Connection establishment retries with jittered exponential backoff
// (reference: src/retry.rs, src/net.rs).
#pragma once

#include <atomic>
#include <cstdint>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace fthttp {

int64_t now_ms();  // monotonic milliseconds

struct Request {
  std::string method;
  std::string path;
  std::string body;
  std::map<std::string, std::string> headers;  // lowercase keys
  int64_t deadline_ms = 0;  // absolute (now_ms clock); always set by server
  // The serving connection's fd (set by the server; -1 in tests that
  // build Requests by hand). Long-poll handlers may PEEK it to detect a
  // vanished client — a handler parked in a cv wait never reads the
  // socket, so a disconnect is otherwise invisible until the wait ends.
  int client_fd = -1;
};

struct Response {
  int status = 200;
  std::string content_type = "application/json";
  std::string body;
};

using Handler = std::function<Response(const Request&)>;

class HttpServer {
 public:
  // Binds immediately (port 0 = ephemeral); serving starts on start().
  HttpServer(const std::string& host, int port);
  ~HttpServer();

  void set_handler(Handler h) { handler_ = std::move(h); }
  void start();
  void shutdown();

  int port() const { return port_; }
  const std::string& host() const { return host_; }
  // Lifetime count of accepted connections: with client-side connection
  // pooling this stays near the number of distinct clients instead of
  // growing with every heartbeat (observability for keep-alive tests).
  int total_accepted() const { return total_accepted_.load(); }

 private:
  void accept_loop();
  void serve_conn(int fd);

  std::string host_;
  int port_ = 0;
  int listen_fd_ = -1;
  Handler handler_;
  std::thread accept_thread_;
  std::atomic<bool> stopping_{false};
  std::atomic<int> active_conns_{0};
  std::atomic<int> total_accepted_{0};
  std::mutex conn_mu_;
  std::vector<int> conn_fds_;
};

struct ClientResult {
  int status = 0;          // HTTP status; 0 on transport error
  std::string body;
  std::string error;       // non-empty on transport error/timeout
  bool timed_out = false;  // transport-level deadline expiry
};

// Parse "http://host:port[/...]" or "host:port" into host/port.
bool parse_http_addr(const std::string& addr, std::string* host, int* port);

// What one door-knock found at an address: a plain TCP connect, closed
// the moment it is answered; nothing is sent.
enum class Knock {
  kNoAddress,   // does not parse or resolve: no connection was tried
  kConnected,   // something listens there
  kRefused,     // every resolved address answered ECONNREFUSED (the
                // kernel's RST: the host is up and no process listens)
  kUnanswered,  // timed out, unreachable, or any other error
};

// Knock on every "http://host:port" at once: non-blocking connects, all
// in flight together, polled until deadline_ms (now_ms clock). IPv4
// only, like HttpServer: a v6 address of the same name would refuse
// while the server lives. Name resolution is the one step the deadline
// does not bound.
std::vector<Knock> knock(const std::vector<std::string>& addrs,
                         int64_t deadline_ms);

// POST with an absolute deadline; sets x-timeout-ms from the remaining
// budget; retries connection establishment with backoff until the deadline.
ClientResult http_post(const std::string& host, int port,
                       const std::string& path, const std::string& body,
                       int64_t deadline_ms);

ClientResult http_get(const std::string& host, int port,
                      const std::string& path, int64_t deadline_ms);

}  // namespace fthttp
