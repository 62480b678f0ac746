#include "httpx.h"

#include <arpa/inet.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <chrono>
#include <cstring>
#include <mutex>
#include <random>
#include <sstream>
#include <stdexcept>

namespace fthttp {

int64_t now_ms() {
  return std::chrono::duration_cast<std::chrono::milliseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

namespace {

void set_socket_timeout(int fd, int64_t ms) {
  if (ms < 1) ms = 1;
  struct timeval tv;
  tv.tv_sec = ms / 1000;
  tv.tv_usec = (ms % 1000) * 1000;
  setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
  setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
}

bool send_all(int fd, const char* data, size_t len) {
  size_t sent = 0;
  while (sent < len) {
    ssize_t n = ::send(fd, data + sent, len - sent, MSG_NOSIGNAL);
    if (n <= 0) return false;
    sent += static_cast<size_t>(n);
  }
  return true;
}

std::string lower(std::string s) {
  for (auto& c : s) c = static_cast<char>(tolower(c));
  return s;
}

// Buffered reader for one connection.
struct ConnReader {
  int fd;
  std::string buf;
  size_t pos = 0;

  // Returns false on EOF/error.
  bool fill() {
    char tmp[8192];
    ssize_t n = ::recv(fd, tmp, sizeof(tmp), 0);
    if (n <= 0) return false;
    buf.append(tmp, static_cast<size_t>(n));
    return true;
  }

  // Read a line terminated by \r\n (returned without terminator).
  bool read_line(std::string* out) {
    while (true) {
      size_t nl = buf.find("\r\n", pos);
      if (nl != std::string::npos) {
        *out = buf.substr(pos, nl - pos);
        pos = nl + 2;
        return true;
      }
      if (!fill()) return false;
    }
  }

  bool read_exact(size_t n, std::string* out) {
    while (buf.size() - pos < n) {
      if (!fill()) return false;
    }
    *out = buf.substr(pos, n);
    pos += n;
    // compact occasionally
    if (pos > (1u << 20)) {
      buf.erase(0, pos);
      pos = 0;
    }
    return true;
  }
};

bool read_request(ConnReader& rd, Request* req) {
  std::string line;
  if (!rd.read_line(&line)) return false;
  std::istringstream ss(line);
  std::string version;
  if (!(ss >> req->method >> req->path >> version)) return false;
  req->headers.clear();
  while (true) {
    std::string h;
    if (!rd.read_line(&h)) return false;
    if (h.empty()) break;
    size_t colon = h.find(':');
    if (colon == std::string::npos) continue;
    std::string key = lower(h.substr(0, colon));
    size_t vstart = colon + 1;
    while (vstart < h.size() && h[vstart] == ' ') vstart++;
    req->headers[key] = h.substr(vstart);
  }
  // Reject unparsable/absurd Content-Length instead of throwing into a
  // detached thread (which would terminate the process) or buffering
  // unboundedly.
  static constexpr size_t kMaxBody = 1ull << 30;  // 1 GiB
  size_t content_length = 0;
  auto it = req->headers.find("content-length");
  if (it != req->headers.end()) {
    try {
      long long v = std::stoll(it->second);
      if (v < 0 || static_cast<size_t>(v) > kMaxBody) return false;
      content_length = static_cast<size_t>(v);
    } catch (...) {
      return false;
    }
  }
  if (content_length > 0) {
    if (!rd.read_exact(content_length, &req->body)) return false;
  } else {
    req->body.clear();
  }
  int64_t timeout = 60000;
  auto t = req->headers.find("x-timeout-ms");
  if (t != req->headers.end()) {
    try {
      timeout = std::stoll(t->second);
    } catch (...) {
    }
  }
  req->deadline_ms = now_ms() + timeout;
  return true;
}

bool write_response(int fd, const Response& resp, bool keep_alive) {
  std::ostringstream ss;
  const char* reason = resp.status == 200 ? "OK" : "Error";
  ss << "HTTP/1.1 " << resp.status << " " << reason << "\r\n"
     << "Content-Type: " << resp.content_type << "\r\n"
     << "Content-Length: " << resp.body.size() << "\r\n"
     << "Connection: " << (keep_alive ? "keep-alive" : "close") << "\r\n"
     << "\r\n";
  std::string head = ss.str();
  return send_all(fd, head.data(), head.size()) &&
         send_all(fd, resp.body.data(), resp.body.size());
}

int connect_with_deadline(const std::string& host, int port,
                          int64_t deadline_ms, std::string* err) {
  struct addrinfo hints;
  memset(&hints, 0, sizeof(hints));
  hints.ai_family = AF_UNSPEC;
  hints.ai_socktype = SOCK_STREAM;
  struct addrinfo* res = nullptr;
  std::string port_s = std::to_string(port);
  int rc = getaddrinfo(host.c_str(), port_s.c_str(), &hints, &res);
  if (rc != 0 || res == nullptr) {
    *err = "getaddrinfo failed for " + host + ": " + gai_strerror(rc);
    return -1;
  }
  int fd = -1;
  for (struct addrinfo* ai = res; ai != nullptr; ai = ai->ai_next) {
    fd = ::socket(ai->ai_family, ai->ai_socktype, ai->ai_protocol);
    if (fd < 0) continue;
    int flags = fcntl(fd, F_GETFL, 0);
    fcntl(fd, F_SETFL, flags | O_NONBLOCK);
    int c = ::connect(fd, ai->ai_addr, ai->ai_addrlen);
    if (c == 0) {
      fcntl(fd, F_SETFL, flags);
      break;
    }
    if (errno == EINPROGRESS) {
      struct pollfd pfd;
      pfd.fd = fd;
      pfd.events = POLLOUT;
      int64_t remaining = deadline_ms - now_ms();
      int pr = ::poll(&pfd, 1, remaining < 0 ? 0 : static_cast<int>(remaining));
      int so_err = 0;
      socklen_t len = sizeof(so_err);
      if (pr > 0 &&
          getsockopt(fd, SOL_SOCKET, SO_ERROR, &so_err, &len) == 0 &&
          so_err == 0) {
        fcntl(fd, F_SETFL, flags);
        break;
      }
    }
    ::close(fd);
    fd = -1;
  }
  freeaddrinfo(res);
  if (fd < 0 && err->empty()) *err = "connect failed to " + host + ":" + port_s;
  return fd;
}

void enable_tcp_keepalive(int fd) {
  // Parity with the reference's HTTP2 keep-alives (src/net.rs:9-20:
  // interval 60s, timeout 20s): detect dead peers on idle pooled
  // connections at the TCP layer.
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_KEEPALIVE, &one, sizeof(one));
#ifdef TCP_KEEPIDLE
  int idle = 60;
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPIDLE, &idle, sizeof(idle));
#endif
#ifdef TCP_KEEPINTVL
  int intvl = 20;
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPINTVL, &intvl, sizeof(intvl));
#endif
#ifdef TCP_KEEPCNT
  int cnt = 3;
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPCNT, &cnt, sizeof(cnt));
#endif
}

// Idle-connection pool keyed by endpoint: heartbeats/quorum long-polls at a
// 100 ms cadence must reuse one connection per (client, server) pair
// instead of opening a socket per request (the role tonic's channel reuse
// plays in the reference, src/net.rs).
class ConnPool {
 public:
  static ConnPool& instance() {
    static ConnPool* pool = new ConnPool();  // leaked: outlives all users
    return *pool;
  }

  // Returns a pooled fd (reused=true) or -1 if none idle.
  int acquire(const std::string& host, int port) {
    std::lock_guard<std::mutex> lk(mu_);
    auto it = idle_.find({host, port});
    if (it == idle_.end() || it->second.empty()) return -1;
    int fd = it->second.back();
    it->second.pop_back();
    --total_;
    // Keep lru_.size() == total_ (otherwise steady acquire/release
    // cycles would grow it forever).
    drop_one_lru_entry_locked({host, port});
    return fd;
  }

  void release(const std::string& host, int port, int fd) {
    std::lock_guard<std::mutex> lk(mu_);
    auto key = std::make_pair(host, port);
    auto& v = idle_[key];
    if (v.size() >= kMaxIdlePerEndpoint) {
      // Per-endpoint cap: retire THIS endpoint's oldest fd for the fresh
      // one (never punish another endpoint's healthy connection).
      ::close(v.front());
      v.erase(v.begin());
      drop_one_lru_entry_locked(key);
      --total_;
    } else if (total_ >= kMaxIdleTotal) {
      // Global cap doubles as garbage collection: endpoints that went
      // away (killed replicas on ephemeral ports) are evicted oldest-
      // first instead of parking dead fds forever.
      evict_oldest_locked();
    }
    v.push_back(fd);
    lru_.push_back(key);
    ++total_;
  }

 private:
  static constexpr size_t kMaxIdlePerEndpoint = 4;
  static constexpr size_t kMaxIdleTotal = 32;

  void drop_one_lru_entry_locked(const std::pair<std::string, int>& key) {
    for (auto lit = lru_.begin(); lit != lru_.end(); ++lit) {
      if (*lit == key) {
        lru_.erase(lit);
        return;
      }
    }
  }

  void evict_oldest_locked() {
    while (!lru_.empty()) {
      auto key = lru_.front();
      lru_.erase(lru_.begin());
      auto it = idle_.find(key);
      if (it == idle_.end() || it->second.empty()) continue;  // stale entry
      ::close(it->second.front());
      it->second.erase(it->second.begin());
      --total_;
      return;
    }
  }

  std::mutex mu_;
  std::map<std::pair<std::string, int>, std::vector<int>> idle_;
  // Insertion-order endpoint keys, one entry per pooled fd (approximate
  // LRU; stale entries are skipped during eviction).
  std::vector<std::pair<std::string, int>> lru_;
  size_t total_ = 0;
};

// One request/response exchange on an established connection. Returns
// false with *retryable=true when the failure happened before any response
// byte arrived on a REUSED connection (stale pooled socket: the server
// closed it while idle) — the caller retries once on a fresh connection.
bool exchange_once(int fd, const std::string& method, const std::string& host,
                   int port, const std::string& path, const std::string& body,
                   int64_t deadline_ms, bool reused, ClientResult* result,
                   bool* retryable, bool* server_wants_close) {
  *retryable = false;
  *server_wants_close = false;
  int64_t remaining = deadline_ms - now_ms();
  if (remaining <= 0) remaining = 1;
  set_socket_timeout(fd, remaining + 1000);  // socket guard > logical deadline

  std::ostringstream ss;
  ss << method << " " << path << " HTTP/1.1\r\n"
     << "Host: " << host << ":" << port << "\r\n"
     << "Content-Type: application/json\r\n"
     << "Content-Length: " << body.size() << "\r\n"
     << "x-timeout-ms: " << remaining << "\r\n"
     << "Connection: keep-alive\r\n\r\n";
  std::string head = ss.str();
  if (!send_all(fd, head.data(), head.size()) ||
      !send_all(fd, body.data(), body.size())) {
    result->error = "send failed";
    *retryable = reused;
    return false;
  }

  ConnReader rd{fd};
  std::string status_line;
  if (!rd.read_line(&status_line)) {
    result->error = "no response (recv failed or timed out)";
    result->timed_out = (now_ms() >= deadline_ms);
    // EOF with zero bytes on a reused conn = stale pooled socket; a
    // timeout is a real deadline failure, never retried. The one-shot
    // resend can double-EXECUTE a POST the server processed before the
    // connection died, so every pooled endpoint must be idempotent:
    // quorum/heartbeat/metadata are rank-keyed set inserts or reads, and
    // the ShouldCommit barrier is step-keyed with a cached-decision
    // replay path (manager.cc handle_should_commit) for exactly this.
    *retryable = reused && !result->timed_out;
    return false;
  }
  // "HTTP/1.1 200 OK"
  {
    std::istringstream sl(status_line);
    std::string version;
    sl >> version >> result->status;
  }
  size_t content_length = 0;
  while (true) {
    std::string h;
    if (!rd.read_line(&h)) {
      result->error = "truncated headers";
      return false;
    }
    if (h.empty()) break;
    size_t colon = h.find(':');
    if (colon == std::string::npos) continue;
    std::string key = lower(h.substr(0, colon));
    std::string val = h.substr(colon + 1);
    while (!val.empty() && val.front() == ' ') val.erase(val.begin());
    if (key == "content-length") {
      try {
        long long v = std::stoll(val);
        if (v < 0) {
          result->error = "bad content-length in response";
          return false;
        }
        content_length = static_cast<size_t>(v);
      } catch (...) {
        result->error = "bad content-length in response";
        return false;
      }
    } else if (key == "connection" && lower(val) == "close") {
      *server_wants_close = true;
    }
  }
  if (content_length > 0 && !rd.read_exact(content_length, &result->body)) {
    result->error = "truncated body";
    return false;
  }
  // Anything the reader over-buffered past this response would desync the
  // next request on this connection; don't pool it.
  if (rd.pos != rd.buf.size()) *server_wants_close = true;
  return true;
}

ClientResult do_request(const std::string& method, const std::string& host,
                        int port, const std::string& path,
                        const std::string& body, int64_t deadline_ms) {
  ClientResult result;
  auto& pool = ConnPool::instance();

  for (int attempt = 0; attempt < 2; ++attempt) {
    result = ClientResult{};
    bool reused = false;
    int fd = -1;
    if (attempt == 0) {
      fd = pool.acquire(host, port);
      reused = fd >= 0;
    }
    if (fd < 0) {
      // Jittered exponential connect retry until deadline (ref
      // src/retry.rs).
      static thread_local std::mt19937 rng{std::random_device{}()};
      int64_t backoff = 10;
      std::string conn_err;
      while (true) {
        conn_err.clear();
        fd = connect_with_deadline(host, port, deadline_ms, &conn_err);
        if (fd >= 0) break;
        int64_t remaining = deadline_ms - now_ms();
        if (remaining <= 0) {
          result.error = "connect deadline exceeded: " + conn_err;
          result.timed_out = true;
          return result;
        }
        std::uniform_int_distribution<int64_t> jitter(0, backoff / 2 + 1);
        int64_t sleep_ms = std::min(backoff + jitter(rng), remaining);
        std::this_thread::sleep_for(std::chrono::milliseconds(sleep_ms));
        backoff = std::min<int64_t>(backoff * 2, 1000);
      }
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      enable_tcp_keepalive(fd);
    }

    bool retryable = false;
    bool server_wants_close = false;
    bool ok = exchange_once(fd, method, host, port, path, body, deadline_ms,
                            reused, &result, &retryable, &server_wants_close);
    if (ok) {
      if (server_wants_close) {
        ::close(fd);
      } else {
        pool.release(host, port, fd);
      }
      return result;
    }
    ::close(fd);
    if (!retryable) return result;
    // stale pooled connection: one retry on a fresh socket
  }
  return result;
}

}  // namespace

bool parse_http_addr(const std::string& addr, std::string* host, int* port) {
  std::string rest = addr;
  const std::string scheme = "http://";
  if (rest.rfind(scheme, 0) == 0) rest = rest.substr(scheme.size());
  size_t slash = rest.find('/');
  if (slash != std::string::npos) rest = rest.substr(0, slash);
  size_t colon = rest.rfind(':');
  if (colon == std::string::npos) return false;
  *host = rest.substr(0, colon);
  if (host->empty()) *host = "127.0.0.1";
  // strip ipv6 brackets
  if (host->size() >= 2 && (*host)[0] == '[' && host->back() == ']')
    *host = host->substr(1, host->size() - 2);
  try {
    *port = std::stoi(rest.substr(colon + 1));
  } catch (...) {
    return false;
  }
  return true;
}

std::vector<Knock> knock(const std::vector<std::string>& addrs,
                         int64_t deadline_ms) {
  struct Target {
    int tried = 0, refused = 0;
    bool connected = false;
  };
  struct Flight {
    size_t target;
    int fd;
  };
  std::vector<Target> targets(addrs.size());
  std::vector<Flight> flights;
  for (size_t i = 0; i < addrs.size(); i++) {
    std::string host;
    int port = 0;
    if (!parse_http_addr(addrs[i], &host, &port)) continue;
    struct addrinfo hints;
    memset(&hints, 0, sizeof(hints));
    hints.ai_family = AF_INET;
    hints.ai_socktype = SOCK_STREAM;
    struct addrinfo* res = nullptr;
    if (getaddrinfo(host.c_str(), std::to_string(port).c_str(), &hints,
                    &res) != 0) {
      continue;
    }
    for (struct addrinfo* ai = res; ai != nullptr; ai = ai->ai_next) {
      int fd = ::socket(ai->ai_family, ai->ai_socktype | SOCK_NONBLOCK,
                        ai->ai_protocol);
      if (fd < 0) continue;
      targets[i].tried += 1;
      if (::connect(fd, ai->ai_addr, ai->ai_addrlen) == 0) {
        targets[i].connected = true;
      } else if (errno == EINPROGRESS) {
        flights.push_back({i, fd});
        continue;
      } else if (errno == ECONNREFUSED) {
        targets[i].refused += 1;
      }
      ::close(fd);
    }
    freeaddrinfo(res);
  }
  while (!flights.empty()) {
    int64_t remaining = deadline_ms - now_ms();
    if (remaining <= 0) break;
    std::vector<struct pollfd> pfds(flights.size());
    for (size_t j = 0; j < flights.size(); j++) {
      pfds[j].fd = flights[j].fd;
      pfds[j].events = POLLOUT;
      pfds[j].revents = 0;
    }
    int pr = ::poll(pfds.data(), pfds.size(), static_cast<int>(remaining));
    if (pr < 0 && errno != EINTR) break;
    std::vector<Flight> still;
    for (size_t j = 0; j < flights.size(); j++) {
      if (pfds[j].revents == 0) {
        still.push_back(flights[j]);
        continue;
      }
      int so_err = 0;
      socklen_t len = sizeof(so_err);
      if (getsockopt(flights[j].fd, SOL_SOCKET, SO_ERROR, &so_err, &len) ==
          0) {
        if (so_err == 0) targets[flights[j].target].connected = true;
        if (so_err == ECONNREFUSED) targets[flights[j].target].refused += 1;
      }
      ::close(flights[j].fd);
    }
    flights.swap(still);
  }
  for (const Flight& f : flights) ::close(f.fd);  // unanswered in time
  std::vector<Knock> out(addrs.size(), Knock::kNoAddress);
  for (size_t i = 0; i < addrs.size(); i++) {
    const Target& t = targets[i];
    if (t.tried == 0) continue;
    out[i] = t.connected                ? Knock::kConnected
             : t.refused == t.tried     ? Knock::kRefused
                                        : Knock::kUnanswered;
  }
  return out;
}

ClientResult http_post(const std::string& host, int port,
                       const std::string& path, const std::string& body,
                       int64_t deadline_ms) {
  return do_request("POST", host, port, path, body, deadline_ms);
}

ClientResult http_get(const std::string& host, int port,
                      const std::string& path, int64_t deadline_ms) {
  return do_request("GET", host, port, path, "", deadline_ms);
}

HttpServer::HttpServer(const std::string& host, int port) : host_(host) {
  listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
  if (listen_fd_ < 0) throw std::runtime_error("socket() failed");
  int one = 1;
  setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  struct sockaddr_in addr;
  memset(&addr, 0, sizeof(addr));
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (host.empty() || host == "0.0.0.0" || host == "[::]") {
    addr.sin_addr.s_addr = INADDR_ANY;
  } else if (inet_pton(AF_INET, host.c_str(), &addr.sin_addr) != 1) {
    addr.sin_addr.s_addr = INADDR_ANY;
  }
  if (::bind(listen_fd_, reinterpret_cast<struct sockaddr*>(&addr),
             sizeof(addr)) != 0) {
    ::close(listen_fd_);
    throw std::runtime_error("bind failed on " + host + ":" +
                             std::to_string(port));
  }
  if (::listen(listen_fd_, 512) != 0) {
    ::close(listen_fd_);
    throw std::runtime_error("listen failed");
  }
  struct sockaddr_in bound;
  socklen_t blen = sizeof(bound);
  getsockname(listen_fd_, reinterpret_cast<struct sockaddr*>(&bound), &blen);
  port_ = ntohs(bound.sin_port);
}

void HttpServer::start() {
  accept_thread_ = std::thread([this] { accept_loop(); });
}

void HttpServer::accept_loop() {
  while (!stopping_.load()) {
    int fd = ::accept(listen_fd_, nullptr, nullptr);
    if (fd < 0) {
      if (stopping_.load()) return;
      continue;
    }
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    enable_tcp_keepalive(fd);
    // Idle pooled client connections are parked in recv(); reap them if
    // silent for 5 min so vanished clients can't leak server threads.
    set_socket_timeout(fd, 300000);
    {
      std::lock_guard<std::mutex> lk(conn_mu_);
      conn_fds_.push_back(fd);
    }
    total_accepted_.fetch_add(1);
    active_conns_.fetch_add(1);
    std::thread([this, fd] {
      serve_conn(fd);
      active_conns_.fetch_sub(1);
    }).detach();
  }
}

void HttpServer::serve_conn(int fd) {
  ConnReader rd{fd};
  while (!stopping_.load()) {
    Request req;
    if (!read_request(rd, &req)) break;
    req.client_fd = fd;
    Response resp;
    try {
      resp = handler_ ? handler_(req)
                      : Response{500, "text/plain", "no handler"};
    } catch (const std::exception& e) {
      resp = Response{500, "text/plain", std::string("error: ") + e.what()};
    }
    bool close_requested = false;
    auto c = req.headers.find("connection");
    if (c != req.headers.end() && lower(c->second) == "close")
      close_requested = true;
    if (!write_response(fd, resp, !close_requested) || close_requested) break;
  }
  ::shutdown(fd, SHUT_RDWR);
  ::close(fd);
  std::lock_guard<std::mutex> lk(conn_mu_);
  for (auto it = conn_fds_.begin(); it != conn_fds_.end(); ++it) {
    if (*it == fd) {
      conn_fds_.erase(it);
      break;
    }
  }
}

void HttpServer::shutdown() {
  if (stopping_.exchange(true)) return;
  ::shutdown(listen_fd_, SHUT_RDWR);
  ::close(listen_fd_);
  if (accept_thread_.joinable()) accept_thread_.join();
  {
    std::lock_guard<std::mutex> lk(conn_mu_);
    for (int fd : conn_fds_) ::shutdown(fd, SHUT_RDWR);
  }
  // Bounded wait for connection threads to drain.
  int64_t deadline = now_ms() + 5000;
  while (active_conns_.load() > 0 && now_ms() < deadline) {
    std::this_thread::sleep_for(std::chrono::milliseconds(5));
  }
}

HttpServer::~HttpServer() { shutdown(); }

}  // namespace fthttp
