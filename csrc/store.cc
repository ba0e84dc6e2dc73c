// TCP key-value store — native analog of the reference's comm bootstrap
// (/root/reference/paddle/fluid/platform/gen_comm_id_helper.cc TCP broadcast
// of NCCL ids, and python/paddle/distributed/parallel.py:108's TCP store).
// On TPU there are no NCCL ids; this store bootstraps multi-host DCN
// rendezvous (coordinator discovery, barriers, rank registration) for the
// launch/elastic subsystems.
//
// Protocol (length-prefixed binary over TCP):
//   u8 op ('S' set, 'G' get-blocking, 'A' add, 'N' add-nonced,
//          'R' counter-read, 'D' delete, 'L' list-count)
//   u32 key_len, key bytes
//   SET: u32 val_len, val bytes            -> reply u8 0
//   GET: u64 timeout_ms                    -> reply u8 ok, u32 len, bytes
//   ADD: i64 delta                         -> reply u8 0, i64 new_value
//   ADN: i64 delta, u64 cid, u64 seq       -> reply u8 0, i64 new_value
//        idempotent form: the server remembers a bounded ring of each
//        client's recently applied (seq -> value); a duplicate
//        (cid, seq) — a client retry after a lost reply — returns the
//        recorded value WITHOUT re-applying the delta. The python
//        client guarantees a retried op resends its nonce BEFORE any
//        other op from the same cid (the op lock spans the whole
//        attempt loop), so correctness needs only the newest entry;
//        kNonceRing=64 is defensive margin (16 bytes x 64 per client)
//        for clients that interleave differently.
//   DEL:                                   -> reply u8 0
//
// C ABI:
//   pt_store_server_start(port) -> handle (>0) or -errno
//   pt_store_server_stop(handle)
//   pt_store_connect(host, port, timeout_ms) -> fd or -1
//   pt_store_close(fd)
//   pt_store_set(fd, key, val, len) -> 0
//   pt_store_get(fd, key, buf, cap, timeout_ms) -> len or -1 (timeout)
//   pt_store_add(fd, key, delta, out_new) -> 0
//   pt_store_delete(fd, key) -> 0
#include <arpa/inet.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

struct StoreServer {
  int listen_fd = -1;
  std::thread accept_thread;
  std::vector<std::thread> workers;
  std::atomic<bool> stop{false};
  std::mutex mu;
  std::condition_variable cv;
  std::map<std::string, std::string> kv;
  std::map<std::string, int64_t> counters;
  // nonce ledger for idempotent adds: cid -> ring of recent
  // (seq, result). A retried (cid, seq) after a lost reply must not
  // double-apply — leader election treats counter values as atomic
  // claims. The client serializes a retry against every other op on
  // its connection (op lock spans the attempt loop), so the newest
  // entry suffices; the ring depth is defensive margin. The ledger is
  // bounded too: clients churn (elastic restarts mint a fresh cid per
  // TCPStore instance, forever), so past kMaxNonceClients the
  // oldest-registered cids are evicted FIFO — a long-lived master
  // must not grow memory with every client generation. An evicted
  // cid only matters if that client still has a lost-ack retry in
  // flight, which needs thousands of NEW clients inside one
  // retry-backoff window.
  std::map<uint64_t, std::deque<std::pair<uint64_t, int64_t>>> add_nonces;
  std::deque<uint64_t> nonce_cid_order;
  // live client fds (guarded by mu): server_stop shuts them down so
  // workers blocked in recv wake and join — shutdown must never
  // require client cooperation (a still-connected idle client used to
  // deadlock pt_store_server_stop in pthread_join forever)
  std::vector<int> client_fds;
};

std::mutex g_servers_mu;
std::map<int, StoreServer*> g_servers;
int g_next_handle = 1;

bool ReadFull(int fd, void* buf, size_t n) {
  char* p = static_cast<char*>(buf);
  while (n > 0) {
    ssize_t r = recv(fd, p, n, 0);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

bool WriteFull(int fd, const void* buf, size_t n) {
  const char* p = static_cast<const char*>(buf);
  while (n > 0) {
    ssize_t r = send(fd, p, n, MSG_NOSIGNAL);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

void ServeClient(StoreServer* s, int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  while (!s->stop.load()) {
    uint8_t op;
    if (!ReadFull(fd, &op, 1)) break;
    uint32_t klen;
    if (!ReadFull(fd, &klen, 4) || klen > (1u << 20)) break;
    std::string key(klen, '\0');
    if (!ReadFull(fd, &key[0], klen)) break;
    if (op == 'S') {
      uint32_t vlen;
      if (!ReadFull(fd, &vlen, 4) || vlen > (64u << 20)) break;
      std::string val(vlen, '\0');
      if (!ReadFull(fd, &val[0], vlen)) break;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        s->kv[key] = std::move(val);
      }
      s->cv.notify_all();
      uint8_t ok = 0;
      if (!WriteFull(fd, &ok, 1)) break;
    } else if (op == 'G') {
      uint64_t timeout_ms;
      if (!ReadFull(fd, &timeout_ms, 8)) break;
      std::string val;
      bool found = false;
      {
        std::unique_lock<std::mutex> lk(s->mu);
        auto deadline = std::chrono::steady_clock::now() +
                        std::chrono::milliseconds(timeout_ms);
        found = s->cv.wait_until(lk, deadline, [&] {
          return s->stop.load() || s->kv.count(key) > 0;
        });
        found = found && s->kv.count(key) > 0;
        if (found) val = s->kv[key];
      }
      uint8_t ok = found ? 1 : 0;
      if (!WriteFull(fd, &ok, 1)) break;
      if (found) {
        uint32_t vlen = static_cast<uint32_t>(val.size());
        if (!WriteFull(fd, &vlen, 4) || !WriteFull(fd, val.data(), vlen))
          break;
      }
    } else if (op == 'A') {
      int64_t delta;
      if (!ReadFull(fd, &delta, 8)) break;
      int64_t nv;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        nv = (s->counters[key] += delta);
      }
      s->cv.notify_all();
      uint8_t ok = 0;
      if (!WriteFull(fd, &ok, 1) || !WriteFull(fd, &nv, 8)) break;
    } else if (op == 'N') {  // idempotent add (client retry nonce)
      int64_t delta;
      uint64_t cid, seq;
      if (!ReadFull(fd, &delta, 8) || !ReadFull(fd, &cid, 8) ||
          !ReadFull(fd, &seq, 8))
        break;
      constexpr size_t kNonceRing = 64;
      constexpr size_t kMaxNonceClients = 4096;
      int64_t nv = 0;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        bool fresh_cid = s->add_nonces.find(cid) == s->add_nonces.end();
        auto& ring = s->add_nonces[cid];
        if (fresh_cid) {
          s->nonce_cid_order.push_back(cid);
          while (s->add_nonces.size() > kMaxNonceClients &&
                 !s->nonce_cid_order.empty()) {
            uint64_t oldest = s->nonce_cid_order.front();
            s->nonce_cid_order.pop_front();
            if (oldest != cid) s->add_nonces.erase(oldest);
          }
        }
        bool dup = false;
        for (const auto& e : ring) {
          if (e.first == seq) {
            nv = e.second;  // duplicate: reply, don't re-apply
            dup = true;
            break;
          }
        }
        if (!dup) {
          nv = (s->counters[key] += delta);
          ring.emplace_back(seq, nv);
          if (ring.size() > kNonceRing) ring.pop_front();
        }
      }
      s->cv.notify_all();
      uint8_t ok = 0;
      if (!WriteFull(fd, &ok, 1) || !WriteFull(fd, &nv, 8)) break;
    } else if (op == 'R') {  // counter read: NON-creating (elastic liveness)
      int64_t nv = 0;
      uint8_t found = 0;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        auto it = s->counters.find(key);
        if (it != s->counters.end()) {
          nv = it->second;
          found = 1;
        }
      }
      if (!WriteFull(fd, &found, 1) || !WriteFull(fd, &nv, 8)) break;
    } else if (op == 'D') {
      {
        std::lock_guard<std::mutex> lk(s->mu);
        s->kv.erase(key);
        s->counters.erase(key);
      }
      uint8_t ok = 0;
      if (!WriteFull(fd, &ok, 1)) break;
    } else {
      break;
    }
  }
  {
    // deregister BEFORE close, under the same mutex server_stop scans:
    // a stop must never shutdown() an fd number the OS already
    // recycled to someone else after this close
    std::lock_guard<std::mutex> lk(s->mu);
    for (auto it = s->client_fds.begin(); it != s->client_fds.end(); ++it) {
      if (*it == fd) {
        s->client_fds.erase(it);
        break;
      }
    }
  }
  close(fd);
}

}  // namespace

extern "C" {

int pt_store_server_start(int port) {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = INADDR_ANY;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (bind(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0 ||
      listen(fd, 128) != 0) {
    close(fd);
    return -1;
  }
  auto* s = new StoreServer();
  s->listen_fd = fd;
  s->accept_thread = std::thread([s] {
    while (!s->stop.load()) {
      int cfd = accept(s->listen_fd, nullptr, nullptr);
      if (cfd < 0) {
        if (s->stop.load()) break;
        continue;
      }
      {
        std::lock_guard<std::mutex> lk(s->mu);
        s->client_fds.push_back(cfd);
      }
      s->workers.emplace_back(ServeClient, s, cfd);
    }
  });
  std::lock_guard<std::mutex> lk(g_servers_mu);
  int h = g_next_handle++;
  g_servers[h] = s;
  return h;
}

// Port actually bound (use port=0 to auto-pick).
int pt_store_server_port(int handle) {
  StoreServer* s;
  {
    std::lock_guard<std::mutex> lk(g_servers_mu);
    auto it = g_servers.find(handle);
    if (it == g_servers.end()) return -1;
    s = it->second;
  }
  sockaddr_in addr{};
  socklen_t len = sizeof(addr);
  if (getsockname(s->listen_fd, reinterpret_cast<sockaddr*>(&addr), &len) != 0)
    return -1;
  return ntohs(addr.sin_port);
}

void pt_store_server_stop(int handle) {
  StoreServer* s = nullptr;
  {
    std::lock_guard<std::mutex> lk(g_servers_mu);
    auto it = g_servers.find(handle);
    if (it == g_servers.end()) return;
    s = it->second;
    g_servers.erase(it);
  }
  s->stop.store(true);
  s->cv.notify_all();
  shutdown(s->listen_fd, SHUT_RDWR);
  close(s->listen_fd);
  if (s->accept_thread.joinable()) s->accept_thread.join();
  {
    // wake workers blocked in recv on idle-but-connected clients:
    // without this, join below waited for every client to disconnect
    // first (observed deadlock: master.close() with a live peer hung
    // the process in pthread_join). Only the read side: a worker that
    // holds a request still writes the reply it owes (a peer's last
    // barrier get, its confirmation) before it sees stop and closes.
    std::lock_guard<std::mutex> lk(s->mu);
    for (int fd : s->client_fds) shutdown(fd, SHUT_RD);
  }
  for (auto& t : s->workers)
    if (t.joinable()) t.join();
  delete s;
}

int pt_store_connect(const char* host, int port, int timeout_ms) {
  addrinfo hints{}, *res = nullptr;
  hints.ai_family = AF_INET;
  hints.ai_socktype = SOCK_STREAM;
  char portstr[16];
  snprintf(portstr, sizeof(portstr), "%d", port);
  if (getaddrinfo(host, portstr, &hints, &res) != 0 || res == nullptr)
    return -1;
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  int fd = -1;
  // retry loop: the server may not be up yet (launch race)
  while (std::chrono::steady_clock::now() < deadline) {
    fd = socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) break;
    if (connect(fd, res->ai_addr, res->ai_addrlen) == 0) {
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      freeaddrinfo(res);
      return fd;
    }
    close(fd);
    fd = -1;
    std::this_thread::sleep_for(std::chrono::milliseconds(100));
  }
  freeaddrinfo(res);
  return -1;
}

void pt_store_close(int fd) {
  if (fd >= 0) close(fd);
}

int pt_store_set(int fd, const char* key, const void* val, int len) {
  uint8_t op = 'S';
  uint32_t klen = static_cast<uint32_t>(strlen(key));
  uint32_t vlen = static_cast<uint32_t>(len);
  if (!WriteFull(fd, &op, 1) || !WriteFull(fd, &klen, 4) ||
      !WriteFull(fd, key, klen) || !WriteFull(fd, &vlen, 4) ||
      !WriteFull(fd, val, vlen))
    return -1;
  uint8_t ok;
  return ReadFull(fd, &ok, 1) ? 0 : -1;
}

int pt_store_get(int fd, const char* key, void* buf, int cap,
                 int64_t timeout_ms) {
  uint8_t op = 'G';
  uint32_t klen = static_cast<uint32_t>(strlen(key));
  uint64_t to = static_cast<uint64_t>(timeout_ms);
  if (!WriteFull(fd, &op, 1) || !WriteFull(fd, &klen, 4) ||
      !WriteFull(fd, key, klen) || !WriteFull(fd, &to, 8))
    return -1;
  uint8_t ok;
  if (!ReadFull(fd, &ok, 1)) return -1;
  if (!ok) return -1;
  uint32_t vlen;
  if (!ReadFull(fd, &vlen, 4)) return -1;
  if (static_cast<int>(vlen) > cap) {
    // drain and report needed size as negative-2-based error
    std::vector<char> tmp(vlen);
    ReadFull(fd, tmp.data(), vlen);
    return -2;
  }
  if (!ReadFull(fd, buf, vlen)) return -1;
  return static_cast<int>(vlen);
}

int pt_store_add(int fd, const char* key, int64_t delta, int64_t* out_new) {
  uint8_t op = 'A';
  uint32_t klen = static_cast<uint32_t>(strlen(key));
  if (!WriteFull(fd, &op, 1) || !WriteFull(fd, &klen, 4) ||
      !WriteFull(fd, key, klen) || !WriteFull(fd, &delta, 8))
    return -1;
  uint8_t ok;
  if (!ReadFull(fd, &ok, 1)) return -1;
  return ReadFull(fd, out_new, 8) ? 0 : -1;
}

// Idempotent add: same wire semantics as pt_store_add plus a client
// nonce (cid, seq). Retrying the SAME nonce after a lost reply gets
// the originally-applied value instead of a second application.
int pt_store_add_nonced(int fd, const char* key, int64_t delta,
                        uint64_t cid, uint64_t seq, int64_t* out_new) {
  uint8_t op = 'N';
  uint32_t klen = static_cast<uint32_t>(strlen(key));
  if (!WriteFull(fd, &op, 1) || !WriteFull(fd, &klen, 4) ||
      !WriteFull(fd, key, klen) || !WriteFull(fd, &delta, 8) ||
      !WriteFull(fd, &cid, 8) || !WriteFull(fd, &seq, 8))
    return -1;
  uint8_t ok;
  if (!ReadFull(fd, &ok, 1)) return -1;
  return ReadFull(fd, out_new, 8) ? 0 : -1;
}

// Non-creating counter read: returns 0 and *out on hit, -2 on miss, -1 io.
int pt_store_counter_get(int fd, const char* key, int64_t* out) {
  uint8_t op = 'R';
  uint32_t klen = static_cast<uint32_t>(strlen(key));
  if (!WriteFull(fd, &op, 1) || !WriteFull(fd, &klen, 4) ||
      !WriteFull(fd, key, klen))
    return -1;
  uint8_t found;
  if (!ReadFull(fd, &found, 1)) return -1;
  int64_t nv;
  if (!ReadFull(fd, &nv, 8)) return -1;
  if (!found) return -2;
  *out = nv;
  return 0;
}

int pt_store_delete(int fd, const char* key) {
  uint8_t op = 'D';
  uint32_t klen = static_cast<uint32_t>(strlen(key));
  if (!WriteFull(fd, &op, 1) || !WriteFull(fd, &klen, 4) ||
      !WriteFull(fd, key, klen))
    return -1;
  uint8_t ok;
  return ReadFull(fd, &ok, 1) ? 0 : -1;
}

}  // extern "C"
