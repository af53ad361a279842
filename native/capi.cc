// C API exposing the native coordination servers to Python via ctypes.
//
// Analog of the reference's PyO3 binding layer (reference: src/lib.rs:742-758
// registers ManagerServer/LighthouseServer/... as Python classes). Here the
// Python side (torchft_tpu/_native.py + coordination.py) owns the client
// protocol (framed JSON over TCP) directly; the C API only manages server
// lifecycles plus a pure-function entry for quorum-result math so tests can
// exercise it natively.
#include <cstdint>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <string>

#include "fragserver.h"
#include "lighthouse.h"
#include "manager.h"
#include "store.h"

namespace {

thread_local std::string g_last_error;

char* dup_string(const std::string& s) {
  char* out = static_cast<char*>(malloc(s.size() + 1));
  memcpy(out, s.c_str(), s.size() + 1);
  return out;
}

struct ServerHandle {
  enum class Kind { Lighthouse, Manager, Store, Frag } kind;
  std::unique_ptr<tft::RpcServer> server;
};

std::mutex g_mu;
std::map<int64_t, ServerHandle> g_servers;
int64_t g_next_handle = 1;

int64_t register_server(ServerHandle h) {
  std::lock_guard<std::mutex> g(g_mu);
  int64_t id = g_next_handle++;
  g_servers[id] = std::move(h);
  return id;
}

tft::RpcServer* find_server(int64_t h) {
  std::lock_guard<std::mutex> g(g_mu);
  auto it = g_servers.find(h);
  return it == g_servers.end() ? nullptr : it->second.server.get();
}

}  // namespace

extern "C" {

const char* tft_last_error() { return g_last_error.c_str(); }

void tft_free(char* p) { free(p); }

int64_t tft_lighthouse_create(const char* bind_host, int port,
                              int64_t min_replicas, int64_t join_timeout_ms,
                              int64_t quorum_tick_ms,
                              int64_t heartbeat_timeout_ms,
                              int64_t status_page_size,
                              int64_t straggler_topk, int64_t timeline_ring,
                              int64_t serving_fanout, const char* peers,
                              int64_t lease_timeout_ms) {
  try {
    tft::LighthouseOpt opt;
    opt.bind_host = bind_host ? bind_host : "";
    opt.port = port;
    opt.min_replicas = min_replicas;
    opt.join_timeout_ms = join_timeout_ms;
    opt.quorum_tick_ms = quorum_tick_ms;
    opt.heartbeat_timeout_ms = heartbeat_timeout_ms;
    if (status_page_size > 0) opt.status_page_size = status_page_size;
    if (straggler_topk > 0) opt.straggler_topk = straggler_topk;
    if (timeline_ring > 0) opt.timeline_ring = timeline_ring;
    if (serving_fanout > 0) opt.serving_fanout = serving_fanout;
    // Coordination-plane HA: comma list of the OTHER lighthouse peers
    // (empty/NULL = single-process mode) + leadership lease duration.
    opt.peers = peers ? peers : "";
    if (lease_timeout_ms > 0) opt.lease_timeout_ms = lease_timeout_ms;
    auto server = std::make_unique<tft::LighthouseServer>(opt);
    server->start_serving();
    return register_server(
        {ServerHandle::Kind::Lighthouse, std::move(server)});
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  }
}

int64_t tft_manager_create(const char* replica_id, const char* lighthouse_addr,
                           const char* bind_host, int port,
                           const char* store_address, int64_t world_size,
                           int64_t heartbeat_interval_ms,
                           int64_t connect_timeout_ms,
                           int64_t quorum_retries) {
  try {
    tft::ManagerOpt opt;
    opt.replica_id = replica_id ? replica_id : "";
    opt.lighthouse_addr = lighthouse_addr ? lighthouse_addr : "";
    opt.bind_host = bind_host ? bind_host : "";
    opt.port = port;
    opt.store_address = store_address ? store_address : "";
    opt.world_size = world_size;
    opt.heartbeat_interval_ms = heartbeat_interval_ms;
    opt.connect_timeout_ms = connect_timeout_ms;
    opt.quorum_retries = quorum_retries;
    auto server = std::make_unique<tft::ManagerServer>(opt);
    server->start_serving();
    return register_server({ServerHandle::Kind::Manager, std::move(server)});
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  }
}

int64_t tft_store_create(const char* bind_host, int port) {
  try {
    auto server = std::make_unique<tft::StoreServer>(
        bind_host ? bind_host : "", port);
    server->start();
    return register_server({ServerHandle::Kind::Store, std::move(server)});
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  }
}

char* tft_server_address(int64_t h) {
  tft::RpcServer* s = find_server(h);
  if (!s) {
    g_last_error = "bad server handle";
    return nullptr;
  }
  return dup_string(s->address());
}

int tft_server_shutdown(int64_t h) {
  std::unique_ptr<tft::RpcServer> server;
  {
    std::lock_guard<std::mutex> g(g_mu);
    auto it = g_servers.find(h);
    if (it == g_servers.end()) {
      g_last_error = "bad server handle";
      return -1;
    }
    server = std::move(it->second.server);
    g_servers.erase(it);
  }
  // Destructor runs stop()/shutdown() for each server type.
  server.reset();
  return 0;
}

// Install (or clear, with NULL) the Prometheus /metrics supplement on a
// lighthouse: the provider writes extra exposition text (the embedding
// process's metric registry) appended to the native metrics.  See
// LighthouseServer::MetricsProvider for the buffer contract.
int tft_lighthouse_set_metrics_provider(int64_t h,
                                        int (*provider)(char*, int)) {
  tft::RpcServer* s = find_server(h);
  auto* lighthouse = dynamic_cast<tft::LighthouseServer*>(s);
  if (lighthouse == nullptr) {
    g_last_error = "bad lighthouse handle";
    return -1;
  }
  lighthouse->set_metrics_provider(provider);
  return 0;
}

// Coordination-plane HA introspection: one JSON object
// {"enabled","term","is_leader","leader","peers","takeovers_total",
// "quorum_id"} for a lighthouse handle (the fleet helper and tests poll
// this to find the current leader without a wire round trip).
char* tft_lighthouse_ha_info(int64_t h) {
  tft::RpcServer* s = find_server(h);
  auto* lighthouse = dynamic_cast<tft::LighthouseServer*>(s);
  if (lighthouse == nullptr) {
    g_last_error = "bad lighthouse handle";
    return nullptr;
  }
  return dup_string(lighthouse->ha_info().dump());
}

// Install (or clear, with NULL) the process-wide span sink: the native
// servers' rpc.<method> spans (and any other native emit_span caller) are
// relayed as one JSON object per span to this callback — the Python side
// registers a ctypes function that forwards into its trace exporter
// (torchft_tpu/utils/tracing.py install_native_span_sink).
int tft_set_span_sink(void (*sink)(const char*)) {
  tft::set_span_sink(sink);
  return 0;
}

// Record a replica group's training progress on its manager server; the
// heartbeat loop piggybacks it on lighthouse heartbeats (straggler
// telemetry — see ManagerServer::report_progress).
int tft_manager_report_progress(int64_t h, int64_t step,
                                const char* inflight_op) {
  tft::RpcServer* s = find_server(h);
  auto* manager = dynamic_cast<tft::ManagerServer*>(s);
  if (manager == nullptr) {
    g_last_error = "bad manager handle";
    return -1;
  }
  manager->report_progress(step, inflight_op ? inflight_op : "");
  return 0;
}

// Record a replica group's per-step digest (JSON: step, phase_ms,
// codec_busy_s, wire_busy_s); the heartbeat loop piggybacks it so the
// lighthouse can aggregate the rolling cluster step-timeline
// (/timeline.json).  Invalid JSON is rejected here rather than poisoning
// the heartbeat path.
int tft_manager_report_summary(int64_t h, const char* summary_json) {
  tft::RpcServer* s = find_server(h);
  auto* manager = dynamic_cast<tft::ManagerServer*>(s);
  if (manager == nullptr) {
    g_last_error = "bad manager handle";
    return -1;
  }
  try {
    tft::Json summary =
        tft::Json::parse(summary_json ? summary_json : "{}");
    if (!summary.is_object()) throw std::runtime_error("summary: not an object");
    manager->report_summary(summary);
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  }
  return 0;
}

// Record a replica's bounded link-state digest (JSON: host, rows[...]);
// the heartbeat loop piggybacks it once (consumed-on-send) so the
// lighthouse can fold it into the fleet host-pair matrix (/links.json).
// Invalid JSON is rejected here rather than poisoning the heartbeat path.
int tft_manager_report_links(int64_t h, const char* links_json) {
  tft::RpcServer* s = find_server(h);
  auto* manager = dynamic_cast<tft::ManagerServer*>(s);
  if (manager == nullptr) {
    g_last_error = "bad manager handle";
    return -1;
  }
  try {
    tft::Json links = tft::Json::parse(links_json ? links_json : "{}");
    if (!links.is_object()) throw std::runtime_error("links: not an object");
    manager->report_links(links);
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  }
  return 0;
}

// Record a replica's bounded fragment-provenance digest (JSON: host,
// frags[...]); the heartbeat loop piggybacks it once (consumed-on-send)
// so the lighthouse can fold it into the fleet per-(host, frag_id)
// version matrix (/fragments.json).  Invalid JSON is rejected here
// rather than poisoning the heartbeat path.
int tft_manager_report_fragments(int64_t h, const char* fragments_json) {
  tft::RpcServer* s = find_server(h);
  auto* manager = dynamic_cast<tft::ManagerServer*>(s);
  if (manager == nullptr) {
    g_last_error = "bad manager handle";
    return -1;
  }
  try {
    tft::Json fragments =
        tft::Json::parse(fragments_json ? fragments_json : "{}");
    if (!fragments.is_object())
      throw std::runtime_error("fragments: not an object");
    manager->report_fragments(fragments);
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  }
  return 0;
}

// ---- native zero-copy fragment data plane (fragserver.{h,cc}) ----------
// Server lifecycle + staging mirror: Python's HTTPTransport keeps the
// control plane (plans, manifests, digests, version advertisement) and
// hands raw fragment payload bytes down here at stage time; every
// subsequent serve is a writev out of the pooled buffer with zero
// user-space copies.

static tft::FragServer* find_frag(int64_t h) {
  auto* s = dynamic_cast<tft::FragServer*>(find_server(h));
  if (s == nullptr) g_last_error = "bad fragserver handle";
  return s;
}

int64_t tft_frag_server_create(const char* bind_host, int port) {
  try {
    auto server = std::make_unique<tft::FragServer>(
        bind_host ? bind_host : "", port);
    return register_server({ServerHandle::Kind::Frag, std::move(server)});
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return -1;
  }
}

int tft_frag_server_port(int64_t h) {
  tft::FragServer* s = find_frag(h);
  return s == nullptr ? -1 : s->port();
}

int tft_frag_begin(int64_t h, int64_t step) {
  tft::FragServer* s = find_frag(h);
  return s == nullptr ? -1 : s->begin(step);
}

// `sha_hex` (NULL = unknown): the payload's sha256, what a conditional
// GET of the fragment is held against.
int tft_frag_stage(int64_t h, int64_t step, const char* resource,
                   const uint8_t* data, int64_t len, const char* sha_hex) {
  tft::FragServer* s = find_frag(h);
  if (s == nullptr || resource == nullptr || len < 0) return -1;
  return s->stage(step, resource, data, static_cast<size_t>(len),
                  sha_hex ? sha_hex : "");
}

// Staging without the copy (FragServer::reserve / commit / release): a
// pooled buffer lent for (step, resource), written by the caller, made
// the staged fragment where it lies, and let go of when the caller's
// last view of it is gone.  reserve returns NULL on an unknown step.
uint8_t* tft_frag_reserve(int64_t h, int64_t step, const char* resource,
                          int64_t len) {
  tft::FragServer* s = find_frag(h);
  if (s == nullptr || resource == nullptr || len < 0) return nullptr;
  return s->reserve(step, resource, static_cast<size_t>(len));
}

int tft_frag_commit(int64_t h, int64_t step, const char* resource,
                    const uint8_t* ptr, int64_t len, const char* sha_hex) {
  tft::FragServer* s = find_frag(h);
  if (s == nullptr || resource == nullptr || len < 0) return -1;
  return s->commit(step, resource, ptr, static_cast<size_t>(len),
                   sha_hex ? sha_hex : "");
}

int tft_frag_release(int64_t h, const uint8_t* ptr) {
  tft::FragServer* s = find_frag(h);
  return s == nullptr ? -1 : s->release(ptr);
}

int tft_frag_finish(int64_t h, int64_t step) {
  tft::FragServer* s = find_frag(h);
  return s == nullptr ? -1 : s->finish(step);
}

int tft_frag_retire(int64_t h, int64_t step) {
  tft::FragServer* s = find_frag(h);
  return s == nullptr ? -1 : s->retire(step);
}

char* tft_frag_counters(int64_t h) {
  tft::FragServer* s = find_frag(h);
  if (s == nullptr) return nullptr;
  return dup_string(s->counters_json().dump());
}

// Chaos-test fault injection on the data server: the next `count`
// requests drop (close mid-exchange) or delay `param_ms` before the
// body.  mode: "off" | "drop" | "delay".
int tft_frag_inject(int64_t h, const char* mode, int64_t param_ms,
                    int64_t count) {
  tft::FragServer* s = find_frag(h);
  if (s == nullptr || mode == nullptr) return -1;
  return s->inject(mode, param_ms, count);
}

// Two-phase GIL-free fetch client (per-thread persistent connections —
// ctypes releases the GIL around both calls, so the byte-moving +
// digest phase never touches the interpreter).  begin returns the HTTP
// status (200/404/503) or -1 on transport error (tft_frag_client_error).
// `unless` (NULL = no condition): a sha256 hex; 304 = the source staged
// the fragment under that digest, and no body follows.
int tft_frag_fetch_begin(const char* addr, int64_t step,
                         const char* resource, int64_t timeout_ms,
                         const char* unless, int64_t* content_len,
                         double* first_byte_s) {
  if (addr == nullptr || resource == nullptr) return -1;
  return tft::frag_fetch_begin(addr, step, resource, timeout_ms,
                               content_len, first_byte_s,
                               unless ? unless : "");
}

int tft_frag_fetch_body(uint8_t* buf, int64_t cap, char* sha_hex_out,
                        int64_t timeout_ms) {
  if (buf == nullptr) return -1;
  return tft::frag_fetch_body(buf, cap, sha_hex_out, timeout_ms);
}

void tft_frag_fetch_abort() { tft::frag_fetch_abort(); }

void tft_frag_client_close() { tft::frag_client_close(); }

const char* tft_frag_client_error() {
  thread_local std::string err;
  err = tft::frag_client_error();
  return err.c_str();
}

// Native SHA-256 over one buffer (lowercase hex into out65) — exposed so
// tests can cross-check the wire digest against hashlib.
int tft_sha256_hex(const uint8_t* data, int64_t len, char* out65) {
  if ((data == nullptr && len > 0) || len < 0 || out65 == nullptr) return -1;
  tft::sha256_hex(data, static_cast<size_t>(len), out65);
  return 0;
}

// `rows` rows of a matrix that memory holds column by column, written row
// by row (fragserver.h copy_transposed): a heal source re-orders a leaf the
// device held the other way round straight into its serving buffer.
int tft_copy_transposed(uint8_t* dst, const uint8_t* src, int64_t rows,
                        int64_t cols, int64_t src_rows, int64_t itemsize) {
  if (dst == nullptr || src == nullptr || rows < 0 || cols < 0 ||
      src_rows < rows)
    return -1;
  return tft::copy_transposed(dst, src, rows, cols, src_rows, itemsize);
}

// Pure quorum-result math, exposed for unit tests: input/output JSON.
char* tft_compute_quorum_results(const char* replica_id, int64_t group_rank,
                                 const char* quorum_json, int init_sync) {
  try {
    tft::Quorum quorum =
        tft::Quorum::from_json(tft::Json::parse(quorum_json));
    tft::QuorumResult result = tft::compute_quorum_results(
        replica_id ? replica_id : "", group_rank, quorum, init_sync != 0);
    return dup_string(result.to_json().dump());
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return nullptr;
  }
}

}  // extern "C"
