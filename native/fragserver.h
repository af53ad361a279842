// Native zero-copy fragment data plane (ROADMAP item 3).
//
// The fragment hot path — serving relay pulls, striped heal, cold
// restore — used to cross the Python HTTP handlers byte by byte.  This
// server owns ONLY the data plane: Python stages raw wire-byte fragment
// payloads down at stage time (one copy into a pooled registered
// buffer; none where the payload was written into a buffer RESERVED here
// and is then COMMITTED in place), and every subsequent serve is a
// writev straight out of that buffer — zero user-space copies
// steady-state, no GIL anywhere.
// Python keeps all control: plans, manifests, digests-of-record,
// staging lifecycle, version advertisement.
//
// Semantics mirror the Python fragment plane exactly so the client can
// fall back per-fetch:
//   * streaming (begun, unfinished) version + missing fragment -> the
//     request PARKS on a condvar up to the long-poll window, then
//     answers 503 retryable-busy (the cut-through contract);
//   * complete version + missing fragment -> 404 (the fragment was
//     never raw-staged natively; Python owns it);
//   * unknown/retired version -> 404 (Python decides: store-serve,
//     legacy encode, or a real miss);
//   * a request that carries `If-None-Match: "<sha256 hex>"` is parked
//     and looked up like any other, and answered 304 with no body when
//     the fragment was staged under that digest (a delta healer holds
//     those bytes already); the bytes, as ever, when it was staged under
//     another digest or under none.  A request without the header is
//     answered exactly as before.
// All responses are keep-alive: the client pipelines fetches over one
// persistent connection per (thread, endpoint).
#pragma once

#include <cstdint>
#include <map>
#include <memory>
#include <string>
#include <vector>

#include "net.h"

namespace tft {

// One staged fragment payload in a pool-recycled buffer.  `refs` counts
// in-flight serves and, for a buffer lent to Python (reserve), the lend
// (guarded by the server mutex); a retire that lands while anything holds
// a ref marks the buffer zombie and the LAST deref recycles it — retire
// never blocks on the wire.  The memory is never zero-filled: nothing
// reads a buffer before it was written whole.
struct FragBuf {
  std::unique_ptr<uint8_t[]> data;  // capacity-pooled backing store
  size_t len = 0;                   // its capacity = the payload's length
  int refs = 0;
  bool retired = false;
  std::string sha_hex;  // the payload's sha256 as its stager gave it, or ""
};

struct FragCounters {
  int64_t pool_hits = 0;
  int64_t pool_misses = 0;
  int64_t stage_copy_bytes = 0;  // the ONE copy: Python buffer -> pool
  int64_t stage_inplace_bytes = 0;  // committed where written: no copy
  int64_t serve_copies = 0;      // must stay 0: serve is pure writev
  int64_t serve_bytes = 0;
  int64_t serves = 0;
  int64_t same_replies = 0;  // 304: the asker's digest was the fragment's
  int64_t parked_waits = 0;  // long-polls that actually waited
  int64_t busy_replies = 0;  // 503 retryable-busy answers
  int64_t miss_replies = 0;  // 404 fall-back-to-Python answers
  int64_t injected_drops = 0;
  int64_t injected_delays = 0;
};

class FragServer : public RpcServer {
 public:
  // bind_host may be "" (all interfaces); port 0 picks a free port.
  FragServer(const std::string& bind_host, int port);
  ~FragServer() override;

  // Staging lifecycle (driven by HTTPTransport's control plane).  All
  // return 0 on success, -1 on unknown/retired step (mirror of the
  // Python staging KeyError — callers treat it as "not mirrored").
  int begin(int64_t step);
  // `sha_hex`: the payload's sha256 where the stager knows it (a heal
  // source hashes each fragment in the pass that writes it); what a
  // conditional GET is held against.  "" = unknown: always the bytes.
  int stage(int64_t step, const std::string& resource, const uint8_t* data,
            size_t len, const std::string& sha_hex = "");
  int finish(int64_t step);
  int retire(int64_t step);

  // Staging without the copy.  `reserve` lends a pooled buffer of `len`
  // bytes for (step, resource): not in the version yet, so readers of it
  // stay parked (never partial bytes); nullptr on an unknown step.  The
  // caller writes the payload into it and `commit`s: the buffer becomes
  // the staged fragment and parked readers wake.  commit returns -1 when
  // `ptr` is no lend made for exactly (step, resource, len), is the
  // staged fragment already, or the step was retired meanwhile (nothing
  // published).  The lend is one more
  // reference, held until `release(ptr)`: a retired buffer returns to the
  // pool only when the lender AND every in-flight serve have let go.
  uint8_t* reserve(int64_t step, const std::string& resource, size_t len);
  int commit(int64_t step, const std::string& resource, const uint8_t* ptr,
             size_t len, const std::string& sha_hex = "");
  int release(const uint8_t* ptr);

  FragCounters counters() const;
  Json counters_json() const;

  // Fault injection for chaos tests: the next `count` data requests
  // either drop (close mid-exchange) or delay `param_ms` before the
  // body.  mode: "off" | "drop" | "delay".
  int inject(const std::string& mode, int64_t param_ms, int64_t count);

 protected:
  Json handle(const std::string& method, const Json& params,
              int64_t timeout_ms) override;
  const char* server_kind() const override { return "fragserver"; }
  bool handle_http_keepalive(int fd, const std::string& request_head) override;
  void wake_blocked() override;

 private:
  struct Version {
    bool complete = false;
    std::map<std::string, std::shared_ptr<FragBuf>> frags;  // by resource
  };

  struct Lend {
    std::shared_ptr<FragBuf> buf;
    int64_t step;
    std::string resource;
  };

  std::shared_ptr<FragBuf> pool_take(size_t len);
  void pool_give_locked(FragBuf& buf);
  void publish_locked(Version& version, const std::string& resource,
                      const std::shared_ptr<FragBuf>& buf);
  void deref(const std::shared_ptr<FragBuf>& buf);
  bool reply_simple(int fd, int status, const std::string& body);
  bool reply_same(int fd, const std::string& sha_hex);
  bool serve_frag(int fd, const std::shared_ptr<FragBuf>& buf);

  mutable std::mutex mu_;
  CondVar cv_;  // fragment-landed / shutdown wakeups for parked readers
  std::map<int64_t, Version> versions_;
  // Free-list keyed by exact capacity: fragment sizes repeat across
  // publishes, so steady-state stage traffic is all pool hits (the
  // bufpool miss-flat idiom, natively).
  std::map<size_t, std::vector<std::unique_ptr<uint8_t[]>>> pool_;
  std::map<const uint8_t*, Lend> lent_;  // buffers Python holds a view of
  FragCounters counters_;
  // injection state (guarded by mu_)
  int inject_mode_ = 0;  // 0 off, 1 drop, 2 delay
  int64_t inject_param_ms_ = 0;
  int64_t inject_count_ = 0;
};

// ---- native fragment client ---------------------------------------------
// Two-phase fetch so Python can own buffer allocation (its bufpool)
// while the byte-moving phase runs without the GIL (ctypes releases it
// around every call):
//   frag_fetch_begin  -> request on a per-(thread, endpoint) persistent
//                        connection; parses the response head; returns
//                        the HTTP status (200/404/503) or -1 transport
//                        error, with content length out.  With `unless`
//                        (a sha256 hex) the request is conditional and
//                        304 = the source staged the fragment under that
//                        digest: no body follows.
//   frag_fetch_body   -> drains the body straight into the caller's
//                        buffer and computes sha256 over it in-place.
// A begin that returned 200 MUST be followed by exactly one body/abort.

int frag_fetch_begin(const std::string& addr, int64_t step,
                     const std::string& resource, int64_t timeout_ms,
                     int64_t* content_len, double* first_byte_s,
                     const std::string& unless = "");
int frag_fetch_body(uint8_t* buf, int64_t cap, char* sha_hex_out /*65B*/,
                    int64_t timeout_ms);
void frag_fetch_abort();
void frag_client_close();
const std::string& frag_client_error();

// Streaming SHA-256 over one buffer, lowercase hex into out[64] + NUL.
void sha256_hex(const uint8_t* data, size_t len, char* out_hex65);

// dst[r * cols + c] = src[c * src_rows + r] for r < rows, in elements of
// `itemsize` bytes (1, 2, 4 or 8; -1 otherwise): `rows` rows of a
// [src_rows, cols] matrix that memory holds column by column (src points
// at the first of them), written out row by row.  In tiles, so that a
// cache line fetched for one row serves its neighbours too.
int copy_transposed(uint8_t* dst, const uint8_t* src, int64_t rows,
                    int64_t cols, int64_t src_rows, int64_t itemsize);

}  // namespace tft
