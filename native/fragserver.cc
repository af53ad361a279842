// Native zero-copy fragment data plane — see fragserver.h for the
// contract.  Server side: staged payloads live in pool-recycled buffers
// and every serve is one sendmsg (header iovec + payload iovec) straight
// from the staged buffer — the serve path never copies payload bytes in
// user space (FragCounters::serve_copies stays 0 by construction).
// Client side: two-phase fetch with per-(thread, endpoint) persistent
// connections; the body phase lands bytes straight in the caller's
// buffer and digests them in place — Python calls it through ctypes,
// which releases the GIL for the duration.
#include "fragserver.h"

#include <poll.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>

#include <algorithm>
#include <cctype>
#include <cstdio>
#include <cstring>
#include <sstream>

namespace tft {

namespace {

// ---- SHA-256 (FIPS 180-4), self-contained ------------------------------
// The digest of record stays Python's hashlib at stage/verify control
// points; this native copy exists so the receive path can verify the
// wire buffer without re-entering the interpreter.  Bit-identical to
// hashlib.sha256 by construction (same algorithm, tested end to end).

constexpr uint32_t kShaK[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

inline uint32_t rotr32(uint32_t x, int n) {
  return (x >> n) | (x << (32 - n));
}

// ---- SHA-NI fast path ----------------------------------------------------
// The x86 SHA extensions run the compression rounds in hardware — about
// an order of magnitude over the scalar block below, and the receive
// path digests EVERY wire buffer in-line, so this is the data plane's
// throughput floor.  Runtime-dispatched; the scalar block remains the
// portable fallback (and the bit-identical reference).
#if defined(__x86_64__) && defined(__GNUC__)
#define TFT_SHA_NI 1

#include <cpuid.h>
#include <immintrin.h>

__attribute__((target("sha,ssse3,sse4.1"))) void sha256_blocks_ni(
    uint32_t state[8], const uint8_t* data, size_t blocks) {
  const __m128i kMask =
      _mm_set_epi64x(0x0c0d0e0f08090a0bULL, 0x0405060700010203ULL);
  __m128i tmp = _mm_loadu_si128(reinterpret_cast<const __m128i*>(&state[0]));
  __m128i st1 = _mm_loadu_si128(reinterpret_cast<const __m128i*>(&state[4]));
  tmp = _mm_shuffle_epi32(tmp, 0xB1);        // CDAB
  st1 = _mm_shuffle_epi32(st1, 0x1B);        // EFGH
  __m128i st0 = _mm_alignr_epi8(tmp, st1, 8);   // ABEF
  st1 = _mm_blend_epi16(st1, tmp, 0xF0);        // CDGH

  while (blocks > 0) {
    const __m128i abef_save = st0;
    const __m128i cdgh_save = st1;
    __m128i msg, msg0, msg1, msg2, msg3;

    // rounds 0-3
    msg = _mm_loadu_si128(reinterpret_cast<const __m128i*>(data + 0));
    msg0 = _mm_shuffle_epi8(msg, kMask);
    msg = _mm_add_epi32(
        msg0, _mm_set_epi64x(0xE9B5DBA5B5C0FBCFULL, 0x71374491428A2F98ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);

    // rounds 4-7
    msg1 = _mm_loadu_si128(reinterpret_cast<const __m128i*>(data + 16));
    msg1 = _mm_shuffle_epi8(msg1, kMask);
    msg = _mm_add_epi32(
        msg1, _mm_set_epi64x(0xAB1C5ED5923F82A4ULL, 0x59F111F13956C25BULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg0 = _mm_sha256msg1_epu32(msg0, msg1);

    // rounds 8-11
    msg2 = _mm_loadu_si128(reinterpret_cast<const __m128i*>(data + 32));
    msg2 = _mm_shuffle_epi8(msg2, kMask);
    msg = _mm_add_epi32(
        msg2, _mm_set_epi64x(0x550C7DC3243185BEULL, 0x12835B01D807AA98ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg1 = _mm_sha256msg1_epu32(msg1, msg2);

    // rounds 12-15
    msg3 = _mm_loadu_si128(reinterpret_cast<const __m128i*>(data + 48));
    msg3 = _mm_shuffle_epi8(msg3, kMask);
    msg = _mm_add_epi32(
        msg3, _mm_set_epi64x(0xC19BF1749BDC06A7ULL, 0x80DEB1FE72BE5D74ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg3, msg2, 4);
    msg0 = _mm_add_epi32(msg0, tmp);
    msg0 = _mm_sha256msg2_epu32(msg0, msg3);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg2 = _mm_sha256msg1_epu32(msg2, msg3);

    // rounds 16-19
    msg = _mm_add_epi32(
        msg0, _mm_set_epi64x(0x240CA1CC0FC19DC6ULL, 0xEFBE4786E49B69C1ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg0, msg3, 4);
    msg1 = _mm_add_epi32(msg1, tmp);
    msg1 = _mm_sha256msg2_epu32(msg1, msg0);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg3 = _mm_sha256msg1_epu32(msg3, msg0);

    // rounds 20-23
    msg = _mm_add_epi32(
        msg1, _mm_set_epi64x(0x76F988DA5CB0A9DCULL, 0x4A7484AA2DE92C6FULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg1, msg0, 4);
    msg2 = _mm_add_epi32(msg2, tmp);
    msg2 = _mm_sha256msg2_epu32(msg2, msg1);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg0 = _mm_sha256msg1_epu32(msg0, msg1);

    // rounds 24-27
    msg = _mm_add_epi32(
        msg2, _mm_set_epi64x(0xBF597FC7B00327C8ULL, 0xA831C66D983E5152ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg2, msg1, 4);
    msg3 = _mm_add_epi32(msg3, tmp);
    msg3 = _mm_sha256msg2_epu32(msg3, msg2);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg1 = _mm_sha256msg1_epu32(msg1, msg2);

    // rounds 28-31
    msg = _mm_add_epi32(
        msg3, _mm_set_epi64x(0x1429296706CA6351ULL, 0xD5A79147C6E00BF3ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg3, msg2, 4);
    msg0 = _mm_add_epi32(msg0, tmp);
    msg0 = _mm_sha256msg2_epu32(msg0, msg3);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg2 = _mm_sha256msg1_epu32(msg2, msg3);

    // rounds 32-35
    msg = _mm_add_epi32(
        msg0, _mm_set_epi64x(0x53380D134D2C6DFCULL, 0x2E1B213827B70A85ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg0, msg3, 4);
    msg1 = _mm_add_epi32(msg1, tmp);
    msg1 = _mm_sha256msg2_epu32(msg1, msg0);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg3 = _mm_sha256msg1_epu32(msg3, msg0);

    // rounds 36-39
    msg = _mm_add_epi32(
        msg1, _mm_set_epi64x(0x92722C8581C2C92EULL, 0x766A0ABB650A7354ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg1, msg0, 4);
    msg2 = _mm_add_epi32(msg2, tmp);
    msg2 = _mm_sha256msg2_epu32(msg2, msg1);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg0 = _mm_sha256msg1_epu32(msg0, msg1);

    // rounds 40-43
    msg = _mm_add_epi32(
        msg2, _mm_set_epi64x(0xC76C51A3C24B8B70ULL, 0xA81A664BA2BFE8A1ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg2, msg1, 4);
    msg3 = _mm_add_epi32(msg3, tmp);
    msg3 = _mm_sha256msg2_epu32(msg3, msg2);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg1 = _mm_sha256msg1_epu32(msg1, msg2);

    // rounds 44-47
    msg = _mm_add_epi32(
        msg3, _mm_set_epi64x(0x106AA070F40E3585ULL, 0xD6990624D192E819ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg3, msg2, 4);
    msg0 = _mm_add_epi32(msg0, tmp);
    msg0 = _mm_sha256msg2_epu32(msg0, msg3);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg2 = _mm_sha256msg1_epu32(msg2, msg3);

    // rounds 48-51
    msg = _mm_add_epi32(
        msg0, _mm_set_epi64x(0x34B0BCB52748774CULL, 0x1E376C0819A4C116ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg0, msg3, 4);
    msg1 = _mm_add_epi32(msg1, tmp);
    msg1 = _mm_sha256msg2_epu32(msg1, msg0);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);
    msg3 = _mm_sha256msg1_epu32(msg3, msg0);

    // rounds 52-55
    msg = _mm_add_epi32(
        msg1, _mm_set_epi64x(0x682E6FF35B9CCA4FULL, 0x4ED8AA4A391C0CB3ULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg1, msg0, 4);
    msg2 = _mm_add_epi32(msg2, tmp);
    msg2 = _mm_sha256msg2_epu32(msg2, msg1);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);

    // rounds 56-59
    msg = _mm_add_epi32(
        msg2, _mm_set_epi64x(0x8CC7020884C87814ULL, 0x78A5636F748F82EEULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    tmp = _mm_alignr_epi8(msg2, msg1, 4);
    msg3 = _mm_add_epi32(msg3, tmp);
    msg3 = _mm_sha256msg2_epu32(msg3, msg2);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);

    // rounds 60-63
    msg = _mm_add_epi32(
        msg3, _mm_set_epi64x(0xC67178F2BEF9A3F7ULL, 0xA4506CEB90BEFFFAULL));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    msg = _mm_shuffle_epi32(msg, 0x0E);
    st0 = _mm_sha256rnds2_epu32(st0, st1, msg);

    st0 = _mm_add_epi32(st0, abef_save);
    st1 = _mm_add_epi32(st1, cdgh_save);
    data += 64;
    --blocks;
  }

  tmp = _mm_shuffle_epi32(st0, 0x1B);        // FEBA
  st1 = _mm_shuffle_epi32(st1, 0xB1);        // DCHG
  st0 = _mm_blend_epi16(tmp, st1, 0xF0);     // DCBA
  st1 = _mm_alignr_epi8(st1, tmp, 8);        // HGFE -> EFGH slots
  _mm_storeu_si128(reinterpret_cast<__m128i*>(&state[0]), st0);
  _mm_storeu_si128(reinterpret_cast<__m128i*>(&state[4]), st1);
}

bool detect_sha_ni() {
  // CPUID directly (not __builtin_cpu_supports: clang rejects "sha"):
  // leaf 7 EBX bit 29 = SHA extensions; leaf 1 ECX bits 19/9 = SSE4.1
  // and SSSE3, which the shuffles in the kernel above also need.
  unsigned int eax = 0, ebx = 0, ecx = 0, edx = 0;
  if (!__get_cpuid_count(7, 0, &eax, &ebx, &ecx, &edx)) return false;
  if (!(ebx & (1u << 29))) return false;
  if (!__get_cpuid(1, &eax, &ebx, &ecx, &edx)) return false;
  return (ecx & (1u << 19)) && (ecx & (1u << 9));
}

const bool kShaNi = detect_sha_ni();
#endif  // __x86_64__ && __GNUC__

struct Sha256 {
  uint32_t h[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
                   0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19};
  uint64_t total = 0;
  uint8_t buf[64];
  size_t buflen = 0;

  void block(const uint8_t* p) {
    uint32_t w[64];
    for (int i = 0; i < 16; ++i) {
      w[i] = (static_cast<uint32_t>(p[4 * i]) << 24) |
             (static_cast<uint32_t>(p[4 * i + 1]) << 16) |
             (static_cast<uint32_t>(p[4 * i + 2]) << 8) |
             static_cast<uint32_t>(p[4 * i + 3]);
    }
    for (int i = 16; i < 64; ++i) {
      uint32_t s0 = rotr32(w[i - 15], 7) ^ rotr32(w[i - 15], 18) ^
                    (w[i - 15] >> 3);
      uint32_t s1 = rotr32(w[i - 2], 17) ^ rotr32(w[i - 2], 19) ^
                    (w[i - 2] >> 10);
      w[i] = w[i - 16] + s0 + w[i - 7] + s1;
    }
    uint32_t a = h[0], b = h[1], c = h[2], d = h[3];
    uint32_t e = h[4], f = h[5], g = h[6], hh = h[7];
    for (int i = 0; i < 64; ++i) {
      uint32_t s1 = rotr32(e, 6) ^ rotr32(e, 11) ^ rotr32(e, 25);
      uint32_t ch = (e & f) ^ (~e & g);
      uint32_t t1 = hh + s1 + ch + kShaK[i] + w[i];
      uint32_t s0 = rotr32(a, 2) ^ rotr32(a, 13) ^ rotr32(a, 22);
      uint32_t maj = (a & b) ^ (a & c) ^ (b & c);
      uint32_t t2 = s0 + maj;
      hh = g;
      g = f;
      f = e;
      e = d + t1;
      d = c;
      c = b;
      b = a;
      a = t1 + t2;
    }
    h[0] += a;
    h[1] += b;
    h[2] += c;
    h[3] += d;
    h[4] += e;
    h[5] += f;
    h[6] += g;
    h[7] += hh;
  }

  void blocks(const uint8_t* p, size_t nblocks) {
#ifdef TFT_SHA_NI
    if (kShaNi) {
      sha256_blocks_ni(h, p, nblocks);
      return;
    }
#endif
    for (size_t i = 0; i < nblocks; ++i) block(p + 64 * i);
  }

  void update(const uint8_t* data, size_t n) {
    total += n;
    if (buflen > 0) {
      while (n > 0 && buflen < 64) {
        buf[buflen++] = *data++;
        --n;
      }
      if (buflen == 64) {
        blocks(buf, 1);
        buflen = 0;
      }
    }
    if (n >= 64) {
      size_t nb = n / 64;
      blocks(data, nb);
      data += nb * 64;
      n -= nb * 64;
    }
    while (n > 0) {
      buf[buflen++] = *data++;
      --n;
    }
  }

  void finish(uint8_t out[32]) {
    uint64_t bits = total * 8;
    uint8_t pad = 0x80;
    update(&pad, 1);
    uint8_t zero = 0;
    while (buflen != 56) update(&zero, 1);
    uint8_t lenbuf[8];
    for (int i = 0; i < 8; ++i)
      lenbuf[i] = static_cast<uint8_t>(bits >> (56 - 8 * i));
    update(lenbuf, 8);
    for (int i = 0; i < 8; ++i) {
      out[4 * i] = static_cast<uint8_t>(h[i] >> 24);
      out[4 * i + 1] = static_cast<uint8_t>(h[i] >> 16);
      out[4 * i + 2] = static_cast<uint8_t>(h[i] >> 8);
      out[4 * i + 3] = static_cast<uint8_t>(h[i]);
    }
  }
};

bool poll_fd(int fd, short events, int64_t deadline_ms) {
  for (;;) {
    int64_t remain = deadline_ms - now_ms();
    if (remain <= 0) return false;
    struct pollfd pfd = {fd, events, 0};
    int rc = poll(&pfd, 1, static_cast<int>(std::min<int64_t>(remain, 1000)));
    if (rc > 0) return true;
    if (rc < 0 && errno != EINTR && errno != EAGAIN) return false;
  }
}

// sendmsg loop over a (header, payload) pair honoring partial writes —
// the zero-copy serve primitive.  Never touches payload bytes.
bool sendv_all(int fd, const char* hdr, size_t hdr_len, const uint8_t* body,
               size_t body_len, int64_t deadline_ms) {
  size_t off = 0;
  const size_t total = hdr_len + body_len;
  while (off < total) {
    if (!poll_fd(fd, POLLOUT, deadline_ms)) return false;
    struct iovec iov[2];
    int cnt = 0;
    if (off < hdr_len) {
      iov[cnt].iov_base = const_cast<char*>(hdr) + off;
      iov[cnt].iov_len = hdr_len - off;
      ++cnt;
      iov[cnt].iov_base = const_cast<uint8_t*>(body);
      iov[cnt].iov_len = body_len;
      ++cnt;
    } else {
      iov[cnt].iov_base = const_cast<uint8_t*>(body) + (off - hdr_len);
      iov[cnt].iov_len = body_len - (off - hdr_len);
      ++cnt;
    }
    struct msghdr msg = {};
    msg.msg_iov = iov;
    msg.msg_iovlen = cnt;
    ssize_t rc = ::sendmsg(fd, &msg, MSG_NOSIGNAL);
    if (rc < 0) {
      if (errno == EINTR || errno == EAGAIN || errno == EWOULDBLOCK) continue;
      return false;
    }
    off += static_cast<size_t>(rc);
  }
  return true;
}

constexpr int64_t kLongPollMs = 250;      // cut-through park window
constexpr int64_t kLongPollCapMs = 5000;  // X-TFT-Poll-Ms request cap
constexpr int64_t kServeTimeoutMs = 60000;
constexpr size_t kPoolPerSizeCap = 64;    // recycled buffers kept per size
}  // namespace

void sha256_hex(const uint8_t* data, size_t len, char* out_hex65) {
  Sha256 s;
  if (len > 0) s.update(data, len);
  uint8_t digest[32];
  s.finish(digest);
  static const char* hex = "0123456789abcdef";
  for (int i = 0; i < 32; ++i) {
    out_hex65[2 * i] = hex[digest[i] >> 4];
    out_hex65[2 * i + 1] = hex[digest[i] & 0xf];
  }
  out_hex65[64] = '\0';
}

// ---- re-ordering copy ----------------------------------------------------
// A TPU hands the host a leaf whose last dimension is no multiple of 128
// with its last two dimensions the other way round; the wire wants rows.
// numpy re-orders element by element down a stride of the whole column
// (0.5 GB/s on a v5e's host, into a temporary); here a tile of kTileRows
// x kTileCols elements is read along the columns' own memory and written
// along the rows', both sides' lines reused while they are in L1 (few
// columns a tile: their addresses lie a column apart and share a cache
// set).

namespace {

template <typename T>
void transpose_tiles(T* dst, const T* src, int64_t rows, int64_t cols,
                     int64_t src_rows) {
  constexpr int64_t kTileRows = 64, kTileCols = 8;
  for (int64_t r0 = 0; r0 < rows; r0 += kTileRows) {
    const int64_t r1 = std::min(r0 + kTileRows, rows);
    for (int64_t c0 = 0; c0 < cols; c0 += kTileCols) {
      const int64_t c1 = std::min(c0 + kTileCols, cols);
      for (int64_t r = r0; r < r1; ++r)
        for (int64_t c = c0; c < c1; ++c)
          dst[r * cols + c] = src[c * src_rows + r];
    }
  }
}

}  // namespace

int copy_transposed(uint8_t* dst, const uint8_t* src, int64_t rows,
                    int64_t cols, int64_t src_rows, int64_t itemsize) {
  switch (itemsize) {
    case 1:
      transpose_tiles(dst, src, rows, cols, src_rows);
      return 0;
    case 2:
      transpose_tiles(reinterpret_cast<uint16_t*>(dst),
                      reinterpret_cast<const uint16_t*>(src), rows, cols,
                      src_rows);
      return 0;
    case 4:
      transpose_tiles(reinterpret_cast<uint32_t*>(dst),
                      reinterpret_cast<const uint32_t*>(src), rows, cols,
                      src_rows);
      return 0;
    case 8:
      transpose_tiles(reinterpret_cast<uint64_t*>(dst),
                      reinterpret_cast<const uint64_t*>(src), rows, cols,
                      src_rows);
      return 0;
    default:
      return -1;
  }
}

// ---- server --------------------------------------------------------------

FragServer::FragServer(const std::string& bind_host, int port)
    : RpcServer(bind_host, port) {
  start();
}

FragServer::~FragServer() {
  // Drain connection threads BEFORE members (cv_, versions_) go away;
  // RpcServer::shutdown is CAS-idempotent so an explicit earlier call
  // (tft_server_shutdown) makes this a no-op.
  shutdown();
  // A buffer still lent has a Python view over it that may yet be read
  // (a serve of the Python plane in flight at teardown): its memory is
  // left allocated rather than pulled from under the reader.
  for (auto& kv : lent_) (void)kv.second.buf->data.release();
}

Json FragServer::handle(const std::string& method, const Json&, int64_t) {
  throw std::runtime_error("fragserver speaks HTTP only: " + method);
}

void FragServer::wake_blocked() {
  std::lock_guard<std::mutex> g(mu_);
  cv_.notify_all();
}

std::shared_ptr<FragBuf> FragServer::pool_take(size_t len) {
  // caller holds mu_
  auto buf = std::make_shared<FragBuf>();
  auto it = pool_.find(len);
  if (it != pool_.end() && !it->second.empty()) {
    buf->data = std::move(it->second.back());
    it->second.pop_back();
    ++counters_.pool_hits;
  } else {
    // uninitialized: a fresh buffer's pages are touched once, by the
    // write that fills it, not by a zero-fill first
    buf->data.reset(new uint8_t[len]);
    ++counters_.pool_misses;
  }
  buf->len = len;
  return buf;
}

void FragServer::pool_give_locked(FragBuf& buf) {
  // caller holds mu_
  if (!buf.data) return;
  auto& slot = pool_[buf.len];
  if (slot.size() < kPoolPerSizeCap) slot.push_back(std::move(buf.data));
  buf.data.reset();
  buf.len = 0;
  buf.sha_hex.clear();
}

void FragServer::deref(const std::shared_ptr<FragBuf>& buf) {
  std::lock_guard<std::mutex> g(mu_);
  if (--buf->refs == 0 && buf->retired) pool_give_locked(*buf);
}

void FragServer::publish_locked(Version& version, const std::string& resource,
                                const std::shared_ptr<FragBuf>& buf) {
  // caller holds mu_
  auto& slot = version.frags[resource];
  if (slot) {
    // restage of the same resource: retire the old buffer
    slot->retired = true;
    if (slot->refs == 0) pool_give_locked(*slot);
  }
  slot = buf;
  cv_.notify_all();
}

int FragServer::begin(int64_t step) {
  std::lock_guard<std::mutex> g(mu_);
  versions_[step];  // streaming slot (complete=false)
  cv_.notify_all();  // readers parked on a future version re-check
  return 0;
}

int FragServer::stage(int64_t step, const std::string& resource,
                      const uint8_t* data, size_t len,
                      const std::string& sha_hex) {
  std::shared_ptr<FragBuf> buf;
  {
    std::lock_guard<std::mutex> g(mu_);
    if (versions_.find(step) == versions_.end()) return -1;
    buf = pool_take(len);
  }
  // The one copy in the plane: Python's staged buffer -> the pooled
  // registered buffer, outside the lock so concurrent stagers overlap.
  if (len > 0) memcpy(buf->data.get(), data, len);
  std::lock_guard<std::mutex> g(mu_);
  auto it = versions_.find(step);
  if (it == versions_.end()) {
    // retired while we copied: recycle, report not-mirrored
    pool_give_locked(*buf);
    return -1;
  }
  buf->sha_hex = sha_hex;
  publish_locked(it->second, resource, buf);
  counters_.stage_copy_bytes += static_cast<int64_t>(len);
  return 0;
}

uint8_t* FragServer::reserve(int64_t step, const std::string& resource,
                             size_t len) {
  std::lock_guard<std::mutex> g(mu_);
  if (versions_.find(step) == versions_.end()) return nullptr;
  auto buf = pool_take(len);
  // in no version yet, so a zombie by the rule above: letting go of the
  // lend alone recycles it, until a commit puts it in one
  buf->refs = 1;
  buf->retired = true;
  uint8_t* ptr = buf->data.get();
  lent_[ptr] = Lend{buf, step, resource};
  return ptr;
}

int FragServer::commit(int64_t step, const std::string& resource,
                       const uint8_t* ptr, size_t len,
                       const std::string& sha_hex) {
  std::lock_guard<std::mutex> g(mu_);
  auto lit = lent_.find(ptr);
  // `retired` on a lend: in no version now (never committed, or replaced
  // there since); one that is being served is not published twice
  if (lit == lent_.end() || lit->second.step != step ||
      lit->second.resource != resource || lit->second.buf->len != len ||
      !lit->second.buf->retired)
    return -1;
  auto it = versions_.find(step);
  if (it == versions_.end()) return -1;  // retired while it was written
  lit->second.buf->retired = false;
  lit->second.buf->sha_hex = sha_hex;
  publish_locked(it->second, resource, lit->second.buf);
  counters_.stage_inplace_bytes += static_cast<int64_t>(len);
  return 0;
}

int FragServer::release(const uint8_t* ptr) {
  std::shared_ptr<FragBuf> buf;
  {
    std::lock_guard<std::mutex> g(mu_);
    auto lit = lent_.find(ptr);
    if (lit == lent_.end()) return -1;
    buf = std::move(lit->second.buf);
    lent_.erase(lit);
  }
  deref(buf);
  return 0;
}

int FragServer::finish(int64_t step) {
  std::lock_guard<std::mutex> g(mu_);
  auto it = versions_.find(step);
  if (it == versions_.end()) return -1;
  it->second.complete = true;
  cv_.notify_all();
  return 0;
}

int FragServer::retire(int64_t step) {
  std::lock_guard<std::mutex> g(mu_);
  auto it = versions_.find(step);
  if (it == versions_.end()) return -1;
  for (auto& kv : it->second.frags) {
    kv.second->retired = true;
    if (kv.second->refs == 0) pool_give_locked(*kv.second);
    // else: in-flight serves finish from the zombie buffer; the last
    // deref recycles it — retire never waits on the wire
  }
  versions_.erase(it);
  cv_.notify_all();  // parked readers re-check and answer 404
  return 0;
}

FragCounters FragServer::counters() const {
  std::lock_guard<std::mutex> g(mu_);
  return counters_;
}

Json FragServer::counters_json() const {
  FragCounters c = counters();
  Json out = Json::object();
  out["pool_hits"] = c.pool_hits;
  out["pool_misses"] = c.pool_misses;
  out["stage_copy_bytes"] = c.stage_copy_bytes;
  out["stage_inplace_bytes"] = c.stage_inplace_bytes;
  out["serve_copies"] = c.serve_copies;
  out["serve_bytes"] = c.serve_bytes;
  out["serves"] = c.serves;
  out["same_replies"] = c.same_replies;
  out["parked_waits"] = c.parked_waits;
  out["busy_replies"] = c.busy_replies;
  out["miss_replies"] = c.miss_replies;
  out["injected_drops"] = c.injected_drops;
  out["injected_delays"] = c.injected_delays;
  return out;
}

int FragServer::inject(const std::string& mode, int64_t param_ms,
                       int64_t count) {
  std::lock_guard<std::mutex> g(mu_);
  if (mode == "off") {
    inject_mode_ = 0;
    inject_count_ = 0;
  } else if (mode == "drop") {
    inject_mode_ = 1;
    inject_count_ = count;
  } else if (mode == "delay") {
    inject_mode_ = 2;
    inject_param_ms_ = param_ms;
    inject_count_ = count;
  } else {
    return -1;
  }
  return 0;
}

bool FragServer::reply_simple(int fd, int status, const std::string& body) {
  const char* reason = status == 200   ? "OK"
                       : status == 404 ? "Not Found"
                       : status == 503 ? "Service Unavailable"
                                       : "Error";
  std::ostringstream os;
  os << "HTTP/1.1 " << status << " " << reason << "\r\n"
     << "Content-Type: text/plain\r\n"
     << "Content-Length: " << body.size() << "\r\n"
     << "Connection: keep-alive\r\n\r\n"
     << body;
  std::string s = os.str();
  return write_all(fd, s.data(), s.size(), now_ms() + kServeTimeoutMs,
                   nullptr);
}

bool FragServer::reply_same(int fd, const std::string& sha_hex) {
  std::string s = "HTTP/1.1 304 Not Modified\r\nETag: \"" + sha_hex +
                  "\"\r\nContent-Length: 0\r\n"
                  "Connection: keep-alive\r\n\r\n";
  return write_all(fd, s.data(), s.size(), now_ms() + kServeTimeoutMs,
                   nullptr);
}

bool FragServer::serve_frag(int fd, const std::shared_ptr<FragBuf>& buf) {
  char hdr[160];
  int hdr_len = snprintf(hdr, sizeof(hdr),
                         "HTTP/1.1 200 OK\r\n"
                         "Content-Type: application/octet-stream\r\n"
                         "Content-Length: %zu\r\n"
                         "Connection: keep-alive\r\n\r\n",
                         buf->len);
  bool ok = sendv_all(fd, hdr, static_cast<size_t>(hdr_len),
                      buf->data.get(), buf->len,
                      now_ms() + kServeTimeoutMs);
  {
    std::lock_guard<std::mutex> g(mu_);
    if (ok) {
      ++counters_.serves;
      counters_.serve_bytes += static_cast<int64_t>(buf->len);
    }
  }
  deref(buf);
  return ok;
}

bool FragServer::handle_http_keepalive(int fd,
                                       const std::string& request_head) {
  // First line: "GET /checkpoint/{step}/{resource} HTTP/1.1"
  std::istringstream is(request_head);
  std::string method, path;
  is >> method >> path;
  if (method != "GET") return reply_simple(fd, 404, "not found\n");
  int64_t step = 0;
  std::string resource;
  {
    const std::string prefix = "/checkpoint/";
    if (path.rfind(prefix, 0) != 0)
      return reply_simple(fd, 404, "not found\n");
    std::string rest = path.substr(prefix.size());
    size_t slash = rest.find('/');
    if (slash == std::string::npos || slash == 0 ||
        slash + 1 >= rest.size())
      return reply_simple(fd, 404, "not found\n");
    try {
      step = std::stoll(rest.substr(0, slash));
    } catch (const std::exception&) {
      return reply_simple(fd, 404, "not found\n");
    }
    resource = rest.substr(slash + 1);
  }

  // Client-requested park window (X-TFT-Poll-Ms): how long the caller
  // can afford us to hold a not-yet-staged fragment before 503.  Absent
  // header keeps the legacy 250 ms window (mixed-fleet peers).
  int64_t poll_ms = kLongPollMs;
  // A conditional GET (`If-None-Match: "<sha256 hex>"`): the asker holds
  // bytes of that digest and wants the fragment only if it is another.
  std::string unless;
  {
    std::string lower = request_head;
    std::transform(lower.begin(), lower.end(), lower.begin(),
                   [](unsigned char ch) { return std::tolower(ch); });
    size_t hp = lower.find("\r\nx-tft-poll-ms:");
    if (hp != std::string::npos) {
      try {
        poll_ms = std::stoll(request_head.substr(hp + 16));
      } catch (const std::exception&) {
      }
      poll_ms = std::max<int64_t>(
          0, std::min<int64_t>(poll_ms, kLongPollCapMs));
    }
    hp = lower.find("\r\nif-none-match:");
    if (hp != std::string::npos) {
      // the value's hex digits, whatever quotes or blanks surround them
      for (size_t i = hp + 16; i < lower.size() && lower[i] != '\r'; ++i)
        if (std::isxdigit(static_cast<unsigned char>(lower[i])))
          unless.push_back(lower[i]);
    }
  }

  // chaos-test fault injection (the native analog of the Python-side
  // serving.frag/transport.heal.frag sites, which fire before dispatch)
  int64_t delay_ms = 0;
  {
    std::lock_guard<std::mutex> g(mu_);
    if (inject_count_ > 0 && inject_mode_ != 0) {
      --inject_count_;
      if (inject_mode_ == 1) {
        ++counters_.injected_drops;
        return false;  // close mid-exchange: client sees transport error
      }
      ++counters_.injected_delays;
      delay_ms = inject_param_ms_;
    }
  }
  if (delay_ms > 0) usleep(static_cast<useconds_t>(delay_ms) * 1000);

  std::shared_ptr<FragBuf> buf;
  bool waited = false;
  {
    std::unique_lock<std::mutex> lk(mu_);
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::milliseconds(poll_ms);
    for (;;) {
      auto it = versions_.find(step);
      if (it == versions_.end()) {
        // Unknown version. If it is newer than everything staged here the
        // upstream simply has not begun it yet (cut-through race between a
        // child's first fetch wave and the parent's begin): park inside the
        // client's poll window instead of bouncing the caller onto the
        // Python fallback plane. Versions at or below the staged max are
        // retired or never existed — answer 404 immediately.
        bool future =
            versions_.empty() || step > versions_.rbegin()->first;
        if (!future || std::chrono::steady_clock::now() >= deadline) {
          if (waited) ++counters_.parked_waits;
          ++counters_.miss_replies;
          lk.unlock();
          return reply_simple(fd, 404, "unknown version\n");
        }
        if (stopping_.load()) {
          lk.unlock();
          return false;
        }
        waited = true;
        cv_.wait_until(lk, deadline);
        continue;
      }
      auto fit = it->second.frags.find(resource);
      if (fit != it->second.frags.end()) {
        buf = fit->second;
        ++buf->refs;
        break;
      }
      if (it->second.complete) {
        // complete and missing: the fragment was never raw-staged here;
        // the Python control plane owns it (or it truly does not exist)
        ++counters_.miss_replies;
        lk.unlock();
        return reply_simple(fd, 404, "no such fragment\n");
      }
      if (stopping_.load()) {
        lk.unlock();
        return false;
      }
      // streaming version, fragment not landed yet: park (cut-through)
      waited = true;
      if (cv_.wait_until(lk, deadline) == std::cv_status::timeout) {
        // one last re-check under the lock, then retryable-busy
        auto it2 = versions_.find(step);
        if (it2 != versions_.end()) {
          auto fit2 = it2->second.frags.find(resource);
          if (fit2 != it2->second.frags.end()) {
            buf = fit2->second;
            ++buf->refs;
            break;
          }
        }
        if (waited) ++counters_.parked_waits;
        ++counters_.busy_replies;
        lk.unlock();
        return reply_simple(fd, 503, "streaming\n");
      }
    }
    if (waited) ++counters_.parked_waits;
    if (!unless.empty() && buf->sha_hex == unless) {
      // staged under the asker's digest: "same", and no body
      ++counters_.same_replies;
      lk.unlock();
      deref(buf);
      return reply_same(fd, unless);
    }
  }
  return serve_frag(fd, buf);
}

// ---- client --------------------------------------------------------------

namespace {

struct PendingBody {
  int fd = -1;
  std::string addr;
  int64_t remaining = 0;
};

struct ClientState {
  std::map<std::string, int> conns;  // endpoint -> connected fd
  PendingBody pending;
  ~ClientState() {
    for (auto& kv : conns) ::close(kv.second);
    // pending.fd is always present in conns
  }
};

thread_local ClientState g_cli;
thread_local std::string g_cli_err;

void cli_drop(const std::string& addr) {
  auto it = g_cli.conns.find(addr);
  if (it != g_cli.conns.end()) {
    ::close(it->second);
    g_cli.conns.erase(it);
  }
  if (g_cli.pending.addr == addr) g_cli.pending = PendingBody{};
}

// Read the response head WITHOUT overshooting into the body: peek a
// window, look for the blank-line terminator, consume exactly what
// belongs to the head.  A handful of syscalls per response instead of
// two per byte.
bool read_head(int fd, std::string* head, int64_t deadline_ms,
               int64_t* first_byte_ms) {
  head->clear();
  char window[1024];
  bool first = true;
  while (head->size() < 64 * 1024) {
    // optimistic peek first; poll only when nothing is queued yet (the
    // common case on a kept-alive loopback exchange skips the poll)
    ssize_t rc = ::recv(fd, window, sizeof(window), MSG_PEEK);
    if (rc == 0) return false;
    if (rc < 0) {
      if (errno == EINTR) continue;
      if (errno != EAGAIN && errno != EWOULDBLOCK) return false;
      if (!poll_fd(fd, POLLIN, deadline_ms)) return false;
      continue;
    }
    if (first) {
      if (first_byte_ms) *first_byte_ms = now_ms();
      first = false;
    }
    // the terminator can straddle the previously-consumed tail: search
    // with 3 bytes of overlap into what this window appends
    size_t prev = head->size();
    head->append(window, static_cast<size_t>(rc));
    size_t pos = head->find("\r\n\r\n", prev >= 3 ? prev - 3 : 0);
    size_t consume = pos == std::string::npos
                         ? static_cast<size_t>(rc)
                         : pos + 4 - prev;
    if (!read_exact(fd, window, consume, deadline_ms, nullptr)) return false;
    if (pos != std::string::npos) {
      head->resize(pos + 4);
      return true;
    }
  }
  return false;
}

int parse_status(const std::string& head) {
  // "HTTP/1.1 NNN ..."
  size_t sp = head.find(' ');
  if (sp == std::string::npos || sp + 4 > head.size()) return -1;
  try {
    return std::stoi(head.substr(sp + 1, 3));
  } catch (const std::exception&) {
    return -1;
  }
}

int64_t parse_content_length(const std::string& head) {
  // our server emits exactly "Content-Length: N\r\n"
  const std::string key = "Content-Length:";
  size_t pos = head.find(key);
  if (pos == std::string::npos) return -1;
  try {
    return std::stoll(head.substr(pos + key.size()));
  } catch (const std::exception&) {
    return -1;
  }
}

}  // namespace

int frag_fetch_begin(const std::string& addr, int64_t step,
                     const std::string& resource, int64_t timeout_ms,
                     int64_t* content_len, double* first_byte_s,
                     const std::string& unless) {
  if (g_cli.pending.fd >= 0) {
    // a begin without its body/abort is a caller bug; recover by
    // dropping the wedged connection
    cli_drop(g_cli.pending.addr);
  }
  int64_t deadline = now_ms() + timeout_ms;
  // Client-driven cut-through park: tell the server how long WE can
  // afford it to hold a not-yet-staged fragment before answering 503.
  // Parking server-side (woken by stage()) beats a 503 + client retry
  // ladder — no duplicate request load, no backoff sleeps — but the
  // park must end before our own deadline or we would misread the
  // stall as a dead connection and drop to the Python path.
  int64_t poll_ms = std::min<int64_t>(timeout_ms - 150, kLongPollCapMs);
  std::string req = "GET /checkpoint/" + std::to_string(step) + "/" +
                    resource + " HTTP/1.1\r\nHost: " + addr +
                    "\r\nConnection: keep-alive\r\n";
  if (poll_ms > 0)
    req += "X-TFT-Poll-Ms: " + std::to_string(poll_ms) + "\r\n";
  if (!unless.empty()) req += "If-None-Match: \"" + unless + "\"\r\n";
  req += "\r\n";
  for (int attempt = 0; attempt < 2; ++attempt) {
    bool fresh = false;
    int fd;
    auto it = g_cli.conns.find(addr);
    if (it != g_cli.conns.end()) {
      fd = it->second;
    } else {
      std::string err;
      fd = connect_once(addr, std::max<int64_t>(deadline - now_ms(), 1),
                        &err);
      if (fd < 0) {
        g_cli_err = "frag connect " + addr + ": " + err;
        return -1;
      }
      g_cli.conns[addr] = fd;
      fresh = true;
    }
    int64_t t0 = now_ms();
    int64_t first_byte_at = t0;
    std::string head;
    if (!write_all(fd, req.data(), req.size(), deadline, nullptr) ||
        !read_head(fd, &head, deadline, &first_byte_at)) {
      // a reused keep-alive connection may have been closed under us:
      // retry exactly once on a fresh connection
      cli_drop(addr);
      if (fresh || now_ms() >= deadline) {
        g_cli_err = "frag fetch " + addr + ": connection lost";
        return -1;
      }
      continue;
    }
    int status = parse_status(head);
    int64_t length = parse_content_length(head);
    if (status < 0 || length < 0) {
      cli_drop(addr);
      g_cli_err = "frag fetch " + addr + ": malformed response";
      return -1;
    }
    if (first_byte_s)
      *first_byte_s = static_cast<double>(first_byte_at - t0) / 1000.0;
    if (status == 200) {
      g_cli.pending.fd = fd;
      g_cli.pending.addr = addr;
      g_cli.pending.remaining = length;
      if (content_len) *content_len = length;
      return 200;
    }
    // small control body (404/503 text; none after a 304): drain it,
    // keep the connection
    char scratch[256];
    int64_t left = length;
    while (left > 0) {
      size_t take = static_cast<size_t>(
          std::min<int64_t>(left, static_cast<int64_t>(sizeof(scratch))));
      if (!read_exact(fd, scratch, take, deadline, nullptr)) {
        cli_drop(addr);
        break;
      }
      left -= static_cast<int64_t>(take);
    }
    if (content_len) *content_len = 0;
    return status;
  }
  g_cli_err = "frag fetch " + addr + ": retries exhausted";
  return -1;
}

int frag_fetch_body(uint8_t* buf, int64_t cap, char* sha_hex_out,
                    int64_t timeout_ms) {
  if (g_cli.pending.fd < 0) {
    g_cli_err = "frag body: no pending fetch";
    return -1;
  }
  PendingBody p = g_cli.pending;
  g_cli.pending = PendingBody{};
  if (cap < p.remaining) {
    cli_drop(p.addr);
    g_cli_err = "frag body: buffer too small";
    return -1;
  }
  if (!read_exact(p.fd, reinterpret_cast<char*>(buf),
                  static_cast<size_t>(p.remaining),
                  now_ms() + timeout_ms, nullptr)) {
    cli_drop(p.addr);
    g_cli_err = "frag body " + p.addr + ": connection lost mid-body";
    return -1;
  }
  if (sha_hex_out)
    sha256_hex(buf, static_cast<size_t>(p.remaining), sha_hex_out);
  return 0;
}

void frag_fetch_abort() {
  if (g_cli.pending.fd >= 0) cli_drop(g_cli.pending.addr);
}

void frag_client_close() {
  for (auto& kv : g_cli.conns) ::close(kv.second);
  g_cli.conns.clear();
  g_cli.pending = PendingBody{};
}

const std::string& frag_client_error() { return g_cli_err; }

}  // namespace tft
