// Native CPU erasure-code engine for ceph_tpu.
//
// Role: the host-side reference/baseline codec the TPU kernels are measured
// against (the reference gets this from gf-complete/ISA-L's SIMD paths;
// reference:src/erasure-code/jerasure/CMakeLists.txt:11-66). Portable C++
// (auto-vectorized by -O3 -march=native), single thread, GF(2^8)/GF(2^16):
//
// - gf8_encode: parity[m][n] = GF matmul of matrix[m][k] with data[k][n],
//   via the same shift-xor doubling scheme as the TPU kernel, on uint64
//   lanes (8 bytes per op), so CPU and TPU produce identical bytes by
//   construction.
// - gf8_mul_region / xor_region: building blocks for tests and the
//   crc/scrub paths.
//
// Exposed with C linkage for ctypes (no pybind11 in this image).

#include <cstdint>
#include <cstring>

#if defined(__GFNI__) && defined(__AVX512BW__) && defined(__AVX512F__)
#include <immintrin.h>
#define CEPH_TPU_GFNI 1
#endif

namespace {

// GF(2^8), poly 0x11d — lane-parallel double on uint64 (8 byte lanes)
static inline uint64_t gf8_double64(uint64_t x) {
  uint64_t high = (x >> 7) & 0x0101010101010101ULL;
  return ((x & 0x7f7f7f7f7f7f7f7fULL) << 1) ^ (high * 0x1dULL);
}

#ifdef CEPH_TPU_GFNI
// GFNI path: multiply-by-constant in GF(2^8)/0x11d expressed as an 8x8
// bit-matrix for vgf2p8affineqb (the ISA-L-class technique; the fixed
// gf2p8mulb polynomial is 0x11b, so the affine form is what makes the
// 0x11d field natively executable).  64 bytes per instruction on zmm.
static inline uint8_t gf8_mul1(uint8_t a, uint8_t b) {
  uint8_t p = 0;
  while (b) {
    if (b & 1) p ^= a;
    b >>= 1;
    a = (uint8_t)((a << 1) ^ ((a & 0x80) ? 0x1d : 0));
  }
  return p;
}

// row for output bit j = mask of source bits feeding it; stored at
// byte (7-j) of the matrix qword (verified against _mm_gf2p8affine)
static uint64_t gf8_affine_matrix(uint8_t c) {
  uint8_t p[8];
  for (int k = 0; k < 8; ++k) p[k] = gf8_mul1(c, (uint8_t)(1 << k));
  uint64_t A = 0;
  for (int j = 0; j < 8; ++j) {
    uint8_t row = 0;
    for (int k = 0; k < 8; ++k) row |= (uint8_t)(((p[k] >> j) & 1) << k);
    A |= ((uint64_t)row) << (8 * (7 - j));
  }
  return A;
}

// parity[i] ^= mul(matrix[i][j], data[j]) for all i, one data pass.
// aff: per-cell affine qwords [m*k]; n % 64 handled with a tail buffer.
static void gf8_encode_gfni(const uint64_t* aff, int k, int m,
                            const uint8_t* const* data,
                            uint8_t* const* parity, int64_t n) {
  const int64_t body = n & ~63LL;
  for (int64_t off = 0; off < body; off += 64) {
    __m512i acc[8];
    for (int i = 0; i < m; ++i) acc[i] = _mm512_setzero_si512();
    for (int j = 0; j < k; ++j) {
      __m512i src = _mm512_loadu_si512(
          (const void*)(data[j] + off));
      for (int i = 0; i < m; ++i) {
        uint64_t A = aff[i * k + j];
        if (!A) continue;
        acc[i] = _mm512_xor_si512(
            acc[i], _mm512_gf2p8affine_epi64_epi8(
                        src, _mm512_set1_epi64((long long)A), 0));
      }
    }
    for (int i = 0; i < m; ++i)
      _mm512_storeu_si512((void*)(parity[i] + off), acc[i]);
  }
  if (body < n) {  // tail: pad into a 64B buffer
    alignas(64) uint8_t sbuf[64], pbuf[8][64];
    for (int i = 0; i < m; ++i) std::memset(pbuf[i], 0, 64);
    for (int j = 0; j < k; ++j) {
      std::memset(sbuf, 0, 64);
      std::memcpy(sbuf, data[j] + body, (size_t)(n - body));
      __m512i src = _mm512_load_si512((const void*)sbuf);
      for (int i = 0; i < m; ++i) {
        uint64_t A = aff[i * k + j];
        if (!A) continue;
        __m512i acc = _mm512_load_si512((const void*)pbuf[i]);
        acc = _mm512_xor_si512(
            acc, _mm512_gf2p8affine_epi64_epi8(
                     src, _mm512_set1_epi64((long long)A), 0));
        _mm512_store_si512((void*)pbuf[i], acc);
      }
    }
    for (int i = 0; i < m; ++i)
      std::memcpy(parity[i] + body, pbuf[i], (size_t)(n - body));
  }
}
#endif  // CEPH_TPU_GFNI

static inline uint64_t gf16_double64(uint64_t x) {
  uint64_t high = (x >> 15) & 0x0001000100010001ULL;
  return ((x & 0x7fff7fff7fff7fffULL) << 1) ^ (high * 0x100bULL);
}

}  // namespace

extern "C" {

// parity[m][n] = matrix[m][k] (GF(2^8) elements) * data rows; n % 8 == 0.
// data: k pointers to n-byte chunks; parity: m pointers to n-byte chunks.
void gf8_encode(const int* matrix, int k, int m, const uint8_t* const* data,
                uint8_t* const* parity, int64_t n) {
#ifdef CEPH_TPU_GFNI
  if (m <= 8) {
    uint64_t aff[32 * 8];
    for (int i = 0; i < m; ++i)
      for (int j = 0; j < k; ++j)
        aff[i * k + j] = gf8_affine_matrix((uint8_t)matrix[i * k + j]);
    gf8_encode_gfni(aff, k, m, data, parity, n);
    return;
  }
#endif
  // powers[j][b] = 2^b * data[j], built lazily per 8-byte block to stay in
  // registers/cache: process in blocks of BLK bytes.
  constexpr int64_t BLK = 4096;
  uint64_t powbuf[8][BLK / 8];
  for (int64_t off = 0; off < n; off += BLK) {
    int64_t len = (n - off < BLK) ? (n - off) : BLK;
    int64_t words = len / 8;
    // zero parity accumulators for this block
    for (int i = 0; i < m; ++i) std::memset(parity[i] + off, 0, len);
    for (int j = 0; j < k; ++j) {
      // which powers of 2 does column j need?
      int needed = 0;
      for (int i = 0; i < m; ++i) needed |= matrix[i * k + j];
      if (!needed) continue;
      const uint64_t* src = reinterpret_cast<const uint64_t*>(data[j] + off);
      int maxb = 0;
      for (int b = 7; b >= 0; --b)
        if (needed & (1 << b)) { maxb = b; break; }
      // build doubling chain
      for (int64_t w = 0; w < words; ++w) powbuf[0][w] = src[w];
      for (int b = 1; b <= maxb; ++b)
        for (int64_t w = 0; w < words; ++w)
          powbuf[b][w] = gf8_double64(powbuf[b - 1][w]);
      for (int i = 0; i < m; ++i) {
        int c = matrix[i * k + j];
        if (!c) continue;
        uint64_t* dst = reinterpret_cast<uint64_t*>(parity[i] + off);
        for (int b = 0; b <= maxb; ++b)
          if (c & (1 << b))
            for (int64_t w = 0; w < words; ++w) dst[w] ^= powbuf[b][w];
      }
    }
  }
}

// Flat-layout convenience wrapper: data [k*n], parity out [m*n].
void gf8_encode_flat(const int* matrix, int k, int m, const uint8_t* data,
                     uint8_t* parity, int64_t n) {
  const uint8_t* dptr[32];
  uint8_t* pptr[32];
  for (int j = 0; j < k; ++j) dptr[j] = data + j * n;
  for (int i = 0; i < m; ++i) pptr[i] = parity + i * n;
  gf8_encode(matrix, k, m, dptr, pptr, n);
}

// Fused stripe-layout encode over the stripe range [s0, s0+nS) of a
// LARGER [S, k, cs] batch whose shard rows are shard_len bytes apart:
// the strided body that lets callers split one batch across worker
// threads (each thread owns a disjoint stripe range, so the writes
// never overlap and the bytes are identical to one serial pass).
// in: the range's first stripe (caller pre-offsets); shards: the FULL
// output base. cs % 8 == 0.
void gf8_encode_stripes_block(const int* matrix, int k, int m, int64_t s0,
                              int64_t nS, int64_t cs, int64_t shard_len,
                              const uint8_t* in, uint8_t* shards) {
  const uint8_t* dptr[32];
  uint8_t* pptr[32];
#ifdef CEPH_TPU_GFNI
  if (m <= 8) {
    // affine table built ONCE for the whole batch (r5 review: building
    // it per stripe cost as much as the vector work at small chunks)
    uint64_t aff[32 * 8];
    for (int i = 0; i < m; ++i)
      for (int j = 0; j < k; ++j)
        aff[i * k + j] = gf8_affine_matrix((uint8_t)matrix[i * k + j]);
    for (int64_t s = s0; s < s0 + nS; ++s) {
      const uint8_t* base = in + (s - s0) * k * cs;
      for (int j = 0; j < k; ++j) {
        dptr[j] = base + j * cs;
        std::memcpy(shards + j * shard_len + s * cs, dptr[j], cs);
      }
      for (int i = 0; i < m; ++i)
        pptr[i] = shards + (k + i) * shard_len + s * cs;
      gf8_encode_gfni(aff, k, m, dptr, pptr, cs);
    }
    return;
  }
#endif
  for (int64_t s = s0; s < s0 + nS; ++s) {
    const uint8_t* base = in + (s - s0) * k * cs;
    for (int j = 0; j < k; ++j) {
      dptr[j] = base + j * cs;
      std::memcpy(shards + j * shard_len + s * cs, dptr[j], cs);
    }
    for (int i = 0; i < m; ++i)
      pptr[i] = shards + (k + i) * shard_len + s * cs;
    gf8_encode(matrix, k, m, dptr, pptr, cs);
  }
}

// Fused stripe-layout encode: one pass over the client buffer produces
// the per-shard buffers (the OSD's deliverable) AND the parity — no
// separate transpose pass re-reading the data (the ceph_tpu codec
// stack's hot entry; ECUtil::encode's per-stripe loop collapsed).
// in: [S, k, cs] stripes; shards: flat [(k+m), S*cs] output whose rows
// are the shard buffers. cs % 8 == 0.
void gf8_encode_stripes(const int* matrix, int k, int m, int64_t S,
                        int64_t cs, const uint8_t* in, uint8_t* shards) {
  gf8_encode_stripes_block(matrix, k, m, 0, S, cs, S * cs, in, shards);
}

void gf8_mul_region(uint8_t c, const uint8_t* src, uint8_t* dst, int64_t n) {
  const uint64_t* s = reinterpret_cast<const uint64_t*>(src);
  uint64_t* d = reinterpret_cast<uint64_t*>(dst);
  int64_t words = n / 8;
  uint64_t pow[8];
  for (int64_t w = 0; w < words; ++w) {
    uint64_t acc = 0, p = s[w];
    for (int b = 0; b < 8; ++b) {
      if (c & (1 << b)) acc ^= p;
      p = gf8_double64(p);
    }
    d[w] = acc;
  }
  (void)pow;
}

void xor_region(const uint8_t* a, const uint8_t* b, uint8_t* dst, int64_t n) {
  const uint64_t* x = reinterpret_cast<const uint64_t*>(a);
  const uint64_t* y = reinterpret_cast<const uint64_t*>(b);
  uint64_t* d = reinterpret_cast<uint64_t*>(dst);
  for (int64_t w = 0; w < n / 8; ++w) d[w] = x[w] ^ y[w];
}

// GF(2^16) variant (elements little-endian uint16; n bytes, n % 8 == 0)
void gf16_encode_flat(const int* matrix, int k, int m, const uint8_t* data,
                      uint8_t* parity, int64_t n) {
  int64_t words = n / 8;
  for (int i = 0; i < m; ++i) {
    uint64_t* dst = reinterpret_cast<uint64_t*>(parity + i * n);
    std::memset(dst, 0, n);
    for (int j = 0; j < k; ++j) {
      int c = matrix[i * k + j];
      if (!c) continue;
      const uint64_t* src = reinterpret_cast<const uint64_t*>(data + j * n);
      for (int64_t w = 0; w < words; ++w) {
        uint64_t acc = 0, p = src[w];
        for (int b = 0; b < 16; ++b) {
          if (c & (1 << b)) acc ^= p;
          p = gf16_double64(p);
        }
        dst[w] ^= acc;
      }
    }
  }
}

}  // extern "C"

// crc32c (Castagnoli, reflected poly 0x82F63B78), slicing-by-8.
// Same semantics as the reference's ceph_crc32c(crc, data, len): the seed is
// used as-is with no pre/post inversion (callers conventionally pass -1), so
// crcs compose: crc(a+b) = crc32c(crc32c(seed, a), b).
// reference:src/common/crc32c.h / common/crc32c_sctp.c (software path).
namespace {

struct Crc32cTables {
  uint32_t t[8][256];
  Crc32cTables() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int j = 0; j < 8; ++j) c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : c >> 1;
      t[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; ++i)
      for (int s = 1; s < 8; ++s)
        t[s][i] = (t[s - 1][i] >> 8) ^ t[0][t[s - 1][i] & 0xff];
  }
};
static const Crc32cTables kCrcTab;

}  // namespace

// ---------------------------------------------------------------------------
// Independent coding-matrix constructions (golden cross-check oracle).
//
// Second implementation of the published matrix algorithms, written against
// the papers rather than the python code, so tests can pin the python
// matrices against an independently-coded oracle (the role the compiled
// reference C played for the CRUSH golden fixtures):
// - systematic RS-Vandermonde per Plank & Ding, "Note: Correction to the
//   1997 Tutorial on Reed-Solomon Coding" (extended Vandermonde,
//   column-operation systematization, parity row normalized to ones);
// - Cauchy original per Blomer et al. / jerasure cauchy.c spec:
//   entry(i, j) = 1 / (i XOR (m + j)) over GF(2^w).
// Field definition: same primitive polynomials as gf-complete's defaults
// (w4 0x13, w8 0x11d, w16 0x1100b) — part of the published spec.

#include <vector>

namespace {

int gfw_poly(int w) {
  switch (w) {
    case 4: return 0x13;
    case 8: return 0x11d;
    case 16: return 0x1100b;
    default: return 0;
  }
}

struct GfW {
  int w, size;
  std::vector<int> logt, expt;
  explicit GfW(int w_) : w(w_), size(1 << w_), logt(size, 0), expt(size, 0) {
    int poly = gfw_poly(w);
    int v = 1;
    for (int i = 0; i < size - 1; i++) {
      expt[i] = v;
      logt[v] = i;
      v <<= 1;
      if (v & size) v ^= poly;
    }
  }
  int mul(int a, int b) const {
    if (a == 0 || b == 0) return 0;
    return expt[(logt[a] + logt[b]) % (size - 1)];
  }
  int inv(int a) const {
    return expt[(size - 1 - logt[a]) % (size - 1)];
  }
};

}  // namespace

extern "C" {

// out is [m*k] row-major; returns 0 on success
int rs_vandermonde_matrix(int k, int m, int w, int32_t* out) {
  if (gfw_poly(w) == 0 || k + m > (1 << w)) return -1;
  GfW g(w);
  const int rows = k + m, cols = k;
  // extended Vandermonde: e0 / power rows / e_{cols-1}
  std::vector<int> D(rows * cols, 0);
  auto at = [&](int r, int c) -> int& { return D[r * cols + c]; };
  at(0, 0) = 1;
  if (rows > 1) {
    at(rows - 1, cols - 1) = 1;
    for (int i = 1; i < rows - 1; i++) {
      int v = 1;
      for (int j = 0; j < cols; j++) {
        at(i, j) = v;
        v = g.mul(v, i);
      }
    }
  }
  // systematize with column ops (these preserve every-k-rows-invertible)
  for (int i = 1; i < cols; i++) {
    int piv = -1;
    for (int r = i; r < rows; r++)
      if (at(r, i) != 0) { piv = r; break; }
    if (piv < 0) return -2;
    if (piv != i)
      for (int c = 0; c < cols; c++) std::swap(at(i, c), at(piv, c));
    if (at(i, i) != 1) {
      int t = g.inv(at(i, i));
      for (int r = 0; r < rows; r++) at(r, i) = g.mul(at(r, i), t);
    }
    for (int j = 0; j < cols; j++) {
      int t = at(i, j);
      if (j != i && t != 0)
        for (int r = 0; r < rows; r++) at(r, j) ^= g.mul(t, at(r, i));
    }
  }
  // parity block, first row normalized to all ones
  for (int j = 0; j < cols; j++) {
    int c = at(k, j);
    if (c == 0) return -3;
    int t = g.inv(c);
    for (int r = 0; r < m; r++)
      out[r * k + j] = g.mul(at(k + r, j), t);
  }
  return 0;
}

int cauchy_original_matrix(int k, int m, int w, int32_t* out) {
  if (gfw_poly(w) == 0 || k + m > (1 << w)) return -1;
  GfW g(w);
  for (int i = 0; i < m; i++)
    for (int j = 0; j < k; j++)
      out[i * k + j] = g.inv(i ^ (m + j));
  return 0;
}

}  // extern "C"

extern "C" {

uint32_t crc32c_sw(uint32_t crc, const uint8_t* data, int64_t n) {
#if defined(__SSE4_2__)
  // hardware CRC32C (SSE4.2 crc32 instruction): the exact Castagnoli
  // reflected polynomial with the same raw-state semantics as the
  // table path below (no pre/post inversion), so the two compose and
  // cross-check bit-identically (pinned by tests/test_native.py).
  // This is the per-frame checksum on every messenger hop — at table
  // speed (~1.5 GB/s) it dominated the zero-copy stack round trip;
  // the instruction runs at tens of GB/s (ceph's crc32c-intel path,
  // reference:src/common/crc32c_intel_fast.c).
  uint64_t c64 = crc;
  while (n >= 8) {
    uint64_t word;
    std::memcpy(&word, data, 8);
    c64 = __builtin_ia32_crc32di(c64, word);
    data += 8;
    n -= 8;
  }
  crc = (uint32_t)c64;
  while (n-- > 0) crc = __builtin_ia32_crc32qi(crc, *data++);
  return crc;
#else
  const uint32_t (*T)[256] = kCrcTab.t;
#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
  while (n >= 8) {
    uint64_t word;
    std::memcpy(&word, data, 8);
    word ^= crc;
    crc = T[7][word & 0xff] ^ T[6][(word >> 8) & 0xff] ^
          T[5][(word >> 16) & 0xff] ^ T[4][(word >> 24) & 0xff] ^
          T[3][(word >> 32) & 0xff] ^ T[2][(word >> 40) & 0xff] ^
          T[1][(word >> 48) & 0xff] ^ T[0][(word >> 56) & 0xff];
    data += 8;
    n -= 8;
  }
#endif  // big-endian hosts take the bytewise loop for all input
  while (n-- > 0) crc = (crc >> 8) ^ T[0][(crc ^ *data++) & 0xff];
  return crc;
#endif
}

// crc32c of each chunk-byte slice of data[0, n), every one seeded with
// seed, into out[n / chunk]: a shard's whole per-stripe table in one
// call instead of one call per chunk (osd/ec_util.py StripeHashes)
void crc32c_chunks(uint32_t seed, const uint8_t* data, int64_t n,
                   int64_t chunk, uint32_t* out) {
  for (int64_t i = 0, count = n / chunk; i < count; i++)
    out[i] = crc32c_sw(seed, data + i * chunk, chunk);
}

// table-path reference, exported so the test suite can cross-check the
// hardware instruction against the software tables on any input
uint32_t crc32c_table(uint32_t crc, const uint8_t* data, int64_t n) {
  const uint32_t (*T)[256] = kCrcTab.t;
  while (n-- > 0) crc = (crc >> 8) ^ T[0][(crc ^ *data++) & 0xff];
  return crc;
}

}  // extern "C"
