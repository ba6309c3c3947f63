// Native ingest hot path: fused write-key encoding.
//
// The reference's ingest hot loop is per-feature JVM code — normalize +
// Z3.split interleave + row byte assembly (reference
// geomesa-index-api/.../index/z3/Z3IndexKeySpace.scala:63-95 over
// geomesa-z3/.../zorder/sfcurve/Z3.scala:73-91). Here the equivalent tier
// is one fused multithreaded C++ pass per ingest batch: epoch-millis
// binning, lon/lat/time bit-normalization, Morton interleave, and the f32
// device-column conversion, writing all five output columns in a single
// traversal (the numpy path materializes ~10 temporaries).
//
// Semantics are bit-exact with geomesa_tpu.curve (zorder.py / normalize.py
// / binnedtime.py); tests/test_native.py asserts exact equality.
//
// Build: g++ -O3 -shared -fPIC [-fopenmp] geomesa_native.cpp -o libgeomesa_native.so

#include <algorithm>
#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <utility>
#include <vector>
#ifdef _OPENMP
#include <omp.h>
#endif

// ------------------------------------------------------------ the stamp
// Every exported function below opens with ``Stamp stamp_;``: the guard
// stores CLOCK_MONOTONIC (Python's ``time.perf_counter`` on Linux) at
// entry and at return in two doubles of the calling thread, so that the
// caller, back in Python with the interpreter lock, can tell the seconds
// the work ran with the lock released from the seconds it then waited to
// get the lock back (geomesa_tpu/native/__init__.py ``_call``; the two
// getters at the end of this file are loaded through ``ctypes.PyDLL``,
// whose calls keep the lock). Only the outermost guard of a thread
// writes: ``zranges_each_cpp`` calls ``zranges_cpp``.
#include <time.h>

static thread_local double g_stamp_entry = 0.0, g_stamp_return = 0.0;
static thread_local int g_stamp_depth = 0;

static inline double mono_s() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (double)ts.tv_sec + 1e-9 * (double)ts.tv_nsec;
}

struct Stamp {
  Stamp() { if (g_stamp_depth++ == 0) g_stamp_entry = mono_s(); }
  ~Stamp() { if (--g_stamp_depth == 0) g_stamp_return = mono_s(); }
};

extern "C" {

// ---------------------------------------------------------------- morton

static inline uint64_t split2(uint64_t x) {
  x &= 0x7FFFFFFFull;
  x = (x ^ (x << 32)) & 0x00000000FFFFFFFFull;
  x = (x ^ (x << 16)) & 0x0000FFFF0000FFFFull;
  x = (x ^ (x << 8)) & 0x00FF00FF00FF00FFull;
  x = (x ^ (x << 4)) & 0x0F0F0F0F0F0F0F0Full;
  x = (x ^ (x << 2)) & 0x3333333333333333ull;
  x = (x ^ (x << 1)) & 0x5555555555555555ull;
  return x;
}

static inline uint64_t combine2(uint64_t z) {
  uint64_t x = z & 0x5555555555555555ull;
  x = (x ^ (x >> 1)) & 0x3333333333333333ull;
  x = (x ^ (x >> 2)) & 0x0F0F0F0F0F0F0F0Full;
  x = (x ^ (x >> 4)) & 0x00FF00FF00FF00FFull;
  x = (x ^ (x >> 8)) & 0x0000FFFF0000FFFFull;
  x = (x ^ (x >> 16)) & 0x00000000FFFFFFFFull;
  return x;
}

static inline uint64_t split3(uint64_t x) {
  x &= 0x1FFFFFull;
  x = (x | (x << 32)) & 0x1F00000000FFFFull;
  x = (x | (x << 16)) & 0x1F0000FF0000FFull;
  x = (x | (x << 8)) & 0x100F00F00F00F00Full;
  x = (x | (x << 4)) & 0x10C30C30C30C30C3ull;
  x = (x | (x << 2)) & 0x1249249249249249ull;
  return x;
}

static inline uint64_t combine3(uint64_t z) {
  uint64_t x = z & 0x1249249249249249ull;
  x = (x ^ (x >> 2)) & 0x10C30C30C30C30C3ull;
  x = (x ^ (x >> 4)) & 0x100F00F00F00F00Full;
  x = (x ^ (x >> 8)) & 0x1F0000FF0000FFull;
  x = (x ^ (x >> 16)) & 0x1F00000000FFFFull;
  x = (x ^ (x >> 32)) & 0x1FFFFFull;
  return x;
}

void morton2(const uint64_t* x, const uint64_t* y, int64_t n, uint64_t* out) {
  Stamp stamp_;
#pragma omp parallel for
  for (int64_t i = 0; i < n; ++i) {
    out[i] = split2(x[i]) | (split2(y[i]) << 1);
  }
}

void morton2_decode(const uint64_t* z, int64_t n, uint64_t* x, uint64_t* y) {
  Stamp stamp_;
#pragma omp parallel for
  for (int64_t i = 0; i < n; ++i) {
    x[i] = combine2(z[i]);
    y[i] = combine2(z[i] >> 1);
  }
}

void morton3(const uint64_t* x, const uint64_t* y, const uint64_t* t, int64_t n,
             uint64_t* out) {
  Stamp stamp_;
#pragma omp parallel for
  for (int64_t i = 0; i < n; ++i) {
    out[i] = split3(x[i]) | (split3(y[i]) << 1) | (split3(t[i]) << 2);
  }
}

void morton3_decode(const uint64_t* z, int64_t n, uint64_t* x, uint64_t* y,
                    uint64_t* t) {
  Stamp stamp_;
#pragma omp parallel for
  for (int64_t i = 0; i < n; ++i) {
    x[i] = combine3(z[i]);
    y[i] = combine3(z[i] >> 1);
    t[i] = combine3(z[i] >> 2);
  }
}

// ----------------------------------------------------------- normalization
// Bit-exact with NormalizedDimension.normalize: floor((d - min) * bins /
// (max - min)) clamped to [0, 2^p - 1]; the normalizer is computed once in
// double, matching numpy's scalar broadcast.

static inline int64_t normalize(double d, double mn, double normalizer,
                                int64_t max_index) {
  int64_t i = (int64_t)std::floor((d - mn) * normalizer);
  if (i < 0) i = 0;
  if (i > max_index) i = max_index;
  return i;
}

// ------------------------------------------------------------- write keys

// Fixed-width periods only (day: bin_ms=86400000, off_div=1; week:
// bin_ms=604800000, off_div=1000). Calendar periods (month/year) stay on
// the numpy path. Returns 0 ok, 1 pre-epoch input, 2 bin overflow.
int32_t z3_write_keys(const double* x, const double* y, const int64_t* millis,
                      int64_t n, int64_t bin_ms, int64_t off_div,
                      double max_off, int32_t max_bin, uint64_t* out_z,
                      int32_t* out_bin, float* out_xf, float* out_yf,
                      int32_t* out_toff) {
  Stamp stamp_;
  const double lon_norm = 2097152.0 / 360.0;  // 2^21 / (180 - -180)
  const double lat_norm = 2097152.0 / 180.0;
  const double t_norm = 2097152.0 / max_off;  // NormalizedTime(21, max_off)
  const int64_t max_index = 2097151;          // 2^21 - 1
  int32_t status = 0;
#pragma omp parallel for reduction(max : status)
  for (int64_t i = 0; i < n; ++i) {
    int64_t ms = millis[i];
    if (ms < 0) {
      status = status > 1 ? status : 1;
      continue;
    }
    int64_t bin = ms / bin_ms;
    int64_t off = (ms - bin * bin_ms) / off_div;
    if (bin > (int64_t)max_bin) {
      status = 2;
      continue;
    }
    uint64_t xi = (uint64_t)normalize(x[i], -180.0, lon_norm, max_index);
    uint64_t yi = (uint64_t)normalize(y[i], -90.0, lat_norm, max_index);
    uint64_t ti = (uint64_t)normalize((double)off, 0.0, t_norm, max_index);
    out_z[i] = split3(xi) | (split3(yi) << 1) | (split3(ti) << 2);
    out_bin[i] = (int32_t)bin;
    out_xf[i] = (float)x[i];
    out_yf[i] = (float)y[i];
    out_toff[i] = (int32_t)off;
  }
  return status;
}

void z2_write_keys(const double* x, const double* y, int64_t n, uint64_t* out_z,
                   float* out_xf, float* out_yf) {
  Stamp stamp_;
  const double lon_norm = 2147483648.0 / 360.0;  // 2^31 / 360
  const double lat_norm = 2147483648.0 / 180.0;
  const int64_t max_index = 2147483647;  // 2^31 - 1
#pragma omp parallel for
  for (int64_t i = 0; i < n; ++i) {
    uint64_t xi = (uint64_t)normalize(x[i], -180.0, lon_norm, max_index);
    uint64_t yi = (uint64_t)normalize(y[i], -90.0, lat_norm, max_index);
    out_z[i] = split2(xi) | (split2(yi) << 1);
    out_xf[i] = (float)x[i];
    out_yf[i] = (float)y[i];
  }
  return;
}

}  // extern "C"

// ------------------------------------------------------------ radix sort
// Ingest-path argsort by (bin, z): LSD radix with u32 payload, replacing
// np.lexsort's comparison sort (the reference gets sorted order for free
// from its KV backends; here the sorted columnar table is built in one
// batch pass — SURVEY §7 hard part (c)). 8-bit digits; passes whose
// histogram collapses to a single bucket are skipped (high z bytes and
// small bin counts make most of the 10 nominal passes no-ops).


static int radix_pass_u64_w(const uint64_t* key, const uint32_t* idx, int64_t n,
                            int shift, int bits, uint64_t* key_out,
                            uint32_t* idx_out, int64_t* hist) {
  const uint64_t mask = ((uint64_t)1 << bits) - 1;
  const int64_t buckets = (int64_t)1 << bits;
  std::fill(hist, hist + buckets, 0);
  for (int64_t i = 0; i < n; ++i) hist[(key[i] >> shift) & mask]++;
  int64_t nonzero = 0;
  for (int64_t b = 0; b < buckets; ++b) nonzero += hist[b] != 0;
  if (nonzero <= 1) return 0;  // all keys share this digit: skip
  int64_t acc = 0;
  for (int64_t b = 0; b < buckets; ++b) {
    const int64_t c = hist[b];
    hist[b] = acc;
    acc += c;
  }
  for (int64_t i = 0; i < n; ++i) {
    int64_t& o = hist[(key[i] >> shift) & mask];
    key_out[o] = key[i];
    idx_out[o] = idx[i];
    ++o;
  }
  return 1;
}

// argsort by (bins asc, zs asc), stable; out_perm must hold n uint32.
// 16-bit digits (4 z passes + 1 bin pass vs 8+4 at 8 bits) for large n,
// 8-bit digits below 1M rows where the 512 KB histogram dominates.
extern "C" void sort_bins_z(const int32_t* bins, const uint64_t* zs, int64_t n,
                 uint32_t* out_perm) {
  Stamp stamp_;
  const int bits = n >= (1 << 20) ? 16 : 8;
  std::vector<int64_t> hist((size_t)1 << bits);
  std::vector<uint64_t> ka(n), kb(n);
  std::vector<uint32_t> ia(n), ib(n);
  for (int64_t i = 0; i < n; ++i) { ka[i] = zs[i]; ia[i] = (uint32_t)i; }
  uint64_t* k0 = ka.data(); uint64_t* k1 = kb.data();
  uint32_t* i0 = ia.data(); uint32_t* i1 = ib.data();
  for (int shift = 0; shift < 64; shift += bits) {
    if (radix_pass_u64_w(k0, i0, n, shift, bits, k1, i1, hist.data())) {
      std::swap(k0, k1);
      std::swap(i0, i1);
    }
  }
  // bin passes: rebuild key as bin (u16 range) of the current order
  for (int64_t i = 0; i < n; ++i) k0[i] = (uint64_t)(uint32_t)bins[i0[i]];
  for (int shift = 0; shift < 32; shift += bits) {
    if (radix_pass_u64_w(k0, i0, n, shift, bits, k1, i1, hist.data())) {
      std::swap(k0, k1);
      std::swap(i0, i1);
    }
  }
  std::memcpy(out_perm, i0, n * sizeof(uint32_t));
}

// permutation gathers for building sorted device/host columns
extern "C" void gather_f32(const float* src, const uint32_t* idx, int64_t n, float* out) {
  Stamp stamp_;
  for (int64_t i = 0; i < n; ++i) out[i] = src[idx[i]];
}
extern "C" void gather_i32(const int32_t* src, const uint32_t* idx, int64_t n, int32_t* out) {
  Stamp stamp_;
  for (int64_t i = 0; i < n; ++i) out[i] = src[idx[i]];
}
extern "C" void gather_i64(const int64_t* src, const uint32_t* idx, int64_t n, int64_t* out) {
  Stamp stamp_;
  for (int64_t i = 0; i < n; ++i) out[i] = src[idx[i]];
}
extern "C" void gather_u64(const uint64_t* src, const uint32_t* idx, int64_t n, uint64_t* out) {
  Stamp stamp_;
  for (int64_t i = 0; i < n; ++i) out[i] = src[idx[i]];
}
extern "C" void gather_f64(const double* src, const uint32_t* idx, int64_t n, double* out) {
  Stamp stamp_;
  for (int64_t i = 0; i < n; ++i) out[i] = src[idx[i]];
}

// row gathers for [n, width] arrays (packed-geometry coords/bboxes):
// out[i, :] = src[idx[i], :]. The random-row reads are memory-latency
// bound; threads hide the misses.
extern "C" void gather_rows_f64(const double* src, const uint32_t* idx,
                                int64_t n, int64_t width, double* out) {
  Stamp stamp_;
#pragma omp parallel for schedule(static) if (n > 65536)
  for (int64_t i = 0; i < n; ++i) {
    const double* s = src + (int64_t)idx[i] * width;
    double* o = out + i * width;
    for (int64_t w = 0; w < width; ++w) o[w] = s[w];
  }
}
extern "C" void gather_rows_f32(const float* src, const uint32_t* idx,
                                int64_t n, int64_t width, float* out) {
  Stamp stamp_;
#pragma omp parallel for schedule(static) if (n > 65536)
  for (int64_t i = 0; i < n; ++i) {
    const float* s = src + (int64_t)idx[i] * width;
    float* o = out + i * width;
    for (int64_t w = 0; w < width; ++w) o[w] = s[w];
  }
}

// ------------------------------------------------- one gather an answer
// FeatureCollection.take for large answers: out[c][i] = src[c][idx[i]] for
// every fixed-width column c of a collection in ONE call (one ctypes
// crossing, one interpreter-lock release), items copied as bytes so a
// `<U24` column is a 96-byte item like any other. Work is cut into
// (column, run of GATHER_RUN rows) tasks: a run's ordinals stay in L1
// across its columns, and columns x runs give a team enough tasks whether
// the answer is 27 columns wide or 2. A team gets one thread a
// GATHER_TEAM_BYTES of output, GATHER_MAX_THREADS at most, and under two
// of them the caller copies alone (a served store has sixteen handler
// threads, and libgomp keeps a pool a calling thread). The reads miss the
// caches row by row, so threads add misses in flight, not arithmetic.
// The constants are from a sweep on the chip's host (PERF.md section 5).
// Ordinals are unchecked here: the caller has bounded them.
static const int64_t GATHER_RUN = 256;
static const int64_t GATHER_TEAM_BYTES = 65536;
static const int64_t GATHER_MAX_THREADS = 12;
static const int64_t GATHER_AHEAD = 8;

template <typename I, typename W>
static inline void gather_words(const char* src, char* out, const I* idx,
                                int64_t lo, int64_t hi) {
  for (int64_t i = lo; i < hi; ++i) {
    W v;  // memcpy of a constant size is one load and one store, aligned or not
    std::memcpy(&v, src + (int64_t)idx[i] * (int64_t)sizeof(W), sizeof(W));
    std::memcpy(out + i * (int64_t)sizeof(W), &v, sizeof(W));
  }
}

template <typename I>
static void gather_run(const char* src, int64_t w, char* out, const I* idx,
                       int64_t lo, int64_t hi) {
  if (w == 4) return gather_words<I, uint32_t>(src, out, idx, lo, hi);
  if (w == 8) return gather_words<I, uint64_t>(src, out, idx, lo, hi);
  for (int64_t i = lo; i < hi; ++i) {
    if (i + GATHER_AHEAD < hi)
      __builtin_prefetch(src + (int64_t)idx[i + GATHER_AHEAD] * w);
    std::memcpy(out + i * w, src + (int64_t)idx[i] * w, (size_t)w);
  }
}

template <typename I>
static void gather_columns_t(const char* const* srcs, const int64_t* widths,
                             char* const* outs, int64_t ncols, const I* idx,
                             int64_t n) {
  const int64_t runs = (n + GATHER_RUN - 1) / GATHER_RUN;
  const int64_t tasks = runs * ncols;
#ifdef _OPENMP
  int64_t row_bytes = 0;
  for (int64_t c = 0; c < ncols; ++c) row_bytes += widths[c];
  const int threads = (int)std::min<int64_t>(
      std::min<int64_t>(omp_get_max_threads(), GATHER_MAX_THREADS),
      std::max<int64_t>(n * row_bytes / GATHER_TEAM_BYTES, 1));
#endif
#pragma omp parallel for schedule(dynamic, 1) num_threads(threads) if (threads > 1)
  for (int64_t t = 0; t < tasks; ++t) {
    const int64_t c = t % ncols, lo = (t / ncols) * GATHER_RUN;
    gather_run<I>(srcs[c], widths[c], outs[c], idx, lo,
                  std::min(lo + GATHER_RUN, n));
  }
}

// idx_width: 4 or 8 bytes an ordinal; the ordinals are non-negative (the
// caller's bounds check), so signed and unsigned read alike.
extern "C" void gather_columns(const char* const* srcs, const int64_t* widths,
                               char* const* outs, int64_t ncols,
                               const void* idx, int32_t idx_width, int64_t n) {
  Stamp stamp_;
  if (idx_width == 4)
    gather_columns_t<uint32_t>(srcs, widths, outs, ncols, (const uint32_t*)idx, n);
  else
    gather_columns_t<uint64_t>(srcs, widths, outs, ncols, (const uint64_t*)idx, n);
}

// ------------------------------------------- GeoJSON text of an answer
// io/exporters.py's GeoJSON serializer for collections whose columns can
// be read without the interpreter: the features [lo, hi) of a collection
// as the bytes json.dumps gives for the dicts geojson_features() builds,
// ", "-joined, in ONE call with the interpreter lock released (a served
// store has sixteen handler threads; the per-feature route holds the lock
// for 50-60 us a row of 27 attributes). Columns come as rows of four
// int64: kind, address, stride in bytes, bytes an item. Row 0 is the ids
// (GJ_STR or GJ_INT), rows 1 and 2 the point column's x and y (GJ_F64, or
// GJ_NONE for "geometry": null), the rest the properties in member order,
// property c's `"name": ` being keys[key_off[c] : key_off[c + 1]] as
// json.dumps wrote it. The text is json.dumps' own: ensure_ascii escapes
// (surrogate pairs past U+FFFF), a `<U` cell cut at its trailing NULs,
// doubles as float.__repr__ writes them, a Date as
// numpy.datetime64(v, "ms") with a Z. A value whose text this code does
// not decide (NaN, an infinity, a year outside 0001-9999, a code point
// past U+10FFFF) ends the call with -1 and the caller takes the
// per-feature route. The bytes land in a buffer of the calling thread's
// that lives until its next call: the caller copies them out.

enum { GJ_NONE = -1, GJ_STR = 0, GJ_BOOL = 1, GJ_INT = 2, GJ_UINT = 3,
       GJ_F32 = 4, GJ_F64 = 5, GJ_DATE = 6 };

// a buffer grown past this is given back to the allocator at the thread's
// next call (a page of 4,096 rows of the widest type is under it)
static const size_t GJ_KEEP_BYTES = 8 << 20;

struct GjBuf {  // realloc, not a vector: growing neither zero-fills nor copies twice
  char* p = nullptr;
  size_t cap = 0, n = 0;
  ~GjBuf() { std::free(p); }
  inline char* need(size_t k) {
    if (n + k > cap) {
      cap = std::max(std::max(cap * 2, n + k), (size_t)1 << 16);
      p = (char*)std::realloc(p, cap);
    }
    return p + n;
  }
  inline void put(const char* s, size_t k) {
    std::memcpy(need(k), s, k);
    n += k;
  }
  template <size_t K>
  inline void lit(const char (&s)[K]) { put(s, K - 1); }
};

static inline void gj_hex4(char* p, uint32_t c) {
  static const char* H = "0123456789abcdef";
  p[0] = '\\'; p[1] = 'u';
  p[2] = H[(c >> 12) & 15]; p[3] = H[(c >> 8) & 15];
  p[4] = H[(c >> 4) & 15]; p[5] = H[c & 15];
}

// json's py_encode_basestring_ascii over UCS4 code points
static bool gj_string(GjBuf& b, const char* cell, int64_t width) {
  int64_t len = width / 4;
  uint32_t c;
  while (len > 0) {  // numpy cuts a `<U` item at its trailing NULs
    std::memcpy(&c, cell + (len - 1) * 4, 4);
    if (c) break;
    --len;
  }
  char* p = b.need((size_t)len * 12 + 2);
  char* const p0 = p;
  *p++ = '"';
  for (int64_t i = 0; i < len; ++i) {
    std::memcpy(&c, cell + i * 4, 4);
    if (c >= ' ' && c <= '~' && c != '\\' && c != '"') { *p++ = (char)c; continue; }
    char e = 0;
    switch (c) {
      case '\\': e = '\\'; break;
      case '"': e = '"'; break;
      case '\b': e = 'b'; break;
      case '\f': e = 'f'; break;
      case '\n': e = 'n'; break;
      case '\r': e = 'r'; break;
      case '\t': e = 't'; break;
    }
    if (e) { *p++ = '\\'; *p++ = e; continue; }
    if (c > 0x10FFFF) return false;
    if (c >= 0x10000) {
      const uint32_t v = c - 0x10000;
      gj_hex4(p, 0xd800 | ((v >> 10) & 0x3ff));
      p += 6;
      c = 0xdc00 | (v & 0x3ff);
    }
    gj_hex4(p, c);
    p += 6;
  }
  *p++ = '"';
  b.n += (size_t)(p - p0);
  return true;
}

static inline void gj_uint(GjBuf& b, uint64_t v, bool neg) {
  char t[24];
  char* e = t + sizeof(t);
  char* p = e;
  do { *--p = (char)('0' + v % 10); v /= 10; } while (v);
  if (neg) *--p = '-';
  b.put(p, (size_t)(e - p));
}

static inline void gj_int(GjBuf& b, int64_t v) {
  gj_uint(b, v < 0 ? (uint64_t)0 - (uint64_t)v : (uint64_t)v, v < 0);
}

// float.__repr__: the shortest digits that read back as v (to_chars'
// and Python's dtoa mode 0 agree on them), laid out as format_float_short
// lays out 'r': fixed while -4 < decimal point <= 16, else d[.ddd]e+XX
static bool gj_double(GjBuf& b, double v) {
  if (!std::isfinite(v)) return false;
  char t[40];
  const auto r = std::to_chars(t, t + sizeof(t), v, std::chars_format::scientific);
  const char* s = t;
  const char* const end = r.ptr;
  char* p = b.need(48);
  char* const p0 = p;
  if (*s == '-') *p++ = *s++;
  const char* e = s;
  while (*e != 'e') ++e;
  int x = 0;
  for (const char* q = e + 2; q < end; ++q) x = x * 10 + (*q - '0');
  const int decpt = (e[1] == '-' ? -x : x) + 1;
  if (decpt <= -4 || decpt > 16) {  // to_chars wrote Python's exponent form
    std::memcpy(p, s, (size_t)(end - s));
    p += end - s;
  } else {
    char d[20];
    int nd = 0;
    for (const char* q = s; q < e; ++q)
      if (*q != '.') d[nd++] = *q;
    if (decpt <= 0) {
      *p++ = '0'; *p++ = '.';
      for (int i = decpt; i < 0; ++i) *p++ = '0';
      std::memcpy(p, d, (size_t)nd);
      p += nd;
    } else if (decpt >= nd) {
      std::memcpy(p, d, (size_t)nd);
      p += nd;
      for (int i = nd; i < decpt; ++i) *p++ = '0';
      *p++ = '.'; *p++ = '0';
    } else {
      std::memcpy(p, d, (size_t)decpt);
      p += decpt;
      *p++ = '.';
      std::memcpy(p, d + decpt, (size_t)(nd - decpt));
      p += nd - decpt;
    }
  }
  b.n += (size_t)(p - p0);
  return true;
}

static inline void gj_pad(char* p, int v, int digits) {
  for (int i = digits - 1; i >= 0; --i) { p[i] = (char)('0' + v % 10); v /= 10; }
}

// "YYYY-MM-DDTHH:MM:SS.mmmZ" of epoch milliseconds (proleptic Gregorian,
// days from the civil epoch as in H. Hinnant's civil_from_days)
static bool gj_date(GjBuf& b, int64_t ms) {
  int64_t days = ms / 86400000, rem = ms % 86400000;
  if (rem < 0) { rem += 86400000; --days; }
  if (days < -719162 || days > 2932896) return false;  // 0001-01-01 .. 9999-12-31
  const int64_t z = days + 719468;
  const int64_t era = z / 146097;  // z > 0 in the range kept
  const int64_t doe = z - era * 146097;
  const int64_t yoe = (doe - doe / 1460 + doe / 36524 - doe / 146096) / 365;
  const int64_t doy = doe - (365 * yoe + yoe / 4 - yoe / 100);
  const int64_t mp = (5 * doy + 2) / 153;
  const int day = (int)(doy - (153 * mp + 2) / 5 + 1);
  const int month = (int)(mp < 10 ? mp + 3 : mp - 9);
  const int year = (int)(yoe + era * 400 + (month <= 2));
  char* p = b.need(26);
  p[0] = '"';
  gj_pad(p + 1, year, 4); p[5] = '-';
  gj_pad(p + 6, month, 2); p[8] = '-';
  gj_pad(p + 9, day, 2); p[11] = 'T';
  gj_pad(p + 12, (int)(rem / 3600000), 2); p[14] = ':';
  gj_pad(p + 15, (int)(rem / 60000 % 60), 2); p[17] = ':';
  gj_pad(p + 18, (int)(rem / 1000 % 60), 2); p[20] = '.';
  gj_pad(p + 21, (int)(rem % 1000), 3);
  p[24] = 'Z'; p[25] = '"';
  b.n += 26;
  return true;
}

template <typename T>
static inline T gj_load(const char* p) {
  T v;
  std::memcpy(&v, p, sizeof(T));
  return v;
}

static bool gj_value(GjBuf& b, int64_t kind, const char* p, int64_t w) {
  switch (kind) {
    case GJ_STR: return gj_string(b, p, w);
    case GJ_BOOL:
      if (*p) b.lit("true"); else b.lit("false");
      return true;
    case GJ_INT:
      gj_int(b, w == 8 ? gj_load<int64_t>(p) : w == 4 ? gj_load<int32_t>(p)
                : w == 2 ? gj_load<int16_t>(p) : gj_load<int8_t>(p));
      return true;
    case GJ_UINT:
      gj_uint(b, w == 8 ? gj_load<uint64_t>(p) : w == 4 ? gj_load<uint32_t>(p)
                 : w == 2 ? gj_load<uint16_t>(p) : gj_load<uint8_t>(p), false);
      return true;
    case GJ_F32: return gj_double(b, (double)gj_load<float>(p));
    case GJ_F64: return gj_double(b, gj_load<double>(p));
    case GJ_DATE: return gj_date(b, gj_load<int64_t>(p));
  }
  return false;
}

extern "C" int64_t geojson_features(const int64_t* cols, int64_t ncols,
                                    const char* keys, const int64_t* key_off,
                                    int64_t lo, int64_t hi, const char** out) {
  Stamp stamp_;
  static thread_local GjBuf b;
  if (b.cap > GJ_KEEP_BYTES) {
    std::free(b.p);
    b.p = nullptr;
    b.cap = 0;
  }
  b.n = 0;
  const int64_t* ids = cols;
  const int64_t* xs = cols + 4;
  const int64_t* ys = cols + 8;
  const int64_t nprops = ncols - 3;
  for (int64_t i = lo; i < hi; ++i) {
    if (i > lo) b.lit(", ");
    b.lit("{\"type\": \"Feature\", \"id\": ");
    const char* id = (const char*)ids[1] + i * ids[2];
    if (ids[0] == GJ_STR) {
      if (!gj_string(b, id, ids[3])) return -1;
    } else {  // str(int) as a JSON string
      b.lit("\"");
      gj_int(b, gj_load<int64_t>(id));
      b.lit("\"");
    }
    if (xs[0] == GJ_NONE) {
      b.lit(", \"geometry\": null, \"properties\": {");
    } else {
      b.lit(", \"geometry\": {\"type\": \"Point\", \"coordinates\": [");
      if (!gj_double(b, gj_load<double>((const char*)xs[1] + i * xs[2]))) return -1;
      b.lit(", ");
      if (!gj_double(b, gj_load<double>((const char*)ys[1] + i * ys[2]))) return -1;
      b.lit("]}, \"properties\": {");
    }
    for (int64_t c = 0; c < nprops; ++c) {
      const int64_t* col = cols + 4 * (c + 3);
      if (c) b.lit(", ");
      b.put(keys + key_off[c], (size_t)(key_off[c + 1] - key_off[c]));
      if (!gj_value(b, col[0], (const char*)col[1] + i * col[2], col[3])) return -1;
    }
    b.lit("}}");
  }
  *out = b.p;
  return (int64_t)b.n;
}

// --------------------------------------------- Arrow batch of an answer
// io/arrow.py's table build for collections whose columns can be read
// without the interpreter: the whole record batch, every buffer of every
// column, in ONE call with the interpreter lock released, handed to Arrow
// through its C data interface (arrow/c/abi.h: the ArrowArray below),
// which imports it in one more. pyarrow's own constructors give the lock
// away once an array or more, and pa.array takes it back a `<U` cell:
// about 130 hand-offs for the 27 attributes of GDELT's type, each a wait
// among a served store's handler threads. Columns come as
// geojson_features' rows of four int64 (ids, the point column's x and y,
// the attributes in schema order) beside one op a row, and `order` names
// the row of each of the batch's columns:
//   AR_STRING  a `<U` column as a string array: int32 offsets, UTF-8 data
//   AR_DICT    a `<U` column as dictionary_encode() gives it: int32 codes
//              and a string dictionary, values in order of first appearance
//   AR_BITS    a bool column as a bitmap, least significant bit first
//   AR_COPY    a fixed-width column (a Date's int64 is timestamp[ms])
//   AR_DATE    the same, where NaT (pyarrow's null) ends the call
//   AR_XY      x's row, y's being the next: FixedSizeList<2 x f64>
// A `<U` cell ends at its first NUL, where pyarrow's NumPy converter cuts
// it. -1 where the bytes are not this code's to decide (a surrogate, a
// code point past U+10FFFF, NaT, 2 GiB of text in one column): the
// caller's pyarrow route then builds the table. The buffers are one
// allocation that the batch's release callback frees, whichever thread
// drops the last reference: Arrow reads them where they lie.

struct ArrowArray {
  int64_t length, null_count, offset, n_buffers, n_children;
  const void** buffers;
  ArrowArray** children;
  ArrowArray* dictionary;
  void (*release)(ArrowArray*);
  void* private_data;
};

enum { AR_STRING = 1, AR_DICT = 2, AR_BITS = 3, AR_COPY = 4, AR_DATE = 5, AR_XY = 6 };

// an array before its buffers have their last address: where up to three
// lie in `bytes` (-1: none, as every validity bitmap), the node of its
// one child or of its dictionary
struct ArNode { int64_t length; int n_buffers; int64_t at[3]; int child, dictionary; };

struct ArBatch {
  GjBuf bytes;
  std::vector<ArNode> nodes;
  std::vector<ArrowArray> arrays;
  std::vector<const void*> buffers;
  std::vector<ArrowArray*> children;
  int add(int64_t length, int n_buffers, int64_t a = -1, int64_t b = -1) {
    nodes.push_back({length, n_buffers, {-1, a, b}, -1, -1});
    return (int)nodes.size() - 1;
  }
};

static inline int64_t ar_begin(GjBuf& b, size_t bytes) {  // buffers start in 64-byte steps
  const size_t at = (b.n + 63) & ~(size_t)63;
  b.need(at - b.n + bytes);
  std::memset(b.p + b.n, 0, at - b.n);
  b.n = at + bytes;
  return (int64_t)at;
}

static inline int64_t ar_cell_len(const char* cell, int64_t width) {
  int64_t len = 0;
  while (len < width / 4 && gj_load<uint32_t>(cell + len * 4)) ++len;
  return len;
}

static bool ar_utf8(GjBuf& b, const char* cell, int64_t len) {
  char* p = b.need((size_t)len * 4);
  char* const p0 = p;
  for (int64_t i = 0; i < len; ++i) {
    const uint32_t c = gj_load<uint32_t>(cell + i * 4);
    if (c < 0x80) {
      *p++ = (char)c;
    } else if (c < 0x800) {
      *p++ = (char)(0xc0 | (c >> 6));
      *p++ = (char)(0x80 | (c & 0x3f));
    } else if (c < 0x10000) {
      if (c >= 0xd800 && c < 0xe000) return false;
      *p++ = (char)(0xe0 | (c >> 12));
      *p++ = (char)(0x80 | ((c >> 6) & 0x3f));
      *p++ = (char)(0x80 | (c & 0x3f));
    } else {
      if (c > 0x10ffff) return false;
      *p++ = (char)(0xf0 | (c >> 18));
      *p++ = (char)(0x80 | ((c >> 12) & 0x3f));
      *p++ = (char)(0x80 | ((c >> 6) & 0x3f));
      *p++ = (char)(0x80 | (c & 0x3f));
    }
  }
  b.n += (size_t)(p - p0);
  return true;
}

static inline void ar_put32(GjBuf& b, int64_t at, int64_t i, int32_t v) {
  std::memcpy(b.p + at + i * 4, &v, 4);
}

static int ar_string(ArBatch& t, const int64_t* col, int64_t rows) {
  GjBuf& b = t.bytes;
  const int64_t offsets = ar_begin(b, (size_t)(rows + 1) * 4);
  const int64_t data = ar_begin(b, 0);
  for (int64_t i = 0; i < rows; ++i) {
    const char* cell = (const char*)col[1] + i * col[2];
    ar_put32(b, offsets, i, (int32_t)(b.n - data));
    if (!ar_utf8(b, cell, ar_cell_len(cell, col[3])) || b.n - data > (size_t)INT32_MAX)
      return -1;
  }
  ar_put32(b, offsets, rows, (int32_t)(b.n - data));
  return t.add(rows, 3, offsets, data);
}

struct ArSeen { const char* cell; int64_t len; uint64_t hash; };

static inline uint64_t ar_hash(const char* cell, int64_t len) {
  uint64_t h = 0x9e3779b97f4a7c15ull ^ (uint64_t)len;
  for (int64_t i = 0; i < len; ++i) {
    h = (h ^ gj_load<uint32_t>(cell + i * 4)) * 0xff51afd7ed558ccdull;
    h ^= h >> 32;
  }
  return h;
}

static int ar_dictionary(ArBatch& t, const int64_t* col, int64_t rows) {
  GjBuf& b = t.bytes;
  const int64_t codes = ar_begin(b, (size_t)rows * 4);
  const int64_t data = ar_begin(b, 0);
  std::vector<ArSeen> seen;
  std::vector<int32_t> ends(1, 0), slots(64, -1);  // open addressing, at most half full
  for (int64_t i = 0; i < rows; ++i) {
    const char* cell = (const char*)col[1] + i * col[2];
    const int64_t len = ar_cell_len(cell, col[3]);
    const uint64_t h = ar_hash(cell, len);
    size_t s = (size_t)h & (slots.size() - 1);
    int32_t code;
    while ((code = slots[s]) >= 0) {
      const ArSeen& e = seen[(size_t)code];
      if (e.hash == h && e.len == len && !std::memcmp(e.cell, cell, (size_t)len * 4)) break;
      s = (s + 1) & (slots.size() - 1);
    }
    if (code < 0) {
      if (seen.size() >= (size_t)INT32_MAX) return -1;
      code = slots[s] = (int32_t)seen.size();
      seen.push_back({cell, len, h});
      if (!ar_utf8(b, cell, len) || b.n - data > (size_t)INT32_MAX) return -1;
      ends.push_back((int32_t)(b.n - data));
      if (seen.size() * 2 > slots.size()) {
        slots.assign(slots.size() * 2, -1);
        for (size_t k = 0; k < seen.size(); ++k) {
          size_t at = (size_t)seen[k].hash & (slots.size() - 1);
          while (slots[at] >= 0) at = (at + 1) & (slots.size() - 1);
          slots[at] = (int32_t)k;
        }
      }
    }
    ar_put32(b, codes, i, code);
  }
  const int64_t offsets = ar_begin(b, ends.size() * 4);
  std::memcpy(b.p + offsets, ends.data(), ends.size() * 4);
  const int node = t.add(rows, 2, codes);
  const int values = t.add((int64_t)seen.size(), 3, offsets, data);
  t.nodes[(size_t)node].dictionary = values;
  return node;
}

static int ar_column(ArBatch& t, const int64_t* col, int64_t op, int64_t rows) {
  GjBuf& b = t.bytes;
  const char* src = (const char*)col[1];
  const int64_t stride = col[2], w = col[3];
  switch (op) {
    case AR_STRING: return ar_string(t, col, rows);
    case AR_DICT: return ar_dictionary(t, col, rows);
    case AR_BITS: {
      const size_t bytes = (size_t)((rows + 7) / 8);
      const int64_t at = ar_begin(b, bytes);
      std::memset(b.p + at, 0, bytes);
      for (int64_t i = 0; i < rows; ++i)
        if (src[i * stride]) b.p[at + (i >> 3)] |= (char)(1 << (i & 7));
      return t.add(rows, 2, at);
    }
    case AR_COPY:
    case AR_DATE: {
      const int64_t at = ar_begin(b, (size_t)(rows * w));
      if (stride == w) std::memcpy(b.p + at, src, (size_t)(rows * w));
      else for (int64_t i = 0; i < rows; ++i) std::memcpy(b.p + at + i * w, src + i * stride, (size_t)w);
      if (op == AR_DATE)
        for (int64_t i = 0; i < rows; ++i)
          if (gj_load<int64_t>(b.p + at + i * 8) == INT64_MIN) return -1;
      return t.add(rows, 2, at);
    }
    case AR_XY: {
      const int64_t* ys = col + 4;
      const int64_t at = ar_begin(b, (size_t)rows * 16);
      for (int64_t i = 0; i < rows; ++i) {
        std::memcpy(b.p + at + i * 16, src + i * stride, 8);
        std::memcpy(b.p + at + i * 16 + 8, (const char*)ys[1] + i * ys[2], 8);
      }
      const int node = t.add(rows, 1);
      const int values = t.add(rows * 2, 2, at);
      t.nodes[(size_t)node].child = values;
      return node;
    }
  }
  return -1;
}

static void ar_release_part(ArrowArray* a) { a->release = nullptr; }

static void ar_release(ArrowArray* a) {
  delete (ArBatch*)a->private_data;
  a->release = nullptr;
}

extern "C" int64_t arrow_batch(const int64_t* cols, const int64_t* ops,
                               const int64_t* order, int64_t nout, int64_t rows,
                               ArrowArray* out) {
  Stamp stamp_;
  ArBatch* t = new ArBatch;
  t->bytes.need(64);
  std::vector<int> columns;
  for (int64_t k = 0; k < nout; ++k) {
    columns.push_back(ar_column(*t, cols + 4 * order[k], ops[order[k]], rows));
    if (columns.back() < 0) {
      delete t;
      return -1;
    }
  }
  GjBuf& b = t->bytes;
  b.p = (char*)std::realloc(b.p, b.cap = std::max(b.n, (size_t)64));  // the doubling's slack
  const size_t n = t->nodes.size();
  t->arrays.resize(n);
  t->buffers.assign(3 * n + 1, nullptr);  // the last: the batch's own, no validity
  t->children.assign((size_t)nout + n, nullptr);  // the batch's, then slot i for node i's child
  for (size_t i = 0; i < n; ++i) {
    const ArNode& nd = t->nodes[i];
    for (int k = 0; k < 3; ++k)
      if (nd.at[k] >= 0) t->buffers[3 * i + k] = b.p + nd.at[k];
    ArrowArray** child = &t->children[(size_t)nout + i];
    if (nd.child >= 0) *child = &t->arrays[(size_t)nd.child];
    t->arrays[i] = {nd.length, 0, 0, nd.n_buffers, nd.child >= 0 ? 1 : 0,
                    &t->buffers[3 * i], child,
                    nd.dictionary >= 0 ? &t->arrays[(size_t)nd.dictionary] : nullptr,
                    ar_release_part, nullptr};
  }
  for (int64_t k = 0; k < nout; ++k)
    t->children[(size_t)k] = &t->arrays[(size_t)columns[(size_t)k]];
  *out = {rows, 0, 0, 1, nout, &t->buffers[3 * n], t->children.data(), nullptr,
          ar_release, t};
  return 0;
}

// an ArrowArray that nothing imported: what arrow_batch made is given back
extern "C" void arrow_batch_release(ArrowArray* a) {
  Stamp stamp_;
  if (a->release) a->release(a);
}

// ----------------------------------------------- point-in-polygon refine
// Host refinement hot loop for polygon queries over point stores: the
// numpy even-odd ray cast materializes an [n_points, n_edges] matrix
// (800 MB at 1M x 100); this streams edges per point in registers with
// the SAME crossing construction (spans half-open in y, intersection x
// strictly right of the point), threaded over points.
// rings are verts[ring_offsets[r] : ring_offsets[r+1]] (closed);
// ring_part[r] groups rings into polygon parts: within a part parity
// XORs (holes subtract), across parts the results OR (multi-polygon).
extern "C" void points_in_polygon_cpp(
    const double* px, const double* py, int64_t n,
    const double* verts /* [total_verts, 2] */,
    const int64_t* ring_offsets, int64_t n_rings,
    const int32_t* ring_part, uint8_t* out) {
  Stamp stamp_;
#pragma omp parallel for schedule(static) if (n > 16384)
  for (int64_t i = 0; i < n; ++i) {
    const double x = px[i], y = py[i];
    bool any = false;
    bool parity = false;
    int32_t cur_part = n_rings ? ring_part[0] : 0;
    for (int64_t r = 0; r < n_rings; ++r) {
      if (ring_part[r] != cur_part) {
        any |= parity;
        parity = false;
        cur_part = ring_part[r];
      }
      const int64_t a = ring_offsets[r], b = ring_offsets[r + 1];
      int64_t crossings = 0;
      for (int64_t e = a; e + 1 < b; ++e) {
        const double y1 = verts[2 * e + 1], y2 = verts[2 * e + 3];
        if ((y1 <= y) != (y2 <= y)) {
          const double x1 = verts[2 * e], x2 = verts[2 * e + 2];
          const double t = (y - y1) / (y2 - y1);
          if (x1 + t * (x2 - x1) > x) ++crossings;
        }
      }
      if (crossings & 1) parity = !parity;
    }
    out[i] = (any | parity) ? 1 : 0;
  }
}

// -------------------------------------------------------- z-range BFS
// Query planning hot path: covering z-ranges for a union of ordinal boxes
// (reference ZN.zranges quad/oct BFS + Tropf/Herzog zdiv tightening,
// geomesa-z3/.../sfcurve/ZN.scala:110-242, :309-361). The Python
// implementation (curve/zranges.py) costs 100-300 ms per query; this is
// the same algorithm in C++ at <1 ms. Containment is classified against a
// separate *inner* ordinal box so that contained-range rows are certain
// hits at f64 precision (ScanConfig.contained -> no refinement).

struct ZCurveOps {
  int dims;
  int bits_per_dim;
  uint64_t (*split)(uint64_t);
  uint64_t (*combine)(uint64_t);
};

static uint64_t z2_index_(const uint64_t* p) { return split2(p[0]) | (split2(p[1]) << 1); }
static uint64_t z3_index_(const uint64_t* p) {
  return split3(p[0]) | (split3(p[1]) << 1) | (split3(p[2]) << 2);
}

static void z_decode(const ZCurveOps& ops, uint64_t z, uint64_t* out) {
  for (int d = 0; d < ops.dims; ++d) out[d] = ops.combine(z >> d);
}

static uint64_t z_index(const ZCurveOps& ops, const uint64_t* p) {
  return ops.dims == 2 ? z2_index_(p) : z3_index_(p);
}

// 2 = cell fully inside some inner box, 1 = overlaps some outer box, 0 = no
static int classify(const uint64_t* lo, const uint64_t* hi, int dims, int64_t nbox,
                    const uint64_t* mins, const uint64_t* maxes,
                    const uint64_t* imins, const uint64_t* imaxes) {
  for (int64_t b = 0; b < nbox; ++b) {
    bool contained = true;
    for (int d = 0; d < dims; ++d)
      if (lo[d] < imins[b * dims + d] || hi[d] > imaxes[b * dims + d]) {
        contained = false;
        break;
      }
    if (contained) return 2;
  }
  for (int64_t b = 0; b < nbox; ++b) {
    bool overlap = true;
    for (int d = 0; d < dims; ++d)
      if (lo[d] > maxes[b * dims + d] || hi[d] < mins[b * dims + d]) {
        overlap = false;
        break;
      }
    if (overlap) return 1;
  }
  return 0;
}

struct ZRange { uint64_t lo, hi; uint8_t contained; };

// The bit patterns zdiv needs at z-bit i (dim i % dims, 1-based dim-local
// bit bl = i / dims + 1): all of that dim's bits up to bl, bit bl alone,
// and the bits below bl. They depend on the curve alone, so a
// decomposition builds them once: a zdiv call walks up to 63 bits, and it
// runs about once a range, so interleaving them there would cost more than
// the BFS itself.
struct ZDivMasks { uint64_t upto[64], top[64], below[64]; };

static void zdiv_masks(const ZCurveOps& ops, ZDivMasks* m) {
  int total = ops.dims * ops.bits_per_dim;
  for (int i = 0; i < total; ++i) {
    int dim = i % ops.dims;
    int bl = i / ops.dims + 1;
    m->upto[i] = ops.split((1ull << bl) - 1) << dim;
    m->top[i] = ops.split(1ull << (bl - 1)) << dim;
    m->below[i] = ops.split((1ull << (bl - 1)) - 1) << dim;
  }
}

// Tropf/Herzog LITMAX/BIGMIN: mirrors curve/zorder.py zdiv.
static void zdiv_cpp(const ZCurveOps& ops, const ZDivMasks& m, uint64_t zmin,
                     uint64_t zmax, uint64_t zval, uint64_t* litmax_out,
                     uint64_t* bigmin_out) {
  int total = ops.dims * ops.bits_per_dim;
  uint64_t litmax = zmin, bigmin = zmax;
  uint64_t zmin_ = zmin, zmax_ = zmax;
  for (int i = total - 1; i >= 0; --i) {
    uint64_t bit = 1ull << i;
    int v = (zval & bit) ? 1 : 0;
    int mn = (zmin_ & bit) ? 1 : 0;
    int mx = (zmax_ & bit) ? 1 : 0;
    if (v == 0 && mn == 0 && mx == 1) {
      bigmin = (zmin_ & ~m.upto[i]) | m.top[i];
      zmax_ = (zmax_ & ~m.upto[i]) | m.below[i];
    } else if (v == 0 && mn == 1 && mx == 1) {
      bigmin = zmin_;
      break;
    } else if (v == 1 && mn == 0 && mx == 0) {
      litmax = zmax_;
      break;
    } else if (v == 1 && mn == 0 && mx == 1) {
      litmax = (zmax_ & ~m.upto[i]) | m.below[i];
      zmin_ = (zmin_ & ~m.upto[i]) | m.top[i];
    }
  }
  *litmax_out = litmax;
  *bigmin_out = bigmin;
}

static bool in_some_box(const ZCurveOps& ops, uint64_t z, int64_t nbox,
                        const uint64_t* mins, const uint64_t* maxes) {
  uint64_t pt[3];
  z_decode(ops, z, pt);
  for (int64_t b = 0; b < nbox; ++b) {
    bool in = true;
    for (int d = 0; d < ops.dims; ++d)
      if (pt[d] < mins[b * ops.dims + d] || pt[d] > maxes[b * ops.dims + d]) {
        in = false;
        break;
      }
    if (in) return true;
  }
  return false;
}

// Covering ranges for a union of ordinal boxes. Returns the number of
// ranges written (<= cap), or -1 if cap was too small.
extern "C" int64_t zranges_cpp(int32_t dims, int32_t bits_per_dim, int64_t nbox,
                    const uint64_t* mins, const uint64_t* maxes,
                    const uint64_t* imins, const uint64_t* imaxes,
                    int64_t max_ranges, int64_t max_recurse,
                    uint64_t* out_lo, uint64_t* out_hi, uint8_t* out_cont,
                    int64_t cap) {
  Stamp stamp_;
  ZCurveOps ops = dims == 2 ? ZCurveOps{2, bits_per_dim, split2, combine2}
                            : ZCurveOps{3, bits_per_dim, split3, combine3};
  int total = dims * bits_per_dim;
  int children = 1 << dims;

  // corner z's + longest common prefix aligned to dims bits
  std::vector<uint64_t> zmins(nbox), zmaxes(nbox);
  for (int64_t b = 0; b < nbox; ++b) {
    zmins[b] = z_index(ops, mins + b * dims);
    zmaxes[b] = z_index(ops, maxes + b * dims);
  }
  int offset = total;
  while (offset > 0) {
    int nxt = offset - dims;
    uint64_t bits = zmins[0] >> nxt;
    bool same = true;
    for (int64_t b = 0; b < nbox && same; ++b)
      same = (zmins[b] >> nxt) == bits && (zmaxes[b] >> nxt) == bits;
    if (same) offset = nxt; else break;
  }
  uint64_t prefix = (zmins[0] >> offset) << offset;

  std::vector<ZRange> ranges;
  std::vector<std::pair<uint64_t, int>> level{{prefix, offset}}, nxt_level;
  uint64_t lo_pt[3], hi_pt[3];
  int recursions = 0;
  while (!level.empty() && recursions < max_recurse &&
         (int64_t)(ranges.size() + level.size() * children) < max_ranges * 2) {
    nxt_level.clear();
    for (auto& cell : level) {
      uint64_t zp = cell.first;
      int free_bits = cell.second;
      if (free_bits == 0) {
        z_decode(ops, zp, lo_pt);
        int c = classify(lo_pt, lo_pt, dims, nbox, mins, maxes, imins, imaxes);
        if (c) ranges.push_back({zp, zp, (uint8_t)(c == 2)});
        continue;
      }
      int child_bits = free_bits - dims;
      for (int q = 0; q < children; ++q) {
        uint64_t cp = zp | ((uint64_t)q << child_bits);
        uint64_t cmax = cp | ((child_bits ? (1ull << child_bits) : 0) - (child_bits ? 1ull : 0));
        z_decode(ops, cp, lo_pt);
        z_decode(ops, cmax, hi_pt);
        int c = classify(lo_pt, hi_pt, dims, nbox, mins, maxes, imins, imaxes);
        if (c == 2) {
          ranges.push_back({cp, cmax, 1});
        } else if (c == 1) {
          if (child_bits == 0) ranges.push_back({cp, cp, 0});
          else nxt_level.push_back({cp, child_bits});
        }
      }
    }
    level.swap(nxt_level);
    ++recursions;
  }
  for (auto& cell : level)
    ranges.push_back({cell.first, cell.first | ((1ull << cell.second) - 1), 0});

  // sort + merge adjacent/overlapping
  std::sort(ranges.begin(), ranges.end(), [](const ZRange& a, const ZRange& b) {
    return a.lo != b.lo ? a.lo < b.lo : a.hi < b.hi;
  });
  // merge only same-kind neighbors (BFS cells are disjoint, so ranges can
  // only be adjacent): a contained range glued to an overlapping one keeps
  // its no-refinement guarantee instead of degrading the pair
  std::vector<ZRange> merged;
  for (auto& r : ranges) {
    if (!merged.empty() && merged.back().hi != ~0ull &&
        r.lo <= merged.back().hi + 1 && r.contained == merged.back().contained) {
      if (r.hi > merged.back().hi) merged.back().hi = r.hi;
    } else {
      merged.push_back(r);
    }
  }
  // reduce below max_ranges by closing the smallest gaps first
  while ((int64_t)merged.size() > max_ranges) {
    // single pass: close all gaps below a threshold found by nth_element
    int64_t k = merged.size() - max_ranges;
    std::vector<uint64_t> gaps(merged.size() - 1);
    for (size_t i = 0; i + 1 < merged.size(); ++i)
      gaps[i] = merged[i + 1].lo - merged[i].hi;
    std::vector<uint64_t> g2(gaps);
    std::nth_element(g2.begin(), g2.begin() + (k - 1), g2.end());
    uint64_t cutoff = g2[k - 1];
    std::vector<ZRange> out;
    out.push_back(merged[0]);
    int64_t closed = 0;
    for (size_t i = 1; i < merged.size(); ++i) {
      if (closed < k && gaps[i - 1] <= cutoff) {
        out.back().hi = merged[i].hi > out.back().hi ? merged[i].hi : out.back().hi;
        out.back().contained = 0;
        ++closed;
      } else {
        out.push_back(merged[i]);
      }
    }
    merged.swap(out);
  }

  // tighten endpoints to in-union z-values (zdiv post-pass; mirrors
  // curve/zranges.py _tighten_ranges against the *outer* boxes)
  ZDivMasks masks;
  zdiv_masks(ops, &masks);
  std::vector<ZRange> out;
  for (auto& r : merged) {
    bool has_lo = false, has_hi = false;
    uint64_t lo = 0, hi = 0;
    for (int64_t b = 0; b < nbox; ++b) {
      uint64_t zmin = zmins[b], zmax = zmaxes[b];
      if (zmax < r.lo || zmin > r.hi) continue;
      uint64_t cand;
      if (r.lo <= zmin) cand = zmin;
      else if (in_some_box(ops, r.lo, 1, mins + b * dims, maxes + b * dims)) cand = r.lo;
      else { uint64_t lm, bm; zdiv_cpp(ops, masks, zmin, zmax, r.lo, &lm, &bm); cand = bm; }
      if (cand <= r.hi && (!has_lo || cand < lo)) { lo = cand; has_lo = true; }
      if (r.hi >= zmax) cand = zmax;
      else if (in_some_box(ops, r.hi, 1, mins + b * dims, maxes + b * dims)) cand = r.hi;
      else { uint64_t lm, bm; zdiv_cpp(ops, masks, zmin, zmax, r.hi, &lm, &bm); cand = lm; }
      if (cand >= r.lo && (!has_hi || cand > hi)) { hi = cand; has_hi = true; }
    }
    if (!has_lo || !has_hi || lo > hi) continue;
    out.push_back({lo, hi, r.contained});
  }

  if ((int64_t)out.size() > cap) return -1;
  for (size_t i = 0; i < out.size(); ++i) {
    out_lo[i] = out[i].lo;
    out_hi[i] = out[i].hi;
    out_cont[i] = out[i].contained;
  }
  return (int64_t)out.size();
}

// nq decompositions in one call (a z3 plan has one per distinct offset
// window): query q is the union of its own nbox boxes; its ranges follow
// query q-1's in the outputs and counts[q] says how many they are.
// Returns the total written, or -1 if cap was too small.
extern "C" int64_t zranges_each_cpp(int32_t dims, int32_t bits_per_dim, int64_t nq,
                    int64_t nbox,
                    const uint64_t* mins, const uint64_t* maxes,
                    const uint64_t* imins, const uint64_t* imaxes,
                    int64_t max_ranges, int64_t max_recurse,
                    uint64_t* out_lo, uint64_t* out_hi, uint8_t* out_cont,
                    int64_t* counts, int64_t cap) {
  Stamp stamp_;
  int64_t total = 0, stride = nbox * dims;
  for (int64_t q = 0; q < nq; ++q) {
    int64_t n = zranges_cpp(dims, bits_per_dim, nbox,
                            mins + q * stride, maxes + q * stride,
                            imins + q * stride, imaxes + q * stride,
                            max_ranges, max_recurse,
                            out_lo + total, out_hi + total, out_cont + total,
                            cap - total);
    if (n < 0) return -1;
    counts[q] = n;
    total += n;
  }
  return total;
}

// ---------------------------------------------------------------------------
// bitmask decode: the scan pull's host decode hot path
// (geomesa_tpu/scan/block_kernels.py decode_bits_pair; bit b of word
// [blk, j, lane] = local row (j*32 + b)*128 + lane). The numpy route
// (unpackbits + transpose + nonzero + fancy index) costs ~25x this.
// ---------------------------------------------------------------------------

extern "C" int64_t bitmask_count(const int32_t* wide, int64_t n_real,
                                 int64_t pack) {
  Stamp stamp_;
  const uint32_t* w = (const uint32_t*)wide;
  int64_t words = n_real * pack * 128;
  int64_t total = 0;
  for (int64_t i = 0; i < words; ++i) total += __builtin_popcount(w[i]);
  return total;
}

extern "C" int64_t bitmask_decode_pair(const int32_t* wide,
                                       const int32_t* inner,
                                       const int64_t* bids, int64_t n_real,
                                       int64_t pack, int64_t block,
                                       int64_t* rows_out, uint8_t* cert_out) {
  Stamp stamp_;
  const uint32_t* w = (const uint32_t*)wide;
  const uint32_t* in = (const uint32_t*)inner;
  int64_t k = 0;
  for (int64_t blk = 0; blk < n_real; ++blk) {
    int64_t base = bids[blk] * block;
    for (int64_t j = 0; j < pack; ++j) {
      const uint32_t* wrow = w + (blk * pack + j) * 128;
      const uint32_t* irow = in + (blk * pack + j) * 128;
      uint32_t any = 0;
      for (int lane = 0; lane < 128; ++lane) any |= wrow[lane];
      if (!any) continue;  // sparse planes: skip empty sub-blocks cheaply
      for (int b = 0; b < 32; ++b) {
        if (!(any & (1u << b))) continue;
        const uint32_t bit = 1u << b;
        const int64_t rbase = base + (j * 32 + b) * 128;
        for (int lane = 0; lane < 128; ++lane) {
          if (wrow[lane] & bit) {
            rows_out[k] = rbase + lane;
            cert_out[k] = (irow[lane] & bit) ? 1 : 0;
            ++k;
          }
        }
      }
    }
  }
  return k;
}

// ---------------------------------------------------------------------------
// contained-span merge: emit the union of contained-span rows (all certain)
// and kernel rows (with their certainty), ascending, deduplicating kernel
// rows that fall inside a span — one two-pointer pass replacing the
// span_rows + rows_in_spans + positional-merge numpy pipeline.
// ---------------------------------------------------------------------------

extern "C" int64_t merge_rows_spans(const int64_t* span_lo,
                                    const int64_t* span_hi, int64_t n_spans,
                                    const int64_t* rows, const uint8_t* cert,
                                    int64_t n_rows, int64_t* out_rows,
                                    uint8_t* out_cert) {
  Stamp stamp_;
  int64_t k = 0, r = 0;
  for (int64_t s = 0; s < n_spans; ++s) {
    const int64_t lo = span_lo[s], hi = span_hi[s];  // [lo, hi)
    // kernel rows strictly before this span
    while (r < n_rows && rows[r] < lo) {
      out_rows[k] = rows[r];
      out_cert[k] = cert[r];
      ++k; ++r;
    }
    // the span itself (all rows certain)
    for (int64_t v = lo; v < hi; ++v) {
      out_rows[k] = v;
      out_cert[k] = 1;
      ++k;
    }
    // skip kernel duplicates inside the span
    while (r < n_rows && rows[r] < hi) ++r;
  }
  while (r < n_rows) {
    out_rows[k] = rows[r];
    out_cert[k] = cert[r];
    ++k; ++r;
  }
  return k;
}

// ---------------------------------------------------------------------------
// counting argsort: stable O(n) argsort of small-integer keys (grid cell
// ids in the spatial join; np.argsort's n log n dominated join setup).
// ---------------------------------------------------------------------------

extern "C" void counting_argsort(const int32_t* keys, int64_t n,
                                 int64_t n_buckets, uint32_t* perm) {
  Stamp stamp_;
  std::vector<int64_t> offsets(static_cast<size_t>(n_buckets) + 1, 0);
  for (int64_t i = 0; i < n; ++i) ++offsets[keys[i] + 1];
  for (int64_t b = 0; b < n_buckets; ++b) offsets[b + 1] += offsets[b];
  for (int64_t i = 0; i < n; ++i)
    perm[offsets[keys[i]]++] = static_cast<uint32_t>(i);
}

// wide-only decode (extent scans skip the inner plane entirely)
extern "C" int64_t bitmask_decode(const int32_t* wide, const int64_t* bids,
                                  int64_t n_real, int64_t pack, int64_t block,
                                  int64_t* rows_out) {
  Stamp stamp_;
  const uint32_t* w = (const uint32_t*)wide;
  int64_t k = 0;
  for (int64_t blk = 0; blk < n_real; ++blk) {
    int64_t base = bids[blk] * block;
    for (int64_t j = 0; j < pack; ++j) {
      const uint32_t* wrow = w + (blk * pack + j) * 128;
      uint32_t any = 0;
      for (int lane = 0; lane < 128; ++lane) any |= wrow[lane];
      if (!any) continue;
      for (int b = 0; b < 32; ++b) {
        if (!(any & (1u << b))) continue;
        const uint32_t bit = 1u << b;
        const int64_t rbase = base + (j * 32 + b) * 128;
        for (int lane = 0; lane < 128; ++lane) {
          if (wrow[lane] & bit) rows_out[k++] = rbase + lane;
        }
      }
    }
  }
  return k;
}

// ---------------------------------------------------------------------------
// XZ index write path: element boxes -> XZ sequence codes (the extent-table
// analogue of z3_write_keys). Same construction as curve/xzsfc.py
// XZSFC.length_at + sequence_code (Boehm et al. XZ-ordering, re-derived;
// reference XZ2SFC.index:54-77): deepest level whose enlarged cell still
// contains the element, then the preorder code of the cell holding the
// element's low corner at that level. One scalar pass per element replaces
// ~2*g full-array numpy passes.
// ---------------------------------------------------------------------------

extern "C" void xz_index(const double* lo, const double* hi, int64_t n,
                         int32_t dims, int32_t g, const int64_t* subtree,
                         int64_t* out) {
  Stamp stamp_;
#ifdef _OPENMP
#pragma omp parallel for schedule(static)
#endif
  for (int64_t e = 0; e < n; ++e) {
    const double* el = lo + e * dims;
    const double* eh = hi + e * dims;
    double extent = 0.0;
    for (int32_t d = 0; d < dims; ++d)
      extent = std::max(extent, eh[d] - el[d]);
    int64_t l1 = (int64_t)std::floor(std::log(std::max(extent, 1e-300)) /
                                     std::log(0.5));
    if (l1 > g) l1 = g;
    const int64_t lp = std::min<int64_t>(l1 + 1, g);
    const double w2 = std::ldexp(1.0, (int)-lp);  // 0.5^lp, exact
    bool fits = true;
    for (int32_t d = 0; d < dims; ++d) {
      const double anchor = std::floor(el[d] / w2) * w2;
      if (eh[d] > anchor + 2.0 * w2) { fits = false; break; }
    }
    int64_t length = fits ? lp : std::max<int64_t>(l1, 0);
    if (length > g) length = g;
    int64_t cs = 0;
    double clo[4] = {0, 0, 0, 0}, chi[4] = {1, 1, 1, 1};
    for (int64_t i = 0; i < length; ++i) {
      int64_t q = 0;
      for (int32_t d = 0; d < dims; ++d) {
        const double c = (clo[d] + chi[d]) * 0.5;
        if (el[d] >= c) { q |= (int64_t)1 << d; clo[d] = c; }
        else chi[d] = c;
      }
      cs += 1 + q * subtree[i + 1];
    }
    out[e] = cs;
  }
}

// ---------------------------------------------------------------------------
// XZ range decomposition: covering sequence-code ranges of query boxes.
// Same BFS + budget + merge semantics as curve/xzsfc.py XZSFC.ranges
// (re-derived XZ-ordering construction; reference XZ2SFC.ranges:146-252):
// a cell whose ENLARGED extent is contained in a query covers its whole
// subtree (contained=true, no row filter); an overlapping cell emits its
// own code and recurses. Per-level budget of 2*max_ranges, then a
// sort+merge that only glues same-kind neighbors and closes the smallest
// gaps to reach max_ranges. Python's per-cell numpy ops cost 3-116 ms per
// query at g=12; this pass is ~100x cheaper.
// ---------------------------------------------------------------------------

namespace {
struct XzCell {
  double lo[4];
  int32_t level;
  int64_t cs;
};
struct XzRange {
  uint64_t lo, hi;
  uint8_t contained;
};
}  // namespace

extern "C" int64_t xz_ranges(int32_t dims, int32_t g, const int64_t* subtree,
                             const double* qlo, const double* qhi, int64_t nq,
                             int64_t max_ranges, uint64_t* out_lo,
                             uint64_t* out_hi, uint8_t* out_cont,
                             int64_t cap) {
  Stamp stamp_;
  if (dims > 4) return -1;
  const int32_t children = 1 << dims;
  std::vector<XzCell> level_cells, nxt;
  XzCell root{};
  for (int32_t d = 0; d < dims; ++d) root.lo[d] = 0.0;
  root.level = 0;
  root.cs = 0;
  level_cells.push_back(root);
  std::vector<XzRange> ranges;

  while (!level_cells.empty()) {
    nxt.clear();
    const int64_t budget_left = max_ranges * 2 - (int64_t)ranges.size();
    if (budget_left <= 0) break;
    for (const XzCell& c : level_cells) {
      const double w = std::ldexp(1.0, -c.level);
      bool contained = false, overlaps = false;
      for (int64_t q = 0; q < nq && !contained; ++q) {
        bool cont = true;
        for (int32_t d = 0; d < dims; ++d) {
          if (!(qlo[q * dims + d] <= c.lo[d] &&
                qhi[q * dims + d] >= c.lo[d] + 2.0 * w)) {
            cont = false;
            break;
          }
        }
        contained |= cont;
      }
      if (contained) {
        ranges.push_back({(uint64_t)c.cs,
                          (uint64_t)(c.cs + subtree[c.level] - 1), 1});
        continue;
      }
      for (int64_t q = 0; q < nq && !overlaps; ++q) {
        bool ov = true;
        for (int32_t d = 0; d < dims; ++d) {
          if (!(qlo[q * dims + d] <= c.lo[d] + 2.0 * w &&
                qhi[q * dims + d] >= c.lo[d])) {
            ov = false;
            break;
          }
        }
        overlaps |= ov;
      }
      if (!overlaps) continue;
      ranges.push_back({(uint64_t)c.cs, (uint64_t)c.cs, 0});
      if (c.level < g) {
        const int64_t sub = subtree[c.level + 1];
        const double half = w * 0.5;
        for (int32_t q = 0; q < children; ++q) {
          XzCell ch{};
          for (int32_t d = 0; d < dims; ++d)
            ch.lo[d] = c.lo[d] + (((q >> d) & 1) ? half : 0.0);
          ch.level = c.level + 1;
          ch.cs = c.cs + 1 + q * sub;
          nxt.push_back(ch);
        }
      }
    }
    level_cells.swap(nxt);
  }
  // budget exhausted: whole subtrees for unprocessed overlapping cells
  for (const XzCell& c : level_cells) {
    const double w = std::ldexp(1.0, -c.level);
    bool overlaps = false;
    for (int64_t q = 0; q < nq && !overlaps; ++q) {
      bool ov = true;
      for (int32_t d = 0; d < dims; ++d) {
        if (!(qlo[q * dims + d] <= c.lo[d] + 2.0 * w &&
              qhi[q * dims + d] >= c.lo[d])) {
          ov = false;
          break;
        }
      }
      overlaps |= ov;
    }
    if (overlaps)
      ranges.push_back({(uint64_t)c.cs,
                        (uint64_t)(c.cs + subtree[c.level] - 1), 0});
  }

  if (ranges.empty()) return 0;
  // sort + merge same-kind neighbors (curve/zranges.py merge_ranges)
  std::sort(ranges.begin(), ranges.end(), [](const XzRange& a, const XzRange& b) {
    return a.lo != b.lo ? a.lo < b.lo : a.hi < b.hi;
  });
  std::vector<XzRange> merged;
  merged.push_back(ranges[0]);
  for (size_t i = 1; i < ranges.size(); ++i) {
    XzRange& last = merged.back();
    const XzRange& r = ranges[i];
    if (r.lo <= last.hi + 1 && r.contained == last.contained) {
      last.hi = std::max(last.hi, r.hi);
    } else {
      merged.push_back(r);
    }
  }
  if (max_ranges > 0 && (int64_t)merged.size() > max_ranges) {
    const int64_t k = (int64_t)merged.size() - max_ranges;
    std::vector<int64_t> gap_idx(merged.size() - 1);
    for (size_t i = 0; i + 1 < merged.size(); ++i) gap_idx[i] = (int64_t)i;
    std::nth_element(
        gap_idx.begin(), gap_idx.begin() + (k - 1), gap_idx.end(),
        [&](int64_t a, int64_t b) {
          return merged[a + 1].lo - merged[a].hi < merged[b + 1].lo - merged[b].hi;
        });
    std::vector<uint8_t> close(merged.size() - 1, 0);
    for (int64_t i = 0; i < k; ++i) close[gap_idx[i]] = 1;
    std::vector<XzRange> out;
    out.push_back(merged[0]);
    for (size_t i = 1; i < merged.size(); ++i) {
      if (close[i - 1]) {
        out.back().hi = std::max(out.back().hi, merged[i].hi);
        out.back().contained = 0;
      } else {
        out.push_back(merged[i]);
      }
    }
    merged.swap(out);
  }
  if ((int64_t)merged.size() > cap) return -1;
  for (size_t i = 0; i < merged.size(); ++i) {
    out_lo[i] = merged[i].lo;
    out_hi[i] = merged[i].hi;
    out_cont[i] = merged[i].contained;
  }
  return (int64_t)merged.size();
}

// ----------------------------------------------------------- the lock probe
// ``nap``: what obs/trace.py's hand-off probe sleeps in: a native call
// that lets the interpreter lock go for ``seconds`` as any call here does,
// so that its return stamp is the moment the sleep ended, the timer's
// slack outside the sample.
extern "C" void nap(double seconds) {
  Stamp stamp_;
  if (seconds <= 0) return;
  struct timespec ts;
  ts.tv_sec = (time_t)seconds;
  ts.tv_nsec = (long)((seconds - (double)ts.tv_sec) * 1e9);
  clock_nanosleep(CLOCK_MONOTONIC, 0, &ts, nullptr);
}

// The calling thread's last stamps. No guard: they read what it wrote.
extern "C" double stamp_entry() { return g_stamp_entry; }
extern "C" double stamp_return() { return g_stamp_return; }
