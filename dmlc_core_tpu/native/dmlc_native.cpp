// Native hot paths for dmlc_core_tpu: text→CSR parsers with OpenMP
// chunk-parallelism and branch-light number scanning.
//
// Capability parity with the reference's native parse stack:
//   * strtonum.h:37-150   — branch-light strtof/strtoint (no INF/NAN/hex)
//   * text_parser.h:90-118 — chunk divided among threads at line boundaries
//   * libsvm_parser.h:36-90 — "label[:weight] idx:val..." records
//   * libfm_parser.h:36-93  — "label[:weight] field:idx:val..." records
//   * csv_parser.h:63-102   — dense rows, configurable label column
//
// This is a fresh implementation in C++17 for the TPU framework's host-side
// ingest; the output is one CSR block (offsets/labels/weights/indices/values
// [+fields]) handed to Python via a C ABI for zero-copy numpy wrapping, then
// staged to TPU HBM by the pipeline layer.
//
// Build: g++ -O3 -std=c++17 -fopenmp -shared -fPIC dmlc_native.cpp -o libdmlc_native.so

#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <new>
#include <type_traits>
#include <utility>
#include <vector>

#if defined(_OPENMP)
#include <omp.h>
#endif

namespace {

// ---------------- branch-light scanners ----------------

inline bool is_space(char c) { return c == ' ' || c == '\t' || c == '\r'; }
inline bool is_eol(char c) { return c == '\n' || c == '\r'; }
inline bool is_digit(char c) { return c >= '0' && c <= '9'; }

// True when the range holds a '\r' NOT followed by '\n' (classic-Mac line
// endings): the memchr('\n') fast path would merge such records.  One
// vectorized scan — cheap next to the parse itself.
inline bool has_lone_cr(const char* p, const char* end) {
  while ((p = static_cast<const char*>(memchr(p, '\r', end - p))) != nullptr) {
    if (p + 1 >= end || p[1] != '\n') return true;
    ++p;
  }
  return false;
}

// Next line end: vectorized memchr('\n') with the trailing '\r' of CRLF
// trimmed, or the byte-wise is_eol scan when the range uses lone-CR
// separators.  Callers resume at the returned pointer: the eol-run skip at
// each loop top consumes the remaining '\r'/'\n' bytes.
inline const char* line_end_of(const char* p, const char* end, bool lone_cr) {
  if (lone_cr) {
    while (p < end && !is_eol(*p)) ++p;
    return p;
  }
  const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
  const char* stop = nl ? nl : end;
  if (stop > p && stop[-1] == '\r') --stop;
  return stop;
}

// Powers of ten for the integer-mantissa fast path (double is exact for
// 10^0..10^22; mantissas up to 2^63 round once — well inside float32 need).
static const uint64_t kPow10Int[9] = {1ULL,       10ULL,       100ULL,
                                      1000ULL,    10000ULL,    100000ULL,
                                      1000000ULL, 10000000ULL, 100000000ULL};

static const double kPow10[23] = {
    1e0,  1e1,  1e2,  1e3,  1e4,  1e5,  1e6,  1e7,  1e8,  1e9,  1e10, 1e11,
    1e12, 1e13, 1e14, 1e15, 1e16, 1e17, 1e18, 1e19, 1e20, 1e21, 1e22};

// 10^k is exact in double for k<=22, so the correctly-rounded division
// 1.0/kPow10[k] has EXACTLY the bits of the literal 1e-k — the table is
// bit-identical to the division it replaces, and an fdiv (~20 cycles) per
// parsed value was ~the single largest cost in the float hot path (the
// common "0.dddd" shape always takes the negative-exponent branch).
static const double kPow10Neg[23] = {
    1e-0,  1e-1,  1e-2,  1e-3,  1e-4,  1e-5,  1e-6,  1e-7,
    1e-8,  1e-9,  1e-10, 1e-11, 1e-12, 1e-13, 1e-14, 1e-15,
    1e-16, 1e-17, 1e-18, 1e-19, 1e-20, 1e-21, 1e-22};

inline double pow10_signed(int e) {
  // |e| <= 100 (saturated by caller); split into table-sized factors
  if (e >= 0) {
    double f = 1.0;
    while (e > 22) { f *= 1e22; e -= 22; }
    return f * kPow10[e];
  }
  int a = -e;
  if (a <= 22) return kPow10Neg[a];
  // rare: keep the old divide-once form so chained negative powers round
  // exactly as before (1.0 / (1e22^n * 10^r))
  double f = 1.0;
  while (a > 22) { f *= 1e22; a -= 22; }
  return 1.0 / (f * kPow10[a]);
}

// SWAR helpers shared by digit_run8 / parse_uint64 / the float fast path
// (one detector + one reducer, so a future fix cannot miss a copy):
// x = chunk ^ 0x30 repeated; mask has bit 0x80 set in every byte that is
// NOT an ASCII digit (the +0x76 carry can only fire above a true
// non-digit, so ctz on it is exact).
inline uint64_t swar_nondigit_mask(uint64_t x) {
  return ((x + 0x7676767676767676ULL) | x) & 0x8080808080808080ULL;
}

// Combine <=8 digit BYTES (values 0-9, least-significant byte = leading
// digit, left-aligned by the caller so the first digit lands on the 10^7
// place) into the numeric value via the two-multiply reduction.
inline uint32_t swar_reduce8(uint64_t x) {
  x = (x * 10) + (x >> 8);
  x = (((x & 0x000000FF000000FFULL) * 0x000F424000000064ULL) +
       (((x >> 16) & 0x000000FF000000FFULL) * 0x0000271000000001ULL)) >> 32;
  return static_cast<uint32_t>(x);
}

// One digit run of up to 8 chars, SWAR-converted (same reduction as
// parse_uint64).  val is the run's numeric value, len its char count
// (0 = no digit at p).
struct DigitRun { uint32_t val; int len; };

inline DigitRun digit_run8(const char* p, const char* end) {
  if (end - p >= 8) {
    uint64_t chunk;
    std::memcpy(&chunk, p, 8);
    uint64_t x = chunk ^ 0x3030303030303030ULL;
    uint64_t nondigit = swar_nondigit_mask(x);
    int run = nondigit ? (__builtin_ctzll(nondigit) >> 3) : 8;
    if (run == 0) return {0, 0};
    if (run < 8) x &= (1ULL << (8 * run)) - 1;
    x <<= 8 * (8 - run);
    return {swar_reduce8(x), run};
  }
  uint32_t v = 0;
  int n = 0;
  while (p != end && is_digit(*p) && n < 7) { v = v * 10 + (*p - '0'); ++p; ++n; }
  return {v, n};
}

// Slow/general float parse: sign, integer, fraction, exponent — handles
// arbitrarily long digit runs with a 19-significant-digit cap.  Mirrors the
// capability of reference strtonum.h:37 (no INF/NAN/hex support — data
// files never contain them).
inline int parse_float_slow(const char* p, const char* end, float* out) {
  const char* s = p;
  if (p == end) return 0;
  bool neg = false;
  if (*p == '-') { neg = true; ++p; }
  else if (*p == '+') { ++p; }
  uint64_t mant = 0;
  int digits = 0;  // SIGNIFICANT digits folded into mant (<= 19 fit uint64)
  int exp10 = 0;
  bool any = false;
  while (p != end && is_digit(*p)) {
    any = true;
    const int d = *p - '0';
    if (mant == 0 && d == 0) {
      // leading integer zero: no significance, no magnitude
    } else if (digits < 19) {
      mant = mant * 10 + d;
      ++digits;
    } else {
      ++exp10;  // extra integer magnitude beyond 19 significant digits
    }
    ++p;
  }
  if (p != end && *p == '.') {
    ++p;
    while (p != end && is_digit(*p)) {
      any = true;
      const int d = *p - '0';
      if (mant == 0 && d == 0) {
        --exp10;  // leading fractional zero: shifts scale, not significance
      } else if (digits < 19) {
        mant = mant * 10 + d;
        ++digits;
        --exp10;
      }
      // fraction digits beyond 19 significant: drop, no magnitude change
      ++p;
    }
  }
  if (!any) return 0;
  if (p != end && (*p == 'e' || *p == 'E')) {
    const char* mark = p;
    ++p;
    int esign = 1;
    if (p != end && (*p == '-' || *p == '+')) { if (*p == '-') esign = -1; ++p; }
    int e = 0;
    bool eany = false;
    // saturate: |exp| > 60 already over/underflows float32, and an unbounded
    // accumulator would be UB / a DoS on hostile exponents like 1e1000000000
    while (p != end && is_digit(*p)) {
      if (e < 1000) e = e * 10 + (*p - '0');
      ++p;
      eany = true;
    }
    if (!eany) { p = mark; }
    else {
      if (e > 60) e = 60;
      exp10 += esign * e;
    }
  }
  if (exp10 > 100) exp10 = 100;     // float32 range is long gone either way
  if (exp10 < -100) exp10 = -100;
  double v = static_cast<double>(mant);
  if (exp10) v *= pow10_signed(exp10);
  *out = static_cast<float>(neg ? -v : v);
  return static_cast<int>(p - s);
}

// Hot-path float parse: the common "d[.dddd]" shapes (≤7-digit integer and
// fraction parts) resolve with two SWAR runs and ONE scale multiply; long
// runs and exponent forms fall through to parse_float_slow.  ≤14 total
// mantissa digits fit uint64 exactly, so leading zeros need no special
// casing here.
//
// Opening fast path: when the WHOLE "ddd.ffff" token (plus one terminator
// byte) fits one 8-byte window, the dot is spliced out with shifts and the
// digits go through a single SWAR reduction — one load instead of two
// digit_run8 calls.  Value math is identical to the general path
// (double(mant) · kPow10Neg[frac_len]), so the result is bit-exact; any
// shape that doesn't fit (sign, exponent, ≥8 chars, no dot) falls through
// unchanged.  Measured ~1.14x on the float-token walk of the bench corpus
// (4.8M values verified bit-identical).
inline int parse_float(const char* p, const char* end, float* out) {
  const char* s = p;
  if (p == end) return 0;
  if (end - p >= 8) {
    uint64_t chunk;
    std::memcpy(&chunk, p, 8);
    uint64_t x = chunk ^ 0x3030303030303030ULL;
    uint64_t nondigit = swar_nondigit_mask(x);
    if (nondigit) {
      const int d = __builtin_ctzll(nondigit) >> 3;  // first non-digit
      // d < 7: a dot at window byte 7 leaves no visible fraction and
      // `x >> 8*(d+1)` would be a shift by 64 (UB) — e.g. "1234567."
      if (d < 7 && p[d] == '.') {
        uint64_t x2 = x >> (8 * (d + 1));
        uint64_t nd2 = swar_nondigit_mask(x2);
        const int avail = 7 - d;
        int fl = nd2 ? (__builtin_ctzll(nd2) >> 3) : 8;
        if (fl > avail) fl = avail;
        const int e = d + 1 + fl;      // token length inside the window
        if (fl > 0 && e <= 7) {        // terminator byte visible in window
          const char nxt = p[e];
          if (nxt != 'e' && nxt != 'E' && !is_digit(nxt)) {
            const uint64_t lo = x & ((d ? (1ULL << (8 * d)) : 1ULL) - 1);
            const uint64_t frac = x2 & ((1ULL << (8 * fl)) - 1);
            uint64_t m = lo | (frac << (8 * d));
            const int n = d + fl;      // total digits (<= 7)
            m <<= 8 * (8 - n);
            *out = static_cast<float>(
                static_cast<double>(swar_reduce8(m)) * kPow10Neg[fl]);
            return e;
          }
        }
      }
    }
  }
  bool neg = false;
  if (*p == '-') { neg = true; ++p; }
  else if (*p == '+') { ++p; }
  DigitRun r1 = digit_run8(p, end);
  if (r1.len >= 8) return parse_float_slow(s, end, out);
  uint64_t mant = r1.val;
  int exp10 = 0;
  bool any = r1.len > 0;
  p += r1.len;
  if (p != end && *p == '.') {
    const char* frac = p + 1;
    DigitRun r2 = digit_run8(frac, end);
    if (r2.len >= 8) return parse_float_slow(s, end, out);
    if (r2.len > 0 || any) {
      mant = mant * kPow10Int[r2.len] + r2.val;
      exp10 = -r2.len;
      any = any || r2.len > 0;
      p = frac + r2.len;
    }
  }
  if (!any) return 0;
  if (p != end && (*p == 'e' || *p == 'E'))
    return parse_float_slow(s, end, out);
  double v = static_cast<double>(mant);
  if (exp10) v *= pow10_signed(exp10);
  *out = static_cast<float>(neg ? -v : v);
  return static_cast<int>(p - s);
}

// SWAR digit-run scan: load 8 bytes, mask of non-digit bytes, run length via
// ctz; convert the run with the well-known eight-digit multiply reduction
// (digits left-shifted so the first char lands on the 10^7 place).  One
// branch per run instead of one per digit — indices in libsvm/libfm average
// 5-7 digits, the hottest scan in ingest.
inline int parse_uint64(const char* p, const char* end, uint64_t* out) {
  const char* s = p;
  uint64_t v = 0;
  while (end - p >= 8) {
    uint64_t chunk;
    std::memcpy(&chunk, p, 8);
    uint64_t x = chunk ^ 0x3030303030303030ULL;
    uint64_t nondigit = swar_nondigit_mask(x);
    int run = nondigit ? (__builtin_ctzll(nondigit) >> 3) : 8;
    if (run == 0) break;
    if (run < 8) x &= (1ULL << (8 * run)) - 1;
    x <<= 8 * (8 - run);
    v = v * kPow10Int[run] + swar_reduce8(x);
    p += run;
    if (run < 8) {
      *out = v;
      return static_cast<int>(p - s);
    }
  }
  while (p != end && is_digit(*p)) { v = v * 10 + (*p - '0'); ++p; }
  if (p == s) return 0;
  *out = v;
  return static_cast<int>(p - s);
}

// ---------------- CSR accumulation ----------------

// Allocator whose default-construct is a no-op: vector::resize(cap) then
// skips the value-initialization memset — the per-value scratch arrays are
// fully overwritten by the parser before being read.
template <typename T, typename A = std::allocator<T>>
struct default_init_alloc : public A {
  template <typename U>
  struct rebind {
    using other = default_init_alloc<
        U, typename std::allocator_traits<A>::template rebind_alloc<U>>;
  };
  using A::A;
  template <typename U>
  void construct(U* ptr) noexcept(
      std::is_nothrow_default_constructible<U>::value) {
    ::new (static_cast<void*>(ptr)) U;
  }
  template <typename U, typename... Args>
  void construct(U* ptr, Args&&... args) {
    std::allocator_traits<A>::construct(static_cast<A&>(*this), ptr,
                                        std::forward<Args>(args)...);
  }
};

template <typename T>
using raw_vector = std::vector<T, default_init_alloc<T>>;

struct ThreadBlock {
  std::vector<int64_t> offsets;     // per-row value counts (converted later)
  std::vector<float> labels;
  std::vector<float> weights;
  raw_vector<uint64_t> indices;
  raw_vector<float> values;
  raw_vector<uint32_t> fields;
  uint64_t max_index = 0;
  uint32_t max_field = 0;
  int64_t bad_lines = 0;
};

struct CSRBlockC {
  int64_t n_rows;
  int64_t n_values;
  int64_t* offsets;    // n_rows + 1
  float* labels;       // n_rows
  float* weights;      // n_rows (1.0 default)
  uint64_t* indices;   // n_values
  float* values;       // n_values
  uint32_t* fields;    // n_values (libfm) or nullptr
  uint64_t max_index;
  uint32_t max_field;
  int64_t bad_lines;
  void* owner;         // non-null: arrays alias an adopted BlockOwner
};

// Zero-copy handoff for the single-thread parse: the ThreadBlock's own
// buffers become the output arrays (moved, not memcpy'd — the merge pass
// re-copies ~1x the input size, pure waste when there is nothing to
// merge); `cum` holds the counts→offsets conversion, the only array that
// must still be built.
struct BlockOwner {
  ThreadBlock tb;
  std::vector<int64_t> cum;
};

// split [data, data+len) into nt ranges cut at line starts
// (reference text_parser.h:100-115 divides the chunk the same way)
std::vector<const char*> line_aligned_cuts(const char* data, int64_t len, int nt) {
  std::vector<const char*> cuts;
  cuts.push_back(data);
  const char* end = data + len;
  for (int t = 1; t < nt; ++t) {
    const char* p = data + (len * t) / nt;
    while (p < end && !is_eol(*p)) ++p;
    while (p < end && is_eol(*p)) ++p;
    if (p < cuts.back()) p = cuts.back();
    cuts.push_back(p);
  }
  cuts.push_back(end);
  return cuts;
}

enum class Fmt { kLibSVM, kLibFM };

// parse "label[:weight] a:b[:c] ..." lines into tb
void parse_sparse_range(const char* p, const char* end, Fmt fmt, ThreadBlock* tb) {
  const bool lone_cr = has_lone_cr(p, end);
  // Per-value arrays are written through bare pointers with NO capacity
  // branch per push — sized to the worst case of one value per 2 chars
  // (value-less binary-feature tokens: "1 1 1 ..."), trimmed once at the
  // end.  ~2x on the value-dense hot path.
  const size_t cap = static_cast<size_t>(end - p) / 2 + 8;
  tb->indices.resize(cap);
  tb->values.resize(cap);
  const bool want_fields = fmt == Fmt::kLibFM;
  if (want_fields) tb->fields.resize(cap);
  uint64_t* ip = tb->indices.data();
  float* vp = tb->values.data();
  uint32_t* fp = want_fields ? tb->fields.data() : nullptr;
  size_t nv_total = 0;
  while (p < end) {
    while (p < end && is_eol(*p)) ++p;
    if (p >= end) break;
    const char* line_end = line_end_of(p, end, lone_cr);
    // label [:weight]
    while (p < line_end && is_space(*p)) ++p;
    float label = 0.f, weight = 1.f;
    int n = parse_float(p, line_end, &label);
    if (n == 0) {  // empty/garbage line: skip (reference skips blank lines)
      const char* q = p;
      while (q < line_end && is_space(*q)) ++q;
      if (q != line_end) ++tb->bad_lines;
      p = line_end;
      continue;
    }
    p += n;
    if (p < line_end && *p == ':') {
      ++p;
      n = parse_float(p, line_end, &weight);
      if (n == 0) {  // 'label:garbage' — drop the whole row
        ++tb->bad_lines;
        p = line_end;
        continue;
      }
      p += n;
    }
    tb->labels.push_back(label);
    tb->weights.push_back(weight);
    int64_t nvals = 0;
    while (p < line_end) {
      while (p < line_end && is_space(*p)) ++p;
      if (p >= line_end) break;
      uint64_t a = 0;
      n = parse_uint64(p, line_end, &a);
      if (n == 0) { ++tb->bad_lines; break; }
      p += n;
      if (fmt == Fmt::kLibSVM && (p >= line_end || *p != ':')) {
        // value-less token 'idx' — implicit value 1.0
        // (reference libsvm_parser.h ParsePair r==1 path)
        ip[nv_total] = a;
        vp[nv_total] = 1.0f;
        ++nv_total;
        if (a > tb->max_index) tb->max_index = a;
        ++nvals;
        continue;
      }
      if (p >= line_end || *p != ':') { ++tb->bad_lines; break; }
      ++p;
      if (fmt == Fmt::kLibSVM) {
        float v = 1.0f;
        n = parse_float(p, line_end, &v);
        if (n == 0) { ++tb->bad_lines; break; }
        p += n;
        ip[nv_total] = a;
        vp[nv_total] = v;
        ++nv_total;
        if (a > tb->max_index) tb->max_index = a;
      } else {  // libfm: field:idx:val
        uint64_t idx = 0;
        n = parse_uint64(p, line_end, &idx);
        if (n == 0) { ++tb->bad_lines; break; }
        p += n;
        if (p >= line_end || *p != ':') { ++tb->bad_lines; break; }
        ++p;
        float v = 1.0f;
        n = parse_float(p, line_end, &v);
        if (n == 0) { ++tb->bad_lines; break; }
        p += n;
        fp[nv_total] = static_cast<uint32_t>(a);
        ip[nv_total] = idx;
        vp[nv_total] = v;
        ++nv_total;
        if (idx > tb->max_index) tb->max_index = idx;
        if (a > tb->max_field) tb->max_field = static_cast<uint32_t>(a);
      }
      ++nvals;
    }
    tb->offsets.push_back(nvals);
    p = line_end;
  }
  tb->indices.resize(nv_total);
  tb->values.resize(nv_total);
  if (want_fields) tb->fields.resize(nv_total);
}

// dense csv: every column a value, one column (or none: -1) the label.
// A row with any unparseable field is dropped whole and counted bad — the
// Python fallback does the same, keeping both kernels' outputs identical.
void parse_csv_range(const char* p, const char* end, int label_col, char delim,
                     ThreadBlock* tb) {
  const bool lone_cr = has_lone_cr(p, end);
  // dense rows: ~2 chars per cell is a safe push_back pre-size
  tb->values.reserve(static_cast<size_t>(end - p) / 2 + 8);
  tb->indices.reserve(static_cast<size_t>(end - p) / 2 + 8);
  while (p < end) {
    while (p < end && is_eol(*p)) ++p;
    if (p >= end) break;
    const char* line_end = line_end_of(p, end, lone_cr);
    float label = 0.f;
    int64_t col = 0, nvals = 0;
    size_t mark = tb->values.size();  // rollback point for bad rows
    bool ok = true;
    while (true) {  // one iteration per field; runs once even for empty tail
      while (p < line_end && is_space(*p)) ++p;
      float v = 0.f;
      int n = parse_float(p, line_end, &v);
      if (n == 0) {
        // empty cell parses as 0.0; anything unparseable kills the row
        if (p < line_end && *p != delim && !is_space(*p)) {
          ok = false;
          break;
        }
      }
      p += n;
      while (p < line_end && is_space(*p)) ++p;
      if (col == label_col) {
        label = v;
      } else {
        tb->indices.push_back(static_cast<uint64_t>(nvals));
        tb->values.push_back(v);
        ++nvals;
      }
      ++col;
      if (p < line_end && *p == delim) { ++p; continue; }
      break;
    }
    if (!ok || p != line_end) {
      ++tb->bad_lines;
      tb->indices.resize(mark);
      tb->values.resize(mark);
      p = line_end;
      continue;
    }
    if (nvals > 0 && static_cast<uint64_t>(nvals - 1) > tb->max_index)
      tb->max_index = static_cast<uint64_t>(nvals - 1);
    tb->labels.push_back(label);
    tb->weights.push_back(1.f);
    tb->offsets.push_back(nvals);
    p = line_end;
  }
}

template <typename F>
int parse_parallel(const char* data, int64_t len, bool want_fields, int nthreads,
                   CSRBlockC* out, F&& range_fn) {
  int nt = 1;
#if defined(_OPENMP)
  nt = nthreads > 0 ? nthreads : omp_get_max_threads();
  if (nt < 1) nt = 1;
  if (len < (1 << 16)) nt = 1;  // small chunks: threading overhead dominates
#endif
  std::vector<const char*> cuts = line_aligned_cuts(data, len, nt);
  std::vector<ThreadBlock> blocks(nt);
// GCC defines __SANITIZE_THREAD__; clang's TSAN only advertises itself
// via __has_feature(thread_sanitizer) — without the second clause a
// clang TSAN build would compile no edges and resurface the 64
// libgomp-barrier false positives these exist to suppress
#if !defined(DMLC_TSAN_ENABLED) && defined(__has_feature)
#if __has_feature(thread_sanitizer)
#define DMLC_TSAN_ENABLED 1
#endif
#endif
#if defined(__SANITIZE_THREAD__) && !defined(DMLC_TSAN_ENABLED)
#define DMLC_TSAN_ENABLED 1
#endif
#if defined(DMLC_TSAN_ENABLED)
  // TSAN-only: explicit release/acquire edges mirroring BOTH OpenMP
  // barriers.  The fork barrier (main's cuts/blocks writes → worker
  // reads) and the join barrier (worker block writes → main's merge
  // reads) live in uninstrumented libgomp, so TSAN cannot see either
  // and reported the whole parse as 64 races.  The real omp barriers
  // already order everything — these atomics only re-express that
  // ordering in tool-visible form, so production builds compile none of
  // it.  Single loads suffice (no spinning): the omp join guarantees
  // the acquire load observes the last release fetch_add, and the RMW
  // release sequence makes every worker's edge visible from it.
  std::atomic<int> tsan_published{0};
  std::atomic<int> tsan_done{0};
  tsan_published.store(1, std::memory_order_release);
#define DMLC_TSAN_WORKER_ENTER() \
    (void)tsan_published.load(std::memory_order_acquire)
#define DMLC_TSAN_WORKER_EXIT() \
    tsan_done.fetch_add(1, std::memory_order_release)
#define DMLC_TSAN_MAIN_JOIN() \
    (void)tsan_done.load(std::memory_order_acquire)
#else
#define DMLC_TSAN_WORKER_ENTER() ((void)0)
#define DMLC_TSAN_WORKER_EXIT() ((void)0)
#define DMLC_TSAN_MAIN_JOIN() ((void)0)
#endif
#if defined(_OPENMP)
#pragma omp parallel for num_threads(nt) schedule(static, 1)
#endif
  for (int t = 0; t < nt; ++t) {
    DMLC_TSAN_WORKER_ENTER();
    // pre-size the per-row arrays (~80 chars per row is a safe lower
    // bound); the sparse range parsers size their own per-value scratch
    int64_t range = cuts[t + 1] - cuts[t];
    blocks[t].labels.reserve(range / 64);
    blocks[t].weights.reserve(range / 64);
    blocks[t].offsets.reserve(range / 64);
    range_fn(cuts[t], cuts[t + 1], &blocks[t]);
    DMLC_TSAN_WORKER_EXIT();
  }
  DMLC_TSAN_MAIN_JOIN();
#undef DMLC_TSAN_WORKER_ENTER
#undef DMLC_TSAN_WORKER_EXIT
#undef DMLC_TSAN_MAIN_JOIN
  // merge
  int64_t n_rows = 0, n_values = 0;
  uint64_t max_index = 0;
  uint32_t max_field = 0;
  int64_t bad = 0;
  for (auto& b : blocks) {
    n_rows += static_cast<int64_t>(b.labels.size());
    n_values += static_cast<int64_t>(b.values.size());
    if (b.max_index > max_index) max_index = b.max_index;
    if (b.max_field > max_field) max_field = b.max_field;
    bad += b.bad_lines;
  }
  out->n_rows = n_rows;
  out->n_values = n_values;
  out->max_index = max_index;
  out->max_field = max_field;
  out->bad_lines = bad;
  out->owner = nullptr;
  if (nt == 1) {
    // single range: adopt the ThreadBlock buffers instead of merging.
    // The range parsers pre-size per-value scratch to a worst-case bound
    // (~len/2 entries); release that capacity before adoption or every
    // queued block pins hundreds of MB of dead heap through the pipeline
    blocks[0].indices.shrink_to_fit();
    blocks[0].values.shrink_to_fit();
    blocks[0].fields.shrink_to_fit();
    blocks[0].labels.shrink_to_fit();
    blocks[0].weights.shrink_to_fit();
    blocks[0].offsets.shrink_to_fit();
    auto* own = new (std::nothrow) BlockOwner{std::move(blocks[0]), {}};
    if (!own) return -1;
    own->cum.resize(n_rows + 1);
    own->cum[0] = 0;
    for (int64_t i = 0; i < n_rows; ++i)
      own->cum[i + 1] = own->cum[i] + own->tb.offsets[i];
    out->owner = own;
    out->offsets = own->cum.data();
    out->labels = own->tb.labels.data();
    out->weights = own->tb.weights.data();
    out->indices = own->tb.indices.data();
    out->values = own->tb.values.data();
    out->fields = want_fields ? own->tb.fields.data() : nullptr;
    return 0;
  }
  out->offsets = static_cast<int64_t*>(std::malloc(sizeof(int64_t) * (n_rows + 1)));
  out->labels = static_cast<float*>(std::malloc(sizeof(float) * (n_rows ? n_rows : 1)));
  out->weights = static_cast<float*>(std::malloc(sizeof(float) * (n_rows ? n_rows : 1)));
  out->indices = static_cast<uint64_t*>(std::malloc(sizeof(uint64_t) * (n_values ? n_values : 1)));
  out->values = static_cast<float*>(std::malloc(sizeof(float) * (n_values ? n_values : 1)));
  out->fields = want_fields
      ? static_cast<uint32_t*>(std::malloc(sizeof(uint32_t) * (n_values ? n_values : 1)))
      : nullptr;
  if (!out->offsets || !out->labels || !out->weights || !out->indices || !out->values ||
      (want_fields && !out->fields)) {
    return -1;
  }
  int64_t row = 0, val = 0;
  out->offsets[0] = 0;
  for (auto& b : blocks) {
    std::memcpy(out->labels + row, b.labels.data(), b.labels.size() * sizeof(float));
    std::memcpy(out->weights + row, b.weights.data(), b.weights.size() * sizeof(float));
    std::memcpy(out->indices + val, b.indices.data(), b.indices.size() * sizeof(uint64_t));
    std::memcpy(out->values + val, b.values.data(), b.values.size() * sizeof(float));
    if (want_fields)
      std::memcpy(out->fields + val, b.fields.data(), b.fields.size() * sizeof(uint32_t));
    for (size_t i = 0; i < b.offsets.size(); ++i) {
      out->offsets[row + 1] = out->offsets[row] + b.offsets[i];
      ++row;
    }
    val += static_cast<int64_t>(b.values.size());
  }
  return 0;
}

// ---------------- fused fixed-shape batch packer ----------------
//
// Packs CSR rows into the pipeline's fused device buffer layout (one int32
// buffer per batch, one h2d transfer: see pipeline/device_loader.py
// _put_fused_buf).  v2 layout — row_ptr instead of per-value segments, and
// the nnz region sized to the *actual* values rounded up to `quantum`
// (bucket B), so a rows-limited batch ships ~half the bytes of the padded
// v1 layout and the per-value segment ids are rebuilt on device from
// row_ptr (a 1 scattered at every row's end, then one prefix sum):
//   [0,        B)            ids      int32   (pad 0)
//   [B,        2B)           vals     f32 bits (pad 0.0 -> scratch row)
//   [2B,       2B+rows+1)    row_ptr  int32   (pad rows repeat nnz)
//   [...,      +rows)        labels   f32 bits
//   [...,      +rows)        weights  f32 bits (padding rows weigh 0)
// words(B) = 2*B + 3*rows + 1.
//
// A row's ids are staged in source order, repeats included, and never
// sorted or merged, in both layouts: a row may be a document and its ids
// its tokens (value-less libsvm tokens carry 1.0).
//
// Replaces the per-batch numpy pack path (reference equivalent: the consumer
// loop materialising RowBlocks, basic_row_iter.h:61-82 — here rows stream
// straight into device-transfer staging).  A batch closes when either
// batch_rows rows or nnz_cap values are reached; closing early on nnz
// pressure loses NO data (the next batch continues), only single rows wider
// than nnz_cap are truncated (counted).  Feature ids must fit int32 unless
// id_mod (feature hashing) is set: overflow returns an error instead of
// silently wrapping (VERDICT r1 #5).
//
// v3 "compact wire" mode (dmlc_packer2_set_compact): host→device bandwidth
// was the pipeline's narrowest link when this was written, so the wire
// format spends host cycles to cut wire bytes — LOSSLESSLY:
//   * ids are bit-packed at the batch's actual width (bucketed to nibble
//     multiples, e.g. a 1M-feature space ships 20-bit ids: -37%);
//   * values are dictionary-coded (u16 codes + f32 dict) when the batch's
//     distinct-value count is small — real-world libsvm values are
//     few-distinct (binary features, 4-decimal quantized floats) — chosen
//     per batch only when codes+dict < raw f32, else raw fallback.
// Layout v3: [ids packed w-bit][codes u16 | raw vals][dict][row_ptr][labels]
// [weights]; decode on device (device_loader make_decoder) is shifts only:
// widths are multiples of 4 (ids) and 2 (codes), so a stream repeats every
// 32/gcd(w,32) <= 16 values and each place in a group has one word and one
// shift, fixed when the program is traced.  The one gather left is the
// dictionary lookup.  Reconstruction is bit-exact; code 0 is reserved for
// 0.0f so nnz padding decodes to 0.0 exactly like v2.  The emit meta is
// B | (id_width << 32) | (log2(dict_words) << 40); id_width 0 = v2 layout,
// dict_bits 0 = raw values.

struct PackerC {
  int64_t batch_rows;
  int64_t nnz_cap;
  int64_t quantum;       // nnz bucket granularity (<= nnz_cap)
  uint64_t id_mod;       // 0 = no hashing; ids must be < 2^31
  // staging batch (separate regions: the emitted offsets depend on B)
  std::vector<int32_t> ids_s, vals_s;   // nnz_cap
  std::vector<int32_t> rp_s;            // batch_rows + 1
  std::vector<int32_t> labs_s, wgts_s;  // batch_rows
  int64_t row_count = 0;
  int64_t nnz_count = 0;
  // v3 compact wire state.  The value dictionary persists across batches:
  // real datasets repeat the same value set (binary features, quantized
  // floats), so after the first batch lookups are pure hits in a small
  // table instead of a rebuild per batch.  It starts tiny and grows 4x on
  // load; after two consecutive overflowing batches (genuinely
  // high-cardinality values) dictionary coding is disabled for good.
  bool compact = false;
  uint32_t ormask = 0;                  // OR of staged ids → bit width
  std::vector<uint16_t> codes_scratch;  // per-batch value codes (pre-pack)
  // open-addressing slots: key | code<<32 in ONE uint64 (one cache line
  // per probe); slot 0 = empty (key 0 ⇒ reserved code 0, never stored)
  std::vector<uint64_t> dslots;
  std::vector<uint32_t> dvals;          // value bit patterns by code
  int64_t dict_tsize = 0;
  int dict_strikes = 0;                 // consecutive overflowing batches
  bool dict_disabled = false;

  void dict_rebuild(int64_t tsize) {
    dict_tsize = tsize;
    dslots.assign(tsize, 0);
    for (size_t c = 1; c < dvals.size(); ++c) {  // code 0 (=0.0f) not stored
      const uint32_t key = dvals[c];
      int64_t h = static_cast<int64_t>(key * 2654435761u) & (tsize - 1);
      while (dslots[h] != 0) h = (h + 1) & (tsize - 1);
      dslots[h] = key | (static_cast<uint64_t>(c) << 32);
    }
  }

  // code for a value bit pattern, inserting if new; -1 when the dict would
  // exceed `cap` entries (caller falls back to raw values for this batch)
  int32_t val_code(uint32_t key, int64_t cap) {
    if (key == 0) return 0;
    const int64_t tmask = dict_tsize - 1;
    int64_t h = static_cast<int64_t>(key * 2654435761u) & tmask;
    for (;;) {
      const uint64_t s = dslots[h];
      if (static_cast<uint32_t>(s) == key)
        return static_cast<int32_t>(s >> 32);
      if (s == 0) {
        if (static_cast<int64_t>(dvals.size()) > cap) return -1;
        const int32_t code = static_cast<int32_t>(dvals.size());
        dvals.push_back(key);
        dslots[h] = key | (static_cast<uint64_t>(code) << 32);
        if (static_cast<int64_t>(dvals.size()) * 2 > dict_tsize)
          dict_rebuild(dict_tsize * 4);
        return code;
      }
      h = (h + 1) & tmask;
    }
  }
  // aggregate stats
  int64_t total_rows = 0;
  int64_t padded_rows = 0;
  int64_t truncated_values = 0;
  int64_t batches = 0;

  PackerC(int64_t rows, int64_t nnz, int64_t quant, uint64_t mod)
      : batch_rows(rows), nnz_cap(nnz),
        quantum(quant <= 0 ? nnz : (quant > nnz ? nnz : quant)),
        id_mod(mod), ids_s(nnz), vals_s(nnz), rp_s(rows + 1),
        labs_s(rows), wgts_s(rows) {
    rp_s[0] = 0;
  }

  // round nnz_count up to the bucket the device-side jit cache is keyed on
  int64_t bucket() const {
    int64_t b = (nnz_count + quantum - 1) / quantum * quantum;
    if (b < quantum) b = quantum;
    return b > nnz_cap ? nnz_cap : b;
  }

  // row_ptr|labels|weights tail shared by both layouts, then reset staging
  void write_tail(int32_t* rp) {
    std::memcpy(rp, rp_s.data(), (row_count + 1) * 4);
    for (int64_t r = row_count + 1; r <= batch_rows; ++r)
      rp[r] = static_cast<int32_t>(nnz_count);
    int32_t* labs = rp + batch_rows + 1;
    std::memcpy(labs, labs_s.data(), row_count * 4);
    std::memset(labs + row_count, 0, (batch_rows - row_count) * 4);
    int32_t* wgts = labs + batch_rows;
    std::memcpy(wgts, wgts_s.data(), row_count * 4);
    std::memset(wgts + row_count, 0, (batch_rows - row_count) * 4);
    padded_rows += batch_rows - row_count;
    total_rows += row_count;
    ++batches;
    row_count = 0;
    nnz_count = 0;
    ormask = 0;
  }

  // write the staged batch into out; returns the emit meta
  // (B | id_width<<32 | dict_bits<<40; id_width 0 = v2 layout)
  int64_t emit(int32_t* out) {
    if (compact) return emit_v3(out);
    const int64_t B = bucket();
    std::memcpy(out, ids_s.data(), nnz_count * 4);
    std::memset(out + nnz_count, 0, (B - nnz_count) * 4);
    std::memcpy(out + B, vals_s.data(), nnz_count * 4);
    std::memset(out + B + nnz_count, 0, (B - nnz_count) * 4);
    write_tail(out + 2 * B);
    return B;
  }

  static int64_t next_pow2(int64_t v) {
    int64_t p = 2;
    while (p < v) p <<= 1;
    return p;
  }

  // pack n w-bit values into dst (dst_words pre-sized; zeroed tail = the
  // nnz padding, which must decode to id 0 / code 0)
  template <typename T>
  static void pack_bits(const T* src, int64_t n, int w, int32_t* dst,
                        int64_t dst_words) {
    std::memset(dst, 0, dst_words * 4);
    uint64_t acc = 0;
    int bits = 0;
    int32_t* d = dst;
    for (int64_t i = 0; i < n; ++i) {
      acc |= static_cast<uint64_t>(static_cast<uint32_t>(src[i])) << bits;
      bits += w;
      while (bits >= 32) {
        *d++ = static_cast<int32_t>(static_cast<uint32_t>(acc));
        acc >>= 32;
        bits -= 32;
      }
    }
    if (bits > 0)
      *d = static_cast<int32_t>(static_cast<uint32_t>(acc));
  }

  int64_t emit_v3(int32_t* out) {
    const int64_t B = bucket();
    // id bit width from the staged OR-mask (same top bit as the max),
    // bucketed to nibble multiples so the device-side jit cache stays small
    int w = 1;
    while (w < 32 && (ormask >> w) != 0) ++w;
    w = (w + 3) & ~3;
    if (w < 8) w = 8;
    const int64_t IW = (B * static_cast<int64_t>(w) + 31) / 32;
    pack_bits(ids_s.data(), nnz_count, w, out, IW);
    // values: dictionary attempt (code 0 reserved for 0.0f = nnz padding);
    // codes bit-pack at exactly dbits = log2(dict_words) — binary-feature
    // datasets (2-entry dict) ship 1-bit codes instead of u16
    const int64_t cap = std::min<int64_t>(65535, B / 2);
    bool dict_ok = cap >= 2 && !dict_disabled;
    int dbits = 0;
    int64_t vw = 0;
    if (dict_ok) {
      if (dict_tsize == 0) {
        dvals.clear();
        dvals.push_back(0);  // code 0 → 0.0f
        dict_rebuild(4096);
      }
      if (static_cast<int64_t>(codes_scratch.size()) < nnz_cap)
        codes_scratch.resize(nnz_cap);
      const uint32_t* vb = reinterpret_cast<const uint32_t*>(vals_s.data());
      for (int64_t i = 0; i < nnz_count; ++i) {
        const int32_t code = val_code(vb[i], cap);
        if (code < 0) {  // value cardinality blew the cap: raw this batch
          dict_ok = false;
          if (++dict_strikes >= 2) dict_disabled = true;
          break;
        }
        codes_scratch[i] = static_cast<uint16_t>(code);
      }
      if (dict_ok) {
        dict_strikes = 0;
        // quantize dbits to the even ladder {2,4,...,16} so a growing
        // dict steps through ≤8 code widths total (dbits is part of the
        // device-side jit cache key, and each new width is a recompile) —
        // binary-feature data still gets 2-bit codes, at most one wasted
        // bit per code elsewhere
        int db = 0;
        for (int64_t t = next_pow2(static_cast<int64_t>(dvals.size()));
             t > 1; t >>= 1) ++db;
        db = ((db + 1) / 2) * 2;
        if (db < 2) db = 2;
        const int64_t DW = 1ll << db;
        const int64_t CW = (B * static_cast<int64_t>(db) + 31) / 32;
        if (CW + DW > B) {
          dict_ok = false;  // dict doesn't beat raw for this (small) batch
        } else {
          pack_bits(codes_scratch.data(), nnz_count, db, out + IW, CW);
          int32_t* dreg = out + IW + CW;
          std::memset(dreg, 0, DW * 4);
          std::memcpy(dreg, dvals.data(), dvals.size() * 4);
          vw = CW + DW;
          dbits = db;
        }
      }
    }
    if (!dict_ok) {  // raw f32 fallback (overwrites any partial codes)
      std::memcpy(out + IW, vals_s.data(), nnz_count * 4);
      std::memset(out + IW + nnz_count, 0, (B - nnz_count) * 4);
      vw = B;
      dbits = 0;
    }
    write_tail(out + IW + vw);
    return B | (static_cast<int64_t>(w) << 32)
             | (static_cast<int64_t>(dbits) << 40);
  }
};

// ---------------- fused streaming parse→pack (libsvm) ----------------
//
// One pass: text chunk → fused wire batches, no CSR block in between.  The
// two-stage path materialises every value three times (ThreadBlock scratch
// → adopted CSR arrays → packer staging); on a serial ingest host those
// extra passes are the measured difference between ~340 and ~400 MB/s of
// text rate (BENCH_capacity: parse_only vs pack_null).  InputSplit chunks
// are record-aligned (io/input_split.py byte-range realign), so rows never
// span a feed call and no cross-chunk carry is needed.
//
// Row semantics mirror parse_sparse_range(kLibSVM) exactly — label[:weight]
// head, value-less tokens ⇒ 1.0, a bad token keeps the values parsed so
// far and counts the line bad — and batch-close semantics mirror
// dmlc_packer2_feed (close on batch_rows or nnz pressure; single rows
// wider than nnz_cap truncated and counted).  Equivalence is pinned by
// tests/test_pipeline.py::test_streampack_matches_two_stage.

struct SpPackC {
  PackerC packer;
  raw_vector<int32_t> row_ids;   // one parsed row, pre-hash, pre-close
  raw_vector<float> row_vals;
  int64_t bad_lines = 0;
  bool lone_cr = false;  // cached per chunk (pos==0) — recomputing on every
                         // resumed feed call would rescan the chunk tail
                         // once per emitted batch
  SpPackC(int64_t rows, int64_t nnz, int64_t quant, uint64_t mod)
      : packer(rows, nnz, quant, mod) {
    row_ids.resize(static_cast<size_t>(nnz));
    row_vals.resize(static_cast<size_t>(nnz));
  }
};

}  // namespace

extern "C" {

void* dmlc_sppack_create(int64_t batch_rows, int64_t nnz_cap,
                         int64_t quantum, uint64_t id_mod) {
  if (batch_rows <= 0 || nnz_cap <= 0) return nullptr;
  return new (std::nothrow) SpPackC(batch_rows, nnz_cap, quantum, id_mod);
}

void dmlc_sppack_destroy(void* p) { delete static_cast<SpPackC*>(p); }

void dmlc_sppack_set_compact(void* p, int32_t on) {
  static_cast<SpPackC*>(p)->packer.compact = on != 0;
}

}  // extern "C" — the sparse feed core below is a C++ template

namespace {

// append one parsed row to the packer staging, emitting first when the
// batch is full.  Returns true when a batch left via out_buf.
inline bool sppack_push_row(PackerC* p, const int32_t* rid, const float* rvl,
                            int64_t k, uint32_t om, float label, float weight,
                            int32_t* out_buf, int64_t* out_meta) {
  const bool close =
      p->row_count == p->batch_rows || p->nnz_count + k > p->nnz_cap;
  if (close) *out_meta = p->emit(out_buf);
  std::memcpy(p->ids_s.data() + p->nnz_count, rid, k * 4);
  std::memcpy(reinterpret_cast<float*>(p->vals_s.data()) + p->nnz_count,
              rvl, k * 4);
  p->ormask |= om;
  reinterpret_cast<float*>(p->labs_s.data())[p->row_count] = label;
  reinterpret_cast<float*>(p->wgts_s.data())[p->row_count] = weight;
  ++p->row_count;
  p->nnz_count += k;
  p->rp_s[p->row_count] = static_cast<int32_t>(p->nnz_count);
  return close;
}

// Sparse-format streaming feed core (libsvm / libfm): parse text rows from
// data+*pos straight into the packer.  Returns 1 when a batch was emitted
// into out_buf (*out_meta = emit meta) — call again with the SAME data to
// continue; 0 when the text is exhausted (partial batch retained across
// calls/chunks); -2 on a feature id above int32 range with no id_mod.
template <Fmt F>
int32_t sppack_feed_sparse(SpPackC* s, const char* data, int64_t len,
                           int64_t* pos, int32_t* out_buf,
                           int64_t* out_meta) {
  PackerC* p = &s->packer;
  const char* cur = data + *pos;
  const char* end = data + len;
  if (*pos == 0) s->lone_cr = has_lone_cr(cur, end);
  const bool lone_cr = s->lone_cr;
  int32_t* rid = s->row_ids.data();
  float* rvl = s->row_vals.data();
  while (cur < end) {
    while (cur < end && is_eol(*cur)) ++cur;
    if (cur >= end) break;
    const char* line_end = line_end_of(cur, end, lone_cr);
    const char* P = cur;
    while (P < line_end && is_space(*P)) ++P;
    float label = 0.f, weight = 1.f;
    int n = parse_float(P, line_end, &label);
    if (n == 0) {  // empty/garbage line: skip
      const char* q = P;
      while (q < line_end && is_space(*q)) ++q;
      if (q != line_end) ++s->bad_lines;
      cur = line_end;
      continue;
    }
    P += n;
    if (P < line_end && *P == ':') {  // label:weight head
      ++P;
      n = parse_float(P, line_end, &weight);
      if (n == 0) {  // 'label:garbage' — drop the whole row
        ++s->bad_lines;
        cur = line_end;
        continue;
      }
      P += n;
    }
    int64_t k = 0;
    uint32_t om = 0;
    while (P < line_end) {
      while (P < line_end && is_space(*P)) ++P;
      if (P >= line_end) break;
      uint64_t a = 0;
      n = parse_uint64(P, line_end, &a);
      if (n == 0) { ++s->bad_lines; break; }
      P += n;
      float v = 1.0f;
      if (F == Fmt::kLibFM) {
        // field:idx:val — the fused wire carries no field region (the
        // loader's fields=False path; FFM uses the two-stage pack), so
        // the field id is validated and dropped
        if (P >= line_end || *P != ':') { ++s->bad_lines; break; }
        ++P;
        n = parse_uint64(P, line_end, &a);  // a = idx now
        if (n == 0) { ++s->bad_lines; break; }
        P += n;
        if (P >= line_end || *P != ':') { ++s->bad_lines; break; }
        ++P;
        n = parse_float(P, line_end, &v);
        if (n == 0) { ++s->bad_lines; break; }
        P += n;
      } else {
        // libsvm: value-less token 'idx' ⇒ implicit 1.0
        if (P < line_end && *P == ':') {
          ++P;
          n = parse_float(P, line_end, &v);
          if (n == 0) { ++s->bad_lines; break; }
          P += n;
        }
      }
      if (k < p->nnz_cap) {
        uint32_t id;
        if (p->id_mod) {
          id = static_cast<uint32_t>(a % p->id_mod);
        } else {
          if (a > 0x7fffffffULL) { *pos = cur - data; return -2; }
          id = static_cast<uint32_t>(a);
        }
        rid[k] = static_cast<int32_t>(id);
        rvl[k] = v;
        om |= id;
        ++k;
      } else {
        // single row wider than a whole batch: tail values are dropped —
        // including any oversized ids in them, matching dmlc_packer2_feed
        // (which truncates k BEFORE its overflow scan)
        ++p->truncated_values;
      }
    }
    const bool close = sppack_push_row(p, rid, rvl, k, om, label, weight,
                                       out_buf, out_meta);
    cur = line_end;
    if (close) {
      *pos = cur - data;
      return 1;
    }
  }
  *pos = end - data;
  return 0;
}

}  // namespace

extern "C" {

int32_t dmlc_sppack_feed_libsvm(void* vp, const char* data, int64_t len,
                                int64_t* pos, int32_t* out_buf,
                                int64_t* out_meta) {
  return sppack_feed_sparse<Fmt::kLibSVM>(static_cast<SpPackC*>(vp), data,
                                          len, pos, out_buf, out_meta);
}

int32_t dmlc_sppack_feed_libfm(void* vp, const char* data, int64_t len,
                               int64_t* pos, int32_t* out_buf,
                               int64_t* out_meta) {
  return sppack_feed_sparse<Fmt::kLibFM>(static_cast<SpPackC*>(vp), data,
                                         len, pos, out_buf, out_meta);
}

// Dense csv rows: every column a value (id = position among value
// columns), one column (or none: -1) the label; a row with any
// unparseable cell is dropped whole (parse_csv_range semantics).
int32_t dmlc_sppack_feed_csv(void* vp, const char* data, int64_t len,
                             int32_t label_col, char delim, int64_t* pos,
                             int32_t* out_buf, int64_t* out_meta) {
  SpPackC* s = static_cast<SpPackC*>(vp);
  PackerC* p = &s->packer;
  const char* cur = data + *pos;
  const char* end = data + len;
  if (*pos == 0) s->lone_cr = has_lone_cr(cur, end);
  const bool lone_cr = s->lone_cr;
  int32_t* rid = s->row_ids.data();
  float* rvl = s->row_vals.data();
  while (cur < end) {
    while (cur < end && is_eol(*cur)) ++cur;
    if (cur >= end) break;
    const char* line_end = line_end_of(cur, end, lone_cr);
    const char* P = cur;
    float label = 0.f;
    int64_t col = 0, k = 0;
    uint32_t om = 0;
    bool ok = true;
    while (true) {  // one iteration per field (runs once for empty tail)
      while (P < line_end && is_space(*P)) ++P;
      float v = 0.f;
      int n = parse_float(P, line_end, &v);
      if (n == 0) {
        // empty cell parses as 0.0; anything unparseable kills the row
        if (P < line_end && *P != delim && !is_space(*P)) {
          ok = false;
          break;
        }
      }
      P += n;
      while (P < line_end && is_space(*P)) ++P;
      if (col == label_col) {
        label = v;
      } else if (k < p->nnz_cap) {
        // column position is the feature id (hashed like any other id)
        const uint32_t id = p->id_mod
            ? static_cast<uint32_t>(static_cast<uint64_t>(k) % p->id_mod)
            : static_cast<uint32_t>(k);
        rid[k] = static_cast<int32_t>(id);
        rvl[k] = v;
        om |= id;
        ++k;
      } else {
        ++p->truncated_values;
      }
      ++col;
      if (P < line_end && *P == delim) { ++P; continue; }
      break;
    }
    if (!ok || P != line_end) {
      ++s->bad_lines;
      cur = line_end;
      continue;
    }
    const bool close = sppack_push_row(p, rid, rvl, k, om, label, 1.0f,
                                       out_buf, out_meta);
    cur = line_end;
    if (close) {
      *pos = cur - data;
      return 1;
    }
  }
  *pos = end - data;
  return 0;
}

int64_t dmlc_sppack_flush(void* vp, int32_t* out_buf, int64_t* out_meta) {
  PackerC* p = &static_cast<SpPackC*>(vp)->packer;
  const int64_t rows = p->row_count;
  if (rows == 0) return 0;
  *out_meta = p->emit(out_buf);
  return rows;
}

void dmlc_sppack_stats(void* vp, int64_t* rows, int64_t* padded_rows,
                       int64_t* truncated_values, int64_t* batches,
                       int64_t* bad_lines) {
  SpPackC* s = static_cast<SpPackC*>(vp);
  // pending partial-batch rows count as parsed rows (the two-stage path
  // counts rows at parse time; stats must agree mid-stream)
  *rows = s->packer.total_rows + s->packer.row_count;
  *padded_rows = s->packer.padded_rows;
  *truncated_values = s->packer.truncated_values;
  *batches = s->packer.batches;
  *bad_lines = s->bad_lines;
}

void* dmlc_packer2_create(int64_t batch_rows, int64_t nnz_cap,
                          int64_t quantum, uint64_t id_mod) {
  if (batch_rows <= 0 || nnz_cap <= 0) return nullptr;
  return new (std::nothrow) PackerC(batch_rows, nnz_cap, quantum, id_mod);
}

void dmlc_packer2_destroy(void* p) { delete static_cast<PackerC*>(p); }

// Toggle the v3 compact wire layout (bit-packed ids + dict-coded values);
// takes effect from the next emitted batch.
void dmlc_packer2_set_compact(void* p, int32_t on) {
  static_cast<PackerC*>(p)->compact = on != 0;
}

// Feed rows [start_row, n_rows) of a CSR block; write finished batches into
// out_bufs[0..max_out) and each batch's nnz bucket B into out_nnz[i].
// Returns the number of batches emitted (>= 0) and sets *consumed_rows to
// the absolute row index reached; the caller loops until consumed == n_rows.
// Returns -2 when a feature id exceeds int32 range and no id_mod is
// configured.  weights/values may be null (implicit 1.0).  A partial batch
// stays in the packer across calls (and across blocks) until flush.
int64_t dmlc_packer2_feed(void* vp, int64_t n_rows, const int64_t* offsets,
                          const float* labels, const float* weights,
                          const uint64_t* indices, const float* values,
                          int64_t start_row, int32_t** out_bufs,
                          int64_t* out_nnz, int64_t max_out,
                          int64_t* consumed_rows) {
  PackerC* p = static_cast<PackerC*>(vp);
  int64_t emitted = 0;
  const int64_t base = offsets[0];
  int64_t r = start_row;
  for (; r < n_rows; ++r) {
    const int64_t b = offsets[r] - base, e = offsets[r + 1] - base;
    int64_t k = e - b;
    if (k > p->nnz_cap) {  // single row wider than a whole batch
      p->truncated_values += k - p->nnz_cap;
      k = p->nnz_cap;
    }
    if (p->row_count == p->batch_rows || p->nnz_count + k > p->nnz_cap) {
      if (emitted == max_out) break;  // caller must drain first
      out_nnz[emitted] = p->emit(out_bufs[emitted]);
      ++emitted;
    }
    int32_t* ids = p->ids_s.data() + p->nnz_count;
    float* vals = reinterpret_cast<float*>(p->vals_s.data()) + p->nnz_count;
    uint32_t om = 0;
    if (p->id_mod) {
      for (int64_t j = 0; j < k; ++j) {
        const uint32_t id = static_cast<uint32_t>(indices[b + j] % p->id_mod);
        om |= id;
        ids[j] = static_cast<int32_t>(id);
      }
    } else {
      for (int64_t j = 0; j < k; ++j) {
        const uint64_t id = indices[b + j];
        if (id > 0x7fffffffULL) { *consumed_rows = r; return -2; }
        om |= static_cast<uint32_t>(id);
        ids[j] = static_cast<int32_t>(id);
      }
    }
    p->ormask |= om;
    if (values) {
      std::memcpy(vals, values + b, k * 4);
    } else {
      for (int64_t j = 0; j < k; ++j) vals[j] = 1.0f;
    }
    reinterpret_cast<float*>(p->labs_s.data())[p->row_count] = labels[r];
    reinterpret_cast<float*>(p->wgts_s.data())[p->row_count] =
        weights ? weights[r] : 1.0f;
    ++p->row_count;
    p->nnz_count += k;
    p->rp_s[p->row_count] = static_cast<int32_t>(p->nnz_count);
  }
  *consumed_rows = r;
  return emitted;
}

// Flush the open partial batch (padded) into out_buf; returns the number of
// real rows flushed (0 = nothing pending) and sets *out_nnz to the bucket.
int64_t dmlc_packer2_flush(void* vp, int32_t* out_buf, int64_t* out_nnz) {
  PackerC* p = static_cast<PackerC*>(vp);
  const int64_t rows = p->row_count;
  if (rows == 0) return 0;
  *out_nnz = p->emit(out_buf);
  return rows;
}

void dmlc_packer2_stats(void* vp, int64_t* total_rows, int64_t* padded_rows,
                        int64_t* truncated_values, int64_t* batches) {
  PackerC* p = static_cast<PackerC*>(vp);
  *total_rows = p->total_rows;
  *padded_rows = p->padded_rows;
  *truncated_values = p->truncated_values;
  *batches = p->batches;
}

int dmlc_parse_libsvm(const char* data, int64_t len, int nthreads, CSRBlockC* out) {
  return parse_parallel(data, len, /*want_fields=*/false, nthreads, out,
                        [](const char* b, const char* e, ThreadBlock* tb) {
                          parse_sparse_range(b, e, Fmt::kLibSVM, tb);
                        });
}

int dmlc_parse_libfm(const char* data, int64_t len, int nthreads, CSRBlockC* out) {
  return parse_parallel(data, len, /*want_fields=*/true, nthreads, out,
                        [](const char* b, const char* e, ThreadBlock* tb) {
                          parse_sparse_range(b, e, Fmt::kLibFM, tb);
                        });
}

int dmlc_parse_csv(const char* data, int64_t len, int label_col, char delim,
                   int nthreads, CSRBlockC* out) {
  return parse_parallel(data, len, /*want_fields=*/false, nthreads, out,
                        [label_col, delim](const char* b, const char* e, ThreadBlock* tb) {
                          parse_csv_range(b, e, label_col, delim, tb);
                        });
}

void dmlc_free_block(CSRBlockC* blk) {
  if (blk->owner) {
    delete static_cast<BlockOwner*>(blk->owner);
    blk->owner = nullptr;
  } else {
    std::free(blk->offsets);
    std::free(blk->labels);
    std::free(blk->weights);
    std::free(blk->indices);
    std::free(blk->values);
    std::free(blk->fields);
  }
  blk->offsets = nullptr;
  blk->labels = blk->weights = blk->values = nullptr;
  blk->indices = nullptr;
  blk->fields = nullptr;
}

int dmlc_num_threads() {
#if defined(_OPENMP)
  return omp_get_max_threads();
#else
  return 1;
#endif
}

}  // extern "C"
