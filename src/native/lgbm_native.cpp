// Native data-loading runtime for lightgbm_tpu.
//
// TPU-native equivalent of the reference's C++ IO layer: the text
// parsers (reference src/io/parser.cpp — CSV/TSV/LibSVM with per-token
// Atof, driven by utils/text_reader.h streaming), and the hot
// value->bin encode loop (reference Feature::PushData + BinMapper::
// ValueToBin binary search, include/LightGBM/bin.h:353-375,
// feature.h:79-85).  The compute path (histograms, split search) lives
// on the TPU; this library keeps host-side ingest off the Python
// interpreter: files are read once into memory, line boundaries are
// found, and rows are parsed in parallel with OpenMP — the same
// structure as the reference's multi-threaded two-pass loader
// (src/io/dataset_loader.cpp:500-605), minus sockets.
//
// Exposed via a C ABI consumed with ctypes (no pybind11 in this image).

#include <cctype>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#ifdef _OPENMP
#include <omp.h>
#endif

namespace {

// Read a whole file into memory (the reference streams 1MB blocks,
// text_reader.h:144-288; at bench scale a single read is simpler and at
// least as fast).
bool ReadFile(const char* path, std::vector<char>* out) {
  FILE* fp = std::fopen(path, "rb");
  if (fp == nullptr) return false;
  std::fseek(fp, 0, SEEK_END);
  long size = std::ftell(fp);
  if (size < 0) {  // non-seekable (FIFO etc.): let the Python path read it
    std::fclose(fp);
    return false;
  }
  std::fseek(fp, 0, SEEK_SET);
  out->resize(static_cast<size_t>(size) + 1);
  size_t got = std::fread(out->data(), 1, static_cast<size_t>(size), fp);
  std::fclose(fp);
  if (got != static_cast<size_t>(size)) return false;
  (*out)[got] = '\0';
  return true;
}

// Offsets of each non-empty line's first char, plus its end.
void SplitLines(const char* buf, size_t len,
                std::vector<std::pair<size_t, size_t>>* lines) {
  size_t start = 0;
  for (size_t i = 0; i <= len; ++i) {
    if (i == len || buf[i] == '\n') {
      size_t end = i;
      if (end > start && buf[end - 1] == '\r') --end;
      if (end > start) lines->emplace_back(start, end);
      start = i + 1;
    }
  }
}

inline bool IsSep(char c, char sep) {
  return sep == ' ' ? (c == ' ' || c == '\t') : c == sep;
}

// Recognized NA spellings: pandas' default NA set plus the explicit
// na_values the python fallback passes (io/parser.py) — the two readers
// must accept the SAME tokens or a file parses under one and hard-fails
// under the other.
bool IsNaToken(const char* p, const char* end) {
  size_t len = static_cast<size_t>(end - p);
  if (len == 0) return true;
  static const char* kNa[] = {
      "NA",   "N/A", "NaN",  "nan",  "NULL", "null", "None", "n/a",
      "<NA>", "#NA", "#N/A", "-NaN", "-nan", "NaT",
  };
  for (const char* na : kNa) {
    size_t nl = std::strlen(na);
    if (len == nl && std::strncmp(p, na, nl) == 0) return true;
  }
  return false;
}

// Parse one delimited line into row[0..cols); missing/empty/NA -> NaN.
// Returns false on malformed input (extra fields or non-numeric garbage)
// so the caller can fail the whole parse and fall back to the strict
// Python reader — silent truncation must never feed training.
bool ParseDelimited(const char* s, const char* end, char sep, double* row,
                    long cols) {
  long j = 0;
  const char* p = s;
  while (p < end) {
    // skip leading blanks inside field boundaries for space-separated
    if (sep == ' ') {
      while (p < end && (*p == ' ' || *p == '\t')) ++p;
      if (p >= end) break;
    }
    if (j >= cols) return false;  // ragged line with EXTRA fields
    const char* field_end = p;
    while (field_end < end && !IsSep(*field_end, sep)) ++field_end;
    if (field_end == p) {
      row[j++] = NAN;  // empty field
    } else {
      char* q = nullptr;
      double v = std::strtod(p, &q);
      if (q == field_end) {
        row[j++] = v;
      } else if (IsNaToken(p, field_end)) {
        row[j++] = NAN;
      } else {
        return false;  // malformed numeric (e.g. "1.5abc")
      }
    }
    p = field_end;
    if (sep != ' ' && p < end && IsSep(*p, sep)) ++p;
  }
  while (j < cols) row[j++] = NAN;  // SHORT lines pad with NaN (pandas-like)
  return true;
}

// Count fields of a delimited line.
long CountFields(const char* s, const char* end, char sep) {
  if (sep == ' ') {
    long cnt = 0;
    const char* p = s;
    while (p < end) {
      while (p < end && (*p == ' ' || *p == '\t')) ++p;
      if (p >= end) break;
      ++cnt;
      while (p < end && *p != ' ' && *p != '\t') ++p;
    }
    return cnt;
  }
  long cnt = 1;
  for (const char* p = s; p < end; ++p)
    if (*p == sep) ++cnt;
  return cnt;
}

}  // namespace

extern "C" {

void lgbm_free(void* p) { std::free(p); }

// Detect format from the first data line: 3=libsvm (all idx:value after
// the first token), 1=csv, 2=tab/whitespace (parser.cpp:72-144).
int lgbm_detect_format(const char* path, int skip_header) {
  std::vector<char> buf;
  if (!ReadFile(path, &buf)) return -1;
  std::vector<std::pair<size_t, size_t>> lines;
  SplitLines(buf.data(), buf.size() - 1, &lines);
  size_t first = skip_header ? 1 : 0;
  if (lines.size() <= first) return -1;
  const char* s = buf.data() + lines[first].first;
  const char* end = buf.data() + lines[first].second;
  // tokenize on any whitespace/comma
  bool has_colon_all = true, any_token = false, has_tab = false,
       has_comma = false;
  const char* p = s;
  int token_i = 0;
  while (p < end) {
    while (p < end && (*p == ' ' || *p == '\t' || *p == ',')) {
      if (*p == '\t') has_tab = true;
      if (*p == ',') has_comma = true;
      ++p;
    }
    if (p >= end) break;
    const char* tok = p;
    while (p < end && *p != ' ' && *p != '\t' && *p != ',') ++p;
    if (token_i > 0) {
      any_token = true;
      bool colon = false;
      for (const char* q = tok; q < p; ++q)
        if (*q == ':') colon = true;
      if (!colon) has_colon_all = false;
    }
    ++token_i;
  }
  if (any_token && has_colon_all) return 3;
  if (has_comma && !has_tab) return 1;
  return 2;
}

// Parse a delimited (csv=1 / whitespace-or-tab=2) file into a dense
// row-major double matrix.  Returns 0 on success; caller frees *out_data
// with lgbm_free.
int lgbm_parse_delimited(const char* path, int fmt, int skip_header,
                         double** out_data, long* out_rows, long* out_cols) {
  std::vector<char> buf;
  if (!ReadFile(path, &buf)) return 1;
  std::vector<std::pair<size_t, size_t>> lines;
  SplitLines(buf.data(), buf.size() - 1, &lines);
  size_t first = skip_header ? 1 : 0;
  if (lines.size() <= first) return 2;
  long n = static_cast<long>(lines.size() - first);

  char sep = ',';
  if (fmt != 1) {  // fmt 2: whitespace, honoring real tabs
    sep = ' ';
    const char* s = buf.data() + lines[first].first;
    const char* e = buf.data() + lines[first].second;
    for (const char* p = s; p < e; ++p)
      if (*p == '\t') {
        sep = '\t';
        break;
      }
  }
  long cols = CountFields(buf.data() + lines[first].first,
                          buf.data() + lines[first].second, sep);
  if (cols <= 0) return 3;

  double* data =
      static_cast<double*>(std::malloc(sizeof(double) * n * cols));
  if (data == nullptr) return 4;

  int bad = 0;
#pragma omp parallel for schedule(static) reduction(| : bad)
  for (long i = 0; i < n; ++i) {
    const auto& ln = lines[first + i];
    if (!ParseDelimited(buf.data() + ln.first, buf.data() + ln.second, sep,
                        data + i * cols, cols))
      bad |= 1;
  }
  if (bad) {  // malformed file: strict python reader takes over
    std::free(data);
    return 5;
  }
  *out_data = data;
  *out_rows = n;
  *out_cols = cols;
  return 0;
}

// Parse a LibSVM file ("label idx:val ...") into a dense matrix with the
// label in column 0 (mirroring how the loader consumes it).
int lgbm_parse_libsvm(const char* path, int skip_header, double** out_data,
                      long* out_rows, long* out_cols) {
  std::vector<char> buf;
  if (!ReadFile(path, &buf)) return 1;
  std::vector<std::pair<size_t, size_t>> lines;
  SplitLines(buf.data(), buf.size() - 1, &lines);
  size_t first = skip_header ? 1 : 0;
  if (lines.size() <= first) return 2;
  long n = static_cast<long>(lines.size() - first);

  // pass 1: max feature index (parallel reduction).  Non-integer index
  // tokens (e.g. "qid:3") make the whole parse fail so the strict python
  // path reports them instead of silently corrupting column 0.
  long max_idx = -1;
  int bad = 0;
#pragma omp parallel for schedule(static) reduction(max : max_idx) \
    reduction(| : bad)
  for (long i = 0; i < n; ++i) {
    const char* p = buf.data() + lines[first + i].first;
    const char* end = buf.data() + lines[first + i].second;
    bool first_tok = true;
    while (p < end) {
      const char* colon = nullptr;
      const char* tok = p;
      while (p < end && *p != ' ' && *p != '\t') {
        if (*p == ':') colon = p;
        ++p;
      }
      if (!first_tok) {
        if (colon == nullptr || colon == tok) {
          bad |= 1;
        } else {
          char* q = nullptr;
          long idx = std::strtol(tok, &q, 10);
          if (q != colon) {
            bad |= 1;  // index token isn't a pure integer ("qid" et al)
          } else if (idx > max_idx) {
            max_idx = idx;
          }
        }
      }
      first_tok = false;
      while (p < end && (*p == ' ' || *p == '\t')) ++p;
    }
  }
  if (bad) return 5;
  long cols = max_idx + 2;  // +1 label column
  double* data =
      static_cast<double*>(std::calloc(static_cast<size_t>(n) * cols,
                                       sizeof(double)));
  if (data == nullptr) return 4;

#pragma omp parallel for schedule(static)
  for (long i = 0; i < n; ++i) {
    const char* p = buf.data() + lines[first + i].first;
    const char* end = buf.data() + lines[first + i].second;
    double* row = data + i * cols;
    bool first_tok = true;
    while (p < end) {
      const char* tok = p;
      const char* colon = nullptr;
      while (p < end && *p != ' ' && *p != '\t') {
        if (*p == ':') colon = p;
        ++p;
      }
      if (first_tok) {
        row[0] = std::strtod(tok, nullptr);
        first_tok = false;
      } else if (colon != nullptr) {
        long idx = std::strtol(tok, nullptr, 10);
        double v = std::strtod(colon + 1, nullptr);
        if (idx >= 0 && idx + 1 < cols) row[idx + 1] = v;
      }
      while (p < end && (*p == ' ' || *p == '\t')) ++p;
    }
  }
  *out_data = data;
  *out_rows = n;
  *out_cols = cols;
  return 0;
}

// Hot encode loop: values -> bins by upper-bound binary search for many
// numerical features at once (BinMapper::ValueToBin, bin.h:353-366;
// Feature::PushData, feature.h:79-85).  X is row-major [n, f_total];
// col_idx[j] names the source column of used feature j; bounds holds the
// concatenated per-feature upper-bound arrays with prefix offsets.
// out is row-major [n, n_used], u8 or u16 selected by out_is_u16.
void lgbm_value_to_bin(const double* X, long n, long f_total,
                       const long* col_idx, long n_used,
                       const double* bounds, const long* bound_offsets,
                       void* out, int out_is_u16) {
  uint8_t* out8 = static_cast<uint8_t*>(out);
  uint16_t* out16 = static_cast<uint16_t*>(out);
#pragma omp parallel for schedule(static)
  for (long i = 0; i < n; ++i) {
    const double* row = X + i * f_total;
    for (long j = 0; j < n_used; ++j) {
      double v = row[col_idx[j]];
      const double* b = bounds + bound_offsets[j];
      long nb = bound_offsets[j + 1] - bound_offsets[j];
      // a NaN last bound marks the column's missing bin (io/binner.py):
      // a NaN value goes there; elsewhere NA reads as 0 before binning
      const bool has_missing = nb > 1 && std::isnan(b[nb - 1]);
      long lo = 0, hi = nb - 1 - (has_missing ? 1 : 0);
      if (std::isnan(v)) {
        if (has_missing) lo = hi = nb - 1;
        v = 0.0;
      }
      // first bound >= v (upper_bound[k-1] < v <= upper_bound[k])
      while (lo < hi) {
        long mid = (lo + hi) >> 1;
        if (b[mid] < v)
          lo = mid + 1;
        else
          hi = mid;
      }
      if (out_is_u16)
        out16[i * n_used + j] = static_cast<uint16_t>(lo);
      else
        out8[i * n_used + j] = static_cast<uint8_t>(lo);
    }
  }
}

int lgbm_num_threads() {
#ifdef _OPENMP
  return omp_get_max_threads();
#else
  return 1;
#endif
}

// ---------------------------------------------------------------------
// Chunked streaming reader — the native half of two-round loading
// (reference TextReader/PipelineReader, utils/text_reader.h:144-288 +
// dataset_loader.cpp:181-209): rows are parsed block by block so peak
// memory is one block + the caller's chunk buffer, never the file.

namespace {

constexpr size_t kBlockBytes = 4 << 20;  // 4MB read granularity

struct ChunkReader {
  FILE* fp = nullptr;
  char sep = ',';
  long cols = 0;
  bool sep_known = false;
  std::vector<char> carry;  // unconsumed text (partial or surplus lines)
  bool eof = false;
};

// Establish sep + column count from the first non-empty line.
bool SniffLine(const char* s, const char* end, int fmt, char* sep,
               long* cols) {
  char sp = ',';
  if (fmt != 1) {
    sp = ' ';
    for (const char* p = s; p < end; ++p)
      if (*p == '\t') {
        sp = '\t';
        break;
      }
  }
  long c = CountFields(s, end, sp);
  if (c <= 0) return false;
  *sep = sp;
  *cols = c;
  return true;
}

}  // namespace

void* lgbm_chunk_open(const char* path, int fmt, int skip_header,
                      long* out_cols) {
  FILE* fp = std::fopen(path, "rb");
  if (fp == nullptr) return nullptr;
  ChunkReader* r = new ChunkReader();
  r->fp = fp;
  // pull blocks until the header (if any) and one full data line are seen
  std::vector<char> buf;
  long skipped = skip_header ? 0 : 1;  // 0 = header still pending
  while (true) {
    size_t off = buf.size();
    buf.resize(off + kBlockBytes);
    size_t got = std::fread(buf.data() + off, 1, kBlockBytes, fp);
    buf.resize(off + got);
    if (got == 0) r->eof = true;
    // find first data line
    size_t start = 0;
    for (size_t i = 0; i <= buf.size(); ++i) {
      if (i == buf.size() && !r->eof) break;  // need more data
      if (i == buf.size() || buf[i] == '\n') {
        size_t end = i;
        if (end > start && buf[end - 1] == '\r') --end;
        bool blank = true;
        for (size_t k = start; k < end; ++k)
          if (!std::isspace(static_cast<unsigned char>(buf[k]))) blank = false;
        if (!blank && skipped == 0) {
          skipped = 1;  // header consumed: drop it from the carry
          r->carry.assign(buf.begin() + (i == buf.size() ? i : i + 1),
                          buf.end());
          buf = r->carry;
          start = 0;
          i = static_cast<size_t>(-1);  // restart scan on remaining text
          continue;
        }
        if (!blank) {
          if (!SniffLine(buf.data() + start, buf.data() + end, fmt, &r->sep,
                         &r->cols)) {
            std::fclose(fp);
            delete r;
            return nullptr;
          }
          r->sep_known = true;
          r->carry = std::move(buf);
          *out_cols = r->cols;
          return r;
        }
        start = i + 1;
      }
    }
    if (r->eof) {  // empty (or header-only) file
      r->carry = std::move(buf);
      *out_cols = 0;
      return r;
    }
  }
}

// Parse up to max_rows rows into out (row-major [max_rows, cols]).
// Returns rows parsed; 0 at EOF; -1 on malformed input (caller falls
// back to the strict python reader / raises).
long lgbm_chunk_next(void* handle, double* out, long max_rows) {
  ChunkReader* r = static_cast<ChunkReader*>(handle);
  if (r->cols == 0) return 0;
  // top up the carry until it holds max_rows complete lines or EOF.
  // Count incrementally — only freshly read bytes are scanned, so the
  // loop stays linear in the chunk size.
  auto count_in_range = [&](size_t beg, size_t endpos) {
    long cnt = 0;
    size_t start = beg;
    for (size_t i = beg; i < endpos; ++i) {
      if (r->carry[i] == '\n') {
        size_t end = i;
        if (end > start && r->carry[end - 1] == '\r') --end;
        bool blank = true;
        for (size_t k = start; k < end; ++k)
          if (!std::isspace(static_cast<unsigned char>(r->carry[k])))
            blank = false;
        if (!blank) ++cnt;
        start = i + 1;
      }
    }
    return cnt;
  };
  // scanning must restart at the line START containing the first
  // unscanned byte, so track the last newline seen instead of raw bytes
  long complete = count_in_range(0, r->carry.size());
  while (!r->eof && complete < max_rows) {
    size_t off = r->carry.size();
    size_t line_start = off;
    while (line_start > 0 && r->carry[line_start - 1] != '\n') --line_start;
    r->carry.resize(off + kBlockBytes);
    size_t got = std::fread(r->carry.data() + off, 1, kBlockBytes, r->fp);
    r->carry.resize(off + got);
    if (got == 0) r->eof = true;
    complete += count_in_range(line_start, r->carry.size());
  }
  // split the carry into lines; keep surplus + partial tail
  std::vector<std::pair<size_t, size_t>> lines;
  size_t consumed = 0;
  size_t start = 0;
  for (size_t i = 0; i <= r->carry.size(); ++i) {
    bool is_end = (i == r->carry.size());
    if (is_end && !r->eof) break;  // partial tail stays in carry
    if (is_end || r->carry[i] == '\n') {
      size_t end = i;
      if (end > start && r->carry[end - 1] == '\r') --end;
      bool blank = true;
      for (size_t k = start; k < end; ++k)
        if (!std::isspace(static_cast<unsigned char>(r->carry[k])))
          blank = false;
      if (!blank) {
        if (static_cast<long>(lines.size()) >= max_rows) break;
        lines.emplace_back(start, end);
      }
      consumed = is_end ? i : i + 1;
      start = i + 1;
    }
  }
  long n = static_cast<long>(lines.size());
  if (n == 0) return 0;
  int bad = 0;
#pragma omp parallel for schedule(static) reduction(| : bad)
  for (long i = 0; i < n; ++i) {
    if (!ParseDelimited(r->carry.data() + lines[i].first,
                        r->carry.data() + lines[i].second, r->sep,
                        out + i * r->cols, r->cols))
      bad |= 1;
  }
  if (bad) return -1;
  r->carry.erase(r->carry.begin(), r->carry.begin() + consumed);
  return n;
}

void lgbm_chunk_close(void* handle) {
  ChunkReader* r = static_cast<ChunkReader*>(handle);
  if (r->fp) std::fclose(r->fp);
  delete r;
}

}  // extern "C"
