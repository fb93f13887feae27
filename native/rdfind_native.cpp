// Native ingest runtime: multi-file N-Triples/N-Quads reader + tokenizer +
// string interner, exposed as a C API for ctypes.
//
// Plays the role of the reference's JVM ingest infrastructure —
// MultiFileTextInputFormat (rdfind-flink/.../persistence/MultiFileTextInputFormat
// .java:49-368: many files, gz-aware, comment filtering) plus the rdf-converter
// NTriples/NQuads parsers (RDFind.scala:219-237) plus the value dictionary
// (here exact interning, see rdfind_tpu/dictionary.py) — fused into one pass so
// triple ids land directly in an int32 buffer ready for the device pipeline.
//
// Two execution modes share one handle type:
//
//   * the SERIAL path (rdf_ingest_file + rdf_ingest_finalize): one thread,
//     one interner, byte-sort + remap at the end.  This is the reference
//     implementation of the id contract below and stays deliberately simple
//     (its scalar scan is the differential oracle for the SWAR fast path).
//   * the PARALLEL STREAMING path (rdf_ingest_begin / rdf_ingest_next_block /
//     rdf_ingest_stream_finish): a work-stealing unit queue feeding N worker
//     threads, each with its own arena-backed interner emitting provisional
//     thread-local ids.  Committed unit blocks stream to the caller IN UNIT
//     ORDER while later units still parse; the finish step hash-partitions
//     the per-thread interners into S shards (crc32 % S — the SAME partition
//     function as the multi-host dictionary, rdfind_tpu/dictionary.py:
//     value_shard), dedupes each shard in parallel, S-way-merges the
//     shard-sorted runs into the byte-sorted global rank order, and exports
//     per-thread local→global remap tables for the caller to rewrite its
//     streamed blocks.
//
// The byte-level hot loop runs three speed rungs (each independently
// switchable via rdf_ingest_set_opts, all bit-identical to the scalar
// reference by construction):
//
//   1. SWAR scanning: newline / field / literal delimiters are found 8 bytes
//      at a time with the zero-byte trick ((x - 0x0101..) & ~x & 0x8080..);
//      a scalar loop finishes the tail, so CRLF / comment / quad edge cases
//      see exactly the bytes the scalar path sees.
//   2. mmap zero-copy: plain files are mapped once per handle and interners
//      store (ptr, len) views INTO the mapping — term bytes are copied only
//      when a distinct value first enters the arena from a transient buffer
//      (gz output, fread chunks, subtask buffers).  Mappings outlive
//      finalize: the exported sorted values view them directly.
//   3. Parallel gzip: multi-member .gz files are split at exact member
//      boundaries (cheap magic-candidate scan, then an inflate pass that
//      records the consumed offset at each Z_STREAM_END — candidates alone
//      are not trustworthy) and the members fan out onto the unit queue;
//      a large single-member .gz gets a two-stage decode→parse pipeline:
//      a decoder thread inflates into newline-snapped chunk buffers pushed
//      onto a bounded subtask queue that idle workers (and the unit's own
//      leader) parse concurrently, delivered to the caller in chunk order.
//
// The id contract (ALL paths, bit-identical by construction):
//   * terms keep surface syntax (<iri>, _:blank, "lit"@lang, "lit"^^<t>);
//   * ids are ranks in byte-sorted order of the distinct values, which equals
//     np.unique's code-point order for valid UTF-8;
//   * triples keep input order (file order, then line order; a split plain
//     file's chunks and a split gz's members/subtasks are delivered in
//     offset order);
//   * universal newlines (\n, \r\n, \r), '#' comment lines skipped;
//   * .gz inputs transparently decompressed; gzip content is detected by
//     magic sniff as well as extension (zlib gzopen also passes through
//     plain files, so one stream path serves both).
//
// Chunk ownership rule (Hadoop-style line splits): a chunk [o, e) with o > 0
// first discards bytes through the first line terminator at/after o, then
// parses every line whose first byte starts at position <= e (reading past e
// to finish its last line).  A line starting exactly at e belongs to the
// chunk ENDING at e; the next chunk's unconditional discard drops it.  Every
// line is therefore parsed exactly once, for any chunking.

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>
#include <zlib.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <numeric>
#include <string>
#include <string_view>
#include <thread>
#include <unordered_map>
#include <utility>
#include <vector>

namespace {

using Clock = std::chrono::steady_clock;

int64_t ns_since(Clock::time_point t0) {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(Clock::now() -
                                                              t0)
      .count();
}

// --- SWAR primitives -------------------------------------------------------
//
// The ctz-based first-match index assumes little-endian byte order; on a
// big-endian build the word loop compiles out and every find falls through
// to the scalar tail, which is always correct.
#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
#define RDF_SWAR_LE 1
#else
#define RDF_SWAR_LE 0
#endif

constexpr uint64_t kOnes = 0x0101010101010101ull;
constexpr uint64_t kHigh = 0x8080808080808080ull;

inline uint64_t load64(const char* p) {
  uint64_t w;
  memcpy(&w, p, 8);
  return w;
}

// High bit set in every byte of x that was zero.
inline uint64_t zero_bytes(uint64_t x) { return (x - kOnes) & ~x & kHigh; }

// First byte in [p, end) equal to a or b; end if absent.
inline const char* find2(const char* p, const char* end, char a, char b,
                         bool swar) {
#if RDF_SWAR_LE
  if (swar) {
    const uint64_t ba = kOnes * static_cast<uint8_t>(a);
    const uint64_t bb = kOnes * static_cast<uint8_t>(b);
    while (end - p >= 8) {
      uint64_t w = load64(p);
      uint64_t hit = zero_bytes(w ^ ba) | zero_bytes(w ^ bb);
      if (hit) return p + (__builtin_ctzll(hit) >> 3);
      p += 8;
    }
  }
#else
  (void)swar;
#endif
  while (p < end && *p != a && *p != b) p++;
  return p;
}

inline const char* find_eol(const char* p, const char* end, bool swar) {
  return find2(p, end, '\n', '\r', swar);
}

// 64-bit bytes hash for the interner's open-addressing table (murmur-style
// finalizer over 8-byte SWAR strides).  Ids never depend on this hash —
// they are first-occurrence insertion order — so any mixing change is
// output-invisible.
inline uint64_t hash_bytes(const char* p, size_t n) {
  uint64_t h = 0x9e3779b97f4a7c15ull ^ static_cast<uint64_t>(n);
  size_t i = 0;
  for (; i + 8 <= n; i += 8) {
    h = (h ^ load64(p + i)) * 0xff51afd7ed558ccdull;
    h ^= h >> 29;
  }
  if (i < n) {
    uint64_t w = 0;
    memcpy(&w, p + i, n - i);
    h = (h ^ w) * 0xc4ceb9fe1a85ec53ull;
    h ^= h >> 29;
  }
  h *= 0xff51afd7ed558ccdull;
  h ^= h >> 32;
  return h;
}

// Per-phase ingest telemetry (exported via rdf_ingest_stats/stats2).
// Worker-side counters are atomics (summed across threads); merge-stage and
// begin-stage counters are written single-threaded.
struct Stats {
  std::atomic<int64_t> bytes_read{0};  // post-decompression bytes parsed
  std::atomic<int64_t> read_ns{0};     // plain-file fread time
  std::atomic<int64_t> decode_ns{0};   // gz read+inflate time (zlib fuses them)
  std::atomic<int64_t> parse_ns{0};    // tokenize+intern (unit wall - I/O)
  int64_t intern_ns = 0;               // shard dedupe+sort (dictionary build)
  int64_t merge_ns = 0;                // partition + global rank merge
  int64_t remap_ns = 0;                // local->global table construction
  std::atomic<int64_t> queue_stalls{0};  // next_block waits that blocked
  std::atomic<int64_t> stall_ns{0};      // total blocked time in next_block
  std::atomic<int64_t> n_subtasks{0};    // pipelined gz chunks emitted
  int64_t mmap_bytes = 0;                // input bytes served zero-copy
  int64_t n_members = 0;                 // gz members split onto the queue
  int64_t n_units = 0;
  int64_t n_files = 0;
  int n_threads = 1;
};

// Interner: open-addressing hash table over (ptr, len) value views.  Stable
// bytes (mmap-backed) are referenced in place; transient bytes (gz output,
// fread buffers) are copied into stable deque arena chunks first.  One per
// handle on the serial path; one per worker thread on the parallel path.
struct Interner {
  std::deque<std::string> arena;         // owned bytes for transient inputs
  std::vector<std::string_view> by_id;   // provisional id -> value bytes
  // Power-of-two open addressing: keys live in by_id; probes compare the
  // stored 64-bit hash first and memcmp only on hash match.
  std::vector<uint64_t> slot_hash;
  std::vector<int32_t> slot_id;
  size_t mask = 0;
  size_t grow_at = 0;

  Interner() { rehash(1 << 12); }

  void rehash(size_t cap) {
    std::vector<uint64_t> oh = std::move(slot_hash);
    std::vector<int32_t> oi = std::move(slot_id);
    slot_hash.assign(cap, 0);
    slot_id.assign(cap, -1);
    mask = cap - 1;
    grow_at = cap - cap / 4;  // resize at 3/4 load
    for (size_t s = 0; s < oi.size(); s++) {
      if (oi[s] < 0) continue;
      size_t j = oh[s] & mask;
      while (slot_id[j] >= 0) j = (j + 1) & mask;
      slot_hash[j] = oh[s];
      slot_id[j] = oi[s];
    }
  }

  int32_t intern_token(const char* s, size_t len, bool stable) {
    uint64_t h = hash_bytes(s, len);
    size_t j = h & mask;
    while (slot_id[j] >= 0) {
      if (slot_hash[j] == h) {
        std::string_view v = by_id[slot_id[j]];
        if (v.size() == len && memcmp(v.data(), s, len) == 0)
          return slot_id[j];
      }
      j = (j + 1) & mask;
    }
    const char* bytes = s;
    if (!stable) {
      arena.emplace_back(s, len);
      bytes = arena.back().data();
    }
    int32_t id = static_cast<int32_t>(by_id.size());
    by_id.emplace_back(bytes, len);
    slot_hash[j] = h;
    slot_id[j] = id;
    if (by_id.size() >= grow_at) rehash((mask + 1) * 2);
    return id;
  }
};

// Everything one parsed line needs: where ids come from, where triples go,
// where errors land, and which scan mode / byte-stability applies.
struct ParseCtx {
  Interner* in;
  std::vector<int32_t>* triples;
  std::string* error;
  bool swar = true;    // SWAR delimiter scanning (scalar oracle when false)
  bool stable = false; // line bytes outlive the handle (mmap-backed)
};

struct Parallel;  // fwd

struct Mapping {
  void* addr;
  size_t len;
};

struct Ingest {
  Interner dict;                  // serial-path interner
  std::vector<int32_t> triples;   // serial path: flat (n, 3)
  std::vector<int32_t> remap;     // serial path: provisional id -> rank
  // Export representation shared by both paths after finalize/stream_finish:
  std::vector<std::string_view> sorted_vals;  // byte-sorted distinct values
  std::vector<int64_t> sorted_offsets;        // prefix offsets
  int64_t values_bytes = 0;
  std::string error;
  bool finalized = false;
  Stats stats;
  // Speed-rung knobs (rdf_ingest_set_opts; resolved Python-side from env).
  bool opt_swar = true;
  bool opt_mmap = true;
  bool opt_gz_pipeline = true;
  int64_t opt_gz_chunk = 8ll << 20;  // decoded bytes per pipelined subtask
  // File mappings live as long as the handle: interner views and the
  // exported sorted values point into them.
  std::vector<Mapping> mappings;
  std::unordered_map<std::string, const char*> mapped_by_path;
  std::unique_ptr<Parallel> par;  // non-null once rdf_ingest_begin ran

  const char* map_file(const std::string& path, int64_t size) {
    auto it = mapped_by_path.find(path);
    if (it != mapped_by_path.end()) return it->second;
    int fd = open(path.c_str(), O_RDONLY);
    if (fd < 0) return nullptr;
    void* a = mmap(nullptr, static_cast<size_t>(size), PROT_READ, MAP_PRIVATE,
                   fd, 0);
    close(fd);
    if (a == MAP_FAILED) return nullptr;
#ifdef MADV_SEQUENTIAL
    (void)madvise(a, static_cast<size_t>(size), MADV_SEQUENTIAL);
#endif
    mappings.push_back({a, static_cast<size_t>(size)});
    mapped_by_path.emplace(path, static_cast<const char*>(a));
    return static_cast<const char*>(a);
  }

  ~Ingest();
};

// --- Tokenizer (mirrors ntriples._scan_term) -------------------------------

struct Term {
  const char* p;
  size_t len;
};

bool is_ws(char c) { return c == ' ' || c == '\t'; }

// Scans one term at line[i]; returns next index or (size_t)-1 on error.
size_t scan_term(const char* line, size_t i, size_t n, Term* out,
                 std::string* err, bool swar) {
  char c = line[i];
  if (c == '<') {  // IRI
    const char* close =
        static_cast<const char*>(memchr(line + i + 1, '>', n - i - 1));
    if (!close) {
      *err = "unterminated IRI";
      return static_cast<size_t>(-1);
    }
    size_t j = static_cast<size_t>(close - line) + 1;
    *out = {line + i, j - i};
    return j;
  }
  if (c == '"') {  // literal with escapes, optional @lang / ^^<dtype>
    const char* end = line + n;
    const char* q = line + i + 1;
    while (true) {
      if (q >= end) {
        *err = "unterminated literal";
        return static_cast<size_t>(-1);
      }
      q = find2(q, end, '"', '\\', swar);
      if (q == end) {
        *err = "unterminated literal";
        return static_cast<size_t>(-1);
      }
      if (*q == '"') break;
      q += 2;  // skip the escape pair, keep scanning
    }
    size_t j = static_cast<size_t>(q - line) + 1;  // past closing quote
    if (j < n && line[j] == '@') {
      j = static_cast<size_t>(find2(line + j, line + n, ' ', '\t', swar) -
                              line);
    } else if (j + 1 < n && line[j] == '^' && line[j + 1] == '^') {
      j += 2;
      if (j < n && line[j] == '<') {
        const char* close =
            static_cast<const char*>(memchr(line + j + 1, '>', n - j - 1));
        if (!close) {
          *err = "unterminated datatype IRI";
          return static_cast<size_t>(-1);
        }
        j = static_cast<size_t>(close - line) + 1;
      }
    }
    *out = {line + i, j - i};
    return j;
  }
  // blank node / bare token: read to whitespace
  size_t j =
      static_cast<size_t>(find2(line + i, line + n, ' ', '\t', swar) - line);
  *out = {line + i, j - i};
  return j;
}

// Parses one line into interned (s, p, o); returns 1 on triple, 0 on blank
// line, -1 on error.
int parse_line(ParseCtx* ctx, const char* line, size_t n, bool tabs,
               bool expect_quad) {
  if (tabs) {
    // split("\t"), need >= 3 fields (parse_tab_line).
    bool blank = true;
    for (size_t k = 0; k < n; k++) {
      if (!is_ws(line[k])) {
        blank = false;
        break;
      }
    }
    if (blank) return 0;
    const char* field = line;
    const char* end = line + n;
    int32_t ids[3];
    int got = 0;
    while (got < 3) {
      const char* tab =
          static_cast<const char*>(memchr(field, '\t', end - field));
      const char* fe = tab ? tab : end;
      ids[got++] = ctx->in->intern_token(field, fe - field, ctx->stable);
      if (!tab) break;
      field = tab + 1;
    }
    if (got < 3) {
      *ctx->error = "expected 3 tab-separated fields";
      return -1;
    }
    ctx->triples->insert(ctx->triples->end(), ids, ids + 3);
    return 1;
  }
  size_t i = 0;
  int32_t ids[3];
  int got = 0;
  int want = expect_quad ? 4 : 3;
  while (i < n && got < want) {
    while (i < n && is_ws(line[i])) i++;
    if (i >= n || line[i] == '.') break;
    Term t;
    i = scan_term(line, i, n, &t, ctx->error, ctx->swar);
    if (i == static_cast<size_t>(-1)) return -1;
    if (got < 3) ids[got] = ctx->in->intern_token(t.p, t.len, ctx->stable);
    got++;
  }
  if (got == 0) return 0;
  if (got < 3) {
    *ctx->error = "expected 3 terms, got " + std::to_string(got);
    return -1;
  }
  ctx->triples->insert(ctx->triples->end(), ids, ids + 3);
  return 1;
}

// --- Byte sources ----------------------------------------------------------

// Sequential decoded-byte reader: one interface serves gzopen streams (gz
// files and plain passthrough) and single raw gzip members, so the line
// streamer and the pipeline decoder share one read loop.
struct ByteSource {
  virtual int64_t read(char* dst, int64_t cap) = 0;  // >0 bytes, 0 EOF, <0 err
  virtual std::string error_detail() const = 0;
  virtual ~ByteSource() {}
};

struct GzSource : ByteSource {
  gzFile f = nullptr;
  std::string err;
  explicit GzSource(const char* path) {
    f = gzopen(path, "rb");
    if (f) gzbuffer(f, 1 << 20);
  }
  bool ok() const { return f != nullptr; }
  int64_t read(char* dst, int64_t cap) override {
    int n = gzread(f, dst, static_cast<unsigned>(cap));
    if (n < 0) {
      int errnum = 0;
      err = gzerror(f, &errnum);
    }
    return n;
  }
  std::string error_detail() const override { return err; }
  ~GzSource() {
    if (f) gzclose(f);
  }
};

// Inflates exactly ONE gzip member occupying [off, off+len) of path (raw
// inflate with the gzip wrapper; stops at Z_STREAM_END).
struct MemberSource : ByteSource {
  FILE* f = nullptr;
  z_stream strm{};
  std::vector<char> inbuf;
  int64_t remaining;
  bool stream_end = false;
  bool inited = false;
  std::string err;
  MemberSource(const char* path, int64_t off, int64_t len)
      : inbuf(1 << 18), remaining(len) {
    f = fopen(path, "rb");
    if (!f) return;
    if (off > 0 && fseek(f, static_cast<long>(off), SEEK_SET) != 0) {
      fclose(f);
      f = nullptr;
      return;
    }
    if (inflateInit2(&strm, 16 + MAX_WBITS) != Z_OK) {
      fclose(f);
      f = nullptr;
      return;
    }
    inited = true;
  }
  bool ok() const { return f != nullptr; }
  int64_t read(char* dst, int64_t cap) override {
    if (stream_end) return 0;
    strm.next_out = reinterpret_cast<Bytef*>(dst);
    strm.avail_out = static_cast<uInt>(cap);
    while (strm.avail_out > 0) {
      if (strm.avail_in == 0 && remaining > 0) {
        size_t want = static_cast<size_t>(
            std::min<int64_t>(static_cast<int64_t>(inbuf.size()), remaining));
        size_t n = fread(inbuf.data(), 1, want, f);
        if (n == 0) {
          err = "truncated gzip member";
          return -1;
        }
        remaining -= static_cast<int64_t>(n);
        strm.next_in = reinterpret_cast<Bytef*>(inbuf.data());
        strm.avail_in = static_cast<uInt>(n);
      }
      int rc = inflate(&strm, Z_NO_FLUSH);
      if (rc == Z_STREAM_END) {
        stream_end = true;
        break;
      }
      if (rc != Z_OK && rc != Z_BUF_ERROR) {
        err = "corrupt gzip member";
        return -1;
      }
      if (strm.avail_in == 0 && remaining == 0) {
        err = "truncated gzip member";
        return -1;
      }
    }
    return cap - static_cast<int64_t>(strm.avail_out);
  }
  std::string error_detail() const override { return err; }
  ~MemberSource() {
    if (inited) inflateEnd(&strm);
    if (f) fclose(f);
  }
};

// --- Line streaming --------------------------------------------------------

// Streams universal-newline lines from a ByteSource into
// handle(line, len) -> bool.  Returns false on read error or handle failure
// (err set).  io_ns/bytes accumulate read+decode telemetry.
template <typename H>
bool for_stream_lines(ByteSource& src, const char* path, bool swar,
                      std::string* err, H&& handle, int64_t* io_ns,
                      int64_t* bytes_read) {
  std::vector<char> buf(1 << 20);
  std::string carry;  // partial line across read chunks
  bool ok = true;
  while (ok) {
    auto t0 = Clock::now();
    int64_t nread = src.read(buf.data(), static_cast<int64_t>(buf.size()));
    *io_ns += ns_since(t0);
    if (nread < 0) {
      *err = std::string("read error in ") + path + ": " + src.error_detail();
      return false;
    }
    if (nread == 0) break;
    *bytes_read += nread;
    const char* p = buf.data();
    const char* end = p + nread;
    while (p < end) {
      const char* nl = find_eol(p, end, swar);
      if (nl == end) {  // no terminator in the rest of this chunk
        carry.append(p, end - p);
        break;
      }
      if (!carry.empty()) {
        carry.append(p, nl - p);
        ok = handle(carry.data(), carry.size());
        carry.clear();
      } else {
        ok = handle(p, nl - p);
      }
      if (!ok) break;
      // universal newlines: \r\n counts once
      p = nl + ((*nl == '\r' && nl + 1 < end && nl[1] == '\n') ? 2 : 1);
      // NB: a \r\n split exactly across chunks yields one empty extra line,
      // which parses as blank — harmless.
    }
  }
  if (ok && !carry.empty()) ok = handle(carry.data(), carry.size());
  return ok;
}

// Streams the lines OWNED by byte range [off, off+len) of a plain file (see
// the chunk ownership rule in the header comment) into handle().  The fread
// path: used when mmap is disabled or failed.
template <typename H>
bool for_chunk_lines(const char* path, int64_t off, int64_t len, bool swar,
                     std::string* err, H&& handle, int64_t* read_ns,
                     int64_t* bytes_read) {
  FILE* f = fopen(path, "rb");
  if (!f) {
    *err = std::string("cannot open ") + path;
    return false;
  }
  if (off > 0 && fseek(f, static_cast<long>(off), SEEK_SET) != 0) {
    *err = std::string("cannot seek in ") + path;
    fclose(f);
    return false;
  }
  const int64_t end = off + len;  // lines starting at pos <= end are ours
  std::vector<char> buf(1 << 20);
  std::string carry;
  bool discard = off > 0;  // drop through the first terminator (prev owns it)
  bool pending_cr = false;  // '\r' consumed at buffer end; eat a leading '\n'
  int64_t pos = off;        // absolute offset of the next unread byte
  int64_t line_start = off;
  bool ok = true;
  bool done = false;
  while (ok && !done) {
    // Read the owned range in full-buffer strides, then finish the final
    // line in small tail reads — the overshoot past `end` stays bounded by
    // one tail stride instead of a whole buffer.
    size_t want = buf.size();
    if (pos <= end)
      want = static_cast<size_t>(
          std::min<int64_t>(static_cast<int64_t>(want), end - pos + 1));
    else
      want = 4096;
    auto t0 = Clock::now();
    size_t nread = fread(buf.data(), 1, want, f);
    *read_ns += ns_since(t0);
    if (nread == 0) break;  // EOF (or error: tail handled below)
    *bytes_read += static_cast<int64_t>(nread);
    const char* p = buf.data();
    const char* bend = p + nread;
    if (pending_cr) {
      pending_cr = false;
      if (*p == '\n') {
        p++;
        pos++;
        line_start = pos;
        if (line_start > end) {
          done = true;
          break;
        }
      }
    }
    while (p < bend) {
      const char* nl = find_eol(p, bend, swar);
      if (nl == bend) {
        if (!discard) carry.append(p, bend - p);
        pos += bend - p;
        break;
      }
      if (discard) {
        discard = false;
      } else if (!carry.empty()) {
        carry.append(p, nl - p);
        ok = handle(carry.data(), carry.size());
        carry.clear();
      } else {
        ok = handle(p, nl - p);
      }
      if (!ok) break;
      int64_t term = 1;
      if (*nl == '\r') {
        if (nl + 1 < bend) {
          if (nl[1] == '\n') term = 2;
        } else {
          pending_cr = true;  // resolve against the next refill
        }
      }
      pos += (nl - p) + term;
      p = nl + term;
      line_start = pos;
      if (line_start > end) {
        done = true;
        break;
      }
    }
  }
  if (ok && !done && !discard && !carry.empty() && line_start <= end)
    ok = handle(carry.data(), carry.size());  // final unterminated line
  fclose(f);
  return ok;
}

// Streams the lines OWNED by [off, off+len) of a fully in-memory buffer
// (an mmap'd file, or one decoded pipeline subtask with off=0, len=size).
// Same ownership rule as for_chunk_lines, but zero-copy: handle() sees
// views into the buffer, and the final line of a chunk simply reads past
// `end` — no carry string, no pending-CR state.
template <typename H>
bool for_mapped_lines(const char* data, int64_t size, int64_t off,
                      int64_t len, bool swar, H&& handle,
                      int64_t* bytes_read) {
  const char* const eof = data + size;
  const int64_t end = off + len;  // lines starting at pos <= end are ours
  const char* p = data + off;
  if (off > 0) {  // discard through the first terminator (prev chunk owns it)
    const char* nl = find_eol(p, eof, swar);
    if (nl == eof) {  // chunk is the tail of the previous chunk's last line
      *bytes_read += eof - p;
      return true;
    }
    p = nl + ((*nl == '\r' && nl + 1 < eof && nl[1] == '\n') ? 2 : 1);
  }
  bool ok = true;
  while (ok && p < eof && (p - data) <= end) {
    const char* nl = find_eol(p, eof, swar);
    ok = handle(p, static_cast<size_t>(nl - p));
    if (nl == eof) {  // final unterminated line
      p = eof;
      break;
    }
    p = nl + ((*nl == '\r' && nl + 1 < eof && nl[1] == '\n') ? 2 : 1);
  }
  *bytes_read += p - (data + off);
  return ok;
}

// --- gz member discovery ---------------------------------------------------

// Exact member boundaries of a multi-member gzip file, or an empty vector
// when the file is single-member / unreadable / not worth splitting.  Two
// passes: a cheap scan for gzip magic candidates (1f 8b 08 with sane flag
// bits — NOT trustworthy, the magic can occur inside compressed data), then,
// only if a candidate exists, an inflate-discard pass recording the consumed
// input offset at each Z_STREAM_END — the only exact answer.  Any decode
// trouble returns empty so the normal single-unit path surfaces the error
// with the serial path's message.
std::vector<std::pair<int64_t, int64_t>> scan_gz_members(const char* path,
                                                         int64_t size) {
  std::vector<std::pair<int64_t, int64_t>> out;
  FILE* f = fopen(path, "rb");
  if (!f) return out;
  bool candidate = false;
  {
    std::vector<char> buf(1 << 20);
    int64_t base = 0;
    size_t carry = 0;
    while (!candidate) {
      size_t n = fread(buf.data() + carry, 1, buf.size() - carry, f);
      if (n == 0) break;
      size_t avail = carry + n;
      for (size_t i = 0; i + 4 <= avail; i++) {
        if (static_cast<unsigned char>(buf[i]) == 0x1f &&
            static_cast<unsigned char>(buf[i + 1]) == 0x8b &&
            static_cast<unsigned char>(buf[i + 2]) == 0x08 &&
            (static_cast<unsigned char>(buf[i + 3]) & 0xe0) == 0 &&
            base + static_cast<int64_t>(i) > 0) {
          candidate = true;
          break;
        }
      }
      size_t keep = avail >= 3 ? 3 : avail;
      memmove(buf.data(), buf.data() + avail - keep, keep);
      base += static_cast<int64_t>(avail - keep);
      carry = keep;
    }
  }
  if (!candidate) {
    fclose(f);
    return out;
  }
  if (fseek(f, 0, SEEK_SET) != 0) {
    fclose(f);
    return out;
  }
  z_stream strm{};
  if (inflateInit2(&strm, 16 + MAX_WBITS) != Z_OK) {
    fclose(f);
    return out;
  }
  std::vector<char> in(1 << 20), scratch(1 << 20);
  std::vector<int64_t> starts{0};
  int64_t fed = 0;
  bool fail = false;
  while (!fail) {
    if (strm.avail_in == 0) {
      size_t n = fread(in.data(), 1, in.size(), f);
      if (n == 0 && fed >= size) {
        fail = true;  // ran off the end without a final Z_STREAM_END
        break;
      }
      if (n == 0) {
        fail = true;
        break;
      }
      fed += static_cast<int64_t>(n);
      strm.next_in = reinterpret_cast<Bytef*>(in.data());
      strm.avail_in = static_cast<uInt>(n);
    }
    strm.next_out = reinterpret_cast<Bytef*>(scratch.data());
    strm.avail_out = static_cast<uInt>(scratch.size());
    int rc = inflate(&strm, Z_NO_FLUSH);
    if (rc == Z_STREAM_END) {
      int64_t consumed = fed - static_cast<int64_t>(strm.avail_in);
      if (consumed >= size) break;  // final member
      starts.push_back(consumed);
      if (inflateReset(&strm) != Z_OK) fail = true;
      continue;
    }
    if (rc != Z_OK && rc != Z_BUF_ERROR) fail = true;
  }
  inflateEnd(&strm);
  fclose(f);
  if (fail || starts.size() < 2) return out;
  for (size_t i = 0; i < starts.size(); i++) {
    int64_t end = (i + 1 < starts.size()) ? starts[i + 1] : size;
    out.emplace_back(starts[i], end - starts[i]);
  }
  return out;
}

// --- Parallel streaming engine ---------------------------------------------

enum UnitKind {
  K_STREAM,  // whole file via gzopen (gz single-member, or plain fallback)
  K_CHUNK,   // plain-file byte range via fread (mmap off/failed)
  K_MMAP,    // plain-file byte range via the handle's mapping (zero-copy)
  K_MEMBER,  // one gzip member: raw inflate of [off, off+len)
};

struct Unit {
  std::string path;
  UnitKind kind = K_STREAM;
  int64_t off = 0;
  int64_t len = 0;   // byte range (chunks/members) or file size (K_STREAM)
  bool is_gz = false;           // gzip content (extension or magic sniff)
  const char* map = nullptr;    // K_MMAP: base of the whole-file mapping
  int64_t map_size = 0;
};

// One decoded chunk of a pipelined gz unit, parsed by whichever worker pops
// it off the subtask queue; delivered to the caller in chunk order.
struct SubBlock {
  std::vector<int32_t> triples;  // provisional thread-local ids
  int thread = -1;
  std::string error;
  bool done = false;  // guarded by Parallel::mu
};

struct Subtask {
  size_t unit = 0;
  size_t idx = 0;     // index into results[unit].subs
  std::string data;   // decoded bytes, newline-snapped
};

struct UnitResult {
  std::vector<int32_t> triples;  // provisional thread-local ids
  int thread = -1;
  std::string error;
  bool skipped = false;  // queued after a failed unit; never delivered
  // Pipelined gz delivery state (all guarded by Parallel::mu):
  bool pipelined = false;
  bool decoder_done = false;
  std::deque<SubBlock> subs;           // grows as the decoder emits
  size_t n_subs_final = 0;             // valid once decoder_done
  size_t next_sub = 0;                 // delivery cursor
};

struct ThreadShard {
  Interner in;
  std::vector<int32_t> to_global;  // local id -> byte-sorted global rank
  // Per-merge-shard local-id buckets (filled by the partition stage, read by
  // the dedupe and remap stages).
  std::vector<std::vector<int32_t>> buckets;
};

struct Parallel {
  std::vector<Unit> units;
  std::vector<UnitResult> results;
  std::vector<std::unique_ptr<ThreadShard>> shards;  // one per worker thread
  std::vector<std::thread> workers;
  std::atomic<size_t> next_unit{0};
  // First failed unit index: workers skip units queued after it (best-effort
  // cancellation; earlier units still complete so in-order delivery reaches
  // the failure deterministically — the same "first error wins" surface as
  // the serial path).
  std::atomic<int64_t> abort_after{INT64_MAX};
  std::mutex mu;
  std::condition_variable cv;
  std::vector<char> done;  // guarded by mu
  size_t next_deliver = 0;
  std::vector<int32_t>* cur_triples = nullptr;  // last delivered block
  int cur_thread = -1;
  bool tabs = false, quad = false, skip_comments = true;
  bool swar = true, gz_pipeline = true;
  int64_t gz_chunk = 8ll << 20;
  // Decode→parse pipeline state (guarded by mu): decoders block while the
  // queue is at capacity; workers that run out of units drain it.
  std::deque<Subtask> subq;
  size_t subq_cap = 8;
  int active_pipelines = 0;
  bool joined = false;
  bool drained = false;

  void join_workers() {
    if (joined) return;
    for (auto& t : workers)
      if (t.joinable()) t.join();
    joined = true;
  }
  ~Parallel() { join_workers(); }
};

Ingest::~Ingest() {
  par.reset();  // joins workers before the mappings they read go away
  for (auto& m : mappings) munmap(m.addr, m.len);
}

void abort_at(Parallel* p, size_t u) {
  int64_t cur = p->abort_after.load();
  while (static_cast<int64_t>(u) < cur &&
         !p->abort_after.compare_exchange_weak(cur, static_cast<int64_t>(u)))
    ;
}

// Parses one decoded subtask buffer into its SubBlock slot.
void parse_subtask(Parallel* p, Subtask&& st, int thread_idx, Stats* stats) {
  UnitResult* res = &p->results[st.unit];
  SubBlock* sb;
  {
    std::lock_guard<std::mutex> lk(p->mu);
    sb = &res->subs[st.idx];  // deque: stable across concurrent push_back
  }
  std::vector<int32_t> triples;
  std::string err;
  if (p->abort_after.load() >= static_cast<int64_t>(st.unit)) {
    const Unit& u = p->units[st.unit];
    ThreadShard* sh = p->shards[thread_idx].get();
    ParseCtx ctx{&sh->in, &triples, &err, p->swar, /*stable=*/false};
    auto handle = [&](const char* line, size_t len) -> bool {
      if (p->skip_comments && len > 0 && line[0] == '#') return true;
      int rc = parse_line(&ctx, line, len, p->tabs, p->quad);
      if (rc < 0) {
        err += std::string(" in ") + u.path;
        return false;
      }
      return true;
    };
    int64_t dummy = 0;
    auto t0 = Clock::now();
    bool ok = for_mapped_lines(st.data.data(),
                               static_cast<int64_t>(st.data.size()), 0,
                               static_cast<int64_t>(st.data.size()), p->swar,
                               handle, &dummy);
    stats->parse_ns += ns_since(t0);
    if (!ok) abort_at(p, st.unit);
  }
  {
    std::lock_guard<std::mutex> lk(p->mu);
    sb->triples = std::move(triples);
    sb->thread = thread_idx;
    if (!err.empty()) sb->error = err;
    sb->done = true;
  }
  p->cv.notify_all();
}

// Largest prefix of s ending exactly after a line terminator (a '\n', or a
// '\r' that is provably not the first half of a straddling \r\n); 0 when no
// safe split point exists yet.
size_t split_point(const std::string& s) {
  for (size_t i = s.size(); i-- > 0;) {
    if (s[i] == '\n') return i + 1;
    if (s[i] == '\r' && i + 1 < s.size())
      return i + (s[i + 1] == '\n' ? 2 : 1);
  }
  return 0;
}

void emit_sub(Parallel* p, UnitResult* res, size_t u, size_t idx,
              std::string&& data, Stats* stats) {
  {
    std::unique_lock<std::mutex> lk(p->mu);
    p->cv.wait(lk, [&] { return p->subq.size() < p->subq_cap; });
    res->subs.emplace_back();
    p->subq.push_back(Subtask{u, idx, std::move(data)});
  }
  stats->n_subtasks++;
  p->cv.notify_all();
}

// Decoder half of the two-stage gz pipeline: inflate into newline-snapped
// chunk buffers and feed the bounded subtask queue.
void decoder_main(Parallel* p, size_t u, UnitResult* res, Stats* stats) {
  const Unit& unit = p->units[u];
  std::string derr;
  size_t emitted = 0;
  std::unique_ptr<ByteSource> src;
  if (unit.kind == K_MEMBER) {
    auto ms = std::make_unique<MemberSource>(unit.path.c_str(), unit.off,
                                             unit.len);
    if (ms->ok()) src = std::move(ms);
  } else {
    auto gs = std::make_unique<GzSource>(unit.path.c_str());
    if (gs->ok()) src = std::move(gs);
  }
  if (!src) {
    derr = std::string("cannot open ") + unit.path;
  } else {
    const int64_t chunk = std::max<int64_t>(p->gz_chunk, 256);
    std::vector<char> buf(
        static_cast<size_t>(std::min<int64_t>(chunk, 1 << 20)));
    std::string pend;
    while (true) {
      if (p->abort_after.load() < static_cast<int64_t>(u)) {
        pend.clear();  // cancelled: this unit will never be delivered
        break;
      }
      auto t0 = Clock::now();
      int64_t n = src->read(buf.data(), static_cast<int64_t>(buf.size()));
      stats->decode_ns += ns_since(t0);
      if (n < 0) {
        derr = std::string("read error in ") + unit.path + ": " +
               src->error_detail();
        break;
      }
      if (n == 0) break;
      stats->bytes_read += n;
      pend.append(buf.data(), static_cast<size_t>(n));
      while (static_cast<int64_t>(pend.size()) >= chunk) {
        size_t cut = split_point(pend);
        if (cut == 0) break;  // one line longer than the chunk: keep growing
        emit_sub(p, res, u, emitted++, pend.substr(0, cut), stats);
        pend.erase(0, cut);
      }
    }
    if (derr.empty() && !pend.empty() &&
        p->abort_after.load() >= static_cast<int64_t>(u))
      emit_sub(p, res, u, emitted++, std::move(pend), stats);
  }
  {
    std::lock_guard<std::mutex> lk(p->mu);
    if (!derr.empty()) res->error = derr;
    res->n_subs_final = emitted;
    res->decoder_done = true;
  }
  if (!derr.empty()) abort_at(p, u);
  p->cv.notify_all();
}

// Leader half of the pipeline: spawn the decoder, then parse subtasks (its
// own unit's or any other pipeline's) until the decoder finishes.
void run_pipeline(Parallel* p, size_t u, UnitResult* res, int thread_idx,
                  Stats* stats) {
  {
    std::lock_guard<std::mutex> lk(p->mu);
    res->pipelined = true;
    p->active_pipelines++;
  }
  p->cv.notify_all();
  std::thread dec(decoder_main, p, u, res, stats);
  while (true) {
    Subtask st;
    {
      std::unique_lock<std::mutex> lk(p->mu);
      p->cv.wait(lk, [&] { return !p->subq.empty() || res->decoder_done; });
      if (p->subq.empty()) break;  // implies decoder_done
      st = std::move(p->subq.front());
      p->subq.pop_front();
    }
    p->cv.notify_all();  // wake a decoder blocked on queue capacity
    parse_subtask(p, std::move(st), thread_idx, stats);
  }
  dec.join();
  {
    std::lock_guard<std::mutex> lk(p->mu);
    p->active_pipelines--;
  }
  p->cv.notify_all();
}

void process_unit(Parallel* p, size_t ui, int thread_idx, Stats* stats) {
  const Unit& u = p->units[ui];
  UnitResult* res = &p->results[ui];
  bool gz_unit = (u.kind == K_STREAM && u.is_gz) || u.kind == K_MEMBER;
  if (gz_unit && p->gz_pipeline && u.len > p->gz_chunk) {
    run_pipeline(p, ui, res, thread_idx, stats);
    return;
  }
  ThreadShard* sh = p->shards[thread_idx].get();
  std::string err;
  ParseCtx ctx{&sh->in, &res->triples, &err, p->swar,
               /*stable=*/u.kind == K_MMAP};
  auto handle = [&](const char* line, size_t len) -> bool {
    if (p->skip_comments && len > 0 && line[0] == '#') return true;
    int rc = parse_line(&ctx, line, len, p->tabs, p->quad);
    if (rc < 0) {
      err += std::string(" in ") + u.path;
      return false;
    }
    return true;
  };
  int64_t io_ns = 0, bytes = 0;
  auto t0 = Clock::now();
  bool ok;
  switch (u.kind) {
    case K_MMAP:
      ok = for_mapped_lines(u.map, u.map_size, u.off, u.len, p->swar, handle,
                            &bytes);
      break;
    case K_CHUNK:
      ok = for_chunk_lines(u.path.c_str(), u.off, u.len, p->swar, &err,
                           handle, &io_ns, &bytes);
      break;
    case K_MEMBER: {
      MemberSource src(u.path.c_str(), u.off, u.len);
      if (!src.ok()) {
        res->error = std::string("cannot open ") + u.path;
        return;
      }
      ok = for_stream_lines(src, u.path.c_str(), p->swar, &err, handle,
                            &io_ns, &bytes);
      break;
    }
    case K_STREAM:
    default: {
      GzSource src(u.path.c_str());
      if (!src.ok()) {
        res->error = std::string("cannot open ") + u.path;
        return;
      }
      ok = for_stream_lines(src, u.path.c_str(), p->swar, &err, handle,
                            &io_ns, &bytes);
      break;
    }
  }
  int64_t wall = ns_since(t0);
  if (gz_unit)
    stats->decode_ns += io_ns;
  else
    stats->read_ns += io_ns;
  stats->parse_ns += wall - io_ns;
  stats->bytes_read += bytes;
  if (!ok) res->error = err;
}

void worker_main(Parallel* p, int thread_idx, Stats* stats) {
  while (true) {
    size_t u = p->next_unit.fetch_add(1);
    if (u >= p->units.size()) break;
    UnitResult* res = &p->results[u];
    res->thread = thread_idx;
    if (static_cast<int64_t>(u) > p->abort_after.load()) {
      res->skipped = true;  // after a failure; never delivered
    } else {
      process_unit(p, u, thread_idx, stats);
      if (!res->error.empty()) abort_at(p, u);
    }
    {
      std::lock_guard<std::mutex> lk(p->mu);
      p->done[u] = 1;
    }
    p->cv.notify_all();
  }
  // Drain phase: units are exhausted, but live pipelines may still be
  // emitting subtasks — keep parsing until every decoder has finished and
  // the queue is empty.
  while (true) {
    Subtask st;
    {
      std::unique_lock<std::mutex> lk(p->mu);
      p->cv.wait(lk,
                 [&] { return !p->subq.empty() || p->active_pipelines == 0; });
      if (p->subq.empty()) break;  // implies no active pipelines
      st = std::move(p->subq.front());
      p->subq.pop_front();
    }
    p->cv.notify_all();
    parse_subtask(p, std::move(st), thread_idx, stats);
  }
}

// Runs fn(i) for i in [0, n) on up to `threads` std::threads (merge-stage
// parallelism; workers have already joined by the time this runs).
template <typename F>
void parallel_for(int64_t n, int threads, F&& fn) {
  if (n <= 0) return;
  int use = static_cast<int>(
      std::max<int64_t>(1, std::min<int64_t>(threads, n)));
  if (use == 1) {
    for (int64_t i = 0; i < n; i++) fn(i);
    return;
  }
  std::atomic<int64_t> next{0};
  std::vector<std::thread> pool;
  for (int t = 0; t < use; t++)
    pool.emplace_back([&] {
      int64_t i;
      while ((i = next.fetch_add(1)) < n) fn(i);
    });
  for (auto& t : pool) t.join();
}

int64_t file_size(const char* path) {
  struct stat st;
  if (stat(path, &st) != 0) return -1;
  return static_cast<int64_t>(st.st_size);
}

bool ends_with_gz(const std::string& p) {
  return p.size() >= 3 && p.compare(p.size() - 3, 3, ".gz") == 0;
}

// gzip magic sniff: gzopen transparently decompresses gzip CONTENT whatever
// the extension, so routing (mmap vs stream, member scan) must look at the
// bytes, not the name, to keep every engine's behavior identical.
bool has_gz_magic(const char* path) {
  FILE* f = fopen(path, "rb");
  if (!f) return false;
  unsigned char m[2];
  size_t n = fread(m, 1, 2, f);
  fclose(f);
  return n == 2 && m[0] == 0x1f && m[1] == 0x8b;
}

}  // namespace

extern "C" {

Ingest* rdf_ingest_new() { return new Ingest(); }

void rdf_ingest_free(Ingest* ing) { delete ing; }

const char* rdf_ingest_error(Ingest* ing) { return ing->error.c_str(); }

// Speed-rung knobs, resolved Python-side (RDFIND_INGEST_SWAR,
// RDFIND_INGEST_MMAP, RDFIND_INGEST_GZ_CHUNK_BYTES,
// RDFIND_INGEST_GZ_PIPELINE).  Call before any file/begin call;
// gz_chunk_bytes <= 0 keeps the default.
void rdf_ingest_set_opts(Ingest* ing, int swar, int use_mmap,
                         int64_t gz_chunk_bytes, int gz_pipeline) {
  ing->opt_swar = swar != 0;
  ing->opt_mmap = use_mmap != 0;
  ing->opt_gz_pipeline = gz_pipeline != 0;
  if (gz_chunk_bytes > 0)
    ing->opt_gz_chunk = std::max<int64_t>(gz_chunk_bytes, 256);
}

// --- Serial path (the reference implementation of the id contract) ---------

// Reads and parses one file; returns triples parsed from it, or -1 on error.
int64_t rdf_ingest_file(Ingest* ing, const char* path, int tabs,
                        int expect_quad, int skip_comments) {
  if (ing->finalized) {
    ing->error = "ingest already finalized";
    return -1;
  }
  if (ing->par) {
    ing->error = "streaming ingest already begun; use the block API";
    return -1;
  }
  int64_t count = 0;
  ParseCtx ctx{&ing->dict, &ing->triples, &ing->error, ing->opt_swar,
               /*stable=*/false};
  auto handle = [&](const char* line, size_t len) -> bool {
    if (skip_comments && len > 0 && line[0] == '#') return true;
    int rc = parse_line(&ctx, line, len, tabs != 0, expect_quad != 0);
    if (rc < 0) {
      ing->error += std::string(" in ") + path;
      return false;
    }
    count += rc;
    return true;
  };
  int64_t io_ns = 0, bytes = 0;
  int64_t size = file_size(path);
  bool gz = ends_with_gz(path) || (size >= 2 && has_gz_magic(path));
  bool ok;
  auto t0 = Clock::now();
  const char* data =
      (!gz && ing->opt_mmap && size > 0) ? ing->map_file(path, size) : nullptr;
  if (data) {
    ctx.stable = true;
    ok = for_mapped_lines(data, size, 0, size, ing->opt_swar, handle, &bytes);
    ing->stats.mmap_bytes += size;
  } else {
    GzSource src(path);
    if (!src.ok()) {
      ing->error = std::string("cannot open ") + path;
      return -1;
    }
    ok = for_stream_lines(src, path, ing->opt_swar, &ing->error, handle,
                          &io_ns, &bytes);
  }
  if (gz)
    ing->stats.decode_ns += io_ns;
  else
    ing->stats.read_ns += io_ns;
  ing->stats.parse_ns += ns_since(t0) - io_ns;
  ing->stats.bytes_read += bytes;
  ing->stats.n_files++;
  ing->stats.n_units++;
  return ok ? count : -1;
}

// Sorts the dictionary by bytes, remaps triple ids to sorted ranks.
// Returns the number of distinct values.
int64_t rdf_ingest_finalize(Ingest* ing) {
  if (!ing->finalized) {
    auto t0 = Clock::now();
    size_t nvals = ing->dict.by_id.size();
    std::vector<int32_t> order(nvals);
    std::iota(order.begin(), order.end(), 0);
    std::sort(order.begin(), order.end(), [&](int32_t a, int32_t b) {
      return ing->dict.by_id[a] < ing->dict.by_id[b];
    });
    ing->remap.assign(nvals, 0);
    for (size_t rank = 0; rank < nvals; rank++)
      ing->remap[order[rank]] = static_cast<int32_t>(rank);
    ing->stats.merge_ns += ns_since(t0);
    t0 = Clock::now();
    for (auto& id : ing->triples) id = ing->remap[id];
    // sorted export views + offsets.
    ing->sorted_vals.resize(nvals);
    ing->sorted_offsets.assign(nvals + 1, 0);
    int64_t off = 0;
    for (size_t rank = 0; rank < nvals; rank++) {
      std::string_view s = ing->dict.by_id[order[rank]];
      ing->sorted_vals[rank] = s;
      ing->sorted_offsets[rank] = off;
      off += static_cast<int64_t>(s.size());
    }
    ing->sorted_offsets[nvals] = off;
    ing->values_bytes = off;
    ing->stats.remap_ns += ns_since(t0);
    ing->finalized = true;
  }
  return static_cast<int64_t>(ing->sorted_vals.size());
}

int64_t rdf_ingest_num_triples(Ingest* ing) {
  return static_cast<int64_t>(ing->triples.size() / 3);
}

void rdf_ingest_get_triples(Ingest* ing, int32_t* out) {
  memcpy(out, ing->triples.data(), ing->triples.size() * sizeof(int32_t));
}

int64_t rdf_ingest_values_bytes(Ingest* ing) { return ing->values_bytes; }

// buf receives the concatenated sorted value bytes; offsets receives
// num_values + 1 prefix offsets into buf.
void rdf_ingest_get_values(Ingest* ing, char* buf, int64_t* offsets) {
  if (!ing->finalized) return;
  size_t nvals = ing->sorted_vals.size();
  for (size_t i = 0; i < nvals; i++)
    memcpy(buf + ing->sorted_offsets[i], ing->sorted_vals[i].data(),
           ing->sorted_vals[i].size());
  memcpy(offsets, ing->sorted_offsets.data(), (nvals + 1) * sizeof(int64_t));
}

// --- Parallel streaming path -----------------------------------------------

// Enqueues all files as parse units (splitting large plain files into
// chunk_bytes byte ranges at newline boundaries and multi-member gz files at
// exact member boundaries) and starts n_threads workers.  chunk_bytes <= 0
// auto-sizes the grain to input_bytes / (threads * 4), clamped to
// [1 MiB, 64 MiB], so every thread sees several units without shredding the
// input into sub-megabyte stripes.  Returns the number of units, or -1.
int64_t rdf_ingest_begin(Ingest* ing, const char** paths, int64_t n_paths,
                         int tabs, int expect_quad, int skip_comments,
                         int n_threads, int64_t chunk_bytes) {
  if (ing->par) {
    ing->error = "streaming ingest already begun";
    return -1;
  }
  if (ing->finalized || !ing->triples.empty()) {
    ing->error = "handle already used by the serial API";
    return -1;
  }
  if (n_threads < 1) n_threads = 1;
  if (n_threads > 256) n_threads = 256;
  std::vector<int64_t> sizes(n_paths);
  int64_t total_bytes = 0;
  for (int64_t i = 0; i < n_paths; i++) {
    sizes[i] = file_size(paths[i]);
    if (sizes[i] > 0) total_bytes += sizes[i];
  }
  if (chunk_bytes <= 0) {
    chunk_bytes = total_bytes / (static_cast<int64_t>(n_threads) * 4);
    chunk_bytes = std::max<int64_t>(1ll << 20,
                                    std::min<int64_t>(chunk_bytes, 64ll << 20));
  }
  auto par = std::make_unique<Parallel>();
  par->tabs = tabs != 0;
  par->quad = expect_quad != 0;
  par->skip_comments = skip_comments != 0;
  par->swar = ing->opt_swar;
  par->gz_pipeline = ing->opt_gz_pipeline;
  par->gz_chunk = ing->opt_gz_chunk;
  par->subq_cap = static_cast<size_t>(2 * n_threads + 2);
  for (int64_t i = 0; i < n_paths; i++) {
    std::string path(paths[i]);
    int64_t size = sizes[i];
    ing->stats.n_files++;
    bool gz =
        ends_with_gz(path) || (size >= 2 && has_gz_magic(paths[i]));
    if (gz) {
      std::vector<std::pair<int64_t, int64_t>> members;
      if (ing->opt_gz_pipeline && n_threads > 1 && size > 64)
        members = scan_gz_members(paths[i], size);
      if (members.size() >= 2) {
        ing->stats.n_members += static_cast<int64_t>(members.size());
        for (auto& m : members) {
          Unit u;
          u.path = path;
          u.kind = K_MEMBER;
          u.is_gz = true;
          u.off = m.first;
          u.len = m.second;
          par->units.push_back(std::move(u));
        }
      } else {
        Unit u;
        u.path = path;
        u.kind = K_STREAM;
        u.is_gz = true;
        u.len = size;
        par->units.push_back(std::move(u));
      }
      continue;
    }
    const char* data =
        (ing->opt_mmap && size > 0) ? ing->map_file(path, size) : nullptr;
    if (data) {
      ing->stats.mmap_bytes += size;
      for (int64_t off = 0; off == 0 || off < size; off += chunk_bytes) {
        Unit u;
        u.path = path;
        u.kind = K_MMAP;
        u.map = data;
        u.map_size = size;
        u.off = off;
        u.len = std::min(chunk_bytes, size - off);
        par->units.push_back(std::move(u));
        if (chunk_bytes >= size) break;
      }
    } else if (size > chunk_bytes) {
      for (int64_t off = 0; off < size; off += chunk_bytes) {
        Unit u;
        u.path = path;
        u.kind = K_CHUNK;
        u.off = off;
        u.len = std::min(chunk_bytes, size - off);
        par->units.push_back(std::move(u));
      }
    } else {
      Unit u;  // small plain file (or unknown size): one gzopen stream unit
      u.path = path;
      u.kind = K_STREAM;
      u.len = size;
      par->units.push_back(std::move(u));
    }
  }
  par->results.resize(par->units.size());
  par->done.assign(par->units.size(), 0);
  par->shards.reserve(n_threads);
  for (int t = 0; t < n_threads; t++)
    par->shards.push_back(std::make_unique<ThreadShard>());
  ing->stats.n_threads = n_threads;
  ing->stats.n_units = static_cast<int64_t>(par->units.size());
  Parallel* p = par.get();
  ing->par = std::move(par);
  for (int t = 0; t < n_threads; t++)
    p->workers.emplace_back(worker_main, p, t, &ing->stats);
  return static_cast<int64_t>(p->units.size());
}

// Blocks until the next block (in unit order; a pipelined gz unit delivers
// one block per decoded chunk, in chunk order) is parsed; returns its row
// count (possibly 0), -1 when the stream is exhausted, -2 on parse error
// (rdf_ingest_error holds the first failing unit's message).
int64_t rdf_ingest_next_block(Ingest* ing) {
  Parallel* p = ing->par.get();
  if (!p) {
    ing->error = "rdf_ingest_begin was not called";
    return -2;
  }
  while (true) {
    if (p->next_deliver >= p->units.size()) {
      p->drained = true;
      p->join_workers();
      return -1;
    }
    size_t u = p->next_deliver;
    enum { DELIVER, ADVANCE, FAIL } outcome;
    int64_t nrows = 0;
    {
      std::unique_lock<std::mutex> lk(p->mu);
      auto ready = [&] {
        UnitResult& r = p->results[u];
        if (r.pipelined) {
          if (r.next_sub < r.subs.size() && r.subs[r.next_sub].done)
            return true;
          return r.decoder_done && r.next_sub >= r.n_subs_final;
        }
        return p->done[u] != 0;
      };
      if (!ready()) {
        ing->stats.queue_stalls++;
        auto t0 = Clock::now();
        p->cv.wait(lk, ready);
        ing->stats.stall_ns += ns_since(t0);
      }
      UnitResult& r = p->results[u];
      if (r.pipelined && r.next_sub < r.subs.size()) {
        SubBlock& sb = r.subs[r.next_sub];
        if (!sb.error.empty()) {
          ing->error = sb.error;
          outcome = FAIL;
        } else {
          p->cur_triples = &sb.triples;
          p->cur_thread = sb.thread;
          r.next_sub++;
          nrows = static_cast<int64_t>(sb.triples.size() / 3);
          outcome = DELIVER;
        }
      } else if (r.pipelined) {
        // Pipelined unit exhausted: surface a decode error, else move on.
        if (!r.error.empty()) {
          ing->error = r.error;
          outcome = FAIL;
        } else {
          p->next_deliver++;
          outcome = ADVANCE;
        }
      } else if (!r.error.empty()) {
        ing->error = r.error;
        outcome = FAIL;
      } else {
        p->cur_triples = &r.triples;
        p->cur_thread = r.thread;
        p->next_deliver++;
        nrows = static_cast<int64_t>(r.triples.size() / 3);
        outcome = DELIVER;
      }
    }
    if (outcome == FAIL) {
      p->join_workers();
      return -2;
    }
    if (outcome == DELIVER) return nrows;
    // ADVANCE: loop for the next unit.
  }
}

int rdf_ingest_block_thread(Ingest* ing) {
  Parallel* p = ing->par.get();
  if (!p || !p->cur_triples) return -1;
  return p->cur_thread;
}

// Copies the current block's (n, 3) provisional-id rows out and frees them.
void rdf_ingest_block_copy(Ingest* ing, int32_t* out) {
  Parallel* p = ing->par.get();
  if (!p || !p->cur_triples) return;
  auto& t = *p->cur_triples;
  memcpy(out, t.data(), t.size() * sizeof(int32_t));
  std::vector<int32_t>().swap(t);  // streamed blocks never linger
}

// Merges the per-thread interners into the global byte-sorted dictionary:
// crc32-shard partition -> parallel per-shard dedupe+sort -> S-way rank
// merge -> per-thread local->global tables.  Returns the number of distinct
// values, or -1 on error.  Requires the stream to be drained first.
int64_t rdf_ingest_stream_finish(Ingest* ing) {
  Parallel* p = ing->par.get();
  if (!p) {
    ing->error = "rdf_ingest_begin was not called";
    return -1;
  }
  if (!p->drained) {
    ing->error = "stream not drained; pull blocks until -1 first";
    return -1;
  }
  if (ing->finalized) return static_cast<int64_t>(ing->sorted_vals.size());
  p->join_workers();
  const int n_threads = static_cast<int>(p->shards.size());
  const int S = n_threads;  // merge shards (same partition fn as dictionary.py)

  // Partition: per-thread local ids bucketed by crc32(value) % S.
  auto t0 = Clock::now();
  parallel_for(n_threads, n_threads, [&](int64_t ti) {
    ThreadShard* sh = p->shards[ti].get();
    sh->buckets.assign(S, {});
    size_t nvals = sh->in.by_id.size();
    sh->to_global.assign(nvals, 0);
    for (size_t lid = 0; lid < nvals; lid++) {
      std::string_view s = sh->in.by_id[lid];
      uint32_t h = crc32(0L, reinterpret_cast<const Bytef*>(s.data()),
                         static_cast<uInt>(s.size()));
      sh->buckets[h % S].push_back(static_cast<int32_t>(lid));
    }
  });
  int64_t partition_ns = ns_since(t0);

  // Dedupe+sort per shard (the parallel dictionary build).  Each entry's
  // in-shard rank lands in its thread's to_global slot (upgraded to the
  // global rank below).
  struct Entry {
    std::string_view v;
    int32_t thread;
    int32_t lid;
  };
  std::vector<std::vector<std::string_view>> shard_distinct(S);
  t0 = Clock::now();
  parallel_for(S, n_threads, [&](int64_t s) {
    std::vector<Entry> entries;
    size_t total = 0;
    for (int t = 0; t < n_threads; t++)
      total += p->shards[t]->buckets[s].size();
    entries.reserve(total);
    for (int t = 0; t < n_threads; t++)
      for (int32_t lid : p->shards[t]->buckets[s])
        entries.push_back({p->shards[t]->in.by_id[lid], t, lid});
    std::sort(entries.begin(), entries.end(),
              [](const Entry& a, const Entry& b) { return a.v < b.v; });
    auto& distinct = shard_distinct[s];
    int32_t rank = -1;
    std::string_view prev;
    for (const Entry& e : entries) {
      if (rank < 0 || e.v != prev) {
        rank++;
        prev = e.v;
        distinct.push_back(e.v);
      }
      p->shards[e.thread]->to_global[e.lid] = rank;  // in-shard rank, for now
    }
  });
  ing->stats.intern_ns += ns_since(t0);

  // S-way merge of the shard-sorted runs into the byte-sorted global order
  // (shards are hash-disjoint, so no cross-shard duplicates).  The order is
  // cut into ranges at pivots drawn from the largest shard; a range starts
  // in every shard at the pivot's lower bound and at the sum of those
  // bounds globally, so the ranges merge independently, in parallel.
  t0 = Clock::now();
  int64_t total = 0;
  int big = 0;
  for (int s = 0; s < S; s++) {
    total += shard_distinct[s].size();
    if (shard_distinct[s].size() > shard_distinct[big].size()) big = s;
  }
  if (total >= (1ll << 31) - 1) {
    ing->error = "dictionary exceeds int32 id space";
    return -1;
  }
  ing->sorted_vals.resize(total);
  std::vector<std::vector<int32_t>> shard_to_global(S);
  for (int s = 0; s < S; s++)
    shard_to_global[s].resize(shard_distinct[s].size());
  const int R = n_threads;
  // lo[r * S + s]: range r's first position in shard s; range R ends them.
  std::vector<size_t> lo((R + 1) * S);
  std::vector<int64_t> first_rank(R + 1, 0);
  for (int r = 0; r <= R; r++) {
    for (int s = 0; s < S; s++) {
      const auto& d = shard_distinct[s];
      size_t at = d.size();
      if (r == 0) {
        at = 0;
      } else if (r < R && !shard_distinct[big].empty()) {
        std::string_view pivot =
            shard_distinct[big][shard_distinct[big].size() * r / R];
        at = std::lower_bound(d.begin(), d.end(), pivot) - d.begin();
      }
      lo[r * S + s] = at;
      first_rank[r] += at;
    }
  }
  parallel_for(R, n_threads, [&](int64_t r) {
    std::vector<size_t> cursor(lo.begin() + r * S, lo.begin() + (r + 1) * S);
    const size_t* end = lo.data() + (r + 1) * S;
    for (int64_t rank = first_rank[r]; rank < first_rank[r + 1]; rank++) {
      int best = -1;
      for (int s = 0; s < S; s++) {
        if (cursor[s] >= end[s]) continue;
        if (best < 0 ||
            shard_distinct[s][cursor[s]] < shard_distinct[best][cursor[best]])
          best = s;
      }
      shard_to_global[best][cursor[best]] = static_cast<int32_t>(rank);
      ing->sorted_vals[rank] = shard_distinct[best][cursor[best]];
      cursor[best]++;
    }
  });
  ing->stats.merge_ns += partition_ns + ns_since(t0);

  // Upgrade the per-thread tables from in-shard ranks to global ranks.
  t0 = Clock::now();
  parallel_for(n_threads, n_threads, [&](int64_t ti) {
    ThreadShard* sh = p->shards[ti].get();
    for (int s = 0; s < S; s++)
      for (int32_t lid : sh->buckets[s])
        sh->to_global[lid] = shard_to_global[s][sh->to_global[lid]];
    sh->buckets.clear();
  });
  ing->stats.remap_ns += ns_since(t0);

  ing->sorted_offsets.assign(total + 1, 0);
  int64_t off = 0;
  for (int64_t i = 0; i < total; i++) {
    ing->sorted_offsets[i] = off;
    off += static_cast<int64_t>(ing->sorted_vals[i].size());
  }
  ing->sorted_offsets[total] = off;
  ing->values_bytes = off;
  ing->finalized = true;
  return total;
}

int64_t rdf_ingest_thread_vocab(Ingest* ing, int thread_idx) {
  Parallel* p = ing->par.get();
  if (!p || thread_idx < 0 ||
      thread_idx >= static_cast<int>(p->shards.size()))
    return -1;
  return static_cast<int64_t>(p->shards[thread_idx]->in.by_id.size());
}

// Copies thread thread_idx's local->global id table (rdf_ingest_thread_vocab
// entries); only valid after rdf_ingest_stream_finish.
void rdf_ingest_thread_remap(Ingest* ing, int thread_idx, int32_t* out) {
  Parallel* p = ing->par.get();
  if (!p || !ing->finalized || thread_idx < 0 ||
      thread_idx >= static_cast<int>(p->shards.size()))
    return;
  auto& tg = p->shards[thread_idx]->to_global;
  memcpy(out, tg.data(), tg.size() * sizeof(int32_t));
}

// Legacy 12-lane ingest telemetry —
// [bytes_read, read_ms, parse_ms, intern_ms, merge_ms, remap_ms, n_threads,
//  n_units, queue_stalls, stall_ms, n_files, reserved].
// Worker-phase ms are SUMS across threads (divide by n_threads for wall).
void rdf_ingest_stats(Ingest* ing, double* out) {
  const Stats& s = ing->stats;
  out[0] = static_cast<double>(s.bytes_read.load());
  out[1] = s.read_ns.load() / 1e6;
  out[2] = s.parse_ns.load() / 1e6;
  out[3] = s.intern_ns / 1e6;
  out[4] = s.merge_ns / 1e6;
  out[5] = s.remap_ns / 1e6;
  out[6] = static_cast<double>(s.n_threads);
  out[7] = static_cast<double>(s.n_units);
  out[8] = static_cast<double>(s.queue_stalls.load());
  out[9] = s.stall_ns.load() / 1e6;
  out[10] = static_cast<double>(s.n_files);
  out[11] = 0.0;
}

// Extended telemetry: the 12 legacy lanes plus
// [11] decode_ms (gz read+inflate), [12] mmap_bytes, [13] n_gz_members,
// [14] n_gz_subtasks, [15] swar, [16] mmap, [17] gz_pipeline.
// Fills min(n, 18) lanes; returns the number filled.
int64_t rdf_ingest_stats2(Ingest* ing, double* out, int64_t n) {
  double full[18];
  rdf_ingest_stats(ing, full);
  const Stats& s = ing->stats;
  full[11] = s.decode_ns.load() / 1e6;
  full[12] = static_cast<double>(s.mmap_bytes);
  full[13] = static_cast<double>(s.n_members);
  full[14] = static_cast<double>(s.n_subtasks.load());
  full[15] = ing->opt_swar ? 1.0 : 0.0;
  full[16] = ing->opt_mmap ? 1.0 : 0.0;
  full[17] = ing->opt_gz_pipeline ? 1.0 : 0.0;
  int64_t fill = std::min<int64_t>(n, 18);
  for (int64_t i = 0; i < fill; i++) out[i] = full[i];
  return fill;
}

}  // extern "C"
