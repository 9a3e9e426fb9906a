// The native codec. High-throughput event decoder: newline-delimited
// JSON (and native Kafka v2 record batches) -> typed columnar buffers,
// the TPU framework's ingest hot path; and the encoder of the way back
// out: a result batch's columns -> the sinks' newline-delimited JSON.
//
// Role in the reference: the EventHub/Kafka receivers deserialize AMQP
// payloads and Spark's from_json does the per-event parse on executors
// (datax-host input/EventHubStreamingFactory.scala:86,
// processor/CommonProcessorFactory.scala:90-103). Here the parse runs
// host-side in native code and lands directly in numpy-compatible
// buffers that device_put ships to the chip — no Python object per
// event.
//
// Design:
//  - hand-rolled recursive-descent JSON scanner with SWAR (8-byte
//    word) structural scanning: string contents, skipped values and
//    containers advance by word, not by char; the newline framing uses
//    memchr (SIMD in libc);
//  - numbers parse on a fast integer/decimal path (one multiply-add
//    per digit) and only fall back to strtod for exponents/overlong
//    mantissas, preserving strtod's acceptance exactly;
//  - string columns dictionary-encode against a persistent
//    string->int32 map shared (via sync calls) with the Python
//    StringDictionary so device-side comparisons stay int32;
//  - timestamps accept epoch seconds/millis or basic ISO-8601 Zulu and
//    land as int64 millis (row path) or int32 batch-relative millis
//    (packed path — the decoder applies the base_ms rebase itself);
//  - **packed output** (dx_decode_packed / dx_decode_kafka_packed):
//    columns write straight into rows of the caller's persistent
//    [n_cols+1, capacity] int32 matrix — the exact single-transfer
//    H2D layout runtime/processor.py pack_raw builds — so the Python
//    side performs zero per-batch column allocations and no pack copy;
//  - sharded decode: newline-aligned chunks (or Kafka record-index
//    ranges) parse on N worker shards into disjoint row-slot ranges,
//    string misses intern thread-locally against the frozen shared
//    dictionary, and a serial merge assigns global ids (the
//    single-writer step is O(new distinct strings), not O(rows));
//  - the way back out (dx_encode_ndjson): the sinks' newline-JSON
//    payload written from a result batch's columns into the caller's
//    reused byte buffer, byte for byte what Python's json.dumps writes
//    a row (digits by std::to_chars, doubles laid out by
//    float.__repr__'s rule); no Python object per row or value;
//  - Kafka fast path (dx_decode_kafka_packed): walks message-format-v2
//    record batches directly — varint record framing, per-batch
//    CRC-32C verification (corrupt batches skip + count instead of
//    mis-parsing), control batches skipped, compressed batches
//    rejected with the codec id so Python can raise a typed error —
//    and feeds each record value to the JSON column decoder in the
//    same call. No Python object per record, no newline-join detour.
//
// C ABI for ctypes; no external dependencies.

#include <charconv>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <ctime>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

enum ColType : int32_t { T_LONG = 0, T_DOUBLE = 1, T_BOOL = 2, T_STR = 3, T_TS = 4 };

struct Column {
  std::string name;
  ColType type;
};

// Schema trie: dotted column paths split on '.' into one node per
// nesting level. The parser resolves each JSON key against the
// CURRENT level's entries by (length, bytes) — no dotted-path
// building, no string hashing, no per-key copy on the fast path.
// Nodes are tiny (schemas have a handful of keys per level), so a
// linear probe beats any hash.
struct TrieEntry {
  std::string key;
  int32_t ci;     // column index when this path is a leaf, else -1
  int32_t child;  // child node index when deeper columns exist, else -1
};

struct TrieNode {
  std::vector<TrieEntry> entries;
};

struct Decoder {
  std::vector<Column> cols;
  std::unordered_map<std::string, int32_t> col_index;
  std::vector<TrieNode> trie;  // [0] = root
  std::unordered_map<std::string, int32_t> dict;
  std::vector<std::string> dict_entries;  // id -> string
  std::string err;
  int64_t bad_ts_count = 0;  // rows dropped for garbage timestamps (last decode)
};

const TrieEntry* trie_find(const TrieNode& node, const char* k, size_t n) {
  for (const TrieEntry& e : node.entries) {
    if (e.key.size() == n && memcmp(e.key.data(), k, n) == 0) return &e;
  }
  return nullptr;
}

// Output sink: per-column base pointers + validity. Two layouts share
// every parse path:
//  - row layout (legacy dx_decode): per-column numpy arrays (int32 /
//    float32 / uint8 / int64 for timestamps), uint8 validity;
//  - packed layout: every column is an int32 row of the caller's H2D
//    matrix (floats bitcast, bools widened, timestamps rebased to
//    int32 batch-relative ms), validity an int32 row.
struct OutBufs {
  void** col_ptrs;       // per column: base pointer of its output row
  uint8_t* valid;        // [cap] (row layout)
  int32_t* valid32;      // [cap] (packed layout)
  int64_t cap;
  bool packed = false;
  int64_t base_ms = 0;   // packed: timestamp rebase origin
};

struct Cursor {
  const char* p;
  const char* end;
};

// ---------------------------------------------------------------------------
// SWAR helpers: find structural bytes 8 at a time
// ---------------------------------------------------------------------------
inline uint64_t load64(const char* p) {
  uint64_t w;
  memcpy(&w, p, 8);
  return w;
}

inline uint64_t has_zero(uint64_t v) {
  return (v - 0x0101010101010101ULL) & ~v & 0x8080808080808080ULL;
}

inline uint64_t has_value(uint64_t w, char c) {
  return has_zero(w ^ (0x0101010101010101ULL * (uint8_t)c));
}

// first '"' or '\\' in [p, end), or end (little-endian ctz indexing —
// the build targets x86-64/aarch64 like the rest of the toolchain)
inline const char* scan_quote(const char* p, const char* end) {
  while (p + 8 <= end) {
    uint64_t w = load64(p);
    uint64_t m = has_value(w, '"') | has_value(w, '\\');
    if (m) return p + (__builtin_ctzll(m) >> 3);
    p += 8;
  }
  while (p < end && *p != '"' && *p != '\\') ++p;
  return p;
}

// first of {'"', open, close} in [p, end), or end
inline const char* scan_container(const char* p, const char* end,
                                  char open, char close) {
  while (p + 8 <= end) {
    uint64_t w = load64(p);
    uint64_t m = has_value(w, '"') | has_value(w, open) | has_value(w, close);
    if (m) return p + (__builtin_ctzll(m) >> 3);
    p += 8;
  }
  while (p < end && *p != '"' && *p != open && *p != close) ++p;
  return p;
}

inline void skip_ws(Cursor& c) {
  while (c.p < c.end) {
    char ch = *c.p;
    if (ch == ' ' || ch == '\t' || ch == '\r') {
      ++c.p;
    } else {
      break;
    }
  }
}

bool skip_value(Cursor& c);

bool skip_string(Cursor& c) {
  // c.p at opening quote
  ++c.p;
  for (;;) {
    const char* q = scan_quote(c.p, c.end);
    if (q >= c.end) {
      c.p = c.end;
      return false;
    }
    if (*q == '"') {
      c.p = q + 1;
      return true;
    }
    c.p = q + 2;  // backslash escape: skip escaped char
    if (c.p > c.end) {
      c.p = c.end;
      return false;
    }
  }
}

bool skip_container(Cursor& c, char open, char close) {
  int depth = 0;
  while (c.p < c.end) {
    const char* q = scan_container(c.p, c.end, open, close);
    if (q >= c.end) {
      c.p = c.end;
      return false;
    }
    c.p = q;
    char ch = *c.p;
    if (ch == '"') {
      if (!skip_string(c)) return false;
      continue;
    }
    if (ch == open) ++depth;
    if (ch == close) {
      --depth;
      if (depth == 0) {
        ++c.p;
        return true;
      }
    }
    ++c.p;
  }
  return false;
}

bool skip_value(Cursor& c) {
  skip_ws(c);
  if (c.p >= c.end) return false;
  char ch = *c.p;
  if (ch == '"') return skip_string(c);
  if (ch == '{') return skip_container(c, '{', '}');
  if (ch == '[') return skip_container(c, '[', ']');
  while (c.p < c.end) {
    ch = *c.p;
    if (ch == ',' || ch == '}' || ch == ']' || ch == ' ' || ch == '\n') break;
    ++c.p;
  }
  return true;
}

// parse a JSON string starting at the opening quote into out
// (unescapes the common cases; \uXXXX is copied through raw).
// Escape-free strings — the overwhelmingly common case — are ONE
// SWAR scan + one bulk assign, no per-char loop.
bool parse_string(Cursor& c, std::string& out) {
  ++c.p;
  const char* start = c.p;
  const char* q = scan_quote(c.p, c.end);
  if (q >= c.end) {
    c.p = c.end;
    return false;
  }
  if (*q == '"') {
    out.assign(start, q - start);
    c.p = q + 1;
    return true;
  }
  // escape path: bulk-copy the clean prefix, then unescape
  out.assign(start, q - start);
  c.p = q;
  while (c.p < c.end) {
    char ch = *c.p;
    if (ch == '"') {
      ++c.p;
      return true;
    }
    if (ch == '\\' && c.p + 1 < c.end) {
      char esc = c.p[1];
      switch (esc) {
        case 'n': out.push_back('\n'); break;
        case 't': out.push_back('\t'); break;
        case 'r': out.push_back('\r'); break;
        case 'b': out.push_back('\b'); break;
        case 'f': out.push_back('\f'); break;
        case '"': out.push_back('"'); break;
        case '\\': out.push_back('\\'); break;
        case '/': out.push_back('/'); break;
        default:
          out.push_back('\\');
          out.push_back(esc);
      }
      c.p += 2;
      // bulk-copy up to the next special byte
      const char* nq = scan_quote(c.p, c.end);
      out.append(c.p, nq - c.p);
      c.p = nq;
      continue;
    }
    out.push_back(ch);
    ++c.p;
  }
  return false;
}

const double POW10[19] = {
    1e0, 1e1, 1e2, 1e3, 1e4, 1e5, 1e6, 1e7, 1e8, 1e9, 1e10, 1e11,
    1e12, 1e13, 1e14, 1e15, 1e16, 1e17, 1e18,
};

// Fast-path JSON number parse: integer + fixed-point decimals in one
// multiply-add per digit; exponents / >18-digit mantissas / non-digit
// forms fall back to strtod so acceptance (incl. strtod-isms like
// "inf" on unquoted tokens) is IDENTICAL to the previous decoder.
double parse_number(Cursor& c, bool* ok) {
  const char* p = c.p;
  bool neg = false;
  if (p < c.end && *p == '-') {
    neg = true;
    ++p;
  }
  const char* ds = p;
  uint64_t ip = 0;
  while (p < c.end && (unsigned)(*p - '0') < 10u) {
    ip = ip * 10 + (uint64_t)(*p - '0');
    ++p;
  }
  int idig = (int)(p - ds);
  double v = (double)ip;
  if (p < c.end && *p == '.') {
    ++p;
    const char* fs = p;
    uint64_t fp = 0;
    while (p < c.end && (unsigned)(*p - '0') < 10u) {
      fp = fp * 10 + (uint64_t)(*p - '0');
      ++p;
    }
    int fdig = (int)(p - fs);
    if (fdig > 18) {
      idig = 100;  // precision fallback
    } else {
      v += (double)fp / POW10[fdig];
    }
  }
  if (idig == 0 || idig > 18 ||
      (p < c.end && (*p == 'e' || *p == 'E'))) {
    char* endp = nullptr;
    double sv = strtod(c.p, &endp);
    if (endp == c.p) {
      *ok = false;
      return 0.0;
    }
    c.p = endp;
    *ok = true;
    return sv;
  }
  c.p = p;
  *ok = true;
  return neg ? -v : v;
}

// basic ISO-8601 Zulu: YYYY-MM-DD[T ]HH:MM:SS[.fff][Z]
int64_t parse_iso8601_ms(const std::string& s, bool* ok) {
  *ok = false;
  if (s.size() < 19) return 0;
  struct tm tmv;
  memset(&tmv, 0, sizeof(tmv));
  tmv.tm_year = atoi(s.substr(0, 4).c_str()) - 1900;
  tmv.tm_mon = atoi(s.substr(5, 2).c_str()) - 1;
  tmv.tm_mday = atoi(s.substr(8, 2).c_str());
  tmv.tm_hour = atoi(s.substr(11, 2).c_str());
  tmv.tm_min = atoi(s.substr(14, 2).c_str());
  tmv.tm_sec = atoi(s.substr(17, 2).c_str());
  if (s[4] != '-' || s[7] != '-' || s[13] != ':' || s[16] != ':') return 0;
  int64_t ms = 0;
  if (s.size() > 20 && s[19] == '.') {
    size_t i = 20;
    int mult = 100;
    while (i < s.size() && s[i] >= '0' && s[i] <= '9' && mult > 0) {
      ms += (s[i] - '0') * mult;
      mult /= 10;
      ++i;
    }
  }
  int64_t epoch_s = timegm(&tmv);
  *ok = true;
  return epoch_s * 1000 + ms;
}

// String interning sink. Single-threaded decodes insert into the
// decoder's dictionary directly (``direct``); parallel workers treat
// the shared map as FROZEN (safe concurrent reads) and collect misses
// in a thread-local map with provisional ids >= shared_size — the
// merge pass after join() assigns global ids and rewrites only that
// worker's row range, so provisional id spaces may overlap across
// threads without ever colliding in the output.
struct DictSink {
  Decoder* direct = nullptr;
  const std::unordered_map<std::string, int32_t>* shared = nullptr;
  int32_t shared_size = 0;
  std::unordered_map<std::string, int32_t> local;
  std::vector<std::string> local_entries;

  int32_t intern(const std::string& s) {
    if (direct) {
      auto it = direct->dict.find(s);
      if (it != direct->dict.end()) return it->second;
      int32_t id = (int32_t)direct->dict_entries.size();
      direct->dict.emplace(s, id);
      direct->dict_entries.push_back(s);
      return id;
    }
    auto it = shared->find(s);
    if (it != shared->end()) return it->second;
    auto lt = local.find(s);
    if (lt != local.end()) return lt->second;
    int32_t id = shared_size + (int32_t)local_entries.size();
    local.emplace(s, id);
    local_entries.push_back(s);
    return id;
  }
};

struct ParseCtx {
  Decoder* d;
  OutBufs* out;
  DictSink* dict;
  int64_t row;
  std::string path;      // reusable dotted-path buffer
  std::string sbuf;      // reusable string scratch
  bool bad_ts = false;   // row hit an unparseable string timestamp
};

inline void store_ts(ParseCtx& ctx, int32_t ci, int64_t ms) {
  OutBufs* o = ctx.out;
  if (o->packed) {
    // the encode-path rebase (runtime/processor.py): slots at ms==0
    // (field missing / epoch zero) stay at relative 0; deltas saturate
    // at the int32 range like the Python encoder instead of wrapping
    int64_t rel = 0;
    if (ms != 0) {
      rel = ms - o->base_ms;
      if (rel > 2147483647LL) rel = 2147483647LL;
      if (rel < -2147483648LL) rel = -2147483648LL;
    }
    static_cast<int32_t*>(o->col_ptrs[ci])[ctx.row] = (int32_t)rel;
  } else {
    static_cast<int64_t*>(o->col_ptrs[ci])[ctx.row] = ms;
  }
}

void store_scalar(ParseCtx& ctx, int32_t ci, Cursor& c) {
  Decoder* d = ctx.d;
  OutBufs* o = ctx.out;
  const Column& col = d->cols[ci];
  char ch = *c.p;
  switch (col.type) {
    case T_LONG: {
      bool ok = false;
      double v = 0;
      if (ch == '"') {
        if (!parse_string(c, ctx.sbuf)) return;
        v = atof(ctx.sbuf.c_str());
        ok = true;
      } else if (ch == 't' || ch == 'f') {
        v = (ch == 't') ? 1 : 0;
        skip_value(c);
        ok = true;
      } else {
        v = parse_number(c, &ok);
      }
      if (ok) static_cast<int32_t*>(o->col_ptrs[ci])[ctx.row] = (int32_t)v;
      break;
    }
    case T_DOUBLE: {
      bool ok = false;
      double v;
      if (ch == '"') {
        if (!parse_string(c, ctx.sbuf)) return;
        v = atof(ctx.sbuf.c_str());
        ok = true;
      } else {
        v = parse_number(c, &ok);
      }
      // both layouts store float32 (packed rows bitcast on device)
      if (ok) static_cast<float*>(o->col_ptrs[ci])[ctx.row] = (float)v;
      break;
    }
    case T_BOOL: {
      int32_t v = 0;
      if (ch == 't') v = 1;
      else if (ch == '"') {
        if (!parse_string(c, ctx.sbuf)) return;
        v = (ctx.sbuf == "true" || ctx.sbuf == "1") ? 1 : 0;
        if (o->packed) {
          static_cast<int32_t*>(o->col_ptrs[ci])[ctx.row] = v;
        } else {
          static_cast<uint8_t*>(o->col_ptrs[ci])[ctx.row] = (uint8_t)v;
        }
        return;
      }
      skip_value(c);
      if (o->packed) {
        static_cast<int32_t*>(o->col_ptrs[ci])[ctx.row] = v;
      } else {
        static_cast<uint8_t*>(o->col_ptrs[ci])[ctx.row] = (uint8_t)v;
      }
      break;
    }
    case T_STR: {
      if (ch == '"') {
        if (!parse_string(c, ctx.sbuf)) return;
      } else {
        // non-string scalar stored as its literal text
        const char* start = c.p;
        skip_value(c);
        ctx.sbuf.assign(start, c.p - start);
      }
      static_cast<int32_t*>(o->col_ptrs[ci])[ctx.row] =
          ctx.dict->intern(ctx.sbuf);
      break;
    }
    case T_TS: {
      int64_t ms = 0;
      if (ch == '"') {
        if (!parse_string(c, ctx.sbuf)) return;
        bool ok = false;
        ms = parse_iso8601_ms(ctx.sbuf, &ok);
        if (!ok) {
          // bare epoch digits, with the same digits-only acceptance and
          // seconds-vs-millis heuristic as the Python encode path
          // (core/batch.py parse_timestamp_ms: strip, then
          // s.replace('.','',1).isdigit()); anything else — including
          // 'nan'/'inf'/hex/exponent/sign forms strtod would take —
          // invalidates the row, since silently anchoring it at time 0
          // would window it wrongly
          size_t b = ctx.sbuf.find_first_not_of(" \t\r\n");
          size_t e = ctx.sbuf.find_last_not_of(" \t\r\n");
          bool digits = (b != std::string::npos);
          int dots = 0;
          for (size_t i = b; digits && i <= e; ++i) {
            char dc = ctx.sbuf[i];
            if (dc == '.') {
              if (++dots > 1) digits = false;
            } else if (dc < '0' || dc > '9') {
              digits = false;
            }
          }
          // a lone '.' has no digits; mirror isdigit() == false
          if (digits && e - b + 1 == (size_t)dots) digits = false;
          if (digits) {
            double v = strtod(ctx.sbuf.c_str() + b, nullptr);
            ms = (v > 1e12) ? (int64_t)v : (int64_t)(v * 1000.0);
          } else {
            ctx.bad_ts = true;
            return;
          }
        }
      } else {
        bool ok = false;
        double v = parse_number(c, &ok);
        if (!ok) return;
        // heuristics: epoch seconds vs millis
        ms = (v > 1e12) ? (int64_t)v : (int64_t)(v * 1000.0);
      }
      store_ts(ctx, ci, ms);
      break;
    }
  }
}

// Parse one JSON object level against trie node ``node_idx``. Keys
// resolve as raw byte spans (escape-free keys — the overwhelmingly
// common case — are matched in place with zero copies); nested
// objects recurse into the key's trie child, or skip wholesale when
// no column lives under them.
bool parse_object(ParseCtx& ctx, Cursor& c, int32_t node_idx) {
  // c.p at '{'
  ++c.p;
  const TrieNode& node = ctx.d->trie[(size_t)node_idx];
  for (;;) {
    skip_ws(c);
    if (c.p >= c.end) return false;
    if (*c.p == '}') {
      ++c.p;
      return true;
    }
    if (*c.p == ',') {
      ++c.p;
      continue;
    }
    if (*c.p != '"') return false;
    const char* kstart = c.p + 1;
    const char* kq = scan_quote(kstart, c.end);
    if (kq >= c.end) return false;
    const TrieEntry* entry;
    if (*kq == '"') {
      entry = trie_find(node, kstart, kq - kstart);
      c.p = kq + 1;
    } else {
      // escaped key: unescape into the scratch buffer, then match
      if (!parse_string(c, ctx.sbuf)) return false;
      entry = trie_find(node, ctx.sbuf.data(), ctx.sbuf.size());
    }
    skip_ws(c);
    if (c.p >= c.end || *c.p != ':') return false;
    ++c.p;
    skip_ws(c);
    if (c.p >= c.end) return false;

    if (*c.p == '{') {
      if (entry != nullptr && entry->child >= 0) {
        if (!parse_object(ctx, c, entry->child)) return false;
      } else {
        if (!skip_container(c, '{', '}')) return false;
      }
    } else if (entry != nullptr && entry->ci >= 0) {
      store_scalar(ctx, entry->ci, c);
    } else {
      if (!skip_value(c)) return false;
    }
  }
}

size_t elem_size(ColType t, bool packed) {
  if (packed) return 4;  // every packed row is int32
  switch (t) {
    case T_BOOL: return 1;
    case T_TS: return 8;
    default: return 4;
  }
}

// A failed parse may have stored some scalars before the error; zero the
// row slot so the next line decoded into it starts from defaults.
void zero_row(Decoder* d, OutBufs* o, int64_t row) {
  for (size_t ci = 0; ci < d->cols.size(); ++ci) {
    size_t sz = elem_size(d->cols[ci].type, o->packed);
    memset(static_cast<char*>(o->col_ptrs[ci]) + (size_t)row * sz, 0, sz);
  }
}

inline void mark_valid(OutBufs* o, int64_t row) {
  if (o->valid32) {
    o->valid32[row] = 1;
  } else {
    o->valid[row] = 1;
  }
}

// Decode newline-delimited lines in [start, end) into row slots
// [row_base, row_base + budget); returns rows produced. Shared by the
// single-threaded entry point and each decoder shard.
int64_t decode_range(Decoder* d, OutBufs* out, DictSink* sink,
                     const char* start, const char* end,
                     int64_t row_base, int64_t budget,
                     int64_t* bad_out, const char** consumed_to) {
  ParseCtx ctx{d, out, sink, 0, std::string(), std::string()};
  ctx.path.reserve(128);
  ctx.sbuf.reserve(256);
  const char* p = start;
  const char* line_start = p;
  int64_t rows = 0;
  int64_t bad = 0;
  while (p < end && rows < budget) {
    const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
    const char* line_end = nl ? nl : end;
    Cursor c{line_start, line_end};
    skip_ws(c);
    if (c.p < c.end && *c.p == '{') {
      ctx.row = row_base + rows;
      ctx.bad_ts = false;
      if (parse_object(ctx, c, 0) && !ctx.bad_ts) {
        mark_valid(out, row_base + rows);
        ++rows;
      } else {
        if (ctx.bad_ts) ++bad;
        zero_row(d, out, row_base + rows);
      }
    }
    if (!nl) {
      p = end;
      line_start = end;
      break;
    }
    p = nl + 1;
    line_start = p;
  }
  if (bad_out) *bad_out = bad;
  if (consumed_to) *consumed_to = line_start;
  return rows;
}

// Serial post-shard merge: assign global dictionary ids to each
// shard's local entries and rewrite that shard's provisional string
// cells (>= shared_size) in rows [row_base, row_base + n_slots).
void merge_shard_dicts(Decoder* d, void** col_ptrs, int32_t shared_size,
                       std::vector<DictSink>& sinks,
                       const std::vector<int64_t>& row_base,
                       const std::vector<int64_t>& n_slots) {
  std::vector<size_t> str_cols;
  for (size_t ci = 0; ci < d->cols.size(); ++ci) {
    if (d->cols[ci].type == T_STR) str_cols.push_back(ci);
  }
  if (str_cols.empty()) return;
  for (size_t k = 0; k < sinks.size(); ++k) {
    if (sinks[k].local_entries.empty()) continue;
    std::vector<int32_t> remap(sinks[k].local_entries.size());
    for (size_t j = 0; j < sinks[k].local_entries.size(); ++j) {
      const std::string& s = sinks[k].local_entries[j];
      auto it = d->dict.find(s);
      if (it != d->dict.end()) {
        remap[j] = it->second;
      } else {
        int32_t id = (int32_t)d->dict_entries.size();
        d->dict.emplace(s, id);
        d->dict_entries.push_back(s);
        remap[j] = id;
      }
    }
    for (size_t ci : str_cols) {
      int32_t* cells = static_cast<int32_t*>(col_ptrs[ci]);
      for (int64_t r = row_base[k]; r < row_base[k] + n_slots[k]; ++r) {
        int32_t v = cells[r];
        if (v >= shared_size &&
            v - shared_size < (int32_t)remap.size()) {
          cells[r] = remap[v - shared_size];
        }
      }
    }
  }
}

// Shared newline-sharded decode over either output layout.
int64_t decode_mt_impl(Decoder* d, const char* buf, int64_t len,
                       int64_t max_rows, OutBufs* out,
                       int64_t* consumed, int32_t n_threads,
                       int64_t mt_threshold) {
  if (n_threads <= 1 || len < mt_threshold) {
    DictSink sink;
    sink.direct = d;
    int64_t bad = 0;
    const char* consumed_to = buf;
    int64_t rows = decode_range(d, out, &sink, buf, buf + len, 0, max_rows,
                                &bad, &consumed_to);
    d->bad_ts_count = bad;
    if (consumed) *consumed = consumed_to - buf;
    return rows;
  }
  const char* end = buf + len;
  // chunk boundaries on newline edges
  std::vector<const char*> bounds;
  bounds.push_back(buf);
  for (int32_t t = 1; t < n_threads; ++t) {
    const char* target = buf + (len * t) / n_threads;
    if (target <= bounds.back()) continue;
    const char* nl = static_cast<const char*>(
        memchr(target, '\n', end - target));
    const char* b = nl ? nl + 1 : end;
    if (b > bounds.back() && b < end) bounds.push_back(b);
  }
  bounds.push_back(end);
  size_t nchunks = bounds.size() - 1;

  // line counts -> disjoint row-slot ranges
  std::vector<int64_t> lines(nchunks, 0);
  int64_t total_lines = 0;
  for (size_t k = 0; k < nchunks; ++k) {
    const char* p = bounds[k];
    while (p < bounds[k + 1]) {
      const char* nl = static_cast<const char*>(
          memchr(p, '\n', bounds[k + 1] - p));
      ++lines[k];
      if (!nl) break;
      p = nl + 1;
    }
    total_lines += lines[k];
  }
  if (total_lines > max_rows) {
    // a line without a slot would shift every later chunk's slots;
    // bounded decodes take the sequential path
    DictSink sink;
    sink.direct = d;
    int64_t bad = 0;
    const char* consumed_to = buf;
    int64_t rows = decode_range(d, out, &sink, buf, buf + len, 0, max_rows,
                                &bad, &consumed_to);
    d->bad_ts_count = bad;
    if (consumed) *consumed = consumed_to - buf;
    return rows;
  }

  int32_t shared_size = (int32_t)d->dict_entries.size();
  std::vector<DictSink> sinks(nchunks);
  std::vector<int64_t> row_base(nchunks, 0), rows_k(nchunks, 0),
      bad_k(nchunks, 0);
  std::vector<const char*> consumed_k(nchunks);
  for (size_t k = 1; k < nchunks; ++k) {
    row_base[k] = row_base[k - 1] + lines[k - 1];
  }
  std::vector<std::thread> workers;
  for (size_t k = 0; k < nchunks; ++k) {
    sinks[k].shared = &d->dict;
    sinks[k].shared_size = shared_size;
    workers.emplace_back([&, k] {
      rows_k[k] = decode_range(d, out, &sinks[k], bounds[k],
                               bounds[k + 1], row_base[k], lines[k],
                               &bad_k[k], &consumed_k[k]);
    });
  }
  for (auto& w : workers) w.join();

  int64_t total_rows = 0;
  int64_t total_bad = 0;
  for (size_t k = 0; k < nchunks; ++k) {
    total_rows += rows_k[k];
    total_bad += bad_k[k];
  }
  merge_shard_dicts(d, out->col_ptrs, shared_size, sinks, row_base, lines);
  d->bad_ts_count = total_bad;
  if (consumed) *consumed = consumed_k[nchunks - 1] - buf;
  return total_rows;
}

// ---------------------------------------------------------------------------
// CRC-32C (Castagnoli) — the Kafka v2 record-batch checksum.
// Slicing-by-8 table, built once.
// ---------------------------------------------------------------------------
uint32_t CRC32C_TABLE[8][256];
std::once_flag crc_once;

void crc32c_init() {
  const uint32_t poly = 0x82F63B78u;
  for (uint32_t i = 0; i < 256; ++i) {
    uint32_t c = i;
    for (int k = 0; k < 8; ++k) {
      c = (c & 1) ? (c >> 1) ^ poly : c >> 1;
    }
    CRC32C_TABLE[0][i] = c;
  }
  for (uint32_t i = 0; i < 256; ++i) {
    uint32_t c = CRC32C_TABLE[0][i];
    for (int t = 1; t < 8; ++t) {
      c = CRC32C_TABLE[0][c & 0xFF] ^ (c >> 8);
      CRC32C_TABLE[t][i] = c;
    }
  }
}

uint32_t crc32c(const uint8_t* p, size_t n) {
  std::call_once(crc_once, crc32c_init);
  uint32_t crc = 0xFFFFFFFFu;
  while (n >= 8) {
    uint64_t w;
    memcpy(&w, p, 8);
    w ^= crc;
    crc = CRC32C_TABLE[7][w & 0xFF] ^ CRC32C_TABLE[6][(w >> 8) & 0xFF] ^
          CRC32C_TABLE[5][(w >> 16) & 0xFF] ^ CRC32C_TABLE[4][(w >> 24) & 0xFF] ^
          CRC32C_TABLE[3][(w >> 32) & 0xFF] ^ CRC32C_TABLE[2][(w >> 40) & 0xFF] ^
          CRC32C_TABLE[1][(w >> 48) & 0xFF] ^ CRC32C_TABLE[0][(w >> 56) & 0xFF];
    p += 8;
    n -= 8;
  }
  while (n--) {
    crc = CRC32C_TABLE[0][(crc ^ *p++) & 0xFF] ^ (crc >> 8);
  }
  return crc ^ 0xFFFFFFFFu;
}

// ---------------------------------------------------------------------------
// Kafka v2 record-batch walking
// ---------------------------------------------------------------------------
inline uint32_t be32(const uint8_t* p) {
  return ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16) |
         ((uint32_t)p[2] << 8) | (uint32_t)p[3];
}

inline uint16_t be16(const uint8_t* p) {
  return (uint16_t)(((uint16_t)p[0] << 8) | (uint16_t)p[1]);
}

// zigzag varint; returns false on truncation
inline bool read_varint(const uint8_t*& p, const uint8_t* end, int64_t* out) {
  uint64_t z = 0;
  int shift = 0;
  while (p < end) {
    uint8_t b = *p++;
    z |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *out = (int64_t)(z >> 1) ^ -(int64_t)(z & 1);
      return true;
    }
    shift += 7;
    if (shift > 63) return false;
  }
  return false;
}

struct RecordSlice {
  const char* p;
  int64_t len;  // -1 = null value
};

// stats layout (int64[6]):
//   [0] records seen (data records in verified batches)
//   [1] malformed record values (JSON parse failures / bad timestamps
//       counted separately via dx_bad_timestamps)
//   [2] corrupt batches (CRC-32C mismatch) — skipped whole
//   [3] control batches skipped
//   [4] records dropped because max_rows was exhausted
//   [5] compression codec encountered (-1 = none; walking stops there)
enum KStat { K_RECORDS = 0, K_MALFORMED, K_CORRUPT, K_CONTROL, K_OVERFLOW,
             K_CODEC };

// Walk concatenated v2 record batches; collect data-record value
// slices (bounded by max_records). A trailing partial batch — normal
// at the fetch-size boundary — is ignored.
void walk_batches(const uint8_t* buf, int64_t len, int64_t max_records,
                  std::vector<RecordSlice>& values, int64_t* stats) {
  const uint8_t* p = buf;
  const uint8_t* end = buf + len;
  while (end - p >= 61) {
    // frame: baseOffset(8) batchLength(4) body...
    uint32_t batch_len = be32(p + 8);
    const uint8_t* body = p + 12;
    if ((int64_t)(end - body) < (int64_t)batch_len) break;  // partial
    const uint8_t* next = body + batch_len;
    if (batch_len < 49 || body[4] != 2) {  // magic != 2: skip
      p = next;
      continue;
    }
    uint16_t attributes = be16(body + 9);
    if (attributes & 0x07) {
      stats[K_CODEC] = attributes & 0x07;
      return;  // typed rejection at the Python layer
    }
    uint32_t crc_stored = be32(body + 5);
    if (crc32c(body + 9, batch_len - 9) != crc_stored) {
      ++stats[K_CORRUPT];  // skip whole batch instead of mis-parsing
      p = next;
      continue;
    }
    if (attributes & 0x20) {
      ++stats[K_CONTROL];  // transaction markers: metadata, not data
      p = next;
      continue;
    }
    uint32_t n_records = be32(body + 45);
    const uint8_t* rp = body + 49;
    for (uint32_t i = 0; i < n_records && rp < next; ++i) {
      int64_t rec_len = 0;
      if (!read_varint(rp, next, &rec_len) || rec_len < 0 ||
          rp + rec_len > next) {
        ++stats[K_MALFORMED];
        break;  // framing broken: rest of batch unusable
      }
      const uint8_t* rend = rp + rec_len;
      const uint8_t* q = rp + 1;  // skip record attributes
      int64_t v = 0;
      bool ok = read_varint(q, rend, &v)      // timestampDelta
             && read_varint(q, rend, &v);     // offsetDelta
      int64_t klen = 0;
      ok = ok && read_varint(q, rend, &klen);
      if (ok && klen > 0) {
        if (q + klen > rend) ok = false; else q += klen;
      }
      int64_t vlen = 0;
      ok = ok && read_varint(q, rend, &vlen);
      if (ok && vlen >= 0 && q + vlen > rend) ok = false;
      if (!ok) {
        ++stats[K_MALFORMED];
        rp = rend;
        continue;
      }
      ++stats[K_RECORDS];
      if ((int64_t)values.size() >= max_records) {
        ++stats[K_OVERFLOW];  // slotless records are DROPPED — count loud
      } else {
        values.push_back(RecordSlice{
            (const char*)q, vlen >= 0 ? vlen : -1});
      }
      rp = rend;
    }
    p = next;
  }
}

// decode one shard of record-value slices into row slots [i0, i1)
int64_t decode_values_range(Decoder* d, OutBufs* out, DictSink* sink,
                            const RecordSlice* recs, int64_t i0, int64_t i1,
                            int64_t* bad_out, int64_t* malformed_out) {
  ParseCtx ctx{d, out, sink, 0, std::string(), std::string()};
  ctx.path.reserve(128);
  ctx.sbuf.reserve(256);
  int64_t rows = 0;
  int64_t bad = 0;
  int64_t malformed = 0;
  for (int64_t i = i0; i < i1; ++i) {
    const RecordSlice& r = recs[i];
    if (r.len <= 0) {
      ++malformed;  // null/empty record value: no event to decode
      continue;
    }
    Cursor c{r.p, r.p + r.len};
    skip_ws(c);
    if (c.p < c.end && *c.p == '{') {
      ctx.row = i;  // row slot == record index: shards never overlap
      ctx.bad_ts = false;
      if (parse_object(ctx, c, 0) && !ctx.bad_ts) {
        mark_valid(out, i);
        ++rows;
        continue;
      }
      if (ctx.bad_ts) ++bad; else ++malformed;
      zero_row(d, out, i);
    } else {
      ++malformed;
    }
  }
  if (bad_out) *bad_out = bad;
  if (malformed_out) *malformed_out = malformed;
  return rows;
}

// ---- NDJSON encoder --------------------------------------------------
// One result column as the encoder is handed it (runtime/materialize.py
// ColumnBatch, rendered: the batch base added, float32 widened).
enum EncKind : int32_t { E_INT = 0, E_DOUBLE = 1, E_BOOL = 2, E_STR = 3 };

struct EncCol {
  int32_t kind;
  const void* data;        // int64 / double / uint8 a row; E_STR: int64
                           // index a row into the distinct strings
  const char* prefix;      // `{"name": ` or `, "name": `
  int64_t prefix_len;
  const char* strs;        // E_STR: the distinct strings as json.dumps
  const int64_t* str_off;  // spells them, back to back; n + 1 offsets
  int64_t width;           // the widest spelling of a value
};

constexpr int64_t kIntWidth = 20;     // -9223372036854775808
constexpr int64_t kDoubleWidth = 24;  // -2.2250738585072014e-308
constexpr int64_t kBoolWidth = 5;     // false

// json.dumps of a double: float.__repr__ (the shortest digits that
// round-trip; exponent form `d.ddde-XX` when the decimal exponent is
// under -4 or over 15, else positional, `.0` after an integer value),
// and NaN / Infinity / -Infinity.
inline char* put_double(char* p, double v) {
  if (v != v) {
    memcpy(p, "NaN", 3);
    return p + 3;
  }
  if (v - v != 0.0) {  // an infinity
    if (v < 0) *p++ = '-';
    memcpy(p, "Infinity", 8);
    return p + 8;
  }
  // [-]d[.ddd]e[+-]XX[X]: to_chars' scientific form is repr's own
  char tmp[32];
  const char* s = tmp;
  const char* end =
      std::to_chars(tmp, tmp + sizeof tmp, v, std::chars_format::scientific)
          .ptr;
  if (*s == '-') *p++ = *s++;
  const char* e = end - 3;  // the exponent has two or three digits
  while (*e != 'e') --e;
  int exp10 = 0;
  for (const char* q = e + 2; q < end; ++q) exp10 = exp10 * 10 + (*q - '0');
  if (e[1] == '-') exp10 = -exp10;
  if (exp10 < -4 || exp10 > 15) {
    memcpy(p, s, (size_t)(end - s));
    return p + (end - s);
  }
  const char* rest = s + 2;  // the digits after the first
  int n_rest = e - s > 1 ? (int)(e - rest) : 0;
  if (exp10 < 0) {  // 0.000ddd
    *p++ = '0';
    *p++ = '.';
    for (int z = -exp10 - 1; z > 0; --z) *p++ = '0';
    *p++ = *s;
    memcpy(p, rest, (size_t)n_rest);
    return p + n_rest;
  }
  *p++ = *s;
  if (exp10 >= n_rest) {  // ddd000.0
    memcpy(p, rest, (size_t)n_rest);
    p += n_rest;
    for (int z = exp10 - n_rest; z > 0; --z) *p++ = '0';
    *p++ = '.';
    *p++ = '0';
    return p;
  }
  memcpy(p, rest, (size_t)exp10);  // ddd.ddd
  p += exp10;
  *p++ = '.';
  memcpy(p, rest + exp10, (size_t)(n_rest - exp10));
  return p + (n_rest - exp10);
}

// The columns' n_rows rows as one JSON object a line at p; returns the
// end. A row takes at most sum(prefix_len + width) + 2 bytes.
char* encode_rows(const EncCol* cols, int32_t n_cols, int64_t n_rows,
                  char* p) {
  for (int64_t i = 0; i < n_rows; ++i) {
    for (int32_t c = 0; c < n_cols; ++c) {
      const EncCol& col = cols[c];
      memcpy(p, col.prefix, (size_t)col.prefix_len);
      p += col.prefix_len;
      switch (col.kind) {
        case E_INT:
          p = std::to_chars(p, p + kIntWidth,
                            static_cast<const int64_t*>(col.data)[i]).ptr;
          break;
        case E_DOUBLE:
          p = put_double(p, static_cast<const double*>(col.data)[i]);
          break;
        case E_BOOL:
          if (static_cast<const uint8_t*>(col.data)[i]) {
            memcpy(p, "true", 4);
            p += 4;
          } else {
            memcpy(p, "false", 5);
            p += 5;
          }
          break;
        default: {
          int64_t k = static_cast<const int64_t*>(col.data)[i];
          int64_t n = col.str_off[k + 1] - col.str_off[k];
          memcpy(p, col.strs + col.str_off[k], (size_t)n);
          p += n;
        }
      }
    }
    *p++ = '}';
    *p++ = '\n';
  }
  return p;
}

}  // namespace

extern "C" {

// schema_desc: "name\ttype\n" per column; type in {long,double,boolean,
// string,timestamp}
void* dx_decoder_create(const char* schema_desc) {
  Decoder* d = new Decoder();
  const char* p = schema_desc;
  while (*p) {
    const char* tab = strchr(p, '\t');
    if (!tab) break;
    const char* nl = strchr(tab, '\n');
    if (!nl) nl = tab + strlen(tab);
    std::string name(p, tab - p);
    std::string type(tab + 1, nl - tab - 1);
    ColType t = T_STR;
    if (type == "long") t = T_LONG;
    else if (type == "double") t = T_DOUBLE;
    else if (type == "boolean") t = T_BOOL;
    else if (type == "string") t = T_STR;
    else if (type == "timestamp") t = T_TS;
    d->col_index.emplace(name, (int32_t)d->cols.size());
    d->cols.push_back({name, t});
    p = (*nl) ? nl + 1 : nl;
  }
  // build the schema trie: one node per nesting level, dotted names
  // split on '.' (the flattened-schema path convention)
  d->trie.emplace_back();
  for (size_t ci = 0; ci < d->cols.size(); ++ci) {
    const std::string& name = d->cols[ci].name;
    size_t pos = 0;
    int32_t node = 0;
    for (;;) {
      size_t dot = name.find('.', pos);
      std::string part = name.substr(
          pos, dot == std::string::npos ? std::string::npos : dot - pos);
      size_t ei = 0;
      for (; ei < d->trie[(size_t)node].entries.size(); ++ei) {
        if (d->trie[(size_t)node].entries[ei].key == part) break;
      }
      if (ei == d->trie[(size_t)node].entries.size()) {
        d->trie[(size_t)node].entries.push_back({part, -1, -1});
      }
      if (dot == std::string::npos) {
        d->trie[(size_t)node].entries[ei].ci = (int32_t)ci;
        break;
      }
      if (d->trie[(size_t)node].entries[ei].child < 0) {
        int32_t child = (int32_t)d->trie.size();
        d->trie.emplace_back();  // may move nodes; index stays valid
        d->trie[(size_t)node].entries[ei].child = child;
      }
      node = d->trie[(size_t)node].entries[ei].child;
      pos = dot + 1;
    }
  }
  return d;
}

void dx_decoder_destroy(void* dv) { delete static_cast<Decoder*>(dv); }

int64_t dx_num_columns(void* dv) {
  return (int64_t)static_cast<Decoder*>(dv)->cols.size();
}

// Decode up to max_rows newline-delimited JSON events from buf into the
// caller-provided column buffers (numpy arrays, pre-zeroed by caller).
// Returns rows decoded; *consumed gets bytes consumed (whole lines only)
// so callers can stream partial buffers.
int64_t dx_decode(void* dv, const char* buf, int64_t len, int64_t max_rows,
                  void** col_ptrs, uint8_t* valid, int64_t* consumed) {
  Decoder* d = static_cast<Decoder*>(dv);
  OutBufs out{col_ptrs, valid, nullptr, max_rows};
  DictSink sink;
  sink.direct = d;
  int64_t bad = 0;
  const char* consumed_to = buf;
  int64_t rows = decode_range(d, &out, &sink, buf, buf + len, 0, max_rows,
                              &bad, &consumed_to);
  d->bad_ts_count = bad;
  if (consumed) *consumed = consumed_to - buf;
  return rows;
}

// Sharded decode into the row layout: newline-aligned byte chunks parse
// concurrently, each into its own contiguous row-slot range (slot
// budget = the chunk's line count, so ranges never overlap). String
// misses intern into thread-local maps against the FROZEN shared
// dictionary and a serial merge pass assigns global ids + rewrites
// each shard's string cells. Falls back to the single-threaded path
// when the work is small, the shard count is 1, or the buffer holds
// more lines than max_rows (whole-buffer slot layout needs every line
// to have a slot).
int64_t dx_decode_mt(void* dv, const char* buf, int64_t len,
                     int64_t max_rows, void** col_ptrs, uint8_t* valid,
                     int64_t* consumed, int32_t n_threads) {
  Decoder* d = static_cast<Decoder*>(dv);
  OutBufs out{col_ptrs, valid, nullptr, max_rows};
  return decode_mt_impl(d, buf, len, max_rows, &out, consumed, n_threads,
                        1 << 20);
}

// From this many bytes a packed decode that was asked for shards takes
// them (a plain one from 1 MiB).
constexpr int64_t kPackedShardBytes = 256 << 10;

// Packed decode: newline-delimited JSON straight into the caller's
// persistent [*, capacity] int32 H2D matrix (the pack_raw layout —
// floats bitcast, bools widened, timestamps rebased to int32
// batch-relative ms against base_ms, validity int32). col_rows[i] maps
// decoder column i to its matrix row; valid_row is the validity row.
// The decoder zeroes its own rows for [0, max_rows) first, so the
// buffer pool can hand back reused (dirty) matrices for free. `matrix`
// may point at a later row slot of the caller's matrix (row_stride is
// the whole matrix's): a batch decoded in passes hands each pass the
// slot the one before stopped at, and max_rows its line count.
// n_threads > 1 shards the decode (same dictionary-delta merge as
// dx_decode_mt) with a lower engage threshold — the conf'd shard
// count is an explicit ask.
int64_t dx_decode_packed(void* dv, const char* buf, int64_t len,
                         int64_t max_rows, int32_t* matrix,
                         int64_t row_stride, const int64_t* col_rows,
                         int64_t valid_row, int64_t base_ms,
                         int64_t* consumed, int32_t n_threads) {
  Decoder* d = static_cast<Decoder*>(dv);
  size_t ncols = d->cols.size();
  std::vector<void*> ptrs(ncols);
  for (size_t i = 0; i < ncols; ++i) {
    ptrs[i] = matrix + col_rows[i] * row_stride;
    memset(ptrs[i], 0, (size_t)max_rows * 4);
  }
  int32_t* vrow = matrix + valid_row * row_stride;
  memset(vrow, 0, (size_t)max_rows * 4);
  OutBufs out{ptrs.data(), nullptr, vrow, max_rows, true, base_ms};
  return decode_mt_impl(d, buf, len, max_rows, &out, consumed, n_threads,
                        n_threads > 1 ? kPackedShardBytes : (1 << 20));
}

// kPackedShardBytes, for a caller that decodes a batch in passes and
// wants every pass multi-threaded.
int64_t dx_packed_shard_bytes() { return kPackedShardBytes; }

// Kafka v2 fast path: walk record batches (CRC-32C verified; corrupt
// batches skipped + counted; control batches skipped; compressed
// batches abort with the codec in stats[5]) and decode each record's
// JSON value straight into the packed matrix, sharding the value
// decode across n_threads when the record count is large. Row slot ==
// record index, so the validity row is the ONLY authoritative mask.
// Returns decoded (valid) rows; stats: see KStat.
int64_t dx_decode_kafka_packed(void* dv, const char* buf, int64_t len,
                               int64_t max_rows, int32_t* matrix,
                               int64_t row_stride, const int64_t* col_rows,
                               int64_t valid_row, int64_t base_ms,
                               int64_t* stats, int32_t n_threads) {
  Decoder* d = static_cast<Decoder*>(dv);
  for (int i = 0; i < 6; ++i) stats[i] = 0;
  stats[K_CODEC] = -1;

  std::vector<RecordSlice> values;
  values.reserve(4096);
  walk_batches((const uint8_t*)buf, len, max_rows, values, stats);

  size_t ncols = d->cols.size();
  std::vector<void*> ptrs(ncols);
  for (size_t i = 0; i < ncols; ++i) {
    ptrs[i] = matrix + col_rows[i] * row_stride;
    memset(ptrs[i], 0, (size_t)max_rows * 4);
  }
  int32_t* vrow = matrix + valid_row * row_stride;
  memset(vrow, 0, (size_t)max_rows * 4);
  OutBufs out{ptrs.data(), nullptr, vrow, max_rows, true, base_ms};

  int64_t n = (int64_t)values.size();
  int64_t rows = 0, bad = 0, malformed = 0;
  if (n_threads <= 1 || n < 8192) {
    DictSink sink;
    sink.direct = d;
    rows = decode_values_range(d, &out, &sink, values.data(), 0, n,
                               &bad, &malformed);
  } else {
    size_t nshards = (size_t)n_threads;
    int32_t shared_size = (int32_t)d->dict_entries.size();
    std::vector<DictSink> sinks(nshards);
    std::vector<int64_t> row_base(nshards, 0), n_slots(nshards, 0),
        rows_k(nshards, 0), bad_k(nshards, 0), mal_k(nshards, 0);
    std::vector<std::thread> workers;
    for (size_t k = 0; k < nshards; ++k) {
      row_base[k] = (n * (int64_t)k) / (int64_t)nshards;
      n_slots[k] = (n * (int64_t)(k + 1)) / (int64_t)nshards - row_base[k];
      sinks[k].shared = &d->dict;
      sinks[k].shared_size = shared_size;
      workers.emplace_back([&, k] {
        rows_k[k] = decode_values_range(
            d, &out, &sinks[k], values.data(), row_base[k],
            row_base[k] + n_slots[k], &bad_k[k], &mal_k[k]);
      });
    }
    for (auto& w : workers) w.join();
    for (size_t k = 0; k < nshards; ++k) {
      rows += rows_k[k];
      bad += bad_k[k];
      malformed += mal_k[k];
    }
    merge_shard_dicts(d, ptrs.data(), shared_size, sinks, row_base, n_slots);
  }
  d->bad_ts_count = bad;
  stats[K_MALFORMED] += malformed;
  return rows;
}

// CRC-32C over a buffer (exposed so the Python wire client shares the
// native implementation instead of its table-per-byte fallback).
uint32_t dx_crc32c(const char* buf, int64_t len) {
  return crc32c((const uint8_t*)buf, (size_t)len);
}

// Walk the whole (newline-terminated) lines of buf[0, len) until
// max_lines of them that hold more than whitespace have been passed;
// an unterminated tail is never passed. Returns those lines' count,
// *cut = the offset just past the last line passed, *blank = the
// whitespace-only lines passed on the way. The socket source's line
// accounting (runtime/sources.py): what `for line in f: line.strip()`
// did there, without an object a line.
int64_t dx_scan_lines(const char* buf, int64_t len, int64_t max_lines,
                      int64_t* cut, int64_t* blank) {
  const char* p = buf;
  const char* end = buf + len;
  int64_t lines = 0;
  int64_t blanks = 0;
  while (p < end && lines < max_lines) {
    const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
    if (!nl) break;
    const char* q = p;
    while (q < nl && (*q == ' ' || (*q >= '\t' && *q <= '\r'))) ++q;
    if (q == nl) {
      ++blanks;
    } else {
      ++lines;
    }
    p = nl + 1;
  }
  *cut = p - buf;
  *blank = blanks;
  return lines;
}

// The sinks' NDJSON payload of n_rows rows, straight from the columns
// (kinds[c]: EncKind; cols[c]: the column's buffer; prefixes with
// n_cols + 1 offsets: `{"name": ` / `, "name": ` as json.dumps spells
// the names; a string column's distinct strings, spelled by json.dumps,
// in str_bytes[c] with str_counts[c] + 1 offsets in str_offsets[c] and
// its rows' indices into them in cols[c]). Byte for byte
// json.dumps(row) + "\n" a row. Returns the bytes written into out; or,
// when out_cap is under the most these rows can take, minus that many
// bytes, with nothing written (the caller grows its buffer and asks
// again). Holds no lock and no state: concurrent calls need only
// buffers of their own.
int64_t dx_encode_ndjson(int64_t n_rows, int32_t n_cols, const int32_t* kinds,
                         const void* const* cols, const char* prefixes,
                         const int64_t* prefix_offsets,
                         const char* const* str_bytes,
                         const int64_t* const* str_offsets,
                         const int64_t* str_counts, char* out,
                         int64_t out_cap) {
  std::vector<EncCol> enc((size_t)n_cols);
  int64_t row_bound = 2;  // "}\n"
  for (int32_t c = 0; c < n_cols; ++c) {
    EncCol& col = enc[(size_t)c];
    col.kind = kinds[c];
    col.data = cols[c];
    col.prefix = prefixes + prefix_offsets[c];
    col.prefix_len = prefix_offsets[c + 1] - prefix_offsets[c];
    col.strs = nullptr;
    col.str_off = nullptr;
    switch (col.kind) {
      case E_INT: col.width = kIntWidth; break;
      case E_DOUBLE: col.width = kDoubleWidth; break;
      case E_BOOL: col.width = kBoolWidth; break;
      case E_STR:
        col.strs = str_bytes[c];
        col.str_off = str_offsets[c];
        col.width = 0;
        for (int64_t k = 0; k < str_counts[c]; ++k) {
          int64_t n = col.str_off[k + 1] - col.str_off[k];
          if (n > col.width) col.width = n;
        }
        break;
      default:
        return 0;
    }
    row_bound += col.prefix_len + col.width;
  }
  if (n_rows * row_bound > out_cap) return -(n_rows * row_bound);
  return encode_rows(enc.data(), n_cols, n_rows, out) - out;
}

// Rows dropped by the last decode because a string timestamp was
// unparseable (matches the Python encoder's bad_timestamps stat).
int64_t dx_bad_timestamps(void* dv) {
  return static_cast<Decoder*>(dv)->bad_ts_count;
}

// ---- dictionary sync -------------------------------------------------
int64_t dx_dict_size(void* dv) {
  return (int64_t)static_cast<Decoder*>(dv)->dict_entries.size();
}

// Seed an entry; must be called in id order starting at current size.
int32_t dx_dict_push(void* dv, const char* s) {
  Decoder* d = static_cast<Decoder*>(dv);
  auto it = d->dict.find(s);
  if (it != d->dict.end()) return it->second;
  int32_t id = (int32_t)d->dict_entries.size();
  d->dict.emplace(s, id);
  d->dict_entries.push_back(s);
  return id;
}

// Fetch entry text (for syncing new ids back to Python). Returns length
// or -1 if out of range; copies at most outcap-1 bytes + NUL.
int64_t dx_dict_get(void* dv, int64_t id, char* outbuf, int64_t outcap) {
  Decoder* d = static_cast<Decoder*>(dv);
  if (id < 0 || id >= (int64_t)d->dict_entries.size()) return -1;
  const std::string& s = d->dict_entries[(size_t)id];
  int64_t n = (int64_t)s.size();
  if (outcap > 0) {
    int64_t c = n < outcap - 1 ? n : outcap - 1;
    memcpy(outbuf, s.data(), (size_t)c);
    outbuf[c] = 0;
  }
  return n;
}

}  // extern "C"
