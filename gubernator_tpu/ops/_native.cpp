// Native host ops for gubernator-tpu.
//
// The reference is pure Go (SURVEY.md §2.2) so there is no reference
// native component to mirror; this extension exists because the
// host-side request-ingest path (string hashing while the device runs
// the decision step) is the framework's CPU bottleneck, the role Go's
// compiled hashmap/hash code plays in the reference.
//
// Exposed primitives (wrapped by ops/native.py):
//   fnv1a64_batch([str|bytes, ...]) -> (bytes, n)   raw FNV-1a 64
//   fnv1a64_pair_batch(names, keys) -> (bytes, n)   hash(name + "_" + key)
//   parse_get_rate_limits(bytes) -> None | tuple    wire -> packed columns
//   build_rate_limit_resps(...) -> bytes            packed columns -> wire
//   build_responses_from_columns(...) -> bytes      shared-column rows
//                                                   [lo, hi) -> wire
//   route_plan(...) / route_fill(...)               a wave's rows routed
//                                                   by shard, into its pair
//   thread_files(dir, name) -> [(tid, bytes)]       /proc/self/task/*/name,
//                                                   the GIL released once
//
// The avalanche finalizer stays in Python/numpy (hashing.mix64_np) so
// there is exactly one source of truth for it.
//
// The parse/build pair is the service-path fast lane: a
// GetRateLimitsReq wire message is decoded straight into fixed-dtype
// column buffers (key hash, hits, limit, duration, algorithm, behavior,
// burst) without constructing any per-request Python object, and the
// response columns from the device step are serialized straight back to
// a GetRateLimitsResp.  Anything the fast lane doesn't model (metadata,
// empty name/key, unknown fields) makes parse return None and the
// caller falls back to the pb2 path — identical behavior, just slower.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <dirent.h>
#include <fcntl.h>
#include <unistd.h>

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <new>
#include <string>
#include <utility>
#include <vector>

static const uint64_t FNV_OFFSET = 0xCBF29CE484222325ULL;
static const uint64_t FNV_PRIME = 0x100000001B3ULL;

static inline uint64_t fnv1a64(const unsigned char* p, Py_ssize_t n,
                               uint64_t h = FNV_OFFSET) {
  for (Py_ssize_t i = 0; i < n; i++) {
    h ^= (uint64_t)p[i];
    h *= FNV_PRIME;
  }
  return h;
}

// Borrow a UTF-8 view of a str/bytes item.  Returns false on error.
static inline bool utf8_view(PyObject* obj, const unsigned char** p,
                             Py_ssize_t* n) {
  if (PyUnicode_Check(obj)) {
    const char* s = PyUnicode_AsUTF8AndSize(obj, n);
    if (s == nullptr) return false;
    *p = (const unsigned char*)s;
    return true;
  }
  if (PyBytes_Check(obj)) {
    *p = (const unsigned char*)PyBytes_AS_STRING(obj);
    *n = PyBytes_GET_SIZE(obj);
    return true;
  }
  PyErr_SetString(PyExc_TypeError, "expected str or bytes");
  return false;
}

static PyObject* fnv1a64_batch(PyObject*, PyObject* arg) {
  PyObject* seq = PySequence_Fast(arg, "expected a sequence");
  if (seq == nullptr) return nullptr;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  PyObject* out = PyBytes_FromStringAndSize(nullptr, n * 8);
  if (out == nullptr) {
    Py_DECREF(seq);
    return nullptr;
  }
  uint64_t* dst = (uint64_t*)PyBytes_AS_STRING(out);
  for (Py_ssize_t i = 0; i < n; i++) {
    const unsigned char* p;
    Py_ssize_t len;
    if (!utf8_view(PySequence_Fast_GET_ITEM(seq, i), &p, &len)) {
      Py_DECREF(seq);
      Py_DECREF(out);
      return nullptr;
    }
    dst[i] = fnv1a64(p, len);
  }
  Py_DECREF(seq);
  return Py_BuildValue("(Nn)", out, n);
}

// hash(name + "_" + unique_key) without building the joined string —
// the exact key-identity hash of the request path.
static PyObject* fnv1a64_pair_batch(PyObject*, PyObject* args) {
  PyObject *names_arg, *keys_arg;
  if (!PyArg_ParseTuple(args, "OO", &names_arg, &keys_arg)) return nullptr;
  PyObject* names = PySequence_Fast(names_arg, "expected a sequence");
  if (names == nullptr) return nullptr;
  PyObject* keys = PySequence_Fast(keys_arg, "expected a sequence");
  if (keys == nullptr) {
    Py_DECREF(names);
    return nullptr;
  }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(names);
  if (PySequence_Fast_GET_SIZE(keys) != n) {
    Py_DECREF(names);
    Py_DECREF(keys);
    PyErr_SetString(PyExc_ValueError, "length mismatch");
    return nullptr;
  }
  PyObject* out = PyBytes_FromStringAndSize(nullptr, n * 8);
  if (out == nullptr) {
    Py_DECREF(names);
    Py_DECREF(keys);
    return nullptr;
  }
  uint64_t* dst = (uint64_t*)PyBytes_AS_STRING(out);
  const unsigned char underscore = '_';
  for (Py_ssize_t i = 0; i < n; i++) {
    const unsigned char *pn, *pk;
    Py_ssize_t ln, lk;
    if (!utf8_view(PySequence_Fast_GET_ITEM(names, i), &pn, &ln) ||
        !utf8_view(PySequence_Fast_GET_ITEM(keys, i), &pk, &lk)) {
      Py_DECREF(names);
      Py_DECREF(keys);
      Py_DECREF(out);
      return nullptr;
    }
    uint64_t h = fnv1a64(pn, ln);
    h = fnv1a64(&underscore, 1, h);
    dst[i] = fnv1a64(pk, lk, h);
  }
  Py_DECREF(names);
  Py_DECREF(keys);
  return Py_BuildValue("(Nn)", out, n);
}

// ---------------------------------------------------------------------------
// Protobuf wire fast lane (hand-rolled proto3 varint/length-delimited codec;
// field numbers from proto/gubernator.proto — the schema is frozen by the
// reference contract, SURVEY.md §2.4).

// Strict UTF-8 validation (RFC 3629: no surrogates, no overlongs, max
// U+10FFFF) — mirrors protobuf's string-field check so the fast lane
// accepts exactly what pb2 accepts.
static inline bool valid_utf8(const uint8_t* p, uint64_t n) {
  const uint8_t* end = p + n;
  while (p < end) {
    uint8_t c = *p;
    if (c < 0x80) {
      p++;
    } else if ((c & 0xE0) == 0xC0) {
      if (end - p < 2 || (p[1] & 0xC0) != 0x80 || c < 0xC2) return false;
      p += 2;
    } else if ((c & 0xF0) == 0xE0) {
      if (end - p < 3 || (p[1] & 0xC0) != 0x80 || (p[2] & 0xC0) != 0x80)
        return false;
      if (c == 0xE0 && p[1] < 0xA0) return false;          // overlong
      if (c == 0xED && p[1] >= 0xA0) return false;         // surrogate
      p += 3;
    } else if ((c & 0xF8) == 0xF0) {
      if (end - p < 4 || (p[1] & 0xC0) != 0x80 || (p[2] & 0xC0) != 0x80 ||
          (p[3] & 0xC0) != 0x80)
        return false;
      if (c == 0xF0 && p[1] < 0x90) return false;          // overlong
      if (c > 0xF4 || (c == 0xF4 && p[1] >= 0x90)) return false;  // >10FFFF
      p += 4;
    } else {
      return false;
    }
  }
  return true;
}

static inline bool read_varint(const uint8_t** p, const uint8_t* end,
                               uint64_t* out) {
  uint64_t v = 0;
  int shift = 0;
  const uint8_t* q = *p;
  while (q < end && shift < 64) {
    uint8_t b = *q++;
    v |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *p = q;
      *out = v;
      return true;
    }
    shift += 7;
  }
  return false;
}

// parse_get_rate_limits(bytes) ->
//   None                                  (needs the pb2 fallback path)
// | (n, khash_raw u64le, hits i64le, limit i64le, duration i64le,
//    algorithm i32le, behavior i32le, burst i64le, behavior_or,
//    tlv_off u64le, tlv_len u64le, created_at i64le, name_hash u64le)
// name_hash is the FNV-1a64 state after the request's `name` alone —
// what khash_raw continues from before "_" and the unique key are mixed
// in; the analytics tap learns a key's tenant from it without reading
// the name again.
// created_at (field 10, 0 = unset) is the caller's accepted-at clock,
// stamped by the forward hop (stamp_req_tlvs) so the owner applies the
// request at the caller's time base — mixing bases resets buckets and
// silently drops debits (the cold-key conservation loss).
// tlv_off/tlv_len delimit each complete `requests` TLV (tag byte through
// payload end) in the input: a clustered daemon forwards a sub-batch to
// its owner by concatenating those slices verbatim — the peer wire's
// GetPeerRateLimitsReq.requests uses the same field number (1), so the
// framing is byte-compatible (proto/peers.proto).
static PyObject* parse_get_rate_limits(PyObject*, PyObject* arg) {
  Py_buffer view;
  if (PyObject_GetBuffer(arg, &view, PyBUF_SIMPLE) < 0) return nullptr;
  const uint8_t* base = (const uint8_t*)view.buf;
  const uint8_t* p = base;
  const uint8_t* end = p + view.len;
  std::vector<uint64_t> khash, name_hash;
  std::vector<int64_t> hits, limit, duration, burst, created;
  std::vector<int32_t> alg, beh;
  std::vector<uint64_t> tlv_off, tlv_len;
  khash.reserve(64);
  uint64_t beh_or = 0;
  bool fallback = false;
  while (p < end) {
    const uint8_t* tlv_start = p;
    uint64_t tag;
    if (!read_varint(&p, end, &tag) || tag != 0x0A) {  // field 1, LEN
      fallback = true;
      break;
    }
    uint64_t len;
    if (!read_varint(&p, end, &len) || (uint64_t)(end - p) < len) {
      fallback = true;
      break;
    }
    const uint8_t* q = p;
    const uint8_t* qend = p + len;
    p = qend;
    const uint8_t* name_p = nullptr;
    const uint8_t* key_p = nullptr;
    uint64_t name_len = 0, key_len = 0;
    int64_t f_hits = 0, f_limit = 0, f_dur = 0, f_burst = 0;
    int64_t f_created = 0;
    int32_t f_alg = 0, f_beh = 0;
    while (q < qend && !fallback) {
      uint64_t t;
      if (!read_varint(&q, qend, &t)) {
        fallback = true;
        break;
      }
      uint64_t field = t >> 3, wt = t & 7;
      if (wt == 2) {
        uint64_t l;
        if (!read_varint(&q, qend, &l) || (uint64_t)(qend - q) < l) {
          fallback = true;
          break;
        }
        if (field == 1) {
          name_p = q;
          name_len = l;
        } else if (field == 2) {
          key_p = q;
          key_len = l;
        } else {  // metadata (9) or unknown: not modeled here
          fallback = true;
          break;
        }
        q += l;
      } else if (wt == 0) {
        uint64_t v;
        if (!read_varint(&q, qend, &v)) {
          fallback = true;
          break;
        }
        switch (field) {
          case 3: f_hits = (int64_t)v; break;
          case 4: f_limit = (int64_t)v; break;
          case 5: f_dur = (int64_t)v; break;
          case 6: f_alg = (int32_t)v; break;
          case 7: f_beh = (int32_t)v; break;
          case 8: f_burst = (int64_t)v; break;
          case 10: f_created = (int64_t)v; break;
          default: fallback = true;
        }
      } else {
        fallback = true;
      }
    }
    if (fallback) break;
    if (name_p == nullptr || name_len == 0 || key_p == nullptr ||
        key_len == 0 ||
        // pb2 rejects invalid UTF-8 in string fields with DecodeError;
        // the fast lane must not accept what the fallback path rejects
        !valid_utf8(name_p, name_len) || !valid_utf8(key_p, key_len)) {
      // empty name/unique_key produce per-request error responses on
      // the pb2 path; keep that logic in one place
      fallback = true;
      break;
    }
    uint64_t h = fnv1a64(name_p, (Py_ssize_t)name_len);
    name_hash.push_back(h);
    const unsigned char us = '_';
    h = fnv1a64(&us, 1, h);
    h = fnv1a64(key_p, (Py_ssize_t)key_len, h);
    khash.push_back(h);
    hits.push_back(f_hits);
    limit.push_back(f_limit);
    duration.push_back(f_dur);
    burst.push_back(f_burst);
    created.push_back(f_created);
    alg.push_back(f_alg);
    beh.push_back(f_beh);
    beh_or |= (uint64_t)(uint32_t)f_beh;
    tlv_off.push_back((uint64_t)(tlv_start - base));
    tlv_len.push_back((uint64_t)(qend - tlv_start));
  }
  PyBuffer_Release(&view);
  if (fallback) Py_RETURN_NONE;
  Py_ssize_t n = (Py_ssize_t)khash.size();
  // empty vectors may have null data(); Py_BuildValue "y#" would turn
  // a null pointer into None — hand it a valid empty buffer instead
  static const char kEmpty[1] = {0};
  const char* kh_p = n ? (const char*)khash.data() : kEmpty;
  const char* hi_p = n ? (const char*)hits.data() : kEmpty;
  const char* li_p = n ? (const char*)limit.data() : kEmpty;
  const char* du_p = n ? (const char*)duration.data() : kEmpty;
  const char* al_p = n ? (const char*)alg.data() : kEmpty;
  const char* be_p = n ? (const char*)beh.data() : kEmpty;
  const char* bu_p = n ? (const char*)burst.data() : kEmpty;
  const char* to_p = n ? (const char*)tlv_off.data() : kEmpty;
  const char* tl_p = n ? (const char*)tlv_len.data() : kEmpty;
  const char* cr_p = n ? (const char*)created.data() : kEmpty;
  const char* nh_p = n ? (const char*)name_hash.data() : kEmpty;
  PyObject* out = Py_BuildValue(
      "(ny#y#y#y#y#y#y#Ky#y#y#y#)", n, kh_p, n * 8, hi_p, n * 8, li_p,
      n * 8, du_p, n * 8, al_p, n * 4, be_p, n * 4, bu_p, n * 8,
      (unsigned long long)beh_or, to_p, n * 8, tl_p, n * 8, cr_p,
      n * 8, nh_p, n * 8);
  return out;
}

// stamp_req_tlvs(data, tlv_off i64[], tlv_len i64[], created i64[],
//                stamp_ms) -> bytes
// The forward hop's bulk TLV join: concatenates the given request TLV
// slices of `data`, appending `created_at = stamp_ms` (field 10) to
// every slice whose parsed created_at is 0 — so a forwarded request
// applies at the CALLER's clock on the owner (a slice that already
// carries a caller stamp forwards verbatim: first hop wins).  The
// arrays are pre-gathered by the caller (numpy fancy indexing), one
// entry per forwarded row.
static PyObject* stamp_req_tlvs(PyObject*, PyObject* args) {
  Py_buffer view, boff, blen, bcreated;
  long long stamp_ms;
  if (!PyArg_ParseTuple(args, "y*y*y*y*L", &view, &boff, &blen,
                        &bcreated, &stamp_ms))
    return nullptr;
  Py_ssize_t n = boff.len / (Py_ssize_t)sizeof(int64_t);
  const int64_t* toff = (const int64_t*)boff.buf;
  const int64_t* tlen = (const int64_t*)blen.buf;
  const int64_t* created = (const int64_t*)bcreated.buf;
  const uint8_t* base = (const uint8_t*)view.buf;
  bool bad = blen.len != boff.len || bcreated.len != boff.len;
  // field-10 varint suffix: tag 0x50 + up to 10 payload bytes
  uint8_t suffix[11];
  Py_ssize_t suffix_len = 0;
  suffix[suffix_len++] = 0x50;
  uint64_t v = (uint64_t)stamp_ms;
  while (v >= 0x80) {
    suffix[suffix_len++] = (uint8_t)((v & 0x7F) | 0x80);
    v >>= 7;
  }
  suffix[suffix_len++] = (uint8_t)v;
  std::vector<uint8_t> out;
  out.reserve((size_t)view.len + (size_t)n * (size_t)(suffix_len + 3));
  for (Py_ssize_t i = 0; i < n && !bad; i++) {
    const uint8_t* tlv = base + toff[i];
    const uint8_t* tend = tlv + tlen[i];
    if (toff[i] < 0 || tlen[i] < 2 || toff[i] + tlen[i] > view.len ||
        tlv[0] != 0x0A) {
      bad = true;
      break;
    }
    if (created[i] != 0) {  // caller already stamped: verbatim
      out.insert(out.end(), tlv, tend);
      continue;
    }
    const uint8_t* p = tlv + 1;
    uint64_t plen;
    if (!read_varint(&p, tend, &plen) ||
        (uint64_t)(tend - p) != plen) {
      bad = true;
      break;
    }
    uint64_t new_len = plen + (uint64_t)suffix_len;
    out.push_back(0x0A);
    uint64_t lv = new_len;
    while (lv >= 0x80) {
      out.push_back((uint8_t)((lv & 0x7F) | 0x80));
      lv >>= 7;
    }
    out.push_back((uint8_t)lv);
    out.insert(out.end(), p, tend);
    out.insert(out.end(), suffix, suffix + suffix_len);
  }
  PyBuffer_Release(&view);
  PyBuffer_Release(&boff);
  PyBuffer_Release(&blen);
  PyBuffer_Release(&bcreated);
  if (bad) {
    PyErr_SetString(PyExc_ValueError, "malformed request TLV slice");
    return nullptr;
  }
  return PyBytes_FromStringAndSize(
      out.empty() ? "" : (const char*)out.data(),
      (Py_ssize_t)out.size());
}

// count_req_items(bytes, excluded=0) -> n | None
// The fused ingest's pre-pass over a GetRateLimitsReq /
// GetPeerRateLimitsReq: counts the repeated field-1 TLVs, so the ingest
// below can size the call's pair before the single full parse.  None on
// any framing the fast lane doesn't model (caller falls back to pb2).
//
// `excluded` is a mask of Behavior bits the caller's lane does not
// serve (instance.py › _FUSED_EXCLUDED; DURATION_IS_GREGORIAN is not
// among them: pack_wire_wave does the calendar itself): None at the
// FIRST request that carries one, before a pair exists — an all-GLOBAL
// call costs the fused lane one request's header, not a pass.  With a
// mask the walk reads each request's field tags (LEN payloads skipped
// by their length, last behavior wins, as the full parse has it); with
// 0 it touches no payload at all.
static PyObject* count_req_items(PyObject*, PyObject* args) {
  Py_buffer view;
  unsigned long long excluded = 0;
  if (!PyArg_ParseTuple(args, "y*|K", &view, &excluded)) return nullptr;
  const uint8_t* p = (const uint8_t*)view.buf;
  const uint8_t* end = p + view.len;
  Py_ssize_t n = 0;
  bool fallback = false;
  while (p < end) {
    uint64_t tag, len;
    if (!read_varint(&p, end, &tag) || tag != 0x0A ||
        !read_varint(&p, end, &len) || (uint64_t)(end - p) < len) {
      fallback = true;
      break;
    }
    const uint8_t* q = p;
    p += len;
    n++;
    if (!excluded) continue;
    uint64_t beh = 0;
    while (q < p && !fallback) {
      uint64_t t, v;
      if (!read_varint(&q, p, &t) || !read_varint(&q, p, &v)) {
        fallback = true;
      } else if ((t & 7) == 2) {  // v is the payload's length
        if ((uint64_t)(p - q) < v) fallback = true;
        q += v;
      } else if ((t & 7) != 0) {  // the full parse models varint, LEN
        fallback = true;
      } else if ((t >> 3) == 7) {
        beh = (uint64_t)(uint32_t)v;
      }
    }
    if (fallback || (beh & excluded)) {
      fallback = true;
      break;
    }
  }
  PyBuffer_Release(&view);
  if (fallback) Py_RETURN_NONE;
  return PyLong_FromSsize_t(n);
}

// splitmix64 avalanche finalizer — MUST stay bit-identical to
// hashing.mix64_np / hashing.mix64 (tests/test_native.py pins the
// parity); the fused ingest applies it inline so the packed key column
// needs no second numpy pass.
static inline uint64_t mix64(uint64_t x) {
  x ^= x >> 30;
  x *= 0xBF58476D1CE4E5B9ULL;
  x ^= x >> 27;
  x *= 0x94D049BB133111EBULL;
  x ^= x >> 31;
  return x;
}

// ---- the calendar (gregorian.py › gregorian_expiration's twin) --------
//
// The end of the calendar period (UTC) that holds a clock, in epoch-ms:
// what a DURATION_IS_GREGORIAN row expires at.  MINUTES, HOURS, DAYS and
// WEEKS are integer divisions of epoch-ms (1970-01-01 was a Thursday:
// the Monday before it lies 3 days back); MONTHS and YEARS keep the
// proleptic Gregorian calendar by the days-from-civil arithmetic of
// 400-year eras.  tests/test_native_calendar.py holds this to
// gregorian.py ms for ms, all six ordinals.

static const uint32_t GREGORIAN = 4;  // Behavior.DURATION_IS_GREGORIAN
static const int64_t DAY_MS = 86400000;
static const int GREG_ORDINALS = 6;  // types.GregorianDuration: 0..5
// the clocks gregorian_expiration takes for EVERY ordinal (datetime's
// years 1..9999, the next period's first day among them):
// [0001-01-01, 9999-01-01)
static const int64_t CLOCK_MIN = -62135596800000LL;
static const int64_t CLOCK_MAX = 253370764800000LL;

static inline int64_t floor_div(int64_t a, int64_t b) {  // b > 0
  return a / b - (a % b < 0);
}

// days since 1970-01-01 of the first of month m (1..12) of year y
static inline int64_t days_from_civil(int64_t y, int m) {
  y -= m <= 2;
  int64_t era = floor_div(y, 400);
  int64_t yoe = y - era * 400;                               // [0, 399]
  int64_t doy = (153 * (m > 2 ? m - 3 : m + 9) + 2) / 5;     // day 1
  int64_t doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  return era * 146097 + doe - 719468;
}

// the year and month (1..12) of a day counted from 1970-01-01
static inline void civil_from_days(int64_t z, int64_t* y, int* m) {
  z += 719468;
  int64_t era = floor_div(z, 146097);
  int64_t doe = z - era * 146097;                            // [0, 146096]
  int64_t yoe = (doe - doe / 1460 + doe / 36524 - doe / 146096) / 365;
  int64_t doy = doe - (365 * yoe + yoe / 4 - yoe / 100);
  int64_t mp = (5 * doy + 2) / 153;                          // [0, 11]
  *m = (int)(mp < 10 ? mp + 3 : mp - 9);
  *y = yoe + era * 400 + (*m <= 2);
}

// One calendar period [start, end) of an ordinal.  A call's rows share
// a stamp and, in practice, an ordinal: the pass keeps the last period
// and asks the calendar again only for a clock outside it, so MONTHS /
// YEARS cost one days-from-civil a call, not a row.
struct Period {
  int64_t ordinal = -1, start = 0, end = 0;

  bool holds(int64_t ord, int64_t now) const {
    return ord == ordinal && start <= now && now < end;
  }

  // false: an ordinal or a clock the calendar does not take
  bool find(int64_t ord, int64_t now) {
    if (ord < 0 || ord >= GREG_ORDINALS || now < CLOCK_MIN ||
        now >= CLOCK_MAX)
      return false;
    ordinal = ord;
    if (ord <= 3) {
      static const int64_t width[4] = {60000, 3600000, DAY_MS, 7 * DAY_MS};
      int64_t w = width[ord], shift = ord == 3 ? 3 * DAY_MS : 0;
      start = floor_div(now + shift, w) * w - shift;
      end = start + w;
      return true;
    }
    int64_t y;
    int m;
    civil_from_days(floor_div(now, DAY_MS), &y, &m);
    if (ord == 4) {  // MONTHS: the first of the next month
      start = days_from_civil(y, m) * DAY_MS;
      end = (m < 12 ? days_from_civil(y, m + 1)
                    : days_from_civil(y + 1, 1)) * DAY_MS;
    } else {  // YEARS: the first of the next January
      start = days_from_civil(y, 1) * DAY_MS;
      end = days_from_civil(y + 1, 1) * DAY_MS;
    }
    return true;
  }
};

// gregorian_end(now_ms, ordinal) -> epoch-ms | None
// gregorian.py › gregorian_expiration in C++, as pack_wire_wave applies
// it a row; None for an ordinal outside 0..5 or a clock outside
// [0001-01-01, 9999-01-01) — what the fused ingest declines a call for.
static PyObject* gregorian_end(PyObject*, PyObject* args) {
  long long now_ms, ordinal;
  if (!PyArg_ParseTuple(args, "LL", &now_ms, &ordinal)) return nullptr;
  Period p;
  if (!p.find(ordinal, now_ms)) Py_RETURN_NONE;
  return PyLong_FromLongLong(p.end);
}

// What an engine's launch needs to know of a call's rows (parallel/
// sharded.py › ShardedEngine.lay_out), accumulated a row at a time by
// the two passes below.  A row is outside the step program's value
// domain when hits, limit or burst is not in [0, value_bound), or —
// LEAKY_BUCKET only — eff_ms is not in [1, eff_bound), or its
// algorithm is neither 0 nor 1: ops/pallas_step.py ›
// pallas_value_domain_mask, whose bounds come in as arguments.
// value_bound 0 = the full int64 domain: no row is out.
struct Derived {
  std::vector<int64_t> ood;  // valid rows outside the domain
  long long leaky = 0;       // LEAKY rows that stay valid
  long long greg = 0;        // valid DURATION_IS_GREGORIAN rows
  int64_t now_lo = 0, now_hi = 0, prev = 0;
  bool monotone = true;
  Py_ssize_t n = 0;

  void row(bool valid, bool exempt, int64_t hits, int64_t limit,
           int64_t burst, int32_t alg, int32_t behavior, int64_t eff,
           int64_t now, uint64_t value_bound, uint64_t eff_bound) {
    if (valid && ((uint32_t)behavior & GREGORIAN)) greg++;
    bool leaky_row = alg == 1;
    bool ok = !value_bound || exempt ||
              ((alg == 0 || leaky_row) && hits >= 0 &&
               (uint64_t)hits < value_bound && limit >= 0 &&
               (uint64_t)limit < value_bound && burst >= 0 &&
               (uint64_t)burst < value_bound &&
               (!leaky_row || (eff >= 1 && (uint64_t)eff < eff_bound)));
    if (valid && !ok) ood.push_back((int64_t)n);
    if (valid && ok && leaky_row) leaky++;
    if (n == 0) {
      now_lo = now_hi = now;
    } else {
      if (now < prev) monotone = false;
      if (now < now_lo) now_lo = now;
      if (now > now_hi) now_hi = now;
    }
    prev = now;
    n++;
  }

  // (ood i64le bytes, leaky, greg, now_lo, now_hi, monotone)
  PyObject* build() const {
    static const char kNone[1] = {0};
    return Py_BuildValue(
        "(y#LLLLO)", ood.empty() ? kNone : (const char*)ood.data(),
        (Py_ssize_t)(ood.size() * 8), leaky, greg, (long long)now_lo,
        (long long)now_hi, monotone ? Py_True : Py_False);
  }
};

// derive_rows(m64, m32, mslot | None, value_bound, eff_bound) ->
//   (ood i64le, leaky, greg, now_lo, now_hi, monotone)
// The derivation alone, over a call's rows already in the upload
// layout (m64 [8,n] i64, m32 [3,n] i32; rows contiguous, any row
// stride — a view of a wider pair will do): what pack_wire_wave derives
// in its own pass, for the producers that pack in Python.  mslot
// (i32[n], optional): rows with mslot >= 0 are mesh-GLOBAL rows, exempt
// from the domain.  One pass, the GIL kept: ~n × 12 loads.
static PyObject* derive_rows(PyObject*, PyObject* args) {
  PyObject *o64, *o32, *oms;
  unsigned long long value_bound, eff_bound;
  if (!PyArg_ParseTuple(args, "OOOKK", &o64, &o32, &oms, &value_bound,
                        &eff_bound))
    return nullptr;
  Py_buffer b64, b32, bms;
  if (PyObject_GetBuffer(o64, &b64, PyBUF_STRIDES | PyBUF_FORMAT) < 0)
    return nullptr;
  if (PyObject_GetBuffer(o32, &b32, PyBUF_STRIDES | PyBUF_FORMAT) < 0) {
    PyBuffer_Release(&b64);
    return nullptr;
  }
  bool has_ms = oms != Py_None;
  if (has_ms &&
      PyObject_GetBuffer(oms, &bms, PyBUF_STRIDES | PyBUF_FORMAT) < 0) {
    PyBuffer_Release(&b64);
    PyBuffer_Release(&b32);
    return nullptr;
  }
  PyObject* out = nullptr;
  Py_ssize_t n = b64.ndim == 2 ? b64.shape[1] : -1;
  if (b64.ndim != 2 || b32.ndim != 2 || b64.shape[0] != 8 ||
      b32.shape[0] != 3 || b32.shape[1] != n || b64.itemsize != 8 ||
      b32.itemsize != 4 || (n > 1 && (b64.strides[1] != 8 ||
                                      b32.strides[1] != 4)) ||
      (has_ms && (bms.ndim != 1 || bms.shape[0] != n ||
                  bms.itemsize != 4 ||
                  (n > 1 && bms.strides[0] != 4)))) {
    PyErr_SetString(PyExc_ValueError,
                    "derive_rows wants [8,n] i64, [3,n] i32 (rows "
                    "contiguous) and an optional i32[n]");
  } else {
    const char* p64 = (const char*)b64.buf;
    const char* p32 = (const char*)b32.buf;
    auto r64 = [&](int r) {
      return (const int64_t*)(p64 + r * b64.strides[0]);
    };
    auto r32 = [&](int r) {
      return (const int32_t*)(p32 + r * b32.strides[0]);
    };
    const int64_t *hits = r64(1), *limit = r64(2), *eff = r64(4),
                  *burst = r64(6), *now = r64(7);
    const int32_t *beh = r32(0), *alg = r32(1), *valid = r32(2);
    const int32_t* ms = has_ms ? (const int32_t*)bms.buf : nullptr;
    Derived d;
    for (Py_ssize_t i = 0; i < n; i++)
      d.row(valid[i] != 0, ms && ms[i] >= 0, hits[i], limit[i], burst[i],
            alg[i], beh[i], eff[i], now[i], value_bound, eff_bound);
    out = d.build();
  }
  PyBuffer_Release(&b64);
  PyBuffer_Release(&b32);
  if (has_ms) PyBuffer_Release(&bms);
  return out;
}

// ---- the shard route of a device wave (parallel/sharded.py) ------------
//
// route_plan and route_fill are ShardedEngine._build_waves and ._fill in
// one pass each that KEEPS the GIL: the dispatch worker — the one thread
// the devices wait for — ran ~35 numpy calls a device wave there, most
// of which give the GIL up and have to win it back from ~30 handler
// threads (PERF.md §6, PR 36).  The numpy pair stays in sharded.py as
// the path of a checkout without this extension and as the reference
// tests/test_wave_route_native.py holds these two to, byte for byte.

// A buffer argument held for the length of a call.
struct Buf {
  Py_buffer b;
  bool held = false;
  bool get(PyObject* o, bool writable = false) {
    int flags = PyBUF_STRIDES | PyBUF_FORMAT | (writable ? PyBUF_WRITABLE : 0);
    held = PyObject_GetBuffer(o, &b, flags) == 0;
    return held;
  }
  // an integer (or, one byte wide, a bool) array of this item size
  bool ints(Py_ssize_t itemsize) const {
    const char* f = b.format ? b.format : "B";
    if (*f == '@' || *f == '=' || *f == '<') f++;
    return b.itemsize == itemsize && f[0] && !f[1] &&
           strchr(itemsize == 1 ? "?bB" : "hHiIlLqQ", f[0]);
  }
  // [n] of that, any stride
  bool vec(Py_ssize_t itemsize) const { return b.ndim == 1 && ints(itemsize); }
  // [n] of that, one after the other
  bool packed(Py_ssize_t itemsize) const {
    return vec(itemsize) && (b.shape[0] < 2 || b.strides[0] == itemsize);
  }
  // [rows, n] of that, each row contiguous (any row stride: a view of
  // a wider pair will do)
  bool matrix(Py_ssize_t rows, Py_ssize_t itemsize) const {
    return b.ndim == 2 && ints(itemsize) && b.shape[0] == rows &&
           (b.shape[1] < 2 || b.strides[1] == itemsize);
  }
  char* row(int r) const { return (char*)b.buf + r * b.strides[0]; }
  ~Buf() {
    if (held) PyBuffer_Release(&b);
  }
};

// route_plan(khash u64[N], pending i64[k] | None, shards, buckets) ->
//   [(idx i64le bytes, slots i64le bytes, bw_w, wcnt), ...]
// The device waves of rows `pending` (None = every row, in row order):
// a row's shard is hashing.shard_of (((h >> 32) * shards) >> 32), its
// position the count of earlier `pending` rows of its shard (a counting
// sort IS the stable sort), its device wave position / the largest
// bucket; a wave rides bw_w, the smallest bucket covering wcnt, the rows
// of its densest shard, and lists its rows by shard, then position —
// the order a stable argsort by shard lists them — each with its slot,
// shard * bw_w + position % the largest bucket.  buckets: ascending.
static PyObject* route_plan(PyObject*, PyObject* args) {
  // the tables below hold a few words a shard: no mesh comes near this
  const Py_ssize_t MAX_SHARDS = 1 << 16;
  PyObject *okh, *opend, *obuckets;
  Py_ssize_t shards;
  if (!PyArg_ParseTuple(args, "OOnO", &okh, &opend, &shards, &obuckets))
    return nullptr;
  Buf kh, pend;
  bool all = opend == Py_None;
  if (!kh.get(okh) || (!all && !pend.get(opend))) return nullptr;
  std::vector<int64_t> buckets;
  PyObject* seq = PySequence_Fast(obuckets, "route_plan wants buckets");
  if (!seq) return nullptr;
  for (Py_ssize_t i = 0; i < PySequence_Fast_GET_SIZE(seq); i++)
    buckets.push_back(PyLong_AsLongLong(PySequence_Fast_GET_ITEM(seq, i)));
  Py_DECREF(seq);
  if (PyErr_Occurred()) return nullptr;
  bool ascending = !buckets.empty() && buckets[0] > 0;
  for (size_t i = 1; i < buckets.size(); i++)
    ascending = ascending && buckets[i] > buckets[i - 1];
  if (!kh.vec(8) || (!all && !pend.packed(8)) || shards < 1 ||
      shards > MAX_SHARDS || !ascending) {
    PyErr_SetString(PyExc_ValueError,
                    "route_plan wants a 64-bit khash[N], a contiguous "
                    "i64 pending[k] or None, 1..65536 shards and "
                    "ascending positive buckets");
    return nullptr;
  }
  const Py_ssize_t N = kh.b.shape[0], k = all ? N : pend.b.shape[0];
  const int64_t* pending = all ? nullptr : (const int64_t*)pend.b.buf;
  const int64_t Bw = buckets.back();
  // pass 1: every row's shard, and the rows of each shard
  std::vector<int32_t> shard(k);
  std::vector<int64_t> cnt(shards, 0);
  int64_t densest = 0;
  for (Py_ssize_t j = 0; j < k; j++) {
    int64_t i = pending ? pending[j] : j;
    if (i < 0 || i >= N) {
      PyErr_SetString(PyExc_IndexError, "route_plan: pending row not in khash");
      return nullptr;
    }
    uint64_t h =
        *(const uint64_t*)((const char*)kh.b.buf + i * kh.b.strides[0]);
    int32_t s = (int32_t)(((h >> 32) * (uint64_t)shards) >> 32);
    shard[j] = s;
    if (++cnt[s] > densest) densest = cnt[s];
  }
  // each wave's bucket, densest shard and where its shards' runs start
  const Py_ssize_t W = (Py_ssize_t)((densest + Bw - 1) / Bw);
  std::vector<int64_t> start(W * shards), bw(W), wcnt(W, 0);
  std::vector<int64_t*> idx(W), slots(W);
  PyObject* plan = PyList_New(W);
  if (!plan) return nullptr;
  for (Py_ssize_t w = 0; w < W; w++) {
    int64_t rows = 0;
    for (Py_ssize_t s = 0; s < shards; s++) {
      int64_t c = cnt[s] - w * Bw;
      c = c < 0 ? 0 : c > Bw ? Bw : c;
      start[w * shards + s] = rows;
      rows += c;
      if (c > wcnt[w]) wcnt[w] = c;
    }
    bw[w] = Bw;
    for (size_t b = buckets.size(); b-- > 0 && wcnt[w] <= buckets[b];)
      bw[w] = buckets[b];
    PyObject* bi = PyBytes_FromStringAndSize(nullptr, rows * 8);
    PyObject* bs = PyBytes_FromStringAndSize(nullptr, rows * 8);
    PyObject* t = bi && bs ? Py_BuildValue("(OOLL)", bi, bs,
                                           (long long)bw[w],
                                           (long long)wcnt[w])
                           : nullptr;
    Py_XDECREF(bi);
    Py_XDECREF(bs);
    if (!t) {
      Py_DECREF(plan);
      return nullptr;
    }
    PyList_SET_ITEM(plan, w, t);
    idx[w] = (int64_t*)PyBytes_AS_STRING(bi);
    slots[w] = (int64_t*)PyBytes_AS_STRING(bs);
  }
  // pass 2: every row to its place in its wave's lists
  std::vector<int64_t> seen(shards, 0);
  for (Py_ssize_t j = 0; j < k; j++) {
    int32_t s = shard[j];
    int64_t p = seen[s]++, w = p / Bw, r = p - w * Bw;
    int64_t at = start[w * shards + s] + r;
    idx[w][at] = pending ? pending[j] : j;
    slots[w][at] = s * bw[w] + r;
  }
  return plan;
}

// One row of an upload matrix: `get(i)` of row idx[j] at slots[j], `pad`
// in every other slot — each of the m cells written once.  slots:
// strictly ascending, below m.
template <class T, class Get>
static inline void lay_row(T* dst, Py_ssize_t m, const int64_t* idx,
                           const int64_t* slots, Py_ssize_t k, T pad,
                           Get get) {
  Py_ssize_t at = 0;
  for (Py_ssize_t j = 0; j < k; j++) {
    for (Py_ssize_t s = slots[j]; at < s;) dst[at++] = pad;
    dst[at++] = get(idx[j]);
  }
  while (at < m) dst[at++] = pad;
}

// route_fill(m64 [8,N] i64, m32 [3,N] i32, valid bool[N] | None,
//            mslot i32[N] | None, idx i64[k], slots i64[k],
//            a64 [8,m] i64, a32 [3,m] i32, mblk i32[m] | None) -> None
// One device wave of route_plan into its upload pair: rows idx of the
// joined matrices at slots, eleven words each — `valid` in place of the
// rows' own where given —, every other slot empty_batch padding (zeros,
// eff_ms 1), and mslot's lane into mblk (-1 outside the rows; given
// exactly when mslot is).  EVERY cell of a64, a32 and mblk is written,
// as pack_wire_wave writes its pair's: uninitialised memory will do.
// slots: strictly ascending, as route_plan lists them.  A wrong argument
// raises before anything is written.
static PyObject* route_fill(PyObject*, PyObject* args) {
  PyObject *o64, *o32, *ovalid, *omslot, *oidx, *oslots, *oa64, *oa32, *omblk;
  if (!PyArg_ParseTuple(args, "OOOOOOOOO", &o64, &o32, &ovalid, &omslot,
                        &oidx, &oslots, &oa64, &oa32, &omblk))
    return nullptr;
  Buf m64, m32, valid, mslot, idx, slots, a64, a32, mblk;
  bool has_valid = ovalid != Py_None, has_ms = omslot != Py_None;
  if (!m64.get(o64) || !m32.get(o32) || (has_valid && !valid.get(ovalid)) ||
      (has_ms && !mslot.get(omslot)) || !idx.get(oidx) ||
      !slots.get(oslots) || !a64.get(oa64, true) || !a32.get(oa32, true) ||
      (omblk != Py_None && !mblk.get(omblk, true)))
    return nullptr;
  if (!m64.matrix(8, 8) || !m32.matrix(3, 4) || !a64.matrix(8, 8) ||
      !a32.matrix(3, 4) || !idx.packed(8) || !slots.packed(8) ||
      m32.b.shape[1] != m64.b.shape[1] || a32.b.shape[1] != a64.b.shape[1] ||
      slots.b.shape[0] != idx.b.shape[0] ||
      (has_valid && !(valid.vec(1) && valid.b.shape[0] == m64.b.shape[1])) ||
      (has_ms && !(mslot.packed(4) && mslot.b.shape[0] == m64.b.shape[1])) ||
      has_ms != mblk.held ||
      (has_ms && !(mblk.packed(4) && mblk.b.shape[0] == a64.b.shape[1]))) {
    PyErr_SetString(PyExc_ValueError,
                    "route_fill wants [8,N] i64 + [3,N] i32 rows, "
                    "bool[N] | None, i32[N] | None, i64[k] idx and slots, "
                    "a writable [8,m] i64 + [3,m] i32 pair (rows "
                    "contiguous) and an i32[m] mblk with mslot alone");
    return nullptr;
  }
  const Py_ssize_t N = m64.b.shape[1], m = a64.b.shape[1],
                   k = idx.b.shape[0];
  const int64_t* ix = (const int64_t*)idx.b.buf;
  const int64_t* sl = (const int64_t*)slots.b.buf;
  int64_t below = -1;
  for (Py_ssize_t j = 0; j < k; j++) {
    if (ix[j] < 0 || ix[j] >= N || sl[j] <= below || sl[j] >= m) {
      PyErr_SetString(PyExc_IndexError,
                      "route_fill: a row outside the wave, or slots not "
                      "strictly ascending inside the pair");
      return nullptr;
    }
    below = sl[j];
  }
  const int EFF = 4, VALID = 2;  // core/batch.py › PACK64, PACK32
  for (int r = 0; r < 8; r++) {
    const int64_t* src = (const int64_t*)m64.row(r);
    lay_row((int64_t*)a64.row(r), m, ix, sl, k, (int64_t)(r == EFF),
            [src](int64_t i) { return src[i]; });
  }
  for (int r = 0; r < 3; r++) {
    const int32_t* src = (const int32_t*)m32.row(r);
    if (r == VALID && has_valid) {
      const char* v = (const char*)valid.b.buf;
      Py_ssize_t step = valid.b.strides[0];
      lay_row((int32_t*)a32.row(r), m, ix, sl, k, (int32_t)0,
              [v, step](int64_t i) { return (int32_t)(v[i * step] != 0); });
    } else {
      lay_row((int32_t*)a32.row(r), m, ix, sl, k, (int32_t)0,
              [src](int64_t i) { return src[i]; });
    }
  }
  if (has_ms) {
    const int32_t* src = (const int32_t*)mslot.b.buf;
    lay_row((int32_t*)mblk.b.buf, m, ix, sl, k, (int32_t)-1,
            [src](int64_t i) { return src[i]; });
  }
  Py_RETURN_NONE;
}

// pack_wire_wave(data, now_ms, a64, a32, m,
//                duration_max, value_max, eff_max, td_bound,
//                value_bound, eff_bound, greg_eff i64le[6]) ->
//   None                              (needs the classic/pb2 path)
// | (n, khash u64le, behavior_or, tlv_off u64le, tlv_len u64le,
//    name_hash u64le,
//    (ood i64le, leaky, greg, now_lo, now_hi, monotone))
//
// The fused wire ingest: one pass over a GetRateLimitsReq /
// GetPeerRateLimitsReq that parses, validates, clamps (bit-identical to
// core/batch.py › pack_columns — the clamp bounds come in as arguments
// so types.py stays the single source of truth), key-hashes
// (FNV-1a64 + mix64, zero-remapped) and writes the rows STRAIGHT into
// the call's pair in the upload layout (a64 [8,m] i64 row-major:
// key,hits,limit,duration,eff_ms,greg_end,burst,now; a32 [3,m] i32:
// behavior,algorithm,valid — core/batch.py › PACK64/PACK32).  Every
// cell is written: rows [n, m) read as empty_batch padding (zeros,
// eff_ms 1), so the caller may pass uninitialised memory.
//
// A DURATION_IS_GREGORIAN row's `duration` is its ordinal: greg_end is
// the end of the calendar period that holds the clock the row is
// APPLIED at (its created_at stamp, else now_ms — gregorian.py, the
// rule: `now` and `greg_end` of one row never come from two clocks;
// struct Period above), and eff_ms the ordinal's approximate width,
// greg_eff[ordinal] (types.GREGORIAN_APPROX_MS, passed in as the clamp
// bounds are), before the LEAKY clamp — core/batch.py › pack_columns
// and › _calendar_ends, bit for bit.
//
// In the same pass it derives what the engine's launch needs to know of
// the call (struct Derived above): ood, the indices of rows outside the
// step program's value domain (its bounds passed in as the clamp bounds
// are); leaky, the LEAKY_BUCKET rows inside it; greg, the calendar
// rows; now_lo / now_hi, the least and largest arrival time; monotone,
// whether arrival times never decrease in row order.
//
// Returns None (caller falls back) whenever the batch needs host-side
// Python: pb2-fallback framing (as parse_get_rate_limits), n > m, or a
// calendar row whose answer only the classic lane builds — an ordinal
// outside 0..5 (an error on that ROW there, the others served) or a
// clock outside what the calendar takes.  The call is refused WHOLE:
// no row of it is served from here.  GLOBAL/MULTI_REGION gating is the
// caller's policy, applied BEFORE this pass by the pre-pass
// (count_req_items' mask): a call that reaches here is one the lane
// serves.  behavior_or, name_hash: as parse_get_rate_limits returns
// them.
static PyObject* pack_wire_wave(PyObject*, PyObject* args) {
  Py_buffer view, b64, b32, bge;
  long long now_ms;
  Py_ssize_t m;
  unsigned long long duration_max, value_max, eff_max, td_bound;
  unsigned long long value_bound, eff_bound;
  if (!PyArg_ParseTuple(args, "y*Lw*w*nKKKKKKy*", &view, &now_ms, &b64,
                        &b32, &m, &duration_max, &value_max, &eff_max,
                        &td_bound, &value_bound, &eff_bound, &bge))
    return nullptr;
  int64_t greg_eff[GREG_ORDINALS];
  bool sized = bge.len == (Py_ssize_t)sizeof(greg_eff);
  if (sized) memcpy(greg_eff, bge.buf, sizeof(greg_eff));
  PyBuffer_Release(&bge);
  if (!sized || b64.len < m * 8 * (Py_ssize_t)sizeof(int64_t) ||
      b32.len < m * 3 * (Py_ssize_t)sizeof(int32_t)) {
    PyBuffer_Release(&view);
    PyBuffer_Release(&b64);
    PyBuffer_Release(&b32);
    PyErr_SetString(PyExc_ValueError,
                    sized ? "packed buffers too small"
                          : "greg_eff: one i64 width an ordinal");
    return nullptr;
  }
  int64_t* a64 = (int64_t*)b64.buf;  // rows: key hits limit duration
                                     //       eff_ms greg_end burst now
  int32_t* a32 = (int32_t*)b32.buf;  // rows: behavior algorithm valid
  int64_t* r_key = a64;
  int64_t* r_hits = a64 + m;
  int64_t* r_limit = a64 + 2 * m;
  int64_t* r_dur = a64 + 3 * m;
  int64_t* r_eff = a64 + 4 * m;
  int64_t* r_greg = a64 + 5 * m;
  int64_t* r_burst = a64 + 6 * m;
  int64_t* r_now = a64 + 7 * m;
  int32_t* r_beh = a32;
  int32_t* r_alg = a32 + m;
  int32_t* r_valid = a32 + 2 * m;
  const uint8_t* base = (const uint8_t*)view.buf;
  const uint8_t* p = base;
  const uint8_t* end = p + view.len;
  std::vector<uint64_t> khash, name_hash, tlv_off, tlv_len;
  khash.reserve(64);
  Derived derived;
  Period period;  // the last calendar period a row asked for
  uint64_t beh_or = 0;
  bool fallback = false;
  Py_ssize_t n = 0;
  while (p < end) {
    const uint8_t* tlv_start = p;
    uint64_t tag, len;
    if (!read_varint(&p, end, &tag) || tag != 0x0A ||
        !read_varint(&p, end, &len) || (uint64_t)(end - p) < len) {
      fallback = true;
      break;
    }
    const uint8_t* q = p;
    const uint8_t* qend = p + len;
    p = qend;
    const uint8_t* name_p = nullptr;
    const uint8_t* key_p = nullptr;
    uint64_t name_len = 0, key_len = 0;
    int64_t f_hits = 0, f_limit = 0, f_dur = 0, f_burst = 0;
    int64_t f_created = 0;
    int32_t f_alg = 0, f_beh = 0;
    while (q < qend && !fallback) {
      uint64_t t;
      if (!read_varint(&q, qend, &t)) {
        fallback = true;
        break;
      }
      uint64_t field = t >> 3, wt = t & 7;
      if (wt == 2) {
        uint64_t l;
        if (!read_varint(&q, qend, &l) || (uint64_t)(qend - q) < l) {
          fallback = true;
          break;
        }
        if (field == 1) {
          name_p = q;
          name_len = l;
        } else if (field == 2) {
          key_p = q;
          key_len = l;
        } else {
          fallback = true;
          break;
        }
        q += l;
      } else if (wt == 0) {
        uint64_t v;
        if (!read_varint(&q, qend, &v)) {
          fallback = true;
          break;
        }
        switch (field) {
          case 3: f_hits = (int64_t)v; break;
          case 4: f_limit = (int64_t)v; break;
          case 5: f_dur = (int64_t)v; break;
          case 6: f_alg = (int32_t)v; break;
          case 7: f_beh = (int32_t)v; break;
          case 8: f_burst = (int64_t)v; break;
          case 10: f_created = (int64_t)v; break;
          default: fallback = true;
        }
      } else {
        fallback = true;
      }
    }
    if (fallback) break;
    if (name_p == nullptr || name_len == 0 || key_p == nullptr ||
        key_len == 0 || !valid_utf8(name_p, name_len) ||
        !valid_utf8(key_p, key_len) || n >= m) {
      fallback = true;
      break;
    }
    // the caller's accepted-at clock wins when the forward hop stamped
    // it (created_at, field 10): applying a forwarded request at OUR
    // wall clock would mix time bases in the key's bucket row and a
    // later base reads the earlier one as expired — bucket reset,
    // debits silently gone (cold-key conservation loss)
    int64_t now_i = f_created > 0 ? f_created : (int64_t)now_ms;
    // clamps: the exact pack_columns arithmetic (core/batch.py)
    int64_t dur = f_dur < (int64_t)duration_max ? f_dur
                                                : (int64_t)duration_max;
    int64_t eff = dur > 1 ? dur : 1;
    int64_t greg_end = 0;
    if ((uint32_t)f_beh & GREGORIAN) {
      // the period that holds the clock the row is applied at
      if (!period.holds(dur, now_i) && !period.find(dur, now_i)) {
        fallback = true;  // the classic lane builds that row's error
        break;
      }
      greg_end = period.end;
      eff = greg_eff[dur];
    }
    uint64_t h = fnv1a64(name_p, (Py_ssize_t)name_len);
    name_hash.push_back(h);
    const unsigned char us = '_';
    h = fnv1a64(&us, 1, h);
    h = fnv1a64(key_p, (Py_ssize_t)key_len, h);
    uint64_t hm = mix64(h);
    if (hm == 0) hm = 1;
    khash.push_back(hm);
    tlv_off.push_back((uint64_t)(tlv_start - base));
    tlv_len.push_back((uint64_t)(qend - tlv_start));
    int leaky = f_alg == 1;
    uint64_t cap_v = value_max;
    if (leaky) {
      if (eff > (int64_t)eff_max) eff = (int64_t)eff_max;
      uint64_t c = td_bound / (uint64_t)eff;
      cap_v = c < value_max ? c : value_max;
    }
    int64_t lim = f_limit < 0 ? 0 : f_limit;
    if (lim > (int64_t)cap_v) lim = (int64_t)cap_v;
    int64_t hits = f_hits < 0 ? 0 : f_hits;
    if (hits > (int64_t)cap_v) hits = (int64_t)cap_v;
    int64_t burst = f_burst > 0
                        ? (f_burst < (int64_t)cap_v ? f_burst
                                                    : (int64_t)cap_v)
                        : lim;
    r_key[n] = (int64_t)hm;
    r_hits[n] = hits;
    r_limit[n] = lim;
    r_dur[n] = dur;
    r_eff[n] = eff;
    r_greg[n] = greg_end;
    r_burst[n] = burst;
    r_now[n] = now_i;
    r_beh[n] = f_beh;
    r_alg[n] = leaky ? 1 : 0;
    r_valid[n] = 1;
    beh_or |= (uint64_t)(uint32_t)f_beh;
    derived.row(true, false, hits, lim, burst, leaky ? 1 : 0, f_beh, eff,
                now_i, value_bound, eff_bound);
    n++;
  }
  if (!fallback) {
    for (Py_ssize_t i = n; i < m; i++) {  // padding: empty_batch rows
      for (int r = 0; r < 8; r++) a64[r * m + i] = 0;
      r_eff[i] = 1;
      for (int r = 0; r < 3; r++) a32[r * m + i] = 0;
    }
  }
  PyBuffer_Release(&view);
  PyBuffer_Release(&b64);
  PyBuffer_Release(&b32);
  if (fallback) Py_RETURN_NONE;
  static const char kEmptyW[1] = {0};
  const char* kh_p = n ? (const char*)khash.data() : kEmptyW;
  const char* to_p = n ? (const char*)tlv_off.data() : kEmptyW;
  const char* tl_p = n ? (const char*)tlv_len.data() : kEmptyW;
  const char* nh_p = n ? (const char*)name_hash.data() : kEmptyW;
  return Py_BuildValue("(ny#Ky#y#y#N)", n, kh_p, n * 8,
                       (unsigned long long)beh_or, to_p, n * 8, tl_p,
                       n * 8, nh_p, n * 8, derived.build());
}

// split_resp_items(bytes) ->
//   None | (n, tlv_off u64le, tlv_len u64le, status i32le)
// Delimits each repeated field-1 submessage (RateLimitResp) of a
// GetRateLimitsResp / GetPeerRateLimitsResp (both use field 1 —
// proto/gubernator.proto, proto/peers.proto), and extracts each item's
// status (field 1 varint; 0 when omitted).  The clustered wire lane
// merges peer response TLVs into the client response by slicing these
// ranges — no pb2 objects.  Returns None on malformed input or unknown
// top-level fields (caller falls back to pb2).
static PyObject* split_resp_items(PyObject*, PyObject* arg) {
  Py_buffer view;
  if (PyObject_GetBuffer(arg, &view, PyBUF_SIMPLE) < 0) return nullptr;
  const uint8_t* base = (const uint8_t*)view.buf;
  const uint8_t* p = base;
  const uint8_t* end = p + view.len;
  std::vector<uint64_t> tlv_off, tlv_len;
  std::vector<int32_t> status;
  bool fallback = false;
  while (p < end) {
    const uint8_t* tlv_start = p;
    uint64_t tag;
    if (!read_varint(&p, end, &tag) || tag != 0x0A) {  // field 1, LEN
      fallback = true;
      break;
    }
    uint64_t len;
    if (!read_varint(&p, end, &len) || (uint64_t)(end - p) < len) {
      fallback = true;
      break;
    }
    const uint8_t* q = p;
    const uint8_t* qend = p + len;
    p = qend;
    int32_t st = 0;
    // scan the submessage for field 1 (status); skip everything else
    while (q < qend) {
      uint64_t t;
      if (!read_varint(&q, qend, &t)) {
        fallback = true;
        break;
      }
      uint64_t field = t >> 3, wt = t & 7;
      if (wt == 0) {
        uint64_t v;
        if (!read_varint(&q, qend, &v)) {
          fallback = true;
          break;
        }
        if (field == 1) st = (int32_t)v;
      } else if (wt == 2) {
        uint64_t l;
        if (!read_varint(&q, qend, &l) || (uint64_t)(qend - q) < l) {
          fallback = true;
          break;
        }
        q += l;
      } else if (wt == 1) {
        if (qend - q < 8) {
          fallback = true;
          break;
        }
        q += 8;
      } else if (wt == 5) {
        if (qend - q < 4) {
          fallback = true;
          break;
        }
        q += 4;
      } else {
        fallback = true;
        break;
      }
    }
    if (fallback) break;
    tlv_off.push_back((uint64_t)(tlv_start - base));
    tlv_len.push_back((uint64_t)(qend - tlv_start));
    status.push_back(st);
  }
  PyBuffer_Release(&view);
  if (fallback) Py_RETURN_NONE;
  Py_ssize_t n = (Py_ssize_t)tlv_off.size();
  static const char kEmpty2[1] = {0};
  const char* to_p = n ? (const char*)tlv_off.data() : kEmpty2;
  const char* tl_p = n ? (const char*)tlv_len.data() : kEmpty2;
  const char* st_p = n ? (const char*)status.data() : kEmpty2;
  return Py_BuildValue("(ny#y#y#)", n, to_p, n * 8, tl_p, n * 8, st_p,
                       n * 4);
}

static inline void put_varint(std::vector<uint8_t>& out, uint64_t v) {
  while (v >= 0x80) {
    out.push_back((uint8_t)(v | 0x80));
    v >>= 7;
  }
  out.push_back((uint8_t)v);
}

static inline void put_field_varint(std::vector<uint8_t>& out, int field,
                                    uint64_t v) {
  if (v == 0) return;  // proto3: defaults are omitted
  put_varint(out, (uint64_t)(field << 3));
  put_varint(out, v);
}

// Shared serialization core: rows [lo, hi) of the given columns →
// GetRateLimitsResp wire bytes.  ``errors`` (or Py_None) is indexed
// RELATIVE to lo (errors[0] belongs to row lo).  Returns nullptr with
// a Python error set on failure.
static PyObject* build_resp_rows(const int32_t* status,
                                 const int64_t* limit,
                                 const int64_t* remaining,
                                 const int64_t* reset_time,
                                 Py_ssize_t lo, Py_ssize_t hi,
                                 PyObject* errors) {
  std::vector<uint8_t> out;
  out.reserve((size_t)(hi - lo) * 24);
  std::vector<uint8_t> sub;
  bool have_errors = errors != Py_None;
  for (Py_ssize_t i = lo; i < hi; i++) {
    sub.clear();
    put_field_varint(sub, 1, (uint64_t)(uint32_t)status[i]);
    put_field_varint(sub, 2, (uint64_t)limit[i]);
    put_field_varint(sub, 3, (uint64_t)remaining[i]);
    put_field_varint(sub, 4, (uint64_t)reset_time[i]);
    if (have_errors) {
      PyObject* e = PySequence_GetItem(errors, i - lo);
      if (e == nullptr) return nullptr;
      if (e != Py_None) {
        const unsigned char* ep;
        Py_ssize_t elen;
        if (!utf8_view(e, &ep, &elen)) {
          Py_DECREF(e);
          return nullptr;
        }
        if (elen > 0) {
          put_varint(sub, (5 << 3) | 2);
          put_varint(sub, (uint64_t)elen);
          sub.insert(sub.end(), ep, ep + elen);
        }
      }
      Py_DECREF(e);
    }
    out.push_back(0x0A);  // GetRateLimitsResp.responses
    put_varint(out, (uint64_t)sub.size());
    out.insert(out.end(), sub.begin(), sub.end());
  }
  return PyBytes_FromStringAndSize((const char*)out.data(),
                                   (Py_ssize_t)out.size());
}

// build_rate_limit_resps(status i32le, limit i64le, remaining i64le,
//                        reset_time i64le, errors|None) -> bytes
// errors: sequence of str/None per response (None/"" = no error field).
static PyObject* build_rate_limit_resps(PyObject*, PyObject* args) {
  Py_buffer st, li, re, rt;
  PyObject* errors;
  if (!PyArg_ParseTuple(args, "y*y*y*y*O", &st, &li, &re, &rt, &errors))
    return nullptr;
  Py_ssize_t n = st.len / 4;
  PyObject* out = nullptr;
  if (li.len != n * 8 || re.len != n * 8 || rt.len != n * 8) {
    PyErr_SetString(PyExc_ValueError, "column length mismatch");
  } else {
    out = build_resp_rows((const int32_t*)st.buf, (const int64_t*)li.buf,
                          (const int64_t*)re.buf, (const int64_t*)rt.buf,
                          0, n, errors);
  }
  PyBuffer_Release(&st);
  PyBuffer_Release(&li);
  PyBuffer_Release(&re);
  PyBuffer_Release(&rt);
  return out;
}

// build_responses_from_columns(status i32le, limit i64le,
//                              remaining i64le, reset_time i64le,
//                              row_lo, row_hi, errors|None) -> bytes
// The overlapped-pipeline caller-thread lane: the columns are a wave's
// SHARED result buffers (every job of the wave passes the same ones),
// and [row_lo, row_hi) selects this caller's rows — wire bytes are
// written straight from the packed result slice with zero per-request
// Python objects and zero intermediate slices.  ``errors`` is indexed
// relative to row_lo.
static PyObject* build_responses_from_columns(PyObject*, PyObject* args) {
  Py_buffer st, li, re, rt;
  Py_ssize_t lo, hi;
  PyObject* errors;
  if (!PyArg_ParseTuple(args, "y*y*y*y*nnO", &st, &li, &re, &rt, &lo, &hi,
                        &errors))
    return nullptr;
  Py_ssize_t n = st.len / 4;
  PyObject* out = nullptr;
  if (li.len != n * 8 || re.len != n * 8 || rt.len != n * 8) {
    PyErr_SetString(PyExc_ValueError, "column length mismatch");
  } else if (lo < 0 || hi < lo || hi > n) {
    PyErr_SetString(PyExc_ValueError, "row bounds out of range");
  } else {
    out = build_resp_rows((const int32_t*)st.buf, (const int64_t*)li.buf,
                          (const int64_t*)re.buf, (const int64_t*)rt.buf,
                          lo, hi, errors);
  }
  PyBuffer_Release(&st);
  PyBuffer_Release(&li);
  PyBuffer_Release(&re);
  PyBuffer_Release(&rt);
  return out;
}

// ---------------------------------------------------------------------------
// Cold-tier key store (tiering.py): open-addressed khash u64 -> packed
// 8x int64 bucket-state row (store.py column order minus the key).
// Linear probing over a power-of-two table with tombstone deletes and
// 0.7-load growth — the native backing for the host cold tier, so a
// 100M-key residency costs ~72 B/key flat instead of a Python dict of
// tuples.  NOT internally locked: the contract (documented on the
// tiering.py wrappers, soaked by tools/native_soak.py) is that the
// caller serializes mutations (TierController._mu).
static const Py_ssize_t COLD_ROW = 8;  // int64 values per row

struct ColdStore {
  std::vector<uint64_t> keys;
  std::vector<int64_t> rows;   // cap * COLD_ROW
  std::vector<uint8_t> state;  // 0 empty, 1 full, 2 tombstone
  size_t cap = 0;              // power of two
  size_t used = 0;             // full slots
  size_t filled = 0;           // full + tombstone (load-factor basis)
};

static const char* COLD_CAPSULE = "guber.cold_store";

static void cold_destroy(PyObject* capsule) {
  delete (ColdStore*)PyCapsule_GetPointer(capsule, COLD_CAPSULE);
}

static ColdStore* cold_from(PyObject* obj) {
  return (ColdStore*)PyCapsule_GetPointer(obj, COLD_CAPSULE);
}

static void cold_init(ColdStore* cs, size_t cap) {
  cs->cap = cap;
  cs->used = cs->filled = 0;
  cs->keys.assign(cap, 0);
  cs->rows.assign(cap * COLD_ROW, 0);
  cs->state.assign(cap, 0);
}

// Slot of `key`, or the first insertable slot (tombstone/empty) when
// absent.  cap is power-of-two so the linear probe visits every slot.
static size_t cold_find(const ColdStore* cs, uint64_t key, bool* present) {
  size_t mask = cs->cap - 1;
  size_t i = (size_t)key & mask;
  size_t first_free = (size_t)-1;
  for (size_t n = 0; n < cs->cap; n++, i = (i + 1) & mask) {
    uint8_t st = cs->state[i];
    if (st == 1 && cs->keys[i] == key) {
      *present = true;
      return i;
    }
    if (st == 2) {
      if (first_free == (size_t)-1) first_free = i;
      continue;
    }
    if (st == 0) {
      *present = false;
      return first_free != (size_t)-1 ? first_free : i;
    }
  }
  *present = false;
  return first_free;  // table of pure full+tombstone: growth precedes this
}

static void cold_grow(ColdStore* cs, size_t new_cap) {
  ColdStore next;
  cold_init(&next, new_cap);
  for (size_t i = 0; i < cs->cap; i++) {
    if (cs->state[i] != 1) continue;
    bool present;
    size_t j = cold_find(&next, cs->keys[i], &present);
    next.keys[j] = cs->keys[i];
    std::memcpy(&next.rows[j * COLD_ROW], &cs->rows[i * COLD_ROW],
                COLD_ROW * sizeof(int64_t));
    next.state[j] = 1;
  }
  next.used = next.filled = cs->used;
  *cs = std::move(next);
}

// cold_new(cap_hint) -> capsule
static PyObject* cold_new(PyObject*, PyObject* args) {
  Py_ssize_t hint = 0;
  if (!PyArg_ParseTuple(args, "|n", &hint)) return nullptr;
  size_t cap = 64;
  while ((Py_ssize_t)cap < hint) cap <<= 1;
  ColdStore* cs = new ColdStore();
  cold_init(cs, cap);
  PyObject* capsule = PyCapsule_New(cs, COLD_CAPSULE, cold_destroy);
  if (capsule == nullptr) delete cs;
  return capsule;
}

// cold_put(capsule, key u64, row 64 bytes) -> 1 inserted / 0 overwrote
static PyObject* cold_put(PyObject*, PyObject* args) {
  PyObject* obj;
  unsigned long long key;
  Py_buffer row;
  if (!PyArg_ParseTuple(args, "OKy*", &obj, &key, &row)) return nullptr;
  ColdStore* cs = cold_from(obj);
  if (cs == nullptr || row.len != COLD_ROW * (Py_ssize_t)sizeof(int64_t)) {
    if (cs != nullptr)
      PyErr_SetString(PyExc_ValueError, "cold row must be 64 bytes");
    PyBuffer_Release(&row);
    return nullptr;
  }
  if ((cs->filled + 1) * 10 >= cs->cap * 7)
    // mostly-live table doubles; mostly-tombstones rehashes in place
    cold_grow(cs, (cs->used + 1) * 10 >= cs->cap * 5 ? cs->cap * 2
                                                     : cs->cap);
  bool present;
  size_t i = cold_find(cs, (uint64_t)key, &present);
  if (!present) {
    if (cs->state[i] == 0) cs->filled++;
    cs->keys[i] = (uint64_t)key;
    cs->state[i] = 1;
    cs->used++;
  }
  std::memcpy(&cs->rows[i * COLD_ROW], row.buf,
              COLD_ROW * sizeof(int64_t));
  PyBuffer_Release(&row);
  return PyLong_FromLong(present ? 0 : 1);
}

// cold_put_batch(capsule, keys u64le[n], rows i64le[n * 8]) -> inserted
//
// n puts in one pass with the GIL released (the caller holds
// TierController._mu, as for every mutation): a restore's overflow is
// millions of rows, and one cold_put each is a Python call, a tuple and
// a 64-byte bytes object a row.  The table grows ONCE, to what holds
// them all under the load bound; a key that comes twice keeps its last
// row, as n single puts would.  A table that cannot be allocated is a
// MemoryError with the store untouched, not an exception through C.
static PyObject* cold_put_batch(PyObject*, PyObject* args) {
  PyObject* obj;
  Py_buffer keys, rows;
  if (!PyArg_ParseTuple(args, "Oy*y*", &obj, &keys, &rows)) return nullptr;
  ColdStore* cs = cold_from(obj);
  Py_ssize_t n = keys.len / 8;
  if (cs == nullptr || keys.len % 8 ||
      rows.len != n * COLD_ROW * (Py_ssize_t)sizeof(int64_t)) {
    if (cs != nullptr)
      PyErr_SetString(PyExc_ValueError,
                      "want n u64 keys and n rows of 64 bytes");
    PyBuffer_Release(&keys);
    PyBuffer_Release(&rows);
    return nullptr;
  }
  const uint64_t* kp = (const uint64_t*)keys.buf;
  const int64_t* rp = (const int64_t*)rows.buf;
  Py_ssize_t inserted = 0;
  bool no_memory = false;
  Py_BEGIN_ALLOW_THREADS
  size_t cap = cs->cap;
  while ((cs->used + (size_t)n + 1) * 10 >= cap * 7) cap <<= 1;
  if (cap != cs->cap || (cs->filled + (size_t)n + 1) * 10 >= cs->cap * 7) {
    try {
      cold_grow(cs, cap);  // same size: sheds the tombstones
    } catch (const std::bad_alloc&) {
      no_memory = true;  // the store is as it was: nothing was put
    }
  }
  for (Py_ssize_t k = 0; k < n && !no_memory; k++) {
    bool present;
    size_t i = cold_find(cs, kp[k], &present);
    if (!present) {
      if (cs->state[i] == 0) cs->filled++;
      cs->keys[i] = kp[k];
      cs->state[i] = 1;
      cs->used++;
      inserted++;
    }
    std::memcpy(&cs->rows[i * COLD_ROW], &rp[k * COLD_ROW],
                COLD_ROW * sizeof(int64_t));
  }
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&keys);
  PyBuffer_Release(&rows);
  if (no_memory) return PyErr_NoMemory();
  return PyLong_FromSsize_t(inserted);
}

// ---- cold_apply_batch: a wave's cold lane in one pass --------------------
//
// tiering.py › _host_apply, statement for statement, over a 128-bit
// intermediate.  Python's integers do not overflow and its // and %
// floor; every product below is of two values that fit 64 bits (so it
// fits 128), every sum goes through the checked builtins, every
// division floors, and a result that does not fit its 64-bit column is
// OverflowError — as `np.asarray(row, "<i8")` and `column[i] = value`
// are in the Python lane.  A wrapped value is never stored or answered.
typedef __int128 i128;

static inline i128 mul64(int64_t a, int64_t b) { return (i128)a * b; }

static inline i128 wide_div(i128 a, i128 b) {
  i128 q = a / b, r = a % b;
  return (r != 0 && ((r < 0) != (b < 0))) ? q - 1 : q;
}

static inline i128 wide_mod(i128 a, i128 b) {
  i128 r = a % b;
  return (r != 0 && ((r < 0) != (b < 0))) ? r + b : r;
}

static inline bool fits64(i128 v) {
  return v >= (i128)INT64_MIN && v <= (i128)INT64_MAX;
}

static const int64_t COLD_LEAKY = 1;         // Algorithm.LEAKY_BUCKET
static const int64_t COLD_RESET = 8;         // Behavior.RESET_REMAINING
static const int64_t COLD_DRAIN = 32;        // Behavior.DRAIN_OVER_LIMIT

struct ColdReq {
  int64_t hits, limit, duration, eff, greg_end, behavior, alg, burst,
      req_now;
};

struct ColdAns {
  int64_t row[COLD_ROW];  // the key's new row, ROW_COLS order
  int64_t status, out_rem;
  i128 reset_time;  // checked by the caller: it is answered, not stored
};

// One request applied to one cold row (`row`: the stored one, or
// _ZERO_ROW for a missing key).  False where a value of the new row does
// not fit 64 bits or a 128-bit sum overflowed: nothing of `ans` is good.
static bool cold_apply_row(const int64_t* row, const ColdReq& q,
                           int64_t td_bound, int64_t frac_safe,
                           ColdAns* ans) {
  bool wide = false;  // a 128-bit sum overflowed (unreachable: see above)
  auto add = [&wide](i128 a, i128 b) {
    i128 r;
    if (__builtin_add_overflow(a, b, &r)) wide = true;
    return r;
  };
  auto sub = [&wide](i128 a, i128 b) {
    i128 r;
    if (__builtin_sub_overflow(a, b, &r)) wide = true;
    return r;
  };
  const int64_t meta = row[0], i_limit = row[1], i_duration = row[2],
                i_eff = row[3], i_rem = row[5], i_t = row[6],
                i_exp = row[7];
  const int64_t i_alg = meta & 1, i_status = (meta >> 1) & 1;
  const int64_t hits = q.hits, limit = q.limit, eff = q.eff,
                alg = q.alg, burst = q.burst;

  const int64_t now = q.req_now > i_t ? q.req_now : i_t;
  const bool is_leaky = alg == COLD_LEAKY;
  const bool is_greg = (q.behavior & (int64_t)GREGORIAN) != 0;

  // --- fresh determination (missing/expired/algorithm switch)
  bool fresh = now >= i_exp || i_alg != alg;
  const bool tok_dur_change = !is_leaky && !fresh && q.duration != i_duration;
  i128 exp1 = i_exp;
  if (tok_dur_change) {
    exp1 = is_greg ? (i128)q.greg_end : add(i_t, eff);
    if (exp1 <= now) fresh = true;
  }

  // --- adopt fresh or existing state
  const int64_t eff_l = is_leaky ? eff : 1;
  int64_t limit0, eff0, t0, status0;
  i128 rem0, exp0;
  if (fresh) {
    limit0 = limit;
    eff0 = eff;
    rem0 = mul64(is_leaky ? burst : limit, eff_l);
    t0 = now;
    exp0 = (!is_leaky && is_greg) ? (i128)q.greg_end : add(now, eff);
    status0 = 0;
  } else {
    limit0 = i_limit;
    eff0 = i_eff;
    rem0 = i_rem;
    t0 = i_t;
    exp0 = exp1;
    status0 = i_status;
  }

  // --- leaky denominator change -> rescale td fixed point
  if (is_leaky && !fresh && eff != eff0) {
    const int64_t d = eff0 > 1 ? eff0 : 1;
    // rem0 is the stored column here, so whole and frac fit 64 bits
    i128 whole = wide_div(rem0, d);
    const i128 frac = wide_mod(rem0, d);
    const int64_t cap_whole = td_bound / (eff > 1 ? eff : 1);
    if (whole > cap_whole) whole = cap_whole;
    const bool frac_ok = eff0 <= frac_safe && eff <= frac_safe;
    rem0 = add(mul64((int64_t)whole, eff),
               wide_div(mul64(frac_ok ? (int64_t)frac : 0, eff), d));
  }
  if (is_leaky || tok_dur_change) eff0 = eff;

  // --- RESET_REMAINING (existing items only)
  const bool reset_live = (q.behavior & COLD_RESET) != 0 && !fresh;
  if (reset_live) {
    rem0 = mul64(limit, eff_l);
    status0 = 0;
  }
  const int64_t limit_after_reset =
      (reset_live && !is_leaky) ? limit : limit0;

  // --- token limit change in place
  if (!is_leaky && limit != limit_after_reset) {
    rem0 = sub(add(rem0, limit), limit_after_reset);
    if (rem0 < 0)
      rem0 = 0;
    else if (rem0 > limit)
      rem0 = limit;
  }
  const int64_t limit1 = limit;

  // --- leaky replenish (exact: elapsed x limit td, clamped to burst)
  const int64_t burst1 = is_leaky ? burst : limit1;
  int64_t t1;
  if (is_leaky) {
    const i128 elapsed = sub(now, t0);  // >= 0: now is max(req_now, i_t)
    const i128 cap_td = mul64(burst1, eff0);
    const int64_t safe_el = td_bound / (limit1 > 1 ? limit1 : 1);
    if (elapsed > safe_el) {
      rem0 = cap_td;
    } else {  // 0 <= elapsed <= td_bound: fits 64 bits
      rem0 = add(rem0, mul64((int64_t)elapsed, limit1));
      if (rem0 > cap_td) rem0 = cap_td;
    }
    t1 = now;
  } else {
    t1 = t0;
  }

  const int64_t d0 = eff0 > 1 ? eff0 : 1;
  const i128 rate =
      limit1 > 0 ? wide_div(eff0, limit1 > 1 ? limit1 : 1) : (i128)eff0;
  const i128 exp_out = is_leaky ? add(now, eff0) : exp0;
  // leaky: from the request's OWN stamp, not the clamped clock
  const i128 reset_time = is_leaky ? add(q.req_now, rate) : exp_out;

  // --- hits
  const i128 cost = mul64(hits, is_leaky ? eff0 : 1);
  i128 rem2;
  int64_t status1;
  if (hits == 0) {  // query
    rem2 = rem0;
    status1 = status0;
  } else if (cost <= rem0) {
    rem2 = sub(rem0, cost);
    status1 = 0;
  } else {
    rem2 = (q.behavior & COLD_DRAIN) != 0 ? (i128)0 : rem0;
    status1 = 1;
  }

  if (wide || !fits64(rem2) || !fits64(exp_out)) return false;
  ans->row[0] = alg | (status1 << 1);
  ans->row[1] = limit1;
  ans->row[2] = q.duration;
  ans->row[3] = eff0;
  ans->row[4] = burst1;
  ans->row[5] = (int64_t)rem2;
  ans->row[6] = t1;
  ans->row[7] = (int64_t)exp_out;
  ans->status = status1;
  ans->out_rem = (int64_t)(is_leaky ? wide_div(rem2, d0) : rem2);
  ans->reset_time = reset_time;
  return true;
}

// cold_apply_batch(capsule, khash u64le[n], idx i64le[m],
//                  hits, limit, duration, eff_ms, greg_end, behavior,
//                  algorithm, burst, now  (i64le[n] each),
//                  now_ms, td_bound, frac_safe,
//                  status i32[n], limit i64[n], remaining i64[n],
//                  reset i64[n], full bool[n]  (writable))
//   -> (served, created, distinct served keys u64le bytes)
//
// TierController.resolve's loop as one pass: rows `idx` of the wave in
// (effective stamp, index) order — `now[i]` if > 0 else `now_ms` — each
// key's slot found ONCE (find-or-insert; a missing key starts from
// _ZERO_ROW = zeros with eff_ms 1 and counts as created), the transition
// applied, the new row written into the slot and the four answers into
// the response columns, `full[i]` cleared.  The distinct keys come back
// in order of first service (what admission walks).  The table grows at
// most once, up front, for as many inserts as the call may make.  Keeps
// the GIL (a ~1-ms pass); the caller holds TierController._mu.
//
// OverflowError where the Python lane raises it, with the same state
// behind it: the rows before the one at fault are stored and answered,
// that one is not (a `reset` that does not fit is raised AFTER the row,
// `status` and `remaining` were written — `rst_o[i] = rst` is the third
// assignment there).
static PyObject* cold_apply_batch(PyObject*, PyObject* args) {
  PyObject* obj;
  Py_buffer b[16] = {};
  long long now_ms, td_bound, frac_safe;
  if (!PyArg_ParseTuple(
          args, "Oy*y*y*y*y*y*y*y*y*y*y*LLLw*w*w*w*w*", &obj, &b[0], &b[1],
          &b[2], &b[3], &b[4], &b[5], &b[6], &b[7], &b[8], &b[9], &b[10],
          &now_ms, &td_bound, &frac_safe, &b[11], &b[12], &b[13], &b[14],
          &b[15]))
    return nullptr;
  PyObject* out = nullptr;
  ColdStore* cs = cold_from(obj);
  const Py_ssize_t n = b[0].len / 8, m = b[1].len / 8;
  const uint64_t* kh = (const uint64_t*)b[0].buf;
  const int64_t* idx = (const int64_t*)b[1].buf;
  const int64_t* col[9];
  bool shaped = b[0].len % 8 == 0 && b[1].len % 8 == 0 &&
                b[11].len == n * 4 && b[12].len == n * 8 &&
                b[13].len == n * 8 && b[14].len == n * 8 && b[15].len == n &&
                td_bound > 0;
  for (int c = 0; c < 9; c++) {
    col[c] = (const int64_t*)b[2 + c].buf;
    shaped = shaped && b[2 + c].len == n * 8;
  }
  for (Py_ssize_t k = 0; shaped && k < m; k++)
    shaped = idx[k] >= 0 && idx[k] < n;
  if (cs == nullptr) {
    // PyCapsule_GetPointer set the error
  } else if (!shaped) {
    PyErr_SetString(PyExc_ValueError,
                    "want nine i64 columns and five response columns of "
                    "the khash's length, and row indices inside it");
  } else {
    int32_t* o_st = (int32_t*)b[11].buf;
    int64_t* o_lim = (int64_t*)b[12].buf;
    int64_t* o_rem = (int64_t*)b[13].buf;
    int64_t* o_rst = (int64_t*)b[14].buf;
    uint8_t* o_full = (uint8_t*)b[15].buf;
    const int64_t* h_now = col[8];
    try {
      // (effective stamp, index): the device's segment order
      std::vector<std::pair<int64_t, int64_t>> order((size_t)m);
      for (Py_ssize_t k = 0; k < m; k++) {
        int64_t t = h_now[idx[k]];
        order[(size_t)k] = {t > 0 ? t : (int64_t)now_ms, idx[k]};
      }
      if (!std::is_sorted(order.begin(), order.end()))
        std::sort(order.begin(), order.end());
      // the distinct served keys, in order of first service: `seen` is
      // an open-addressed set of row indices, sized so the pass never
      // allocates once the store is touched
      std::vector<uint64_t> distinct;
      distinct.reserve((size_t)m);
      size_t smask = 1;
      while (smask < (size_t)m * 2 + 1) smask <<= 1;
      std::vector<int64_t> seen(smask--, -1);
      auto first_service = [&](uint64_t key, int64_t i) {
        for (size_t j = (size_t)key & smask;; j = (j + 1) & smask) {
          if (seen[j] < 0) {
            seen[j] = i;
            return true;
          }
          if (kh[seen[j]] == key) return false;
        }
      };
      // cold_put's growth rule, for all m possible inserts at once: a
      // mostly-live table doubles, a mostly-tombstone one rehashes in
      // place; either way it ends at most half full
      if ((cs->filled + (size_t)m + 1) * 10 >= cs->cap * 7) {
        size_t cap = cs->cap;
        while ((cs->used + (size_t)m + 1) * 10 >= cap * 5) cap <<= 1;
        cold_grow(cs, cap);
      }
      static const int64_t zero_row[COLD_ROW] = {0, 0, 0, 1, 0, 0, 0, 0};
      Py_ssize_t created = 0;
      bool overflow = false;
      ColdAns ans;
      for (size_t k = 0; k < (size_t)m; k++) {
        const int64_t i = order[k].second;
        const uint64_t key = kh[i];
        bool present;
        const size_t slot = cold_find(cs, key, &present);
        const ColdReq q = {col[0][i], col[1][i], col[2][i], col[3][i],
                           col[4][i], col[5][i], col[6][i], col[7][i],
                           order[k].first};
        if (!cold_apply_row(present ? &cs->rows[slot * COLD_ROW] : zero_row,
                            q, td_bound, frac_safe, &ans)) {
          overflow = true;
          break;
        }
        if (!present) {
          if (cs->state[slot] == 0) cs->filled++;
          cs->keys[slot] = key;
          cs->state[slot] = 1;
          cs->used++;
          created++;
        }
        std::memcpy(&cs->rows[slot * COLD_ROW], ans.row, sizeof ans.row);
        o_st[i] = (int32_t)ans.status;
        o_rem[i] = ans.out_rem;
        if (!fits64(ans.reset_time)) {
          overflow = true;
          break;
        }
        o_rst[i] = (int64_t)ans.reset_time;
        o_lim[i] = ans.row[1];  // the request's limit
        o_full[i] = 0;
        if (first_service(key, i)) distinct.push_back(key);
      }
      if (overflow)
        PyErr_SetString(PyExc_OverflowError,
                        "Python int too large to convert to C long");
      else
        out = Py_BuildValue("(nny#)", m, created,
                            (const char*)distinct.data(),
                            (Py_ssize_t)(distinct.size() * 8));
    } catch (const std::bad_alloc&) {
      PyErr_NoMemory();  // before the first row: the store is as it was
    }
  }
  for (Py_buffer& v : b) PyBuffer_Release(&v);
  return out;
}

// cold_get(capsule, key u64) -> bytes(64) | None
static PyObject* cold_get(PyObject*, PyObject* args) {
  PyObject* obj;
  unsigned long long key;
  if (!PyArg_ParseTuple(args, "OK", &obj, &key)) return nullptr;
  ColdStore* cs = cold_from(obj);
  if (cs == nullptr) return nullptr;
  bool present;
  size_t i = cold_find(cs, (uint64_t)key, &present);
  if (!present) Py_RETURN_NONE;
  return PyBytes_FromStringAndSize((const char*)&cs->rows[i * COLD_ROW],
                                   COLD_ROW * sizeof(int64_t));
}

// cold_pop(capsule, key u64) -> bytes(64) | None
static PyObject* cold_pop(PyObject*, PyObject* args) {
  PyObject* obj;
  unsigned long long key;
  if (!PyArg_ParseTuple(args, "OK", &obj, &key)) return nullptr;
  ColdStore* cs = cold_from(obj);
  if (cs == nullptr) return nullptr;
  bool present;
  size_t i = cold_find(cs, (uint64_t)key, &present);
  if (!present) Py_RETURN_NONE;
  PyObject* out = PyBytes_FromStringAndSize(
      (const char*)&cs->rows[i * COLD_ROW], COLD_ROW * sizeof(int64_t));
  if (out != nullptr) {
    cs->state[i] = 2;  // tombstone keeps later probe chains intact
    cs->used--;
  }
  return out;
}

// cold_take_batch(capsule, keys u64le[n], rows i64le[n * 8] writable,
//                 found u8[n] writable, remove) -> keys found
// The cold side of a migration pass (tiering.py › TierController.migrate)
// in one call each way: the rows of a wave's admitted keys read together
// (remove = 0: found[i] says whether keys[i] is held, rows[i] is its row,
// left as it was where it is not), and the keys the pass placed on the
// device taken out together (remove = 1: cold_pop's tombstone for each
// key found; a key that comes twice is found once).
static PyObject* cold_take_batch(PyObject*, PyObject* args) {
  PyObject* obj;
  Py_buffer keys, rows, found;
  int remove;
  if (!PyArg_ParseTuple(args, "Oy*w*w*p", &obj, &keys, &rows, &found,
                        &remove))
    return nullptr;
  ColdStore* cs = cold_from(obj);
  const Py_ssize_t n = keys.len / 8;
  const Py_ssize_t row_bytes = COLD_ROW * (Py_ssize_t)sizeof(int64_t);
  if (cs == nullptr || rows.len < n * row_bytes || found.len < n) {
    if (cs != nullptr)
      PyErr_SetString(PyExc_ValueError, "rows or found too short");
    PyBuffer_Release(&keys);
    PyBuffer_Release(&rows);
    PyBuffer_Release(&found);
    return nullptr;
  }
  const uint64_t* kp = (const uint64_t*)keys.buf;
  int64_t* rp = (int64_t*)rows.buf;
  uint8_t* fp = (uint8_t*)found.buf;
  Py_ssize_t got = 0;
  for (Py_ssize_t k = 0; k < n; k++) {
    bool present;
    size_t i = cold_find(cs, kp[k], &present);
    fp[k] = present ? 1 : 0;
    if (!present) continue;
    got++;
    std::memcpy(&rp[k * COLD_ROW], &cs->rows[i * COLD_ROW], row_bytes);
    if (remove) {
      cs->state[i] = 2;  // tombstone keeps later probe chains intact
      cs->used--;
    }
  }
  PyBuffer_Release(&keys);
  PyBuffer_Release(&rows);
  PyBuffer_Release(&found);
  return PyLong_FromSsize_t(got);
}

// cold_len(capsule) -> resident key count
static PyObject* cold_len(PyObject*, PyObject* args) {
  PyObject* obj;
  if (!PyArg_ParseTuple(args, "O", &obj)) return nullptr;
  ColdStore* cs = cold_from(obj);
  if (cs == nullptr) return nullptr;
  return PyLong_FromSize_t(cs->used);
}

// cold_contains(capsule, keys u64le bytes, out u8 writable) -> None
// The engine pre-mask read: one call per wave, no per-key Python.
static PyObject* cold_contains(PyObject*, PyObject* args) {
  PyObject* obj;
  Py_buffer keys, out;
  if (!PyArg_ParseTuple(args, "Oy*w*", &obj, &keys, &out)) return nullptr;
  ColdStore* cs = cold_from(obj);
  Py_ssize_t n = keys.len / 8;
  if (cs == nullptr || out.len < n) {
    if (cs != nullptr)
      PyErr_SetString(PyExc_ValueError, "output mask too short");
    PyBuffer_Release(&keys);
    PyBuffer_Release(&out);
    return nullptr;
  }
  const uint64_t* kp = (const uint64_t*)keys.buf;
  uint8_t* op = (uint8_t*)out.buf;
  for (Py_ssize_t i = 0; i < n; i++) {
    bool present;
    cold_find(cs, kp[i], &present);
    op[i] = present ? 1 : 0;
  }
  PyBuffer_Release(&keys);
  PyBuffer_Release(&out);
  Py_RETURN_NONE;
}

// cold_snapshot(capsule) -> (n, keys u64le bytes, rows i64le bytes)
static PyObject* cold_snapshot(PyObject*, PyObject* args) {
  PyObject* obj;
  if (!PyArg_ParseTuple(args, "O", &obj)) return nullptr;
  ColdStore* cs = cold_from(obj);
  if (cs == nullptr) return nullptr;
  Py_ssize_t n = (Py_ssize_t)cs->used;
  PyObject* kb = PyBytes_FromStringAndSize(nullptr, n * 8);
  PyObject* rb =
      PyBytes_FromStringAndSize(nullptr, n * COLD_ROW * sizeof(int64_t));
  if (kb == nullptr || rb == nullptr) {
    Py_XDECREF(kb);
    Py_XDECREF(rb);
    return nullptr;
  }
  uint64_t* kp = (uint64_t*)PyBytes_AS_STRING(kb);
  int64_t* rp = (int64_t*)PyBytes_AS_STRING(rb);
  Py_ssize_t w = 0;
  for (size_t i = 0; i < cs->cap; i++) {
    if (cs->state[i] != 1) continue;
    kp[w] = cs->keys[i];
    std::memcpy(&rp[w * COLD_ROW], &cs->rows[i * COLD_ROW],
                COLD_ROW * sizeof(int64_t));
    w++;
  }
  return Py_BuildValue("(nNN)", w, kb, rb);
}

// cold_clear(capsule) -> None
static PyObject* cold_clear(PyObject*, PyObject* args) {
  PyObject* obj;
  if (!PyArg_ParseTuple(args, "O", &obj)) return nullptr;
  ColdStore* cs = cold_from(obj);
  if (cs == nullptr) return nullptr;
  cold_init(cs, 64);
  Py_RETURN_NONE;
}

// thread_files(dir, name) -> [(tid, bytes), ...] | None
//
// <dir>/<tid>/<name> of every thread under <dir> (/proc/self/task) that
// has the file, for the thread ledger (tracing.py › ThreadLedger), with
// the GIL released ONCE for the whole walk.  A Python loop gives the GIL
// up at every open, read and close, and on a daemon whose 32 handler
// threads want it waits a switch interval to get it back each time:
// 3–5 ms a THREAD on the chip's hosts, 0.7–2.5 s a scrape of 230–470
// threads, every one a forced hand-off for whoever held the GIL (PERF.md
// §6, PR 37).  None where <dir> cannot be listed.
static PyObject* thread_files(PyObject*, PyObject* args) {
  const char *dir, *name;
  if (!PyArg_ParseTuple(args, "ss", &dir, &name)) return nullptr;
  std::vector<std::pair<long, std::string>> got;
  bool listed = false;
  Py_BEGIN_ALLOW_THREADS
  DIR* d = opendir(dir);
  if (d != nullptr) {
    listed = true;
    char path[512], buf[1024];
    while (struct dirent* e = readdir(d)) {
      char* end;
      long tid = strtol(e->d_name, &end, 10);
      if (end == e->d_name || *end != '\0') continue;
      int len = snprintf(path, sizeof path, "%s/%s/%s", dir, e->d_name, name);
      if (len < 0 || (size_t)len >= sizeof path) continue;
      int fd = open(path, O_RDONLY | O_CLOEXEC);
      if (fd < 0) continue;  // exited under the walk, or no such file
      ssize_t n = read(fd, buf, sizeof buf);
      close(fd);
      if (n > 0) got.emplace_back(tid, std::string(buf, (size_t)n));
    }
    closedir(d);
  }
  Py_END_ALLOW_THREADS
  if (!listed) Py_RETURN_NONE;
  PyObject* out = PyList_New((Py_ssize_t)got.size());
  if (out == nullptr) return nullptr;
  for (size_t i = 0; i < got.size(); i++) {
    PyObject* item = Py_BuildValue("(ly#)", got[i].first, got[i].second.data(),
                                   (Py_ssize_t)got[i].second.size());
    if (item == nullptr) {
      Py_DECREF(out);
      return nullptr;
    }
    PyList_SET_ITEM(out, (Py_ssize_t)i, item);
  }
  return out;
}

static PyMethodDef methods[] = {
    {"fnv1a64_batch", fnv1a64_batch, METH_O,
     "Batch raw FNV-1a64 of str/bytes -> (le64 bytes, n)"},
    {"fnv1a64_pair_batch", fnv1a64_pair_batch, METH_VARARGS,
     "Batch FNV-1a64 of name+'_'+key pairs -> (le64 bytes, n)"},
    {"parse_get_rate_limits", parse_get_rate_limits, METH_O,
     "GetRateLimitsReq wire bytes -> packed column buffers (or None)"},
    {"count_req_items", count_req_items, METH_VARARGS,
     "Pre-pass: count repeated field-1 request TLVs; None on foreign "
     "framing or at the first request with an excluded behavior bit"},
    {"pack_wire_wave", pack_wire_wave, METH_VARARGS,
     "Fused ingest: wire bytes -> clamped rows written into leased "
     "packed wave matrices (or None)"},
    {"gregorian_end", gregorian_end, METH_VARARGS,
     "End of the calendar period (UTC) that holds an epoch-ms clock, by "
     "GregorianDuration ordinal (or None): the fused ingest's calendar"},
    {"derive_rows", derive_rows, METH_VARARGS,
     "What a launch needs to know of rows in the upload layout: "
     "out-of-domain rows, leaky rows, the clocks"},
    {"route_plan", route_plan, METH_VARARGS,
     "The device waves of a wave's rows, routed by shard: each wave's "
     "row indices, slots, bucket and densest shard"},
    {"route_fill", route_fill, METH_VARARGS,
     "One routed device wave into its upload pair, every cell written"},
    {"thread_files", thread_files, METH_VARARGS,
     "One file of every thread under /proc/self/task, the GIL released "
     "once for the walk -> [(tid, bytes)] (or None)"},
    {"stamp_req_tlvs", stamp_req_tlvs, METH_VARARGS,
     "Join request TLV slices, appending created_at (field 10) where "
     "unset — the forward hop's caller-clock stamp"},
    {"split_resp_items", split_resp_items, METH_O,
     "RateLimitResp-list wire bytes -> per-item TLV ranges + status"},
    {"build_rate_limit_resps", build_rate_limit_resps, METH_VARARGS,
     "Packed response columns -> GetRateLimitsResp wire bytes"},
    {"build_responses_from_columns", build_responses_from_columns,
     METH_VARARGS,
     "Rows [lo, hi) of shared result columns -> GetRateLimitsResp "
     "wire bytes"},
    {"cold_new", cold_new, METH_VARARGS,
     "Cold-tier store (tiering.py): new open-addressed khash->row "
     "table -> capsule"},
    {"cold_put", cold_put, METH_VARARGS,
     "cold_put(capsule, key, row64B) -> 1 inserted / 0 overwrote"},
    {"cold_put_batch", cold_put_batch, METH_VARARGS,
     "cold_put_batch(capsule, keys u64le, rows i64le) -> keys inserted"},
    {"cold_take_batch", cold_take_batch, METH_VARARGS,
     "cold_take_batch(capsule, keys u64le, rows i64le out, found u8 out, "
     "remove) -> keys found: a batch of rows read, or read and removed"},
    {"cold_apply_batch", cold_apply_batch, METH_VARARGS,
     "cold_apply_batch(capsule, khash, idx, 9 request columns, now_ms, "
     "td_bound, frac_safe, 5 response columns) -> (served, created, "
     "distinct keys): a wave's cold rows applied in one pass"},
    {"cold_get", cold_get, METH_VARARGS,
     "cold_get(capsule, key) -> 64-byte row | None"},
    {"cold_pop", cold_pop, METH_VARARGS,
     "cold_pop(capsule, key) -> 64-byte row | None (tombstone delete)"},
    {"cold_len", cold_len, METH_VARARGS,
     "cold_len(capsule) -> resident key count"},
    {"cold_contains", cold_contains, METH_VARARGS,
     "cold_contains(capsule, keys u64le, out u8) -> membership mask"},
    {"cold_snapshot", cold_snapshot, METH_VARARGS,
     "cold_snapshot(capsule) -> (n, keys bytes, rows bytes)"},
    {"cold_clear", cold_clear, METH_VARARGS,
     "cold_clear(capsule) -> reset to empty"},
    {nullptr, nullptr, 0, nullptr}};

static struct PyModuleDef moduledef = {PyModuleDef_HEAD_INIT, "_native",
                                       "native host ops", -1, methods};

PyMODINIT_FUNC PyInit__native(void) { return PyModule_Create(&moduledef); }
