// Native runtime for dat_replication_protocol_tpu: the host-side hot loops.
//
// The reference's hot receive path is a byte-at-a-time varint scan and
// per-frame dispatch in JS (reference: decode.js:144-169, 251-262).  The
// TPU-native framework needs the same parsing at change-log-replay scale
// (BASELINE.json config 2: 1M-row replay) where per-record Python costs
// ~1us each; this translation unit provides the two tight loops behind a
// plain C ABI (loaded via ctypes — no pybind11 in the image):
//
//   dat_split_frames    multibuffer framing: varint(len+1) | id | payload
//   dat_decode_changes  proto2 `Change` records -> columnar arrays
//                       (zero-copy: strings/bytes become (offset, len)
//                       views into the log buffer — the layout the device
//                       feed packs from directly)
//
// Build: g++ -O3 -shared -fPIC (runtime/native.py does this on demand and
// caches the .so; every entry point has a pure-Python fallback).

#include <cstdint>
#include <cstddef>
#include <cstring>
#include <new>

namespace {

// Decode one unsigned LEB128 varint at buf[i..len).  Returns the number of
// bytes consumed (0 = truncated, -1 = overlong/>10 bytes).
// The 10-byte cap is the wire limit shared with wire/varint.py; datlint's
// wire-constant-parity rule cross-checks it:  // wire: MAX_VARINT_LEN = 10
inline int read_uvarint(const uint8_t* buf, int64_t i, int64_t len,
                        uint64_t* out) {
  uint64_t v = 0;
  int shift = 0;
  for (int k = 0; k < 10; ++k) {
    if (i + k >= len) return 0;
    uint8_t b = buf[i + k];
    // 10th byte may only contribute bit 63: anything else encodes a
    // value >= 2^64 (overlong — matches the Python decoder's rejection).
    if (k == 9 && (b & 0x7F) > 1) return -1;
    v |= static_cast<uint64_t>(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *out = v;
      return k + 1;
    }
    shift += 7;
  }
  return -1;
}

}  // namespace

extern "C" {

// Error codes shared by both entry points.
enum {
  DAT_ERR_TRUNCATED = -1,
  DAT_ERR_CAPACITY = -2,
  DAT_ERR_BAD_VARINT = -3,
  DAT_ERR_BAD_RECORD = -4,
  DAT_ERR_NOMEM = -5,
};

// Split a multibuffer stream into frames.
//
// Returns the count of complete valid frames (<= cap) and fills, per
// frame:
//   starts[f]  byte offset of the payload (after the id byte)
//   lens[f]    payload length (framed length minus the id byte)
//   ids[f]     the 1-byte type id (unvalidated; policy lives above)
// `consumed` gets the offset one past the last complete frame (a partial
// trailing frame is not an error — streaming callers re-feed the tail).
// A malformed header (overlong varint / zero framed length) STOPS the
// scan at that frame: the valid prefix is still returned and `err` gets
// the error code (0 otherwise), so a streaming caller can deliver the
// prefix and surface the error at exactly the offending frame — the same
// observable order as the byte-at-a-time scanner.  Only a capacity
// overflow (caller bug) is a negative return.
int64_t dat_split_frames(const uint8_t* buf, int64_t len, int64_t* starts,
                         int64_t* lens, uint8_t* ids, int64_t cap,
                         int64_t* consumed, int64_t* err) {
  int64_t i = 0;
  int64_t n = 0;
  *consumed = 0;
  *err = 0;
  while (i < len) {
    uint64_t framed;
    int used = read_uvarint(buf, i, len, &framed);
    if (used == 0) break;  // partial header at tail
    if (used < 0) {
      *err = DAT_ERR_BAD_VARINT;
      break;
    }
    if (framed == 0) {  // must include the id byte
      *err = DAT_ERR_BAD_RECORD;
      break;
    }
    // Unsigned compare BEFORE any int64 cast: a hostile length >= 2^63
    // must not wrap negative and walk the cursor backwards.  Anything
    // larger than the bytes on hand is a partial tail (streaming callers
    // re-feed), matching the Python fallback's NeedMoreData behavior.
    uint64_t remaining = static_cast<uint64_t>(len - i) - used;
    if (framed > remaining) break;  // partial frame at tail
    int64_t payload = static_cast<int64_t>(framed) - 1;
    int64_t frame_end = i + used + 1 + payload;
    if (n >= cap) return DAT_ERR_CAPACITY;
    ids[n] = buf[i + used];
    starts[n] = i + used + 1;
    lens[n] = payload;
    ++n;
    i = frame_end;
    *consumed = i;
  }
  return n;
}

// Greedy min/max chunk-size pass over sorted candidate byte offsets (the
// sequential tail of content-defined chunking; ops/rabin.py documents the
// algorithm).  Writes chunk end-offsets (exclusive), always ending with
// `length`.  Returns the cut count, or DAT_ERR_CAPACITY.
int64_t dat_greedy_select(const int64_t* cands, int64_t n, int64_t length,
                          int64_t min_size, int64_t max_size, int64_t* out,
                          int64_t cap) {
  int64_t start = 0, i = 0, m = 0;
  while (length - start > max_size) {
    int64_t lo = start + min_size;
    int64_t hi = start + max_size;
    while (i < n && cands[i] < lo) ++i;
    int64_t cut;
    if (i < n && cands[i] <= hi) {
      cut = cands[i];
      ++i;
    } else {
      cut = hi;
    }
    if (m >= cap) return DAT_ERR_CAPACITY;
    out[m++] = cut;
    start = cut;
  }
  if (m >= cap) return DAT_ERR_CAPACITY;
  out[m++] = length;
  return m;
}

// Proto2 tags for the Change message (reference: messages/schema.proto:1-8).
enum {
  TAG_SUBSET = (1 << 3) | 2,
  TAG_KEY = (2 << 3) | 2,
  TAG_CHANGE = (3 << 3) | 0,
  TAG_FROM = (4 << 3) | 0,
  TAG_TO = (5 << 3) | 0,
  TAG_VALUE = (6 << 3) | 2,
};

// Decode Change payloads [lo, hi) into columnar arrays; returns the index
// of the first corrupt record in the range, or -1 if all decode.  The
// rows are independent, so ranges parallelize (dat_decode_changes_mt).
static int64_t decode_changes_range(
    const uint8_t* buf, const int64_t* starts, const int64_t* lens,
    int64_t lo, int64_t hi, uint32_t* change, uint32_t* from_v,
    uint32_t* to_v, int64_t* key_off, int64_t* key_len, int64_t* sub_off,
    int64_t* sub_len, int64_t* val_off, int64_t* val_len) {
  for (int64_t r = lo; r < hi; ++r) {
    int64_t i = starts[r];
    const int64_t end = i + lens[r];
    bool has_key = false, has_change = false, has_from = false, has_to = false;
    sub_len[r] = -1;
    val_len[r] = -1;
    sub_off[r] = 0;
    val_off[r] = 0;
    while (i < end) {
      uint64_t tag;
      int used = read_uvarint(buf, i, end, &tag);
      if (used <= 0) goto bad;
      i += used;
      switch (tag & 7) {
        case 0: {  // varint
          uint64_t v;
          used = read_uvarint(buf, i, end, &v);
          if (used <= 0) goto bad;
          i += used;
          if (tag == TAG_CHANGE) {
            change[r] = static_cast<uint32_t>(v);
            has_change = true;
          } else if (tag == TAG_FROM) {
            from_v[r] = static_cast<uint32_t>(v);
            has_from = true;
          } else if (tag == TAG_TO) {
            to_v[r] = static_cast<uint32_t>(v);
            has_to = true;
          }
          break;
        }
        case 2: {  // length-delimited
          uint64_t ln;
          used = read_uvarint(buf, i, end, &ln);
          if (used <= 0) goto bad;
          i += used;
          // Unsigned compare before the cast: ln >= 2^63 would go
          // negative as int64 and slip past the bounds check below.
          if (ln > static_cast<uint64_t>(end - i)) goto bad;
          if (tag == TAG_SUBSET) {
            sub_off[r] = i;
            sub_len[r] = static_cast<int64_t>(ln);
          } else if (tag == TAG_KEY) {
            key_off[r] = i;
            key_len[r] = static_cast<int64_t>(ln);
            has_key = true;
          } else if (tag == TAG_VALUE) {
            val_off[r] = i;
            val_len[r] = static_cast<int64_t>(ln);
          }
          i += static_cast<int64_t>(ln);
          break;
        }
        case 5:  // fixed32 (unknown field)
          if (i + 4 > end) goto bad;
          i += 4;
          break;
        case 1:  // fixed64 (unknown field)
          if (i + 8 > end) goto bad;
          i += 8;
          break;
        default:
          goto bad;
      }
    }
    if (!has_key || !has_change || !has_from || !has_to) goto bad;
    continue;
  bad:
    return r;
  }
  return -1;
}

// Decode n Change payloads into columnar arrays (serial entry point).
//
// Absent optional fields get len -1 (host maps to ''/b'').  Unknown fields
// are skipped per proto2.  Returns 0, or a negative error with err_index
// set to the offending record.
int64_t dat_decode_changes(const uint8_t* buf, const int64_t* starts,
                           const int64_t* lens, int64_t n, uint32_t* change,
                           uint32_t* from_v, uint32_t* to_v, int64_t* key_off,
                           int64_t* key_len, int64_t* sub_off,
                           int64_t* sub_len, int64_t* val_off,
                           int64_t* val_len, int64_t* err_index) {
  int64_t bad = decode_changes_range(buf, starts, lens, 0, n, change, from_v,
                                     to_v, key_off, key_len, sub_off, sub_len,
                                     val_off, val_len);
  if (bad >= 0) {
    *err_index = bad;
    return DAT_ERR_BAD_RECORD;
  }
  return 0;
}

}  // extern "C"

namespace {

inline int uvarint_size(uint64_t v) {
  int n = 1;
  while (v >= 0x80) {
    v >>= 7;
    ++n;
  }
  return n;
}

inline int64_t write_uvarint(uint8_t* dst, int64_t i, uint64_t v) {
  while (v >= 0x80) {
    dst[i++] = static_cast<uint8_t>(v) | 0x80;
    v >>= 7;
  }
  dst[i++] = static_cast<uint8_t>(v);
  return i;
}

// proto payload size of record r (fields in ascending field-number order,
// absent optionals omitted) — shared by the serial and parallel encoders.
inline int64_t change_payload_size(int64_t r, const uint32_t* change,
                                   const uint32_t* from_v,
                                   const uint32_t* to_v,
                                   const int64_t* key_len,
                                   const int64_t* sub_len,
                                   const int64_t* val_len) {
  int64_t psize = 0;
  if (sub_len[r] >= 0) psize += 1 + uvarint_size(sub_len[r]) + sub_len[r];
  psize += 1 + uvarint_size(key_len[r]) + key_len[r];
  psize += 1 + uvarint_size(change[r]);
  psize += 1 + uvarint_size(from_v[r]);
  psize += 1 + uvarint_size(to_v[r]);
  if (val_len[r] >= 0) psize += 1 + uvarint_size(val_len[r]) + val_len[r];
  return psize;
}

// Encode record r's full frame at dst[w]; returns the new write offset.
// TAG_* come from the file-scope enum shared with the decoder.
int64_t encode_change_at(const uint8_t* src, int64_t r, int64_t psize,
                         const uint32_t* change, const uint32_t* from_v,
                         const uint32_t* to_v, const int64_t* key_off,
                         const int64_t* key_len, const int64_t* sub_off,
                         const int64_t* sub_len, const int64_t* val_off,
                         const int64_t* val_len, uint8_t* dst, int64_t w) {
  w = write_uvarint(dst, w, psize + 1);
  dst[w++] = 1;  // TYPE_CHANGE
  if (sub_len[r] >= 0) {
    dst[w++] = TAG_SUBSET;
    w = write_uvarint(dst, w, sub_len[r]);
    for (int64_t k = 0; k < sub_len[r]; ++k) dst[w + k] = src[sub_off[r] + k];
    w += sub_len[r];
  }
  dst[w++] = TAG_KEY;
  w = write_uvarint(dst, w, key_len[r]);
  for (int64_t k = 0; k < key_len[r]; ++k) dst[w + k] = src[key_off[r] + k];
  w += key_len[r];
  dst[w++] = TAG_CHANGE;
  w = write_uvarint(dst, w, change[r]);
  dst[w++] = TAG_FROM;
  w = write_uvarint(dst, w, from_v[r]);
  dst[w++] = TAG_TO;
  w = write_uvarint(dst, w, to_v[r]);
  if (val_len[r] >= 0) {
    dst[w++] = TAG_VALUE;
    w = write_uvarint(dst, w, val_len[r]);
    for (int64_t k = 0; k < val_len[r]; ++k) dst[w + k] = src[val_off[r] + k];
    w += val_len[r];
  }
  return w;
}

}  // namespace

extern "C" {

// Bulk-encode n Change records (columnar, offsets into `src`) as framed
// wire bytes: varint(len+1) | 0x01 | proto payload, fields in ascending
// field-number order matching the Python encoder (wire/change_codec.py).
// sub_len/val_len -1 = absent optional.  Returns bytes written into
// `dst` (capacity `cap`), or DAT_ERR_CAPACITY.
int64_t dat_encode_changes(const uint8_t* src, int64_t n,
                           const uint32_t* change, const uint32_t* from_v,
                           const uint32_t* to_v, const int64_t* key_off,
                           const int64_t* key_len, const int64_t* sub_off,
                           const int64_t* sub_len, const int64_t* val_off,
                           const int64_t* val_len, uint8_t* dst,
                           int64_t cap) {
  int64_t w = 0;
  for (int64_t r = 0; r < n; ++r) {
    int64_t psize = change_payload_size(r, change, from_v, to_v, key_len,
                                        sub_len, val_len);
    int64_t need = uvarint_size(psize + 1) + 1 + psize;
    if (w + need > cap) return DAT_ERR_CAPACITY;
    w = encode_change_at(src, r, psize, change, from_v, to_v, key_off,
                         key_len, sub_off, sub_len, val_off, val_len, dst, w);
  }
  return w;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Columnar ChangeBatch payload encoder (wire/batch_codec.py documents the
// layout; the frame rides type id  // wire: TYPE_CHANGE_BATCH = 3
// and is only emitted to peers advertising the capability).  The per-row
// work the Python tier cannot vectorize is the dictionary build — dedup of key /
// subset byte spans — so that is what lives here: an open-addressing
// FNV-1a span hash, first-appearance order, then one sequential pass
// writing every section.  Decode needs no C at all (pure array
// reinterpretation on the host side).
// ---------------------------------------------------------------------------

namespace {

constexpr int BATCH_VERSION = 1;  // wire: BATCH_VERSION = 1

inline uint64_t span_hash(const uint8_t* p, int64_t len) {
  uint64_t h = 1469598103934665603ULL;  // FNV-1a 64
  for (int64_t i = 0; i < len; ++i) {
    h ^= p[i];
    h *= 1099511628211ULL;
  }
  return h | 1;  // 0 marks an empty slot
}

// Open-addressing span dictionary over (off, len) extents of one buffer.
// Insert returns the span's first-appearance index.
struct SpanDict {
  const uint8_t* src;
  int64_t cap = 0;      // power of two
  uint64_t* hashes = nullptr;
  int64_t* slots = nullptr;   // slot -> unique index
  int64_t* u_off = nullptr;   // unique -> span
  int64_t* u_len = nullptr;
  int64_t count = 0;

  bool init(const uint8_t* s, int64_t max_entries) {
    src = s;
    cap = 16;
    while (cap < max_entries * 2) cap <<= 1;
    hashes = new (std::nothrow) uint64_t[cap]();
    slots = new (std::nothrow) int64_t[cap];
    u_off = new (std::nothrow) int64_t[max_entries > 0 ? max_entries : 1];
    u_len = new (std::nothrow) int64_t[max_entries > 0 ? max_entries : 1];
    return hashes != nullptr && slots != nullptr && u_off != nullptr &&
           u_len != nullptr;
  }
  ~SpanDict() {
    delete[] hashes;
    delete[] slots;
    delete[] u_off;
    delete[] u_len;
  }
  int64_t insert(int64_t off, int64_t len) {
    uint64_t h = span_hash(src + off, len);
    int64_t i = static_cast<int64_t>(h) & (cap - 1);
    while (true) {
      if (hashes[i] == 0) {
        hashes[i] = h;
        slots[i] = count;
        u_off[count] = off;
        u_len[count] = len;
        return count++;
      }
      if (hashes[i] == h) {
        int64_t u = slots[i];
        if (u_len[u] == len && std::memcmp(src + u_off[u], src + off,
                                           static_cast<size_t>(len)) == 0)
          return u;
      }
      i = (i + 1) & (cap - 1);
    }
  }
};

inline int batch_width(int64_t max_value) {
  // smallest width whose all-ones value strictly exceeds max_value (the
  // all-ones sentinel must stay unambiguous) — mirrors _pick_width
  if (max_value < 0xFF) return 1;
  if (max_value < 0xFFFF) return 2;
  return 4;
}

inline int64_t put_le(uint8_t* dst, int64_t w, uint64_t v, int width) {
  for (int k = 0; k < width; ++k) {
    dst[w + k] = static_cast<uint8_t>(v >> (8 * k));
  }
  return w + width;
}

}  // namespace

extern "C" {

// Encode n records (columnar spans over `src`, the ChangeColumns
// layout; sub_len/val_len -1 = absent) as ONE ChangeBatch payload into
// dst.  Returns payload bytes written, DAT_ERR_CAPACITY if cap is too
// small, or DAT_ERR_NOMEM.
int64_t dat_encode_change_batch(const uint8_t* src, int64_t n,
                                const uint32_t* change,
                                const uint32_t* from_v, const uint32_t* to_v,
                                const int64_t* key_off,
                                const int64_t* key_len,
                                const int64_t* sub_off,
                                const int64_t* sub_len,
                                const int64_t* val_off,
                                const int64_t* val_len, uint8_t* dst,
                                int64_t cap) {
  SpanDict keys, subs;
  if (!keys.init(src, n) || !subs.init(src, n)) return DAT_ERR_NOMEM;
  int64_t* kidx = new (std::nothrow) int64_t[n > 0 ? n : 1];
  int64_t* sidx = new (std::nothrow) int64_t[n > 0 ? n : 1];
  if (kidx == nullptr || sidx == nullptr) {
    delete[] kidx;
    delete[] sidx;
    return DAT_ERR_NOMEM;
  }
  int64_t max_vlen = -1, vheap = 0, max_dlen = 0;
  for (int64_t r = 0; r < n; ++r) {
    kidx[r] = keys.insert(key_off[r], key_len[r]);
    sidx[r] = sub_len[r] >= 0 ? subs.insert(sub_off[r], sub_len[r]) : -1;
    if (val_len[r] >= 0) {
      if (val_len[r] > max_vlen) max_vlen = val_len[r];
      vheap += val_len[r];
    }
  }
  int64_t kheap = 0, sheap = 0;
  for (int64_t u = 0; u < keys.count; ++u) {
    kheap += keys.u_len[u];
    if (keys.u_len[u] > max_dlen) max_dlen = keys.u_len[u];
  }
  for (int64_t u = 0; u < subs.count; ++u) {
    sheap += subs.u_len[u];
    if (subs.u_len[u] > max_dlen) max_dlen = subs.u_len[u];
  }
  // width-ladder bound, mirroring the Python tier's _pick_width raise:
  // a value that would need the 4-byte all-ones sentinel as a REAL
  // length/index must be rejected, never silently encoded as absent
  if (max_vlen >= 0xFFFFFFFFLL || max_dlen >= 0xFFFFFFFFLL ||
      keys.count > 0xFFFFFFFELL || subs.count > 0xFFFFFFFELL) {
    delete[] kidx;
    delete[] sidx;
    return DAT_ERR_BAD_RECORD;
  }
  const int kw = batch_width(keys.count > 0 ? keys.count - 1 : 0);
  const int sw = subs.count == 0 ? 0 : batch_width(subs.count - 1);
  const int vw = max_vlen < 0 ? 0 : batch_width(max_vlen);
  // dict lengths carry no sentinel, so any width REPRESENTING the max is
  // enough — but batch_width's strict bound keeps the two sides' width
  // pick identical, which the byte-exactness tests pin
  const int dw = batch_width(max_dlen);
  int64_t need = 5 + 4 * 10  // header + 4 varints (10-byte worst case)
                 + (keys.count + subs.count) * dw + kheap + sheap
                 + n * (12 + kw + sw + vw) + vheap;
  if (need > cap) {
    delete[] kidx;
    delete[] sidx;
    return DAT_ERR_CAPACITY;
  }
  int64_t w = 0;
  dst[w++] = BATCH_VERSION;
  dst[w++] = static_cast<uint8_t>(kw);
  dst[w++] = static_cast<uint8_t>(sw);
  dst[w++] = static_cast<uint8_t>(vw);
  dst[w++] = static_cast<uint8_t>(dw);
  w = write_uvarint(dst, w, n);
  w = write_uvarint(dst, w, keys.count);
  w = write_uvarint(dst, w, subs.count);
  w = write_uvarint(dst, w, vheap);
  for (int64_t u = 0; u < keys.count; ++u)
    w = put_le(dst, w, keys.u_len[u], dw);
  for (int64_t u = 0; u < keys.count; ++u) {
    std::memcpy(dst + w, src + keys.u_off[u],
                static_cast<size_t>(keys.u_len[u]));
    w += keys.u_len[u];
  }
  for (int64_t u = 0; u < subs.count; ++u)
    w = put_le(dst, w, subs.u_len[u], dw);
  for (int64_t u = 0; u < subs.count; ++u) {
    std::memcpy(dst + w, src + subs.u_off[u],
                static_cast<size_t>(subs.u_len[u]));
    w += subs.u_len[u];
  }
  std::memcpy(dst + w, change, static_cast<size_t>(n) * 4);
  w += n * 4;
  std::memcpy(dst + w, from_v, static_cast<size_t>(n) * 4);
  w += n * 4;
  std::memcpy(dst + w, to_v, static_cast<size_t>(n) * 4);
  w += n * 4;
  for (int64_t r = 0; r < n; ++r) w = put_le(dst, w, kidx[r], kw);
  if (sw) {
    const uint64_t sent = (1ULL << (8 * sw)) - 1;
    for (int64_t r = 0; r < n; ++r)
      w = put_le(dst, w, sidx[r] < 0 ? sent : sidx[r], sw);
  }
  if (vw) {
    const uint64_t sent = (1ULL << (8 * vw)) - 1;
    for (int64_t r = 0; r < n; ++r)
      w = put_le(dst, w, val_len[r] < 0 ? sent : val_len[r], vw);
  }
  for (int64_t r = 0; r < n; ++r) {
    if (val_len[r] > 0) {
      std::memcpy(dst + w, src + val_off[r],
                  static_cast<size_t>(val_len[r]));
      w += val_len[r];
    }
  }
  delete[] kidx;
  delete[] sidx;
  return w;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// BLAKE2b (RFC 7693) — unkeyed, 32-byte digests, written from the spec.
//
// Why native: reconciliation digests host-born records whose digests are
// consumed as a tiny sketch table (ops/reconcile.py) — shipping the bytes
// to the device buys nothing, and a Python hashlib loop pays ~1us of
// interpreter overhead per record (round-3 verdict weak #3: 26-65k
// records/s end-to-end).  A C loop over extents with thread-parallel
// batches turns digesting into a memory-bandwidth problem.
// ---------------------------------------------------------------------------

#include <cstring>
#include <algorithm>
#include <atomic>
#include <cmath>
#include <thread>
#include <vector>

namespace {

const uint64_t B2B_IV[8] = {
    0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
    0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
    0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL,
};

const uint8_t B2B_SIGMA[12][16] = {
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3},
    {11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4},
    {7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8},
    {9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13},
    {2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9},
    {12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11},
    {13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10},
    {6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5},
    {10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0},
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3},
};

inline uint64_t rotr64(uint64_t x, int n) { return (x >> n) | (x << (64 - n)); }

inline uint64_t load64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);  // little-endian hosts (x86/arm LE) only
  return v;
}

void b2b_compress(uint64_t h[8], const uint8_t block[128], uint64_t t,
                  bool last) {
  uint64_t v[16], m[16];
  for (int i = 0; i < 8; ++i) v[i] = h[i];
  for (int i = 0; i < 8; ++i) v[8 + i] = B2B_IV[i];
  v[12] ^= t;  // t_hi stays 0: extent lengths are int64
  if (last) v[14] = ~v[14];
  for (int i = 0; i < 16; ++i) m[i] = load64(block + 8 * i);
#define DAT_G(a, b, c, d, x, y)                      \
  v[a] += v[b] + (x);                                \
  v[d] = rotr64(v[d] ^ v[a], 32);                    \
  v[c] += v[d];                                      \
  v[b] = rotr64(v[b] ^ v[c], 24);                    \
  v[a] += v[b] + (y);                                \
  v[d] = rotr64(v[d] ^ v[a], 16);                    \
  v[c] += v[d];                                      \
  v[b] = rotr64(v[b] ^ v[c], 63);
  for (int r = 0; r < 12; ++r) {
    const uint8_t* s = B2B_SIGMA[r];
    DAT_G(0, 4, 8, 12, m[s[0]], m[s[1]])
    DAT_G(1, 5, 9, 13, m[s[2]], m[s[3]])
    DAT_G(2, 6, 10, 14, m[s[4]], m[s[5]])
    DAT_G(3, 7, 11, 15, m[s[6]], m[s[7]])
    DAT_G(0, 5, 10, 15, m[s[8]], m[s[9]])
    DAT_G(1, 6, 11, 12, m[s[10]], m[s[11]])
    DAT_G(2, 7, 8, 13, m[s[12]], m[s[13]])
    DAT_G(3, 4, 9, 14, m[s[14]], m[s[15]])
  }
#undef DAT_G
  for (int i = 0; i < 8; ++i) h[i] ^= v[i] ^ v[8 + i];
}

// One unkeyed BLAKE2b-256 digest of data[0..len).
void b2b_hash256(const uint8_t* data, int64_t len, uint8_t out[32]) {
  uint64_t h[8];
  for (int i = 0; i < 8; ++i) h[i] = B2B_IV[i];
  h[0] ^= 0x01010000ULL ^ 32ULL;  // depth=fanout=1, keylen=0, outlen=32
  int64_t t = 0;
  while (len - t > 128) {
    b2b_compress(h, data + t, static_cast<uint64_t>(t) + 128, false);
    t += 128;
  }
  uint8_t block[128];
  std::memset(block, 0, 128);
  if (len > t) std::memcpy(block, data + t, len - t);
  b2b_compress(h, block, static_cast<uint64_t>(len), true);  // empty input:
  // one all-zero final block with t=0, per the RFC
  std::memcpy(out, h, 32);
}

inline int pick_threads(int64_t requested, int64_t n, int64_t min_per) {
  int64_t hw = static_cast<int64_t>(std::thread::hardware_concurrency());
  if (hw <= 0) hw = 1;
  // an EXPLICIT request may exceed the core count (bounded
  // oversubscription is merely slower, and it lets the parallel paths
  // be exercised on single-core test machines), but never unboundedly:
  // a huge DAT_NTHREADS must not hit the OS thread limit and abort the
  // process mid-spawn.  Only the auto default clamps to the hardware.
  int64_t t = requested > 0 ? requested : hw;
  int64_t ceil_t = hw * 4 < 64 ? hw * 4 : 64;
  if (requested > 0 && t > ceil_t) t = ceil_t;
  if (t > n / min_per) t = n / min_per;  // don't spawn for tiny batches
  return static_cast<int>(t < 1 ? 1 : t);
}

// Run work(lo, hi, k) over [0, n) split across threads (serial when one
// suffices) — the one owner of the fan-out/join used by every parallel
// entry point.  ``k`` is the chunk index (< the nt pick_threads chose),
// so callers with per-chunk state never re-derive the split arithmetic.
template <class F>
void parallel_for(int64_t n, int64_t nthreads, int64_t min_per, F work) {
  int nt = pick_threads(nthreads, n, min_per);
  if (nt <= 1) {
    work(static_cast<int64_t>(0), n, 0);
    return;
  }
  std::vector<std::thread> ts;
  int64_t per = (n + nt - 1) / nt;
  for (int64_t k = 0; k < nt; ++k) {
    int64_t lo = k * per, hi = lo + per > n ? n : lo + per;
    if (lo >= hi) break;
    ts.emplace_back(work, lo, hi, k);
  }
  for (auto& th : ts) th.join();
}

}  // namespace

// ---------------------------------------------------------------------------
// 4-way multi-buffer BLAKE2b (AVX2): four independent streams interleaved
// in ymm 64-bit lanes — the host-engine analogue of the device kernel's
// SoA batching.  Hashing one stream is inherently serial; hashing a BATCH
// is lane-parallel, so the 12 rounds run once per 4 blocks.  Ragged
// lengths are handled by lane refill: when a lane's stream finishes, its
// digest is extracted and the lane reloads the next job (per-lane t
// counters and final-block masks are just vectors).  Guarded by a
// runtime cpuid check; the scalar loop remains the portable path.
// ---------------------------------------------------------------------------

#if (defined(__x86_64__) || defined(_M_X64)) && \
    (defined(__GNUC__) || defined(__clang__))
#include <immintrin.h>

namespace {

// per-lane stream state for the 4-way engine
struct B2bLane {
  const uint8_t* data = nullptr;
  int64_t len = 0;
  int64_t off = 0;  // bytes consumed so far (multiple of 128)
  uint8_t* out = nullptr;
  bool active = false;
};

__attribute__((target("avx2"))) inline __m256i ror64x4(__m256i x, int r) {
  if (r == 32) return _mm256_shuffle_epi32(x, _MM_SHUFFLE(2, 3, 0, 1));
  if (r == 24) {
    const __m256i m = _mm256_setr_epi8(
        3, 4, 5, 6, 7, 0, 1, 2, 11, 12, 13, 14, 15, 8, 9, 10,
        3, 4, 5, 6, 7, 0, 1, 2, 11, 12, 13, 14, 15, 8, 9, 10);
    return _mm256_shuffle_epi8(x, m);
  }
  if (r == 16) {
    const __m256i m = _mm256_setr_epi8(
        2, 3, 4, 5, 6, 7, 0, 1, 10, 11, 12, 13, 14, 15, 8, 9,
        2, 3, 4, 5, 6, 7, 0, 1, 10, 11, 12, 13, 14, 15, 8, 9);
    return _mm256_shuffle_epi8(x, m);
  }
  // r == 63: rotl1
  return _mm256_or_si256(_mm256_srli_epi64(x, 63), _mm256_add_epi64(x, x));
}

// one compression over 4 interleaved states; m[16] message vectors,
// t = per-lane byte counters, fmask = per-lane all-ones where final
__attribute__((target("avx2")))
void b2b_compress4(__m256i h[8], const __m256i m[16], __m256i t,
                   __m256i fmask) {
  __m256i v[16];
  for (int i = 0; i < 8; ++i) v[i] = h[i];
  for (int i = 0; i < 8; ++i) v[8 + i] = _mm256_set1_epi64x(
      static_cast<long long>(B2B_IV[i]));
  v[12] = _mm256_xor_si256(v[12], t);
  v[14] = _mm256_xor_si256(v[14], fmask);
#define DAT_G4(a, b, c, d, x, y)                        \
  v[a] = _mm256_add_epi64(_mm256_add_epi64(v[a], v[b]), (x)); \
  v[d] = ror64x4(_mm256_xor_si256(v[d], v[a]), 32);     \
  v[c] = _mm256_add_epi64(v[c], v[d]);                  \
  v[b] = ror64x4(_mm256_xor_si256(v[b], v[c]), 24);     \
  v[a] = _mm256_add_epi64(_mm256_add_epi64(v[a], v[b]), (y)); \
  v[d] = ror64x4(_mm256_xor_si256(v[d], v[a]), 16);     \
  v[c] = _mm256_add_epi64(v[c], v[d]);                  \
  v[b] = ror64x4(_mm256_xor_si256(v[b], v[c]), 63);
  for (int r = 0; r < 12; ++r) {
    const uint8_t* s = B2B_SIGMA[r];
    DAT_G4(0, 4, 8, 12, m[s[0]], m[s[1]])
    DAT_G4(1, 5, 9, 13, m[s[2]], m[s[3]])
    DAT_G4(2, 6, 10, 14, m[s[4]], m[s[5]])
    DAT_G4(3, 7, 11, 15, m[s[6]], m[s[7]])
    DAT_G4(0, 5, 10, 15, m[s[8]], m[s[9]])
    DAT_G4(1, 6, 11, 12, m[s[10]], m[s[11]])
    DAT_G4(2, 7, 8, 13, m[s[12]], m[s[13]])
    DAT_G4(3, 4, 9, 14, m[s[14]], m[s[15]])
  }
#undef DAT_G4
  for (int i = 0; i < 8; ++i)
    h[i] = _mm256_xor_si256(h[i], _mm256_xor_si256(v[i], v[8 + i]));
}

// Hash extents buf[offs[i] .. offs[i]+lens[i]) for i in [0, njobs),
// digests to outbase + i*32, 4 lanes at a time with lane refill.
__attribute__((target("avx2")))
void b2b_many_avx2(const uint8_t* buf, const int64_t* offs,
                   const int64_t* lens, int64_t njobs, uint8_t* outbase) {
  if (njobs <= 0) return;
  B2bLane lanes[4];
  __m256i h[8];
  alignas(32) uint64_t hbuf[8][4] = {};  // zeroed: idle lanes load defined
  alignas(32) uint8_t pad[4][128];       // bytes even before first reset
  int64_t next = 0;
  const uint64_t param = 0x01010000ULL ^ 32ULL;

  auto reset_lane = [&](int L) -> bool {
    if (next >= njobs) {
      lanes[L].active = false;
      return false;
    }
    lanes[L] = {buf + offs[next], lens[next], 0, outbase + next * 32, true};
    ++next;
    for (int w = 0; w < 8; ++w)
      hbuf[w][L] = B2B_IV[w] ^ (w == 0 ? param : 0ULL);
    return true;
  };

  for (int L = 0; L < 4; ++L) reset_lane(L);
  for (int w = 0; w < 8; ++w)
    h[w] = _mm256_load_si256(reinterpret_cast<const __m256i*>(hbuf[w]));

  while (lanes[0].active || lanes[1].active || lanes[2].active ||
         lanes[3].active) {
    // stage one block per lane; inactive lanes chew a zero block
    const uint8_t* blk[4];
    alignas(32) uint64_t tv[4];
    alignas(32) uint64_t fv[4];
    bool finishing[4];
    for (int L = 0; L < 4; ++L) {
      B2bLane& ln = lanes[L];
      if (!ln.active) {
        std::memset(pad[L], 0, 128);
        blk[L] = pad[L];
        tv[L] = 0;
        fv[L] = 0;  // never final: state is discarded at refill anyway
        finishing[L] = false;
        continue;
      }
      int64_t rem = ln.len - ln.off;
      if (rem > 128) {
        blk[L] = ln.data + ln.off;
        ln.off += 128;
        tv[L] = static_cast<uint64_t>(ln.off);
        fv[L] = 0;
        finishing[L] = false;
      } else {  // final block (rem in [0, 128]; 0 only for empty input)
        std::memset(pad[L], 0, 128);
        if (rem > 0) std::memcpy(pad[L], ln.data + ln.off, rem);
        blk[L] = pad[L];
        tv[L] = static_cast<uint64_t>(ln.len);
        fv[L] = ~0ULL;
        finishing[L] = true;
      }
    }
    __m256i m[16];
    for (int w = 0; w < 16; ++w)
      m[w] = _mm256_set_epi64x(
          static_cast<long long>(load64(blk[3] + 8 * w)),
          static_cast<long long>(load64(blk[2] + 8 * w)),
          static_cast<long long>(load64(blk[1] + 8 * w)),
          static_cast<long long>(load64(blk[0] + 8 * w)));
    b2b_compress4(
        h, m,
        _mm256_load_si256(reinterpret_cast<const __m256i*>(tv)),
        _mm256_load_si256(reinterpret_cast<const __m256i*>(fv)));
    if (finishing[0] || finishing[1] || finishing[2] || finishing[3]) {
      // spill the state ONCE, then extract+reset every finishing lane
      // in the spilled rows (a per-lane re-spill would clobber an
      // earlier lane's freshly reset IVs), then reload
      for (int w = 0; w < 8; ++w)
        _mm256_store_si256(reinterpret_cast<__m256i*>(hbuf[w]), h[w]);
      for (int L = 0; L < 4; ++L) {
        if (!finishing[L]) continue;
        for (int w = 0; w < 4; ++w)
          std::memcpy(lanes[L].out + 8 * w, &hbuf[w][L], 8);
        reset_lane(L);
      }
      for (int w = 0; w < 8; ++w)
        h[w] = _mm256_load_si256(reinterpret_cast<const __m256i*>(hbuf[w]));
    }
  }
}

inline bool have_avx2() {
  static const bool ok = __builtin_cpu_supports("avx2");
  return ok;
}

}  // namespace
#else
namespace {
inline bool have_avx2() { return false; }
inline void b2b_many_avx2(const uint8_t*, const int64_t*, const int64_t*,
                          int64_t, uint8_t*) {}
}  // namespace
#endif

extern "C" {

// Digest n extents of buf: out[r*32..] = BLAKE2b-256(buf[offs[r] ..
// offs[r]+lens[r])).  nthreads <= 0 = auto.  Returns 0.
int64_t dat_blake2b_many(const uint8_t* buf, const int64_t* offs,
                         const int64_t* lens, int64_t n, uint8_t* out,
                         int64_t nthreads) {
  parallel_for(n, nthreads, 64, [&](int64_t lo, int64_t hi, int64_t) {
    if (have_avx2()) {
      b2b_many_avx2(buf, offs + lo, lens + lo, hi - lo, out + lo * 32);
      return;
    }
    for (int64_t r = lo; r < hi; ++r)
      b2b_hash256(buf + offs[r], lens[r], out + r * 32);
  });
  return 0;
}

// Build a key-addressed reconciliation sketch in one pass
// (ops/reconcile.py documents the protocol): per record r,
//   slot[r]  = LE32(BLAKE2b-256(key_r)[0:4]) & (nslots - 1)
//   table[slot[r]][w] += LE32words(BLAKE2b-256(rec_r))[w]   (wrapping u32)
// `table` is (1 << log2_slots) * 8 u32, caller-zeroed.  Digesting is
// thread-parallel into a scratch digest array; the scatter-add is one
// serial pass (n * 8 adds — never the bottleneck).  Returns 0, or
// DAT_ERR_CAPACITY if scratch allocation fails.
int64_t dat_sketch(const uint8_t* buf, const int64_t* rec_offs,
                   const int64_t* rec_lens, const int64_t* key_offs,
                   const int64_t* key_lens, int64_t n, int64_t log2_slots,
                   uint32_t* table, uint32_t* slots, int64_t nthreads) {
  uint8_t* scratch = new (std::nothrow) uint8_t[static_cast<size_t>(n) * 32];
  if (scratch == nullptr && n > 0) return DAT_ERR_NOMEM;
  const uint32_t mask = (log2_slots >= 32)
                            ? 0xffffffffu
                            : ((1u << log2_slots) - 1u);
  parallel_for(n, nthreads, 64, [&](int64_t lo, int64_t hi, int64_t) {
    int64_t cnt = hi - lo;
    if (have_avx2()) {
      // 4-way engine over records (straight into scratch) and keys
      // (into a range-local buffer the slot extraction reads)
      uint8_t* kds = new (std::nothrow) uint8_t[static_cast<size_t>(cnt) * 32];
      if (kds != nullptr) {
        b2b_many_avx2(buf, rec_offs + lo, rec_lens + lo, cnt,
                      scratch + lo * 32);
        b2b_many_avx2(buf, key_offs + lo, key_lens + lo, cnt, kds);
        for (int64_t r = lo; r < hi; ++r) {
          uint32_t s;
          std::memcpy(&s, kds + (r - lo) * 32, 4);
          slots[r] = s & mask;
        }
        delete[] kds;
        return;
      }  // allocation failed: scalar path below still succeeds
    }
    uint8_t kd[32];
    for (int64_t r = lo; r < hi; ++r) {
      b2b_hash256(buf + rec_offs[r], rec_lens[r], scratch + r * 32);
      b2b_hash256(buf + key_offs[r], key_lens[r], kd);
      uint32_t s;
      std::memcpy(&s, kd, 4);
      slots[r] = s & mask;
    }
  });
  for (int64_t r = 0; r < n; ++r) {
    uint32_t* cell = table + static_cast<int64_t>(slots[r]) * 8;
    uint32_t w[8];
    std::memcpy(w, scratch + r * 32, 32);
    for (int k = 0; k < 8; ++k) cell[k] += w[k];
  }
  delete[] scratch;
  return 0;
}

// -- rateless coded-symbol build (ops/rateless.py documents the scheme) --
//
// The splitmix64 constants are written down independently in
// ops/rateless.py; a fork here is a ROUTE fork — two "byte-identical"
// engines silently mapping elements to different coded symbols (the
// GEAR_C1/GEAR_C2 precedent).  Parity is machine-checked:
// wire: RATELESS_GAMMA = 0x9E3779B97F4A7C15
// wire: RATELESS_MIX1 = 0xBF58476D1CE4E5B9
// wire: RATELESS_MIX2 = 0x94D049BB133111EB
static inline uint64_t rateless_mix64(uint64_t z) {
  z ^= z >> 30;
  z *= 0xBF58476D1CE4E5B9ULL;
  z ^= z >> 27;
  z *= 0x94D049BB133111EBULL;
  z ^= z >> 31;
  return z;
}

// Advance each element's participation cursor through coded-symbol
// indices below `m`, adding its 11-word row (count=1, 2 checksum
// words, 8 digest words) into cells for every index in [base, m).
// `state` / `next` are INOUT per-element cursors (the caller seeds a
// fresh element with state = LE64(digest[0:8]), next = 0 — every
// element participates at index 0); on return every cursor sits at its
// first index >= m, so repeated calls with a growing bound build the
// prefix incrementally.  `cells` is (m - base) * 11 u32, caller-zeroed.
// The gap draw is IEEE double math (sqrt/ceil are correctly rounded),
// bit-identical to the numpy reference in ops/rateless.py.  Threaded
// over elements with private partial tables (u32 wrapping adds commute,
// so the merge order cannot change a single byte).  Returns 0, or
// DAT_ERR_NOMEM when a partial table cannot be allocated.
int64_t dat_rateless_build(const uint8_t* digests, int64_t n,
                           uint64_t* state, uint64_t* next, int64_t base,
                           int64_t m, uint32_t* cells, int64_t nthreads) {
  const int64_t width = (m - base) * 11;
  int nt = pick_threads(nthreads, n, 1024);
  // every partial table is allocated BEFORE any worker runs: the
  // cursors advance in place, so a mid-flight failure after some
  // threads finished would leave them advanced past cells that were
  // never written — a silently corrupted prefix the Python fallback
  // could not repair.  All-or-nothing: fail before touching anything.
  std::vector<uint32_t*> partials(static_cast<size_t>(nt), nullptr);
  for (int k = 1; k < nt; ++k) {
    partials[static_cast<size_t>(k)] =
        new (std::nothrow) uint32_t[static_cast<size_t>(width)]();
    if (partials[static_cast<size_t>(k)] == nullptr) {
      for (int j = 1; j < k; ++j) delete[] partials[static_cast<size_t>(j)];
      return DAT_ERR_NOMEM;
    }
  }
  parallel_for(n, nt, 1024, [&](int64_t lo, int64_t hi, int64_t k) {
    uint32_t* block = k > 0 ? partials[static_cast<size_t>(k)] : cells;
    for (int64_t e = lo; e < hi; ++e) {
      const uint8_t* d = digests + e * 32;
      uint32_t row[11];
      row[0] = 1u;
      uint64_t lanes[4];
      std::memcpy(lanes, d, 32);
      uint64_t acc = rateless_mix64(lanes[0] + 0x9E3779B97F4A7C15ULL);
      for (int i = 1; i < 4; ++i) acc = rateless_mix64(acc ^ lanes[i]);
      row[1] = static_cast<uint32_t>(acc);
      row[2] = static_cast<uint32_t>(acc >> 32);
      std::memcpy(row + 3, d, 32);
      uint64_t st = state[e], nx = next[e];
      const uint64_t bound = static_cast<uint64_t>(m);
      const uint64_t lo_b = static_cast<uint64_t>(base);
      while (nx < bound) {
        if (nx >= lo_b) {
          uint32_t* c = block + static_cast<int64_t>(nx - lo_b) * 11;
          for (int w = 0; w < 11; ++w) c[w] += row[w];
        }
        st += 0x9E3779B97F4A7C15ULL;
        uint32_t r32 = static_cast<uint32_t>(rateless_mix64(st) >> 32);
        double cur = static_cast<double>(nx);
        double gap = std::ceil(
            (cur + 1.5) * (65536.0 / std::sqrt(static_cast<double>(r32) + 1.0)
                           - 1.0));
        if (gap < 1.0) gap = 1.0;
        nx += static_cast<uint64_t>(gap);
      }
      state[e] = st;
      next[e] = nx;
    }
  });
  for (size_t k = 1; k < partials.size(); ++k) {
    if (partials[k] != nullptr) {
      for (int64_t w = 0; w < width; ++w) cells[w] += partials[k][w];
      delete[] partials[k];
    }
  }
  return 0;
}

// Weighted (variable-size element) twin of dat_rateless_build — the
// "Rateless Bloom Filters" extension the snapshot bootstrap (ISSUE 12)
// reconciles CDC chunk sets with.  Cells are 12 u32 words: count, two
// checksum words (the chain above extended by one mix over the length
// word), 8 digest words, and a wrapping-u32 LENGTH word.  The drawn
// index gap divides (integer division, clamped to >= 1) by
// weight_class + 1, where weight_class = min(W_CAP,
// bit_length(len >> W_SHIFT)) — heavy chunks participate more densely.
// The participation constants are written down independently in
// ops/rateless.py; a fork is a ROUTE fork, parity machine-checked:
// wire: RATELESS_W_SHIFT = 12
// wire: RATELESS_W_CAP = 8
int64_t dat_rateless_build_w(const uint8_t* digests, const int64_t* lens,
                             int64_t n, uint64_t* state, uint64_t* next,
                             int64_t base, int64_t m, uint32_t* cells,
                             int64_t nthreads) {
  const int64_t width = (m - base) * 12;
  int nt = pick_threads(nthreads, n, 1024);
  std::vector<uint32_t*> partials(static_cast<size_t>(nt), nullptr);
  for (int k = 1; k < nt; ++k) {
    partials[static_cast<size_t>(k)] =
        new (std::nothrow) uint32_t[static_cast<size_t>(width)]();
    if (partials[static_cast<size_t>(k)] == nullptr) {
      for (int j = 1; j < k; ++j) delete[] partials[static_cast<size_t>(j)];
      return DAT_ERR_NOMEM;
    }
  }
  parallel_for(n, nt, 1024, [&](int64_t lo, int64_t hi, int64_t k) {
    uint32_t* block = k > 0 ? partials[static_cast<size_t>(k)] : cells;
    for (int64_t e = lo; e < hi; ++e) {
      const uint8_t* d = digests + e * 32;
      const uint64_t len = static_cast<uint64_t>(lens[e]);
      uint32_t row[12];
      row[0] = 1u;
      uint64_t lanes[4];
      std::memcpy(lanes, d, 32);
      uint64_t acc = rateless_mix64(lanes[0] + 0x9E3779B97F4A7C15ULL);
      for (int i = 1; i < 4; ++i) acc = rateless_mix64(acc ^ lanes[i]);
      acc = rateless_mix64(acc ^ static_cast<uint64_t>(
                                     static_cast<uint32_t>(len)));
      row[1] = static_cast<uint32_t>(acc);
      row[2] = static_cast<uint32_t>(acc >> 32);
      std::memcpy(row + 3, d, 32);
      row[11] = static_cast<uint32_t>(len);
      uint64_t wclass = 0;
      for (uint64_t v = len >> 12; v != 0 && wclass < 8; v >>= 1) ++wclass;
      const uint64_t div = wclass + 1;
      uint64_t st = state[e], nx = next[e];
      const uint64_t bound = static_cast<uint64_t>(m);
      const uint64_t lo_b = static_cast<uint64_t>(base);
      while (nx < bound) {
        if (nx >= lo_b) {
          uint32_t* c = block + static_cast<int64_t>(nx - lo_b) * 12;
          for (int w = 0; w < 12; ++w) c[w] += row[w];
        }
        st += 0x9E3779B97F4A7C15ULL;
        uint32_t r32 = static_cast<uint32_t>(rateless_mix64(st) >> 32);
        double cur = static_cast<double>(nx);
        double gap = std::ceil(
            (cur + 1.5) * (65536.0 / std::sqrt(static_cast<double>(r32) + 1.0)
                           - 1.0));
        if (gap < 1.0) gap = 1.0;
        uint64_t g = static_cast<uint64_t>(gap) / div;
        if (g < 1) g = 1;
        nx += g;
      }
      state[e] = st;
      next[e] = nx;
    }
  });
  for (size_t k = 1; k < partials.size(); ++k) {
    if (partials[k] != nullptr) {
      for (int64_t w = 0; w < width; ++w) cells[w] += partials[k][w];
      delete[] partials[k];
    }
  }
  return 0;
}

}  // extern "C"

extern "C" {

// Thread-parallel dat_decode_changes: rows are independent, so ranges
// decode concurrently via parallel_for; the reported error is the
// MINIMUM offending index across ranges (atomic fetch-min), preserving
// the serial entry point's first-corrupt-record semantics.
// nthreads <= 0 = auto.
int64_t dat_decode_changes_mt(const uint8_t* buf, const int64_t* starts,
                              const int64_t* lens, int64_t n,
                              uint32_t* change, uint32_t* from_v,
                              uint32_t* to_v, int64_t* key_off,
                              int64_t* key_len, int64_t* sub_off,
                              int64_t* sub_len, int64_t* val_off,
                              int64_t* val_len, int64_t* err_index,
                              int64_t nthreads) {
  std::atomic<int64_t> first(INT64_MAX);
  parallel_for(n, nthreads, 4096, [&](int64_t lo, int64_t hi, int64_t) {
    int64_t bad = decode_changes_range(buf, starts, lens, lo, hi, change,
                                       from_v, to_v, key_off, key_len,
                                       sub_off, sub_len, val_off, val_len);
    if (bad >= 0) {
      int64_t cur = first.load(std::memory_order_relaxed);
      while (bad < cur &&
             !first.compare_exchange_weak(cur, bad,
                                          std::memory_order_relaxed)) {
      }
    }
  });
  if (first.load() != INT64_MAX) {
    *err_index = first.load();
    return DAT_ERR_BAD_RECORD;
  }
  return 0;
}

}  // extern "C"

extern "C" {

// Thread-parallel bulk encode: pass 1 sizes every frame concurrently, a
// serial prefix sum assigns offsets, pass 2 writes every frame at its
// offset concurrently.  Byte-identical to dat_encode_changes (same
// helpers).  Returns bytes written, or DAT_ERR_CAPACITY.
int64_t dat_encode_changes_mt(const uint8_t* src, int64_t n,
                              const uint32_t* change, const uint32_t* from_v,
                              const uint32_t* to_v, const int64_t* key_off,
                              const int64_t* key_len, const int64_t* sub_off,
                              const int64_t* sub_len, const int64_t* val_off,
                              const int64_t* val_len, uint8_t* dst,
                              int64_t cap, int64_t nthreads) {
  int64_t* offs = new (std::nothrow) int64_t[static_cast<size_t>(n) + 1];
  if (offs == nullptr) return DAT_ERR_NOMEM;
  parallel_for(n, nthreads, 4096, [&](int64_t lo, int64_t hi, int64_t) {
    for (int64_t r = lo; r < hi; ++r) {
      int64_t psize = change_payload_size(r, change, from_v, to_v, key_len,
                                          sub_len, val_len);
      offs[r] = uvarint_size(psize + 1) + 1 + psize;
    }
  });
  int64_t total = 0;
  for (int64_t r = 0; r < n; ++r) {
    int64_t sz = offs[r];
    offs[r] = total;
    total += sz;
  }
  offs[n] = total;
  if (total > cap) {
    delete[] offs;
    return DAT_ERR_CAPACITY;
  }
  parallel_for(n, nthreads, 4096, [&](int64_t lo, int64_t hi, int64_t) {
    for (int64_t r = lo; r < hi; ++r) {
      int64_t psize = change_payload_size(r, change, from_v, to_v, key_len,
                                          sub_len, val_len);
      encode_change_at(src, r, psize, change, from_v, to_v, key_off, key_len,
                       sub_off, sub_len, val_off, val_len, dst, offs[r]);
    }
  });
  delete[] offs;
  return total;
}

}  // extern "C"

namespace {

// One gear scan over buf[lo, hi): h is fully determined by the WINDOW
// bytes preceding a position (contributions shift out after 64 steps),
// so any range can be scanned independently by warming the state from
// the 64 bytes before it — the same seeding trick the device tiling
// uses, which is what makes the "rolling" scan embarrassingly parallel.
// Emits window-thinned positions into dst[0..cap) (straddles across
// range boundaries are resolved by the caller's merge); returns the
// count, or -1 the moment cap would overflow — fail-fast, bounded
// memory, no throwing allocations (the file's nothrow convention).
// Gear state at position lo: warmed from the preceding WINDOW bytes
// (the zero seed at the stream head) — one owner for every scan path.
inline uint64_t gear_seed(const uint8_t* buf, int64_t lo,
                          const uint64_t* tab) {
  uint64_t h = 0;
  if (lo == 0) {
    for (int64_t k = 0; k < 64; ++k) h = (h << 1) + tab[0];
  } else {
    for (int64_t k = lo - 64; k < lo; ++k) h = (h << 1) + tab[buf[k]];
  }
  return h;
}

int64_t gear_scan_range(const uint8_t* buf, int64_t lo, int64_t hi,
                        const uint64_t* tab, uint32_t mask,
                        int64_t thin_bits, int64_t* dst, int64_t cap) {
  uint64_t h = gear_seed(buf, lo, tab);
  int64_t m = 0;
  int64_t last_win = -1;
  for (int64_t j = lo; j < hi; ++j) {
    h = (h << 1) + tab[buf[j]];
    if (((static_cast<uint32_t>(h >> 32)) & mask) == 0) {
      if (thin_bits >= 0) {
        int64_t win = j >> thin_bits;
        if (win == last_win) continue;
        last_win = win;
      }
      if (m >= cap) return -1;
      dst[m++] = j;
    }
  }
  return m;
}

// Four independent sub-range chains interleaved in one loop: a single
// gear chain is latency-bound on h -> h (the byte/table loads are off
// the critical path), so interleaving converts the scan to
// throughput-bound — the scalar-ILP analogue of the Pallas kernel's
// ilp chunks.  Each chain seeds from its preceding WINDOW bytes and
// emits (window-thinned) into its own dst slab; the caller's merge
// resolves straddles at every seam.  cnts[c] = -1 flags slab overflow.
void gear_scan_range4(const uint8_t* buf, const int64_t* qlo,
                      const int64_t* qhi, const uint64_t* tab, uint32_t mask,
                      int64_t thin_bits, int64_t* dst, int64_t cap,
                      int64_t* cnts) {
  uint64_t h[4];
  int64_t j[4], lw[4], m[4];
  for (int c = 0; c < 4; ++c) {
    h[c] = gear_seed(buf, qlo[c], tab);
    j[c] = qlo[c];
    lw[c] = -1;
    m[c] = 0;
  }
  auto emit = [&](int c, int64_t pos) {
    if (m[c] < 0) return;  // STICKY overflow poison: a non-sticky check
    // would pass -1 < cap, write dst[c*cap - 1] (heap underflow /
    // cross-chain corruption) and silently reset the count
    if (thin_bits >= 0) {
      int64_t win = pos >> thin_bits;
      if (win == lw[c]) return;
      lw[c] = win;
    }
    if (m[c] >= cap) {
      m[c] = -1;
      return;
    }
    dst[c * cap + m[c]] = pos;
    ++m[c];
  };
  int64_t steps = qhi[0] - qlo[0];
  for (int c = 1; c < 4; ++c)
    if (qhi[c] - qlo[c] < steps) steps = qhi[c] - qlo[c];
  for (int64_t st = 0; st < steps; ++st) {
    // four independent chains per iteration: the compiler schedules the
    // loads of chain c+1 under the shift+add of chain c
    for (int c = 0; c < 4; ++c) {
      uint64_t hh = (h[c] << 1) + tab[buf[j[c]]];
      h[c] = hh;
      if (((static_cast<uint32_t>(hh >> 32)) & mask) == 0) emit(c, j[c]);
      ++j[c];
    }
  }
  for (int c = 0; c < 4; ++c) {  // ragged tails finish serially
    uint64_t hh = h[c];
    for (int64_t p = j[c]; p < qhi[c]; ++p) {
      hh = (hh << 1) + tab[buf[p]];
      if (((static_cast<uint32_t>(hh >> 32)) & mask) == 0) emit(c, p);
    }
    cnts[c] = m[c];  // -1 (sticky poison) or the chain's count
  }
}

}  // namespace

extern "C" {

// Host gear CDC scan: the seeded-stream definition (ops/rabin.py
// host_candidates) — per byte h = (h << 1) + g[b], candidate where the
// top word masks to zero.  g[b] = (b+1)*C1 | ((b+1)*C2 << 32) is a
// 256-entry table, so the loop is ~4 ops/byte (~1.2 GiB/s per core),
// and ranges scan thread-parallel (see gear_scan_range).  thin_bits >=
// 0 keeps only the first candidate per aligned 2**thin_bits window (the
// chunking policy); pass -1 for every candidate.  Returns the candidate
// count (<= cap; DAT_ERR_CAPACITY on overflow).  Serves CPU-routed
// chunk_stream — "batch or stay home" applies to chunking like hashing:
// the XLA scan formulation of this loop measures ~0.0002 GiB/s e2e on a
// CPU host.
int64_t dat_gear_candidates(const uint8_t* buf, int64_t n, int64_t avg_bits,
                            int64_t thin_bits, int64_t* out, int64_t cap,
                            int64_t nthreads) {
  // (1u << 32) is undefined behavior; reject out-of-range parameters
  // instead of silently computing with a garbage mask
  if (avg_bits < 1 || avg_bits > 31 || thin_bits > 31 || cap < 0)
    return DAT_ERR_BAD_RECORD;
  // wire: GEAR_C1 = 0x9E3779B1
  // wire: GEAR_C2 = 0x85EBCA77
  const uint32_t c1 = 0x9E3779B1u, c2 = 0x85EBCA77u;
  uint64_t tab[256];
  for (uint32_t b = 0; b < 256; ++b) {
    uint64_t lo = static_cast<uint32_t>((b + 1) * c1);
    uint64_t hi = static_cast<uint32_t>((b + 1) * c2);
    tab[b] = lo | (hi << 32);
  }
  const uint32_t mask = (1u << avg_bits) - 1u;
  int nt = pick_threads(nthreads, n, 1 << 22);  // >= 4 MiB per thread
  if (n < (1 << 16) || nthreads < -1) {
    // one plain chain, straight into out, fail fast — for tiny inputs,
    // and as the independently-implemented reference route (nthreads
    // < -1, a test-only sentinel: the equivalence tests need a path
    // that shares none of the quartering/merge machinery).  Explicit
    // nthreads=1 keeps the 4-chain ILP scan on its single thread —
    // bounding CPU usage must not cost the interleave speedup.
    int64_t m = gear_scan_range(buf, 0, n, tab, mask, thin_bits, out, cap);
    return m < 0 ? DAT_ERR_CAPACITY : m;
  }
  // every thread chunk runs FOUR interleaved sub-range chains
  // (gear_scan_range4); each of the nt*4 quarters writes a bounded slab
  // slice and the thinned merge resolves window straddles at every
  // seam, so the output equals the single-chain scan's exactly
  int64_t nq = static_cast<int64_t>(nt) * 4;
  // quarters share their chunk's cap budget (a lone chain legitimately
  // holding more than cap/4 trips ERR_CAPACITY and the caller's
  // geometric retry resolves it) — per-quarter FULL budgets would 4x
  // the transient slab for no correctness gain
  int64_t qcap = cap / 4 + 1;
  int64_t* slab = new (std::nothrow) int64_t[static_cast<size_t>(nq) * qcap];
  if (slab == nullptr && nq * qcap > 0) return DAT_ERR_NOMEM;
  std::vector<int64_t> counts(static_cast<size_t>(nq), 0);
  parallel_for(n, nt, 1 << 22, [&](int64_t lo, int64_t hi, int64_t k) {
    int64_t qlo[4], qhi[4];
    int64_t qlen = (hi - lo) / 4;
    for (int c = 0; c < 4; ++c) {
      qlo[c] = lo + c * qlen;
      qhi[c] = c == 3 ? hi : qlo[c] + qlen;
    }
    gear_scan_range4(buf, qlo, qhi, tab, mask, thin_bits,
                     slab + k * 4 * qcap, qcap, counts.data() + k * 4);
  });
  int64_t m = 0;
  int64_t last_win = -1;
  for (int64_t q = 0; q < nq; ++q) {
    if (counts[q] < 0) {
      delete[] slab;
      return DAT_ERR_CAPACITY;
    }
    for (int64_t i = 0; i < counts[q]; ++i) {
      int64_t j = slab[q * qcap + i];
      if (thin_bits >= 0) {
        int64_t win = j >> thin_bits;
        if (win == last_win) continue;
        last_win = win;
      }
      if (m >= cap) {
        delete[] slab;
        return DAT_ERR_CAPACITY;
      }
      out[m++] = j;
    }
  }
  delete[] slab;
  return m;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Single-pass content addressing: fused gear CDC + BLAKE2b (ISSUE 7).
//
// The two-pass host route streams every blob byte through DRAM twice —
// once for the gear candidate scan, once for the BLAKE2b digest pass.
// dat_cdc_hash collapses the pipeline into ONE sweep: the stream is
// processed in cache-sized slabs, and while slab k+1 is being gear-
// scanned, the chunks finalized in slab k (still cache-resident) are
// hashed by a multi-lane BLAKE2b engine whose compressions are
// interleaved INTO the scan loop's instruction stream.  The gear chain
// is scalar and latency-bound (it leaves the vector ports idle); the
// BLAKE2b rounds are vector-port-bound (they leave the scalar ALUs
// idle) — interleaving the two lets one out-of-order core run both
// concurrently, so the fused pass approaches max(gear, hash) instead of
// gear + hash.  Candidates, thinning, greedy min/max selection, and
// digests are all byte-identical to the two-pass route (same gear_seed,
// same per-window thinning + seam merge, same dat_greedy_select
// semantics, same RFC 7693 compression) — the fuzz suite pins this.
//
// The 8-lane engine below is AVX-512F (native 64-bit rotates via
// vprorq, double the lane width of the AVX2 engine); b2b_many_avx2 and
// its callers are untouched — the incumbent two-pass route keeps its
// tested engine.
// ---------------------------------------------------------------------------

namespace {

// Resumable 4-chain gear scanner: gear_scan_range4's machinery hoisted
// into a struct so the fused loop can advance the scan a few bytes per
// BLAKE2b round.  Same quartering, same per-chain seeding from the
// preceding WINDOW bytes, same per-window thinning with sticky
// overflow poison; the caller's ordered merge resolves seam straddles
// exactly like dat_gear_candidates' merge.
struct GearQuad {
  uint64_t h[4];
  int64_t j[4], qhi[4], lw[4], m[4];
  const uint8_t* buf = nullptr;
  const uint64_t* tab = nullptr;
  int64_t* dst = nullptr;  // 4 slabs of qcap each
  int64_t qcap = 0, thin = -1;
  uint32_t mask = 0;

  void init(const uint8_t* b, int64_t lo, int64_t hi, const uint64_t* t,
            uint32_t msk, int64_t thin_bits, int64_t* d, int64_t cap) {
    buf = b;
    tab = t;
    mask = msk;
    thin = thin_bits;
    dst = d;
    qcap = cap;
    int64_t qlen = (hi - lo) / 4;
    for (int c = 0; c < 4; ++c) {
      int64_t qlo = lo + c * qlen;
      qhi[c] = c == 3 ? hi : qlo + qlen;
      h[c] = gear_seed(buf, qlo, tab);
      j[c] = qlo;
      lw[c] = -1;
      m[c] = 0;
    }
  }

  inline void emit(int c, int64_t pos) {
    if (m[c] < 0) return;  // sticky overflow poison (see gear_scan_range4)
    if (thin >= 0) {
      int64_t win = pos >> thin;
      if (win == lw[c]) return;
      lw[c] = win;
    }
    if (m[c] >= qcap) {
      m[c] = -1;
      return;
    }
    dst[c * qcap + m[c]] = pos;
    ++m[c];
  }

  // Advance every live chain by up to per_chain bytes; returns whether
  // any chain still has bytes.  The lockstep fast path runs all four
  // chains with no per-byte bounds checks (the checked variant measured
  // ~2x slower — the branch per byte per chain defeats the 4-way ILP
  // pipelining the interleave exists for); ragged tails finish in
  // per-chain checked loops once the shortest chain drains.
  inline bool advance(int64_t per_chain) {
    int64_t steps = per_chain;
    for (int c = 0; c < 4; ++c) {
      int64_t rem = qhi[c] - j[c];
      if (rem < steps) steps = rem;
    }
    if (steps > 0) {
      // one 64-bit mask test per byte (vs shift+and+cmp): the top-word
      // candidate check as hh & (mask << 32) — test+branch macro-fuse
      const uint64_t mask64 = static_cast<uint64_t>(mask) << 32;
      uint64_t h0 = h[0], h1 = h[1], h2 = h[2], h3 = h[3];
      int64_t j0 = j[0], j1 = j[1], j2 = j[2], j3 = j[3];
      for (int64_t s = 0; s < steps; ++s) {
        h0 = (h0 << 1) + tab[buf[j0]];
        h1 = (h1 << 1) + tab[buf[j1]];
        h2 = (h2 << 1) + tab[buf[j2]];
        h3 = (h3 << 1) + tab[buf[j3]];
        if ((h0 & mask64) == 0) emit(0, j0);
        if ((h1 & mask64) == 0) emit(1, j1);
        if ((h2 & mask64) == 0) emit(2, j2);
        if ((h3 & mask64) == 0) emit(3, j3);
        ++j0;
        ++j1;
        ++j2;
        ++j3;
      }
      h[0] = h0; h[1] = h1; h[2] = h2; h[3] = h3;
      j[0] = j0; j[1] = j1; j[2] = j2; j[3] = j3;
      per_chain -= steps;
    }
    if (per_chain > 0) {
      for (int c = 0; c < 4; ++c) {
        int64_t lim = j[c] + per_chain;
        if (lim > qhi[c]) lim = qhi[c];
        uint64_t hh = h[c];
        for (int64_t p = j[c]; p < lim; ++p) {
          hh = (hh << 1) + tab[buf[p]];
          if (((static_cast<uint32_t>(hh >> 32)) & mask) == 0) emit(c, p);
        }
        h[c] = hh;
        j[c] = lim;
      }
    }
    return j[0] < qhi[0] || j[1] < qhi[1] || j[2] < qhi[2] || j[3] < qhi[3];
  }
};

}  // namespace

#if (defined(__x86_64__) || defined(_M_X64)) && \
    (defined(__GNUC__) || defined(__clang__))

namespace {

inline bool have_avx512() {
  static const bool ok = __builtin_cpu_supports("avx512f");
  return ok;
}

// Resumable 4-lane AVX2 BLAKE2b engine over (ptr, len) jobs: the lane
// machinery of b2b_many_avx2 restructured so one block-step runs per
// call (jobs addressed by pointer, not base+offset — the entry the
// hash_many_list path uses, ADVICE r5: offsets stay offsets).
struct B2b4State {
  B2bLane lanes[4];
  __m256i h[8];
  alignas(32) uint64_t hbuf[8][4];
  alignas(32) uint8_t pad[4][128];
  const uint8_t* const* jptr = nullptr;
  const int64_t* jlen = nullptr;
  uint8_t* outbase = nullptr;
  int64_t njobs = 0, next = 0;
};

__attribute__((target("avx2")))
inline bool b2b4_reset_lane(B2b4State& st, int L) {
  if (st.next >= st.njobs) {
    st.lanes[L].active = false;
    return false;
  }
  st.lanes[L] = {st.jptr[st.next], st.jlen[st.next], 0,
                 st.outbase + st.next * 32, true};
  ++st.next;
  const uint64_t param = 0x01010000ULL ^ 32ULL;
  for (int w = 0; w < 8; ++w)
    st.hbuf[w][L] = B2B_IV[w] ^ (w == 0 ? param : 0ULL);
  return true;
}

__attribute__((target("avx2")))
void b2b4_init(B2b4State& st, const uint8_t* const* jptr, const int64_t* jlen,
               uint8_t* outbase, int64_t njobs) {
  st.jptr = jptr;
  st.jlen = jlen;
  st.outbase = outbase;
  st.njobs = njobs;
  st.next = 0;
  std::memset(st.hbuf, 0, sizeof(st.hbuf));
  for (int L = 0; L < 4; ++L) b2b4_reset_lane(st, L);
  for (int w = 0; w < 8; ++w)
    st.h[w] = _mm256_load_si256(reinterpret_cast<const __m256i*>(st.hbuf[w]));
}

// One 4-lane block compression (with lane refill); false when all lanes
// are idle.  Identical block staging + spill/extract discipline to
// b2b_many_avx2.
__attribute__((target("avx2")))
bool b2b4_step(B2b4State& st) {
  if (!(st.lanes[0].active || st.lanes[1].active || st.lanes[2].active ||
        st.lanes[3].active))
    return false;
  const uint8_t* blk[4];
  alignas(32) uint64_t tv[4];
  alignas(32) uint64_t fv[4];
  bool finishing[4];
  bool anyfin = false;
  for (int L = 0; L < 4; ++L) {
    B2bLane& ln = st.lanes[L];
    if (!ln.active) {
      std::memset(st.pad[L], 0, 128);
      blk[L] = st.pad[L];
      tv[L] = 0;
      fv[L] = 0;
      finishing[L] = false;
      continue;
    }
    int64_t rem = ln.len - ln.off;
    if (rem > 128) {
      blk[L] = ln.data + ln.off;
      ln.off += 128;
      tv[L] = static_cast<uint64_t>(ln.off);
      fv[L] = 0;
      finishing[L] = false;
    } else {
      std::memset(st.pad[L], 0, 128);
      if (rem > 0) std::memcpy(st.pad[L], ln.data + ln.off, rem);
      blk[L] = st.pad[L];
      tv[L] = static_cast<uint64_t>(ln.len);
      fv[L] = ~0ULL;
      finishing[L] = true;
      anyfin = true;
    }
  }
  __m256i m[16];
  for (int w = 0; w < 16; ++w)
    m[w] = _mm256_set_epi64x(
        static_cast<long long>(load64(blk[3] + 8 * w)),
        static_cast<long long>(load64(blk[2] + 8 * w)),
        static_cast<long long>(load64(blk[1] + 8 * w)),
        static_cast<long long>(load64(blk[0] + 8 * w)));
  b2b_compress4(st.h, m,
                _mm256_load_si256(reinterpret_cast<const __m256i*>(tv)),
                _mm256_load_si256(reinterpret_cast<const __m256i*>(fv)));
  if (anyfin) {
    for (int w = 0; w < 8; ++w)
      _mm256_store_si256(reinterpret_cast<__m256i*>(st.hbuf[w]), st.h[w]);
    for (int L = 0; L < 4; ++L) {
      if (!finishing[L]) continue;
      for (int w = 0; w < 4; ++w)
        std::memcpy(st.lanes[L].out + 8 * w, &st.hbuf[w][L], 8);
      b2b4_reset_lane(st, L);
    }
    for (int w = 0; w < 8; ++w)
      st.h[w] =
          _mm256_load_si256(reinterpret_cast<const __m256i*>(st.hbuf[w]));
  }
  return true;
}

// 8-lane AVX-512F engine: same lane-refill structure at twice the width,
// with native 64-bit rotates (vprorq) replacing the AVX2 shuffle/shift
// emulation.  The fused variant interleaves a few gear-scan bytes after
// every round, so the scalar chain and the vector rounds share the core.
struct B2b8State {
  B2bLane lanes[8];
  __m512i h[8];
  alignas(64) uint64_t hbuf[8][8];
  alignas(64) uint8_t pad[8][128];
  const uint8_t* const* jptr = nullptr;
  const int64_t* jlen = nullptr;
  uint8_t* outbase = nullptr;
  int64_t njobs = 0, next = 0;
};

__attribute__((target("avx512f")))
inline bool b2b8_reset_lane(B2b8State& st, int L) {
  if (st.next >= st.njobs) {
    st.lanes[L].active = false;
    return false;
  }
  st.lanes[L] = {st.jptr[st.next], st.jlen[st.next], 0,
                 st.outbase + st.next * 32, true};
  ++st.next;
  const uint64_t param = 0x01010000ULL ^ 32ULL;
  for (int w = 0; w < 8; ++w)
    st.hbuf[w][L] = B2B_IV[w] ^ (w == 0 ? param : 0ULL);
  return true;
}

__attribute__((target("avx512f")))
void b2b8_init(B2b8State& st, const uint8_t* const* jptr, const int64_t* jlen,
               uint8_t* outbase, int64_t njobs) {
  st.jptr = jptr;
  st.jlen = jlen;
  st.outbase = outbase;
  st.njobs = njobs;
  st.next = 0;
  std::memset(st.hbuf, 0, sizeof(st.hbuf));
  for (int L = 0; L < 8; ++L) b2b8_reset_lane(st, L);
  for (int w = 0; w < 8; ++w)
    st.h[w] = _mm512_load_si512(reinterpret_cast<const void*>(st.hbuf[w]));
}

// 8x8 uint64 transpose: rows r0..r7 (lane L's 64 message bytes) ->
// out[0..7] (message word w across all 8 lanes).  24 shuffle uops
// replace the 64 scalar loads + 56 insert uops of a set_epi64 build —
// message staging was ~60% of the 8-lane engine's cycles without it.
#define DAT_T8(out, r0, r1, r2, r3, r4, r5, r6, r7)                   \
  {                                                                   \
    __m512i t0 = _mm512_unpacklo_epi64(r0, r1);                       \
    __m512i t1 = _mm512_unpackhi_epi64(r0, r1);                       \
    __m512i t2 = _mm512_unpacklo_epi64(r2, r3);                       \
    __m512i t3 = _mm512_unpackhi_epi64(r2, r3);                       \
    __m512i t4 = _mm512_unpacklo_epi64(r4, r5);                       \
    __m512i t5 = _mm512_unpackhi_epi64(r4, r5);                       \
    __m512i t6 = _mm512_unpacklo_epi64(r6, r7);                       \
    __m512i t7 = _mm512_unpackhi_epi64(r6, r7);                       \
    __m512i u0 = _mm512_shuffle_i64x2(t0, t2, 0x88);                  \
    __m512i u1 = _mm512_shuffle_i64x2(t4, t6, 0x88);                  \
    __m512i u2 = _mm512_shuffle_i64x2(t0, t2, 0xDD);                  \
    __m512i u3 = _mm512_shuffle_i64x2(t4, t6, 0xDD);                  \
    __m512i u4 = _mm512_shuffle_i64x2(t1, t3, 0x88);                  \
    __m512i u5 = _mm512_shuffle_i64x2(t5, t7, 0x88);                  \
    __m512i u6 = _mm512_shuffle_i64x2(t1, t3, 0xDD);                  \
    __m512i u7 = _mm512_shuffle_i64x2(t5, t7, 0xDD);                  \
    out[0] = _mm512_shuffle_i64x2(u0, u1, 0x88);                      \
    out[4] = _mm512_shuffle_i64x2(u0, u1, 0xDD);                      \
    out[2] = _mm512_shuffle_i64x2(u2, u3, 0x88);                      \
    out[6] = _mm512_shuffle_i64x2(u2, u3, 0xDD);                      \
    out[1] = _mm512_shuffle_i64x2(u4, u5, 0x88);                      \
    out[5] = _mm512_shuffle_i64x2(u4, u5, 0xDD);                      \
    out[3] = _mm512_shuffle_i64x2(u6, u7, 0x88);                      \
    out[7] = _mm512_shuffle_i64x2(u6, u7, 0xDD);                      \
  }

// One 8-lane block compression (with lane refill); false when all
// lanes are idle.
__attribute__((target("avx512f")))
bool b2b8_step(B2b8State& st) {
  bool any = false;
  for (int L = 0; L < 8; ++L) any = any || st.lanes[L].active;
  if (!any) return false;
  const uint8_t* blk[8];
  alignas(64) uint64_t tv[8];
  alignas(64) uint64_t fv[8];
  bool finishing[8];
  bool anyfin = false;
  for (int L = 0; L < 8; ++L) {
    B2bLane& ln = st.lanes[L];
    if (!ln.active) {
      std::memset(st.pad[L], 0, 128);
      blk[L] = st.pad[L];
      tv[L] = 0;
      fv[L] = 0;
      finishing[L] = false;
      continue;
    }
    int64_t rem = ln.len - ln.off;
    if (rem > 128) {
      blk[L] = ln.data + ln.off;
      ln.off += 128;
      tv[L] = static_cast<uint64_t>(ln.off);
      fv[L] = 0;
      finishing[L] = false;
    } else {
      std::memset(st.pad[L], 0, 128);
      if (rem > 0) std::memcpy(st.pad[L], ln.data + ln.off, rem);
      blk[L] = st.pad[L];
      tv[L] = static_cast<uint64_t>(ln.len);
      fv[L] = ~0ULL;
      finishing[L] = true;
      anyfin = true;
    }
  }
  __m512i m[16];
  {
    __m512i r[8];
    for (int L = 0; L < 8; ++L)
      r[L] = _mm512_loadu_si512(reinterpret_cast<const void*>(blk[L]));
    DAT_T8(m, r[0], r[1], r[2], r[3], r[4], r[5], r[6], r[7]);
    for (int L = 0; L < 8; ++L)
      r[L] = _mm512_loadu_si512(reinterpret_cast<const void*>(blk[L] + 64));
    DAT_T8((m + 8), r[0], r[1], r[2], r[3], r[4], r[5], r[6], r[7]);
  }
  __m512i v[16];
  for (int i = 0; i < 8; ++i) v[i] = st.h[i];
  for (int i = 0; i < 8; ++i)
    v[8 + i] = _mm512_set1_epi64(static_cast<long long>(B2B_IV[i]));
  v[12] = _mm512_xor_si512(
      v[12], _mm512_load_si512(reinterpret_cast<const void*>(tv)));
  v[14] = _mm512_xor_si512(
      v[14], _mm512_load_si512(reinterpret_cast<const void*>(fv)));
#define DAT_G8(a, b, c, d, x, y)                                    \
  v[a] = _mm512_add_epi64(_mm512_add_epi64(v[a], v[b]), (x));       \
  v[d] = _mm512_ror_epi64(_mm512_xor_si512(v[d], v[a]), 32);        \
  v[c] = _mm512_add_epi64(v[c], v[d]);                              \
  v[b] = _mm512_ror_epi64(_mm512_xor_si512(v[b], v[c]), 24);        \
  v[a] = _mm512_add_epi64(_mm512_add_epi64(v[a], v[b]), (y));       \
  v[d] = _mm512_ror_epi64(_mm512_xor_si512(v[d], v[a]), 16);        \
  v[c] = _mm512_add_epi64(v[c], v[d]);                              \
  v[b] = _mm512_ror_epi64(_mm512_xor_si512(v[b], v[c]), 63);
  for (int r = 0; r < 12; ++r) {
    const uint8_t* s = B2B_SIGMA[r];
    DAT_G8(0, 4, 8, 12, m[s[0]], m[s[1]])
    DAT_G8(1, 5, 9, 13, m[s[2]], m[s[3]])
    DAT_G8(2, 6, 10, 14, m[s[4]], m[s[5]])
    DAT_G8(3, 7, 11, 15, m[s[6]], m[s[7]])
    DAT_G8(0, 5, 10, 15, m[s[8]], m[s[9]])
    DAT_G8(1, 6, 11, 12, m[s[10]], m[s[11]])
    DAT_G8(2, 7, 8, 13, m[s[12]], m[s[13]])
    DAT_G8(3, 4, 9, 14, m[s[14]], m[s[15]])
  }
#undef DAT_G8
  for (int i = 0; i < 8; ++i)
    st.h[i] = _mm512_xor_si512(st.h[i], _mm512_xor_si512(v[i], v[8 + i]));
  if (anyfin) {
    for (int w = 0; w < 8; ++w)
      _mm512_store_si512(reinterpret_cast<void*>(st.hbuf[w]), st.h[w]);
    for (int L = 0; L < 8; ++L) {
      if (!finishing[L]) continue;
      for (int w = 0; w < 4; ++w)
        std::memcpy(st.lanes[L].out + 8 * w, &st.hbuf[w][L], 8);
      b2b8_reset_lane(st, L);
    }
    for (int w = 0; w < 8; ++w)
      st.h[w] = _mm512_load_si512(reinterpret_cast<const void*>(st.hbuf[w]));
  }
  return true;
}

}  // namespace

#else
namespace {
inline bool have_avx512() { return false; }
struct B2b4State {};
struct B2b8State {};
inline void b2b4_init(B2b4State&, const uint8_t* const*, const int64_t*,
                      uint8_t*, int64_t) {}
inline bool b2b4_step(B2b4State&) { return false; }
inline void b2b8_init(B2b8State&, const uint8_t* const*, const int64_t*,
                      uint8_t*, int64_t) {}
inline bool b2b8_step(B2b8State&) { return false; }
}  // namespace
#endif

namespace {

// One fused worker range: gear-scan [rlo, rhi) of the current slab and
// hash this thread's share of the chunks finalized in the previous slab
// (their bytes are one slab behind the scan — still cache-resident).
// Engine pick mirrors dat_blake2b_many_ptrs: AVX-512F 8-lane, AVX2
// 4-lane, scalar loop otherwise.
//
// ``hash_first`` anti-phases the two works across workers: even threads
// scan then hash, odd threads hash then scan, so at any instant half
// the threads run the scalar-port-bound gear chain while the other half
// run the vector-port-bound BLAKE2b rounds.  On SMT siblings the two
// engines then share one physical core's DISJOINT ports — measured on
// the 2-hyperthread dev box, this is where the fused pass's win over
// phase-lockstep execution comes from.  (A per-round instruction-level
// interleave inside one thread measured 35% slower: the 32 live zmm of
// state + message spill as soon as the scalar scan joins the loop.)
void fused_range(const uint8_t* buf, int64_t rlo, int64_t rhi,
                 const uint64_t* tab, uint32_t mask, int64_t thin,
                 int64_t* qdst, int64_t qcap, int64_t* qcnt,
                 const uint8_t* const* jptr, const int64_t* jlen,
                 uint8_t* outb, int64_t njobs, bool hash_first) {
  GearQuad gq;
  gq.init(buf, rlo, rhi, tab, mask, thin, qdst, qcap);
  auto scan = [&] {
    while (gq.advance(1 << 14)) {
    }
  };
  auto hash = [&] {
    if (njobs <= 0) return;
    if (have_avx512()) {
      B2b8State st;
      b2b8_init(st, jptr, jlen, outb, njobs);
      while (b2b8_step(st)) {
      }
    } else if (have_avx2()) {
      B2b4State st;
      b2b4_init(st, jptr, jlen, outb, njobs);
      while (b2b4_step(st)) {
      }
    } else {
      for (int64_t r = 0; r < njobs; ++r)
        b2b_hash256(jptr[r], jlen[r], outb + r * 32);
    }
  };
  if (hash_first) {
    hash();
    scan();
  } else {
    scan();
    hash();
  }
  for (int c = 0; c < 4; ++c) qcnt[c] = gq.m[c];
}

}  // namespace

extern "C" {

// BLAKE2b-256 of n (pointer, length) jobs -> out[r*32..]: the pointer-
// array twin of dat_blake2b_many for payloads that are NOT extents of
// one buffer (hash_many_list's zero-copy span path).  Offsets stay
// offsets; addresses ride a dedicated parameter.  nthreads <= 0 = auto.
int64_t dat_blake2b_many_ptrs(const uint8_t* const* ptrs,
                              const int64_t* lens, int64_t n, uint8_t* out,
                              int64_t nthreads) {
  parallel_for(n, nthreads, 64, [&](int64_t lo, int64_t hi, int64_t) {
    int64_t cnt = hi - lo;
    if (have_avx512()) {
      B2b8State st;
      b2b8_init(st, ptrs + lo, lens + lo, out + lo * 32, cnt);
      while (b2b8_step(st)) {
      }
      return;
    }
    if (have_avx2()) {
      B2b4State st;
      b2b4_init(st, ptrs + lo, lens + lo, out + lo * 32, cnt);
      while (b2b4_step(st)) {
      }
      return;
    }
    for (int64_t r = lo; r < hi; ++r)
      b2b_hash256(ptrs[r], lens[r], out + r * 32);
  });
  return 0;
}

// Fused single-pass content addressing: gear CDC candidates, greedy
// min/max cut selection, and per-chunk BLAKE2b-256 in ONE sweep over
// buf.  Emits chunk end-offsets (exclusive, last == n) into cuts[] and
// 32-byte digests into digests[] (digest r covers [cuts[r-1], cuts[r])).
// thin_bits must be in [5, 31] (the chunking thinning policy; callers
// with smaller min sizes take the two-pass route).  Returns the chunk
// count, DAT_ERR_CAPACITY if cap is too small, or DAT_ERR_BAD_RECORD
// for out-of-range parameters.  Byte-identical cuts and digests to
// dat_gear_candidates + dat_greedy_select + dat_blake2b_many.
int64_t dat_cdc_hash(const uint8_t* buf, int64_t n, int64_t avg_bits,
                     int64_t thin_bits, int64_t min_size, int64_t max_size,
                     int64_t* cuts, uint8_t* digests, int64_t cap,
                     int64_t nthreads) {
  if (avg_bits < 1 || avg_bits > 31 || thin_bits < 5 || thin_bits > 31 ||
      min_size < 1 || max_size < min_size || cap < 1)
    return DAT_ERR_BAD_RECORD;
  if (n <= 0) return 0;
  // wire: GEAR_C1 = 0x9E3779B1
  // wire: GEAR_C2 = 0x85EBCA77
  const uint32_t c1 = 0x9E3779B1u, c2 = 0x85EBCA77u;
  uint64_t tab[256];
  for (uint32_t b = 0; b < 256; ++b) {
    uint64_t lo = static_cast<uint32_t>((b + 1) * c1);
    uint64_t hi = static_cast<uint32_t>((b + 1) * c2);
    tab[b] = lo | (hi << 32);
  }
  const uint32_t mask = (1u << avg_bits) - 1u;
  // slab size: big enough to amortize the per-slab thread fan-out and
  // keep the anti-phase windows long, small enough that a slab plus the
  // trailing chunks being hashed stay cache-resident (the single-DRAM-
  // pass property).  Measured on the dev box (512 MiB stream, max of 5
  // reps): 8 MiB 1.02 GiB/s, 16 MiB 1.27, 32 MiB 1.31 — the fan-out
  // cost dominates below 16 MiB, cache effects are flat to 32 MiB.
  const int64_t SLAB = 32 << 20;
  std::vector<int64_t> cand;
  cand.reserve((SLAB >> thin_bits) + 64);
  size_t ci = 0;      // greedy's cursor into cand
  int64_t start = 0;  // last emitted cut
  int64_t m = 0;      // cuts emitted
  int64_t hm = 0;     // cuts already hashed
  int64_t last_win = -1;
  std::vector<const uint8_t*> jptr;
  std::vector<int64_t> jlen;
  std::vector<int64_t> qslab;
  std::vector<int64_t> qcnt;

  for (int64_t slo = 0; slo < n; slo += SLAB) {
    int64_t shi = slo + SLAB < n ? slo + SLAB : n;
    // cuts decidable from the scanned prefix [0, slo): every candidate
    // through start + max_size is known once the scan passed it
    while (slo - start > max_size) {
      int64_t lo2 = start + min_size;
      int64_t hi2 = start + max_size;
      while (ci < cand.size() && cand[ci] < lo2) ++ci;
      int64_t cut = (ci < cand.size() && cand[ci] <= hi2) ? cand[ci++] : hi2;
      if (m >= cap) return DAT_ERR_CAPACITY;
      cuts[m++] = cut;
      start = cut;
    }
    if (ci > 4096) {  // bound the candidate queue: drop consumed head
      cand.erase(cand.begin(), cand.begin() + static_cast<int64_t>(ci));
      ci = 0;
    }
    // this slab's hash jobs: the chunks finalized above (bytes one slab
    // behind the scan frontier — cache-resident by construction)
    jptr.clear();
    jlen.clear();
    for (int64_t c = hm; c < m; ++c) {
      int64_t cs = c == 0 ? 0 : cuts[c - 1];
      jptr.push_back(buf + cs);
      jlen.push_back(cuts[c] - cs);
    }
    int64_t jo = hm;
    hm = m;
    int64_t njobs = static_cast<int64_t>(jptr.size());
    int64_t span = shi - slo;
    int nt = pick_threads(nthreads, span, 1 << 20);
    // Anti-phase schedule: odd threads hash (all of it, split by bytes)
    // then scan a SMALLER range; even threads only scan.  The skew makes
    // both roles finish together, so the scalar-port gear chain and the
    // vector-port BLAKE2b rounds overlap for the whole slab instead of
    // colliding once the (faster) hash phase drains.  RS/RH is the
    // measured scan:hash single-thread rate ratio; a mis-estimate only
    // shifts work between roles, never correctness.
    const double RS_OVER_RH = 0.55;
    int nh = njobs > 0 ? nt / 2 : 0;  // hash-first thread count
    if (njobs > 0 && nh == 0) nh = 1;
    int ns = nt - nh;
    int64_t hbytes = 0;
    for (int64_t r = 0; r < njobs; ++r) hbytes += jlen[r];
    // per-thread scan quotas: even threads x, odd threads y with
    // x = y + (RS/RH) * hbytes/nh and ns*x + nh*y = span
    int64_t y = nt > 0 && nh > 0
        ? static_cast<int64_t>(
              (span - ns * RS_OVER_RH * (static_cast<double>(hbytes) / nh)) /
              nt)
        : span / (nt > 0 ? nt : 1);
    if (y < 0) y = 0;
    std::vector<int64_t> slo_k(static_cast<size_t>(nt) + 1, 0);
    {
      int64_t acc = 0;
      int64_t x = ns > 0 ? (span - nh * y) / ns : 0;
      for (int k = 0; k < nt; ++k) {
        slo_k[k] = acc;
        acc += (nh > 0 && (k & 1) == 1) ? y : x;
        if (acc > span) acc = span;
      }
      slo_k[nt] = span;
      // rounding slack lands on the last thread's range
    }
    // hash jobs: byte-balanced contiguous shares across the odd threads
    std::vector<int64_t> jsplit(static_cast<size_t>(nt) + 1, njobs);
    jsplit[0] = 0;
    if (njobs > 0) {
      int64_t acc = 0;
      int64_t r = 0;
      int hk = 0;
      for (int k = 1; k <= nt; ++k) {
        if (nh > 0 && ((k - 1) & 1) == 1) {
          ++hk;
          int64_t want = hbytes * hk / nh;
          while (r < njobs && acc < want) acc += jlen[r++];
          jsplit[k] = hk == nh ? njobs : r;
        } else {
          jsplit[k] = jsplit[k - 1];  // scan-only threads take no jobs
        }
      }
      if (nt == 1) jsplit[1] = njobs;
    }
    int64_t qcap = (span / 4 >> thin_bits) + 8;  // any thread may scan
    // up to (nearly) the whole span under the skewed split
    qslab.assign(static_cast<size_t>(nt) * 4 * qcap, 0);
    qcnt.assign(static_cast<size_t>(nt) * 4, 0);
    parallel_for(nt, nt, 1, [&](int64_t k0, int64_t, int64_t) {
      int k = static_cast<int>(k0);
      fused_range(buf, slo + slo_k[k], slo + slo_k[k + 1], tab, mask,
                  thin_bits, qslab.data() + k * 4 * qcap, qcap,
                  qcnt.data() + k * 4, jptr.data() + jsplit[k],
                  jlen.data() + jsplit[k], digests + (jo + jsplit[k]) * 32,
                  jsplit[k + 1] - jsplit[k], (k & 1) == 1);
    });
    // ordered merge of this slab's candidates (global window dedup at
    // every seam, exactly like dat_gear_candidates' merge)
    for (int64_t q = 0; q < nt * 4; ++q) {
      if (qcnt[q] < 0) return DAT_ERR_CAPACITY;  // can't trip with thinning
      for (int64_t i = 0; i < qcnt[q]; ++i) {
        int64_t p = qslab[q * qcap + i];
        int64_t win = p >> thin_bits;
        if (win == last_win) continue;
        last_win = win;
        cand.push_back(p);
      }
    }
  }
  // drain: the exact dat_greedy_select tail over the remaining stream
  while (n - start > max_size) {
    int64_t lo2 = start + min_size;
    int64_t hi2 = start + max_size;
    while (ci < cand.size() && cand[ci] < lo2) ++ci;
    int64_t cut = (ci < cand.size() && cand[ci] <= hi2) ? cand[ci++] : hi2;
    if (m >= cap) return DAT_ERR_CAPACITY;
    cuts[m++] = cut;
    start = cut;
  }
  if (m >= cap) return DAT_ERR_CAPACITY;
  cuts[m++] = n;
  // hash the tail chunks (no scan left to interleave with)
  jptr.clear();
  jlen.clear();
  for (int64_t c = hm; c < m; ++c) {
    int64_t cs = c == 0 ? 0 : cuts[c - 1];
    jptr.push_back(buf + cs);
    jlen.push_back(cuts[c] - cs);
  }
  if (!jptr.empty())
    dat_blake2b_many_ptrs(jptr.data(), jlen.data(),
                          static_cast<int64_t>(jptr.size()),
                          digests + hm * 32, nthreads);
  return m;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Transport pump (ISSUE 14): batched-syscall socket loops.
//
// The Python wire pumps (session/transport.py) cost one interpreter
// round-trip per 64 KiB chunk — at r06 that path, not the crypto, was
// the host e2e floor (ROADMAP item 5).  These entry points move whole
// BATCHES of wire bytes per ctypes call (the GIL is released for the
// call's entire duration), so the interpreter sees one wakeup per
// multi-megabyte slab instead of one per chunk:
//
//   dat_pump_probe      which batched syscalls this kernel serves
//   dat_pump_recv_scan  blocking first read + MSG_DONTWAIT recvmmsg
//                       drain + frame index over the received prefix
//                       (the SAME dat_split_frames scanner — one
//                       owner, so the pump cannot fork the framing)
//   dat_pump_send       gather-send spans to a blocking fd
//                       (sendmmsg batches; writev fallback)
//   dat_pump_send_nb    gather-send until EAGAIN on a non-blocking fd
//                       (the fan-out hot path: spans are BroadcastLog
//                       segment memory, never Python-owned copies)
//
// Every path degrades: ENOSYS / ENOTSOCK / EOPNOTSUPP fall back to
// plain read/writev batches, so pipes (sidecar --stdio) and kernels
// without the mmsg syscalls serve the same byte stream.

#include <errno.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>

namespace {

// geometry of one batched syscall: messages per mmsg call x iovecs per
// message.  16 x 64 = up to 1024 spans (or 16 recv slices) per kernel
// entry; past that the syscall itself stops being the bottleneck.
constexpr int PUMP_MSGS = 16;
constexpr int PUMP_IOV = 64;

// errno says this fd/kernel cannot serve the mmsg syscall at all (the
// fallback decision, distinct from transient EAGAIN/EINTR)
inline bool mmsg_unsupported(int e) {
  return e == ENOSYS || e == ENOTSOCK || e == EOPNOTSUPP || e == EINVAL;
}

struct SpanCursor {
  const int64_t* addrs;
  const int64_t* lens;
  int64_t n;
  int64_t si = 0;    // current span
  int64_t off = 0;   // bytes of span si already sent
  bool done() const { return si >= n; }
  // fill up to `cap` iovecs from the cursor; returns the count
  int fill(struct iovec* iov, int cap) const {
    int k = 0;
    int64_t s = si, o = off;
    while (k < cap && s < n) {
      if (lens[s] <= o) { ++s; o = 0; continue; }
      iov[k].iov_base = reinterpret_cast<void*>(
          static_cast<uintptr_t>(addrs[s]) + o);
      iov[k].iov_len = static_cast<size_t>(lens[s] - o);
      ++k; ++s; o = 0;
    }
    return k;
  }
  void advance(int64_t nbytes) {
    while (nbytes > 0 && si < n) {
      int64_t left = lens[si] - off;
      if (nbytes < left) { off += nbytes; return; }
      nbytes -= left;
      ++si; off = 0;
    }
  }
};

}  // namespace

extern "C" {

// Runtime probe: bit 0 = recvmmsg served, bit 1 = sendmmsg served.
// A call on fd -1 distinguishes "syscall exists" (EBADF) from "kernel
// does not serve it" (ENOSYS) without touching any real descriptor.
int64_t dat_pump_probe(void) {
  int64_t caps = 0;
  errno = 0;
  if (recvmmsg(-1, nullptr, 0, 0, nullptr) < 0 && errno != ENOSYS)
    caps |= 1;
  errno = 0;
  if (sendmmsg(-1, nullptr, 0, 0) < 0 && errno != ENOSYS)
    caps |= 2;
  return caps;
}

// Batched receive + native frame scan, one GIL-released call:
//
//   1. ONE blocking read() (the wakeup — works on sockets and pipes);
//   2. drain whatever the kernel already buffered with MSG_DONTWAIT
//      recvmmsg batches (never blocks; pipes/old kernels skip this);
//   3. index the received prefix's complete frames with
//      dat_split_frames (same scanner, same error semantics — the
//      Python side hands the index to the decoder's bulk entry).
//
// Returns total bytes received (0 = EOF before any byte, the caller
// re-observes EOF on its next call after a mid-batch EOF), or -errno.
// nframes/consumed/err are dat_split_frames' outputs over the prefix;
// stats[0] counts syscalls made, stats[1] messages (reads) landed —
// stats[1] - stats[0] is the syscalls the batching saved.
//
// cap must hold at least one maximal frame header or the scan could
// never make progress:  // wire: MAX_HEADER_LEN = 11
int64_t dat_pump_recv_scan(int64_t fd, uint8_t* dst, int64_t cap,
                           int64_t slice, int64_t* starts, int64_t* lens,
                           uint8_t* ids, int64_t icap, int64_t* nframes,
                           int64_t* consumed, int64_t* err,
                           int64_t* stats) {
  *nframes = 0;
  *consumed = 0;
  *err = 0;
  stats[0] = 0;
  stats[1] = 0;
  if (cap < 11 || slice < 1) return DAT_ERR_CAPACITY;
  if (slice > cap) slice = cap;
  int64_t total = 0;
  for (;;) {  // the blocking wakeup read
    ssize_t r = read(static_cast<int>(fd), dst, static_cast<size_t>(slice));
    ++stats[0];
    if (r < 0) {
      if (errno == EINTR) continue;
      return -static_cast<int64_t>(errno);
    }
    total = r;
    break;
  }
  if (total == 0) return 0;  // EOF
  ++stats[1];
  // drain only when the wakeup read filled its slice: a short first
  // read means the kernel buffer is (momentarily) empty, and probing
  // it with recvmmsg would just buy an EAGAIN — the exact per-batch
  // syscall this pump exists to save
  bool more = total >= slice;
  while (more && cap - total > 0) {
    struct mmsghdr hdrs[PUMP_MSGS];
    struct iovec iov[PUMP_MSGS];
    int k = 0;
    int64_t off = total;
    while (k < PUMP_MSGS && off < cap) {
      int64_t take = cap - off < slice ? cap - off : slice;
      iov[k].iov_base = dst + off;
      iov[k].iov_len = static_cast<size_t>(take);
      std::memset(&hdrs[k].msg_hdr, 0, sizeof(hdrs[k].msg_hdr));
      hdrs[k].msg_hdr.msg_iov = &iov[k];
      hdrs[k].msg_hdr.msg_iovlen = 1;
      hdrs[k].msg_len = 0;
      off += take;
      ++k;
    }
    int r = recvmmsg(static_cast<int>(fd), hdrs, static_cast<unsigned>(k),
                     MSG_DONTWAIT, nullptr);
    ++stats[0];
    if (r < 0) {
      // EAGAIN: drained.  unsupported (pipe / old kernel): the
      // blocking read stands alone.  EINTR: just deliver what we have
      // — the next pump call re-enters.  Hard errors too: the bytes
      // already received must reach the decoder before the caller can
      // surface anything.
      break;
    }
    // STREAM semantics: each message is an independent recvmsg into a
    // fixed-offset iovec, so a short message followed by a non-empty
    // one (bytes that landed between the two) leaves a HOLE at the
    // layout offsets.  Compact every message's bytes down to the
    // running cursor — the wire must be contiguous in dst.
    int64_t w = total;
    for (int m2 = 0; m2 < r; ++m2) {
      int64_t got = hdrs[m2].msg_len;
      if (got == 0) { more = false; break; }  // EOF: deliver the prefix
      if (dst + w != static_cast<uint8_t*>(iov[m2].iov_base))
        std::memmove(dst + w, iov[m2].iov_base, static_cast<size_t>(got));
      w += got;
      ++stats[1];
      if (got < static_cast<int64_t>(iov[m2].iov_len))
        more = false;  // short message: kernel buffer drained
    }
    total = w;
    if (r < k) more = false;
  }
  int64_t nf = dat_split_frames(dst, total, starts, lens, ids, icap,
                                consumed, err);
  if (nf == DAT_ERR_CAPACITY) {
    // the filled prefix is a complete, valid index (dat_split_frames
    // stores frames [0, icap) and leaves `consumed` one past the last
    // stored frame): the unindexed tail simply re-enters the decoder's
    // overflow, so callers can size the index for the TYPICAL frame
    // density instead of the 2-byte worst case
    nf = icap;
    *err = 0;
  } else if (nf < 0) {
    nf = 0;
    *consumed = 0;
    *err = 0;
  }
  *nframes = nf;
  return total;
}

}  // extern "C"

namespace {

// Shared gather-send core.  Walks the span cursor with sendmmsg
// batches (PUMP_MSGS messages x PUMP_IOV iovecs per syscall — a
// stream socket concatenates them in order) and degrades to plain
// writev batches when the fd/kernel cannot serve sendmmsg.  Partial
// acceptance (short msg_len / short writev) resumes mid-span.
// `stop_on_block`: return the accepted total at EAGAIN (non-blocking
// fan-out peers) instead of treating it as an error.  Returns total
// bytes the kernel accepted, or -errno on a hard error (the caller
// surfaces it; bytes already accepted are gone either way — same
// contract as a failed os.writev).
int64_t pump_send_core(const int64_t* addrs, const int64_t* lens,
                       int64_t n, int fd, bool stop_on_block,
                       int64_t* stats) {
  SpanCursor cur{addrs, lens, n};
  int64_t total = 0;
  bool use_mmsg = true;
  while (!cur.done()) {
    if (use_mmsg) {
      struct mmsghdr hdrs[PUMP_MSGS];
      struct iovec iov[PUMP_MSGS * PUMP_IOV];
      SpanCursor peek = cur;
      int m = 0;
      int filled = 0;
      while (m < PUMP_MSGS && !peek.done()) {
        int k = peek.fill(iov + filled, PUMP_IOV);
        if (k == 0) break;
        std::memset(&hdrs[m].msg_hdr, 0, sizeof(hdrs[m].msg_hdr));
        hdrs[m].msg_hdr.msg_iov = iov + filled;
        hdrs[m].msg_hdr.msg_iovlen = static_cast<size_t>(k);
        hdrs[m].msg_len = 0;
        int64_t span_bytes = 0;
        for (int i = 0; i < k; ++i)
          span_bytes += static_cast<int64_t>(iov[filled + i].iov_len);
        peek.advance(span_bytes);
        filled += k;
        ++m;
      }
      if (m == 0) break;
      int r = sendmmsg(fd, hdrs, static_cast<unsigned>(m),
                       stop_on_block ? MSG_DONTWAIT : 0);
      ++stats[0];
      if (r < 0) {
        if (errno == EINTR) continue;
        if (errno == EAGAIN || errno == EWOULDBLOCK) {
          if (stop_on_block) return total;
          continue;  // blocking fd: spurious; retry
        }
        if (mmsg_unsupported(errno)) {
          use_mmsg = false;  // degrade to the writev loop
          continue;
        }
        return -static_cast<int64_t>(errno);
      }
      bool partial = false;
      for (int i = 0; i < r; ++i) {
        int64_t sent = hdrs[i].msg_len;
        total += sent;
        cur.advance(sent);
        ++stats[1];
        int64_t msg_total = 0;
        for (size_t v = 0; v < hdrs[i].msg_hdr.msg_iovlen; ++v)
          msg_total += static_cast<int64_t>(hdrs[i].msg_hdr.msg_iov[v].iov_len);
        if (sent < msg_total) { partial = true; break; }
      }
      // a partial message (or fewer messages than requested) means the
      // kernel stopped accepting: non-blocking callers return with the
      // accepted total, blocking ones re-enter from the cursor
      if ((partial || r < m) && stop_on_block) return total;
      continue;
    }
    struct iovec iov[PUMP_IOV];
    int k = cur.fill(iov, PUMP_IOV);
    if (k == 0) break;
    ssize_t w = writev(fd, iov, k);
    ++stats[0];
    if (w < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        if (stop_on_block) return total;
        continue;
      }
      return -static_cast<int64_t>(errno);
    }
    ++stats[1];
    total += w;
    cur.advance(w);
  }
  return total;
}

}  // namespace

extern "C" {

// Gather-send `n` (address, length) spans to a BLOCKING fd.  Returns
// total bytes written (== sum of lens on success) or -errno.
// stats[0] = syscalls, stats[1] = messages/writevs accepted.
int64_t dat_pump_send(const int64_t* addrs, const int64_t* lens,
                      int64_t n, int64_t fd, int64_t* stats) {
  stats[0] = 0;
  stats[1] = 0;
  return pump_send_core(addrs, lens, n, static_cast<int>(fd), false,
                        stats);
}

// Gather-send to a NON-BLOCKING fd: pushes batches until the kernel
// stops accepting (EAGAIN / partial acceptance) and returns the bytes
// accepted so far (>= 0) — the fan-out dispatcher's bookkeeping
// contract, identical to a short os.writev.  Hard errors are -errno
// (EPIPE/EBADF: the caller sheds the peer as a disconnect).
int64_t dat_pump_send_nb(const int64_t* addrs, const int64_t* lens,
                         int64_t n, int64_t fd, int64_t* stats) {
  stats[0] = 0;
  stats[1] = 0;
  return pump_send_core(addrs, lens, n, static_cast<int>(fd), true,
                        stats);
}

}  // extern "C"
