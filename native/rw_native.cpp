// Native runtime kernels for hummock-lite's storage hot path.
//
// Reference parity: the role of the Rust block builder/decoder
// (src/storage/src/hummock/sstable/block.rs) and bloom construction
// (sstable/bloom.rs) — the per-entry byte-wrangling loops that sit on
// the checkpoint-upload and scan paths. Byte-for-byte compatible with
// the pure-Python implementation in risingwave_tpu/storage/sst.py:
// either side can read the other's SSTs (mixed deployments, and the
// Python path remains the portable fallback).
//
// Build: g++ -O2 -shared -fPIC -o librw_native.so rw_native.cpp

#include <algorithm>
#include <cstdint>
#include <cstring>

namespace {

// CRC-32 (IEEE, zlib-compatible): crc32(prev, data) semantics.
uint32_t crc_table[256];
bool crc_init_done = false;

void crc_init() {
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int j = 0; j < 8; j++)
            c = (c & 1) ? (0xEDB88320u ^ (c >> 1)) : (c >> 1);
        crc_table[i] = c;
    }
    crc_init_done = true;
}

uint32_t crc32_z(uint32_t prev, const uint8_t* p, long n) {
    if (!crc_init_done) crc_init();
    uint32_t c = prev ^ 0xFFFFFFFFu;
    for (long i = 0; i < n; i++)
        c = crc_table[(c ^ p[i]) & 0xFF] ^ (c >> 8);
    return c ^ 0xFFFFFFFFu;
}

inline long put_uvarint(uint8_t* out, long pos, uint64_t v) {
    while (v >= 0x80) {
        out[pos++] = (uint8_t)((v & 0x7F) | 0x80);
        v >>= 7;
    }
    out[pos++] = (uint8_t)v;
    return pos;
}

// Bounded varint read for LENGTH fields: returns new pos, or -1 on
// truncation or any value >= 2^28 (no block length is near that; a
// larger value is corrupt data and, if cast to long, could turn the
// caller's bounds checks negative — corrupt object-store bytes must
// fail cleanly, not read OOB).
inline long get_uvarint(const uint8_t* data, long pos, long len,
                        uint64_t* v) {
    int shift = 0;
    uint64_t r = 0;
    for (;;) {
        if (pos >= len || shift > 21) return -1;
        uint8_t b = data[pos++];
        r |= (uint64_t)(b & 0x7F) << shift;
        if (b < 0x80) break;
        shift += 7;
    }
    if (r >= (1u << 28)) return -1;
    *v = r;
    return pos;
}

}  // namespace

extern "C" {

// Prefix-compressed block encode. Entries must be pre-sorted by key.
// Returns bytes written, or -1 if out_cap is insufficient.
long rw_block_encode(const uint8_t* keys, const int32_t* key_lens,
                     const uint8_t* vals, const int32_t* val_lens,
                     int32_t n, int32_t restart_interval,
                     uint8_t* out, long out_cap) {
    long pos = 0;
    const uint8_t* last_key = nullptr;
    int32_t last_len = 0;
    const uint8_t* kp = keys;
    const uint8_t* vp = vals;
    for (int32_t i = 0; i < n; i++) {
        int32_t kl = key_lens[i], vl = val_lens[i];
        int32_t shared = 0;
        if (i % restart_interval != 0 && last_key != nullptr) {
            int32_t m = kl < last_len ? kl : last_len;
            while (shared < m && kp[shared] == last_key[shared]) shared++;
        }
        // worst case: 3 varints (≤10B each) + suffix + value
        if (pos + 30 + (kl - shared) + vl > out_cap) return -1;
        pos = put_uvarint(out, pos, (uint64_t)shared);
        pos = put_uvarint(out, pos, (uint64_t)(kl - shared));
        pos = put_uvarint(out, pos, (uint64_t)vl);
        memcpy(out + pos, kp + shared, (size_t)(kl - shared));
        pos += kl - shared;
        memcpy(out + pos, vp, (size_t)vl);
        pos += vl;
        last_key = kp;
        last_len = kl;
        kp += kl;
        vp += vl;
    }
    return pos;
}

// Block decode → concatenated keys/values + per-entry lengths.
// Returns entry count, or -1 on buffer overflow / malformed input.
long rw_block_decode(const uint8_t* data, long len,
                     uint8_t* keys_out, long keys_cap,
                     int32_t* key_lens,
                     uint8_t* vals_out, long vals_cap,
                     int32_t* val_lens, long max_entries) {
    long pos = 0, n = 0;
    long kpos = 0, vpos = 0;
    uint8_t prev_key[4096];
    long prev_len = 0;
    while (pos < len) {
        if (n >= max_entries) return -1;
        uint64_t shared, unshared, vlen;
        pos = get_uvarint(data, pos, len, &shared);
        if (pos < 0) return -1;
        pos = get_uvarint(data, pos, len, &unshared);
        if (pos < 0) return -1;
        pos = get_uvarint(data, pos, len, &vlen);
        if (pos < 0) return -1;
        long kl = (long)(shared + unshared);
        if (kl > 4096 || (long)shared > prev_len) return -1;
        if (pos + (long)unshared + (long)vlen > len) return -1;
        if (kpos + kl > keys_cap || vpos + (long)vlen > vals_cap)
            return -1;
        memcpy(prev_key + shared, data + pos, (size_t)unshared);
        pos += (long)unshared;
        prev_len = kl;
        memcpy(keys_out + kpos, prev_key, (size_t)kl);
        kpos += kl;
        key_lens[n] = (int32_t)kl;
        memcpy(vals_out + vpos, data + pos, (size_t)vlen);
        pos += (long)vlen;
        vpos += (long)vlen;
        val_lens[n] = (int32_t)vlen;
        n++;
    }
    return n;
}

// Bulk split-Bloom build: for each item, set k bits of bits[nbits].
// Hashes match the Python side: h1 = crc32(item), h2 = crc32(item,
// 0x9E3779B9) | 1, bit_j = (h1 + j*h2) % nbits, MSB-first packing.
void rw_bloom_build(const uint8_t* items, const int32_t* lens,
                    int32_t n, int32_t k, uint8_t* bits, long nbits) {
    const uint8_t* p = items;
    for (int32_t i = 0; i < n; i++) {
        uint32_t h1 = crc32_z(0, p, lens[i]);
        uint32_t h2 = crc32_z(0x9E3779B9u, p, lens[i]) | 1u;
        for (int32_t j = 0; j < k; j++) {
            uint64_t bit = ((uint64_t)h1 + (uint64_t)j * h2) % (uint64_t)nbits;
            bits[bit >> 3] |= (uint8_t)(1u << (7 - (bit & 7)));
        }
        p += lens[i];
    }
}

// Copy n slices blob[offs[i] .. offs[i] + lens[i]) into out, back to
// back (the writer's bloom items: each distinct table ++ user key of a
// block, out of the block's key blob). Returns the bytes written.
long rw_gather(const uint8_t* blob, const int64_t* offs,
               const int32_t* lens, long n, uint8_t* out) {
    long pos = 0;
    for (long i = 0; i < n; i++) {
        memcpy(out + pos, blob + offs[i], (size_t)lens[i]);
        pos += lens[i];
    }
    return pos;
}

// Bloom probe for one item (same hash family). Returns 0/1.
int32_t rw_bloom_may_contain(const uint8_t* item, int32_t len,
                             const uint8_t* bits, long nbits,
                             int32_t k) {
    uint32_t h1 = crc32_z(0, item, len);
    uint32_t h2 = crc32_z(0x9E3779B9u, item, len) | 1u;
    for (int32_t j = 0; j < k; j++) {
        uint64_t bit = ((uint64_t)h1 + (uint64_t)j * h2) % (uint64_t)nbits;
        if (!((bits[bit >> 3] >> (7 - (bit & 7))) & 1)) return 0;
    }
    return 1;
}

// ---- compaction: columnar runs ------------------------------------
//
// A run is one decoded SST in four arrays: keys blob, key lengths,
// values blob, value lengths (what rw_block_decode writes and
// rw_block_encode reads). The Python twin of everything below is the
// row-at-a-time loop in risingwave_tpu/storage/merge.py, which is the
// specification: same survivors, same order, byte-identical SSTs.

// Per entry of an ordered key column: the epoch (the inverted last 8
// bytes of the full key) and whether its table ++ user key differs
// from the entry before it (`prev` stands before entry 0; prev_len 0
// = nothing does). Returns 0, or -1 if a key is shorter than 8 bytes.
long rw_key_columns(const uint8_t* keys, const int32_t* key_lens,
                    long n, const uint8_t* prev, long prev_len,
                    uint64_t* epochs, uint8_t* new_user) {
    const uint8_t* kp = keys;
    for (long i = 0; i < n; i++) {
        long kl = key_lens[i];
        if (kl < 8) return -1;
        uint64_t inv = 0;
        for (int b = 0; b < 8; b++) inv = (inv << 8) | kp[kl - 8 + b];
        epochs[i] = ~inv;
        new_user[i] = !(prev_len == kl
                        && memcmp(prev, kp, (size_t)(kl - 8)) == 0);
        prev = kp;
        prev_len = kl;
        kp += kl;
    }
    return 0;
}

// k-way merge of runs in rank order (rank = position in the arrays,
// lowest = newest layer) with the compaction GC rule, survivors
// gathered into the four output arrays in output order.
//
//   order   bytewise full key, ties by rank
//   rule    an equal full key in a later rank is dropped; every
//           version above `safe` is kept; of the versions at or below
//           `safe` only the newest per table ++ user key is kept, and
//           that one is dropped too if it is a tombstone and `bottom`
//
// Run r is read from entry pos[r] (updated on return) up to counts[r],
// and only while its key is bytewise below `bound` (bound_len 0 = no
// bound). `bound` is a table ++ escaped user key: the escape is
// prefix-free, so a full key compares with it as its user key does and
// a window cut there never splits the versions of one key.
// Returns the entries written and sets *entries_in to the entries
// read; -1 if an output array is too small, -2 on a key shorter than
// 8 bytes or an empty value.
long rw_merge_gc(int32_t k,
                 const uint8_t* const* keys, const int64_t* const* koff,
                 const uint8_t* const* vals, const int64_t* const* voff,
                 int64_t* pos, const int64_t* counts,
                 const uint8_t* bound, long bound_len,
                 uint64_t safe, int32_t bottom,
                 uint8_t* out_keys, long out_keys_cap, int32_t* out_klens,
                 uint8_t* out_vals, long out_vals_cap, int32_t* out_vlens,
                 long out_max, int64_t* entries_in) {
    struct Head { const uint8_t* key; long len; int32_t run; };
    // heads in merge order, the smallest LAST: the run just advanced
    // goes back in by one insertion step (k is a handful: the L0 runs
    // and one L1 run)
    Head* heads = new Head[k > 0 ? k : 1];
    int32_t nheads = 0;
    auto less = [](const Head& a, const Head& b) {
        long m = a.len < b.len ? a.len : b.len;
        int c = memcmp(a.key, b.key, (size_t)m);
        if (c != 0) return c < 0;
        if (a.len != b.len) return a.len < b.len;
        return a.run < b.run;
    };
    auto load = [&](int32_t r, Head* h) {
        if (pos[r] >= counts[r]) return false;
        h->key = keys[r] + koff[r][pos[r]];
        h->len = (long)(koff[r][pos[r] + 1] - koff[r][pos[r]]);
        h->run = r;
        if (bound_len > 0) {
            long m = h->len < bound_len ? h->len : bound_len;
            int c = memcmp(h->key, bound, (size_t)m);
            if (c > 0 || (c == 0 && h->len >= bound_len)) return false;
        }
        return true;
    };
    auto insert = [&](const Head& h) {
        int32_t i = nheads++;
        while (i > 0 && less(heads[i - 1], h)) {
            heads[i] = heads[i - 1];
            i--;
        }
        heads[i] = h;
    };
    for (int32_t r = 0; r < k; r++) {
        Head h;
        if (load(r, &h)) insert(h);
    }
    long n_in = 0, n_out = 0, kpos = 0, vpos = 0, rc = 0;
    const uint8_t* seen = nullptr;      // last distinct full key
    long seen_len = 0;
    bool kept_le_safe = false;
    while (nheads > 0) {
        Head h = heads[--nheads];
        int32_t r = h.run;
        int64_t i = pos[r]++;
        Head next;
        if (load(r, &next)) insert(next);
        n_in++;
        if (h.len < 8) { rc = -2; break; }
        if (seen != nullptr && seen_len == h.len
                && memcmp(seen, h.key, (size_t)h.len) == 0)
            continue;                   // same key+epoch: newer layer wins
        bool same_user = seen != nullptr && seen_len == h.len
            && memcmp(seen, h.key, (size_t)(h.len - 8)) == 0;
        seen = h.key;
        seen_len = h.len;
        if (!same_user) kept_le_safe = false;
        const uint8_t* val = vals[r] + voff[r][i];
        long vl = (long)(voff[r][i + 1] - voff[r][i]);
        if (vl < 1) { rc = -2; break; }
        uint64_t inv = 0;
        for (int b = 0; b < 8; b++) inv = (inv << 8) | h.key[h.len - 8 + b];
        if (~inv <= safe) {
            if (kept_le_safe) continue; // older shadowed version: drop
            kept_le_safe = true;
            // newest <= safe is a delete: gone, but only at the bottom
            // (levels below may still hold the key it deletes)
            if (val[0] == 1 && bottom) continue;
        }
        if (n_out >= out_max || kpos + h.len > out_keys_cap
                || vpos + vl > out_vals_cap) { rc = -1; break; }
        memcpy(out_keys + kpos, h.key, (size_t)h.len);
        out_klens[n_out] = (int32_t)h.len;
        kpos += h.len;
        memcpy(out_vals + vpos, val, (size_t)vl);
        out_vals[vpos] = val[0] == 1;   // as SstBuilder.add writes it
        out_vlens[n_out] = (int32_t)vl;
        vpos += vl;
        n_out++;
    }
    delete[] heads;
    *entries_in = n_in;
    return rc < 0 ? rc : n_out;
}

// ---- checkpoint build: imms to one sorted run -----------------------
//
// HummockLite._build_ssts (storage/hummock.py) turns the imms it drains
// into one columnar run with the three passes below and hands it to
// sst.RunWriter. Their Python twins are the scalar functions the row
// path calls per entry: sst.full_key, value_codec.encode_row, and the
// sort of the (full key, ...) tuples.

// Full keys of one (table, epoch) batch of user keys laid back to back:
// head (the table id, 4 B big-endian) ++ escaped user key (0x00 ->
// 0x00 0xFF, then the terminator 0x00 0x00) ++ tail (the inverted
// epoch, 8 B big-endian). Byte for byte sst.full_key, which packs head
// and tail for it. Returns the bytes written, or -1 if out_cap is too
// small.
long rw_full_keys(const uint8_t* users, const int32_t* user_lens, long n,
                  const uint8_t* head, const uint8_t* tail,
                  uint8_t* out, long out_cap, int32_t* out_lens) {
    const uint8_t* up = users;
    long pos = 0;
    for (long i = 0; i < n; i++) {
        long ul = user_lens[i];
        if (pos + 14 + 2 * ul > out_cap) return -1;
        long start = pos;
        memcpy(out + pos, head, 4);
        pos += 4;
        for (long j = 0; j < ul; j++) {
            out[pos++] = up[j];
            if (up[j] == 0) out[pos++] = 0xFF;
        }
        out[pos++] = 0;
        out[pos++] = 0;
        memcpy(out + pos, tail, 8);
        pos += 8;
        out_lens[i] = (int32_t)(pos - start);
        up += ul;
    }
    return pos;
}

// Stored values of one table's batch, from its rows held by the
// column. Entry i is a tombstone where tombs[i] (one byte, 0x01), else
// the next row: 0x00, the arity varint, then per column the value's
// tag and payload exactly as value_codec.encode_row writes them.
// Column c is kinds[c]:
//   0  every row NULL                   data unused
//   1  int64      (tag 1, zigzag varint)
//   2  float64    (tag 2, 8 bytes little-endian)
//   3  str, 4 bytes (tag 3 / 6, varint length, the bytes): data[c] is
//      the rows' bytes back to back, lens[c] their int32 lengths
//   5  bool       (tag 4 true / 5 false), one byte a row
// valid[c] is null where column c holds no NULL, else one byte a row
// (0 = NULL, tag 0). Wants 11 bytes free before each entry and before
// each value whatever its kind, so out_cap is 11 * (n + rows * ncols)
// + the blobs' bytes (value_codec.encode_values). Returns the bytes
// written, or -1 if out_cap is too small, -2 on a kind it does not know.
long rw_encode_rows(long n, const uint8_t* tombs, int32_t ncols,
                    const int32_t* kinds, const void* const* data,
                    const uint8_t* const* valid,
                    const int32_t* const* lens,
                    uint8_t* out, long out_cap, int32_t* out_lens) {
    long* blob_at = new long[ncols > 0 ? ncols : 1]();
    long pos = 0, r = 0, rc = 0;
    for (long i = 0; i < n && rc == 0; i++) {
        long start = pos;
        if (pos + 11 > out_cap) { rc = -1; break; }
        if (tombs[i]) {
            out[pos++] = 1;
            out_lens[i] = 1;
            continue;
        }
        out[pos++] = 0;
        pos = put_uvarint(out, pos, (uint64_t)ncols);
        for (int32_t c = 0; c < ncols; c++) {
            if (pos + 11 > out_cap) { rc = -1; break; }
            if (kinds[c] == 0 || (valid[c] != nullptr && !valid[c][r])) {
                // a NULL of a str / bytes column has length 0
                out[pos++] = 0;
                continue;
            }
            switch (kinds[c]) {
            case 1: {
                int64_t v = ((const int64_t*)data[c])[r];
                out[pos++] = 1;
                pos = put_uvarint(
                    out, pos, ((uint64_t)v << 1) ^ (uint64_t)(v >> 63));
                break;
            }
            case 2:
                // the host is little-endian, as struct's "<d" writes
                out[pos++] = 2;
                memcpy(out + pos, (const double*)data[c] + r, 8);
                pos += 8;
                break;
            case 3:
            case 4: {
                long ln = lens[c][r];
                if (pos + 11 + ln > out_cap) { rc = -1; break; }
                out[pos++] = kinds[c] == 3 ? 3 : 6;
                pos = put_uvarint(out, pos, (uint64_t)ln);
                memcpy(out + pos, (const uint8_t*)data[c] + blob_at[c],
                       (size_t)ln);
                pos += ln;
                blob_at[c] += ln;
                break;
            }
            case 5:
                out[pos++] = ((const uint8_t*)data[c])[r] ? 4 : 5;
                break;
            default:
                rc = -2;
            }
            if (rc != 0) break;
        }
        out_lens[i] = (int32_t)(pos - start);
        r++;
    }
    delete[] blob_at;
    return rc < 0 ? rc : pos;
}

// The order of n keys laid back to back (koff: their n + 1 offsets):
// perm[i] is the entry that comes i-th in bytewise order, a shorter
// key before the longer one it is a prefix of. What sorting the keys
// as Python bytes gives. Returns 0, or -1 if two keys are equal (a
// drain never holds one full key twice; SstBuilder.add refuses it).
long rw_argsort_keys(const uint8_t* keys, const int64_t* koff, long n,
                     int64_t* perm) {
    for (long i = 0; i < n; i++) perm[i] = i;
    auto cmp = [&](int64_t a, int64_t b) {
        long la = (long)(koff[a + 1] - koff[a]);
        long lb = (long)(koff[b + 1] - koff[b]);
        return memcmp(keys + koff[a], keys + koff[b],
                      (size_t)(la < lb ? la : lb));
    };
    std::sort(perm, perm + n, [&](int64_t a, int64_t b) {
        int c = cmp(a, b);
        if (c != 0) return c < 0;
        return koff[a + 1] - koff[a] < koff[b + 1] - koff[b];
    });
    for (long i = 1; i < n; i++) {
        int64_t a = perm[i - 1], b = perm[i];
        if (koff[a + 1] - koff[a] == koff[b + 1] - koff[b]
                && cmp(a, b) == 0)
            return -1;
    }
    return 0;
}

}  // extern "C"
