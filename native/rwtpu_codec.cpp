// Native codec for the storage layer: memcomparable key encoding,
// varint block encode/decode, crc32c checksums.
//
// Reference counterparts (design, not code): the memcomparable
// OrderedRowSerde (src/common/src/util/memcmp_encoding/) and the
// block-based SSTable format (src/storage/src/hummock/sstable/block.rs).
// The reference implements these in Rust; this is the C++ equivalent for
// the host-side storage path (the TPU compute path never touches it).
//
// Build (storage/codec.py does it, and names the library by a hash of
// this file): g++ -O3 -shared -fPIC rwtpu_codec.cpp -o librwtpu_codec-<hash>.so
// No -march / -m<isa> flag: what needs an instruction carries its own
// target attribute and is called only where the CPU reports it.

#include <cstdint>
#include <cstring>

extern "C" {

// ---------------------------------------------------------------------
// memcomparable scalar encodings: byte-wise lexicographic order == value
// order.  int64: flip sign bit, big-endian.  float64: flip sign bit for
// positives, all bits for negatives (IEEE754 total order), big-endian.

void mc_encode_i64(const int64_t* in, int64_t n, uint8_t* out) {
    for (int64_t i = 0; i < n; ++i) {
        uint64_t u = (uint64_t)in[i] ^ 0x8000000000000000ULL;
        uint8_t* p = out + i * 8;
        for (int b = 0; b < 8; ++b) p[b] = (uint8_t)(u >> (56 - 8 * b));
    }
}

void mc_decode_i64(const uint8_t* in, int64_t n, int64_t* out) {
    for (int64_t i = 0; i < n; ++i) {
        const uint8_t* p = in + i * 8;
        uint64_t u = 0;
        for (int b = 0; b < 8; ++b) u = (u << 8) | p[b];
        out[i] = (int64_t)(u ^ 0x8000000000000000ULL);
    }
}

void mc_encode_f64(const double* in, int64_t n, uint8_t* out) {
    for (int64_t i = 0; i < n; ++i) {
        uint64_t u;
        memcpy(&u, &in[i], 8);
        if (u >> 63) u = ~u;              // negative: flip all
        else u |= 0x8000000000000000ULL;  // positive: flip sign
        uint8_t* p = out + i * 8;
        for (int b = 0; b < 8; ++b) p[b] = (uint8_t)(u >> (56 - 8 * b));
    }
}

void mc_decode_f64(const uint8_t* in, int64_t n, double* out) {
    for (int64_t i = 0; i < n; ++i) {
        const uint8_t* p = in + i * 8;
        uint64_t u = 0;
        for (int b = 0; b < 8; ++b) u = (u << 8) | p[b];
        if (u >> 63) u &= 0x7FFFFFFFFFFFFFFFULL;
        else u = ~u;
        memcpy(&out[i], &u, 8);
    }
}

// ---------------------------------------------------------------------
// varint (LEB128) block of (key, value) records:
//   record := varint(klen) key varint(vlen) value
// Keys must be pre-sorted by the caller; the block is append-ordered.

static inline int put_varint(uint8_t* p, uint64_t v) {
    int n = 0;
    while (v >= 0x80) { p[n++] = (uint8_t)(v | 0x80); v >>= 7; }
    p[n++] = (uint8_t)v;
    return n;
}

static inline int get_varint(const uint8_t* p, const uint8_t* end,
                             uint64_t* v) {
    uint64_t x = 0;
    int shift = 0, n = 0;
    while (p + n < end) {
        uint8_t b = p[n++];
        x |= (uint64_t)(b & 0x7F) << shift;
        if (!(b & 0x80)) { *v = x; return n; }
        shift += 7;
        if (shift > 63) return -1;
    }
    return -1;
}

// Returns bytes written, or -1 if out_cap is too small.
int64_t block_encode(const uint8_t* keys, const int64_t* key_offsets,
                     const uint8_t* vals, const int64_t* val_offsets,
                     int64_t n, uint8_t* out, int64_t out_cap) {
    int64_t w = 0;
    for (int64_t i = 0; i < n; ++i) {
        int64_t klen = key_offsets[i + 1] - key_offsets[i];
        int64_t vlen = val_offsets[i + 1] - val_offsets[i];
        if (w + 20 + klen + vlen > out_cap) return -1;
        w += put_varint(out + w, (uint64_t)klen);
        memcpy(out + w, keys + key_offsets[i], (size_t)klen);
        w += klen;
        w += put_varint(out + w, (uint64_t)vlen);
        memcpy(out + w, vals + val_offsets[i], (size_t)vlen);
        w += vlen;
    }
    return w;
}

// First pass: count records and total key/value bytes.
int64_t block_scan(const uint8_t* in, int64_t len, int64_t* n_out,
                   int64_t* key_bytes, int64_t* val_bytes) {
    const uint8_t* p = in;
    const uint8_t* end = in + len;
    int64_t n = 0, kb = 0, vb = 0;
    while (p < end) {
        uint64_t klen, vlen;
        int adv = get_varint(p, end, &klen);
        if (adv < 0) return -1;
        p += adv;
        // length-vs-remaining check BEFORE advancing: a huge varint
        // must not wrap the pointer past the bounds test
        if (klen > (uint64_t)(end - p)) return -1;
        p += klen;
        adv = get_varint(p, end, &vlen);
        if (adv < 0) return -1;
        p += adv;
        if (vlen > (uint64_t)(end - p)) return -1;
        p += vlen;
        ++n; kb += (int64_t)klen; vb += (int64_t)vlen;
    }
    *n_out = n; *key_bytes = kb; *val_bytes = vb;
    return 0;
}

// Second pass: fill key/value byte pools + offset arrays (n+1 each).
int64_t block_decode(const uint8_t* in, int64_t len,
                     uint8_t* keys, int64_t* key_offsets,
                     uint8_t* vals, int64_t* val_offsets) {
    const uint8_t* p = in;
    const uint8_t* end = in + len;
    int64_t i = 0, ko = 0, vo = 0;
    key_offsets[0] = 0; val_offsets[0] = 0;
    while (p < end) {
        uint64_t klen, vlen;
        int adv = get_varint(p, end, &klen);
        if (adv < 0) return -1;
        p += adv;
        memcpy(keys + ko, p, (size_t)klen);
        p += klen; ko += (int64_t)klen;
        adv = get_varint(p, end, &vlen);
        if (adv < 0) return -1;
        p += adv;
        memcpy(vals + vo, p, (size_t)vlen);
        p += vlen; vo += (int64_t)vlen;
        ++i;
        key_offsets[i] = ko; val_offsets[i] = vo;
    }
    return i;
}

// ---------------------------------------------------------------------
// crc32c (Castagnoli), bit-reflected: block checksums, the version
// log's chain, an epoch object's trailer in the manifest.
//
// The inner loop is picked ONCE, at load, from what the running CPU
// reports, never from a flag on the compiler's command line: a library
// built on one x86-64 host runs on any other.  "hw" is the CPU's own
// crc32c instruction (SSE4.2 `crc32`), one stream, eight bytes a step
// (three interleaved streams would hide the instruction's latency of
// three cycles; one already runs at 6 GB/s, a few ms of an epoch
// object); "slice8" the portable table loop, eight bytes a
// step, and all another architecture gets (ARMv8 has `crc32cx`; nobody
// here could test it).  rw_crc32c_with() runs a named loop, so a test
// holds every loop present to the byte-at-a-time definition.

static uint32_t crc_t[8][256];

static uint32_t crc_bytewise(uint32_t c, const uint8_t* p, size_t n) {
    for (size_t i = 0; i < n; ++i)
        c = crc_t[0][(c ^ p[i]) & 0xFF] ^ (c >> 8);
    return c;
}

static uint32_t crc_slice8(uint32_t c, const uint8_t* p, size_t n) {
    for (; n >= 8; n -= 8, p += 8) {
        uint64_t w;
        memcpy(&w, p, 8);
#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ == __ORDER_BIG_ENDIAN__
        w = __builtin_bswap64(w);
#endif
        w ^= c;
        c = crc_t[7][w & 0xFF] ^ crc_t[6][(w >> 8) & 0xFF]
          ^ crc_t[5][(w >> 16) & 0xFF] ^ crc_t[4][(w >> 24) & 0xFF]
          ^ crc_t[3][(w >> 32) & 0xFF] ^ crc_t[2][(w >> 40) & 0xFF]
          ^ crc_t[1][(w >> 48) & 0xFF] ^ crc_t[0][w >> 56];
    }
    return crc_bytewise(c, p, n);
}

#if defined(__x86_64__)
#include <nmmintrin.h>
#define RW_HW __attribute__((target("sse4.2")))
#define RW_CRC8(c, w) ((uint32_t)_mm_crc32_u64((c), (w)))

static bool crc_hw_supported() {
    __builtin_cpu_init();  // crc_init may run before libgcc's own
    return __builtin_cpu_supports("sse4.2");
}

RW_HW static uint32_t crc_hw(uint32_t c, const uint8_t* p, size_t n) {
    for (; n >= 8; n -= 8, p += 8) {
        uint64_t w;
        memcpy(&w, p, 8);
        c = RW_CRC8(c, w);
    }
    for (; n; --n, ++p) c = _mm_crc32_u8(c, *p);
    return c;
}
#endif

typedef uint32_t (*crc_fn)(uint32_t, const uint8_t*, size_t);
static crc_fn crc_best = crc_slice8;
static const char* crc_best_name = "slice8";

// runs as the library is loaded, before any caller's thread can race
__attribute__((constructor)) static void crc_init() {
    for (uint32_t i = 0; i < 256; ++i) {
        uint32_t c = i;
        for (int k = 0; k < 8; ++k)
            c = (c & 1) ? (0x82F63B78u ^ (c >> 1)) : (c >> 1);
        crc_t[0][i] = c;
    }
    for (int k = 1; k < 8; ++k)
        for (uint32_t i = 0; i < 256; ++i)
            crc_t[k][i] = crc_t[0][crc_t[k - 1][i] & 0xFF]
                        ^ (crc_t[k - 1][i] >> 8);
#ifdef RW_HW
    if (crc_hw_supported()) {
        crc_best = crc_hw;
        crc_best_name = "hw";
    }
#endif
}

uint32_t rw_crc32c(const uint8_t* data, int64_t n) {
    return ~crc_best(0xFFFFFFFFu, data, (size_t)n);
}

// "hw" or "slice8": the loop rw_crc32c runs on this CPU.
const char* rw_crc32c_impl() { return crc_best_name; }

// One named loop ("hw", "slice8", "bytewise"); returns 0 and sets *ok
// to 0 where this CPU or this build lacks it.
uint32_t rw_crc32c_with(const char* impl, const uint8_t* data, int64_t n,
                        int32_t* ok) {
    crc_fn f = nullptr;
    if (!strcmp(impl, "bytewise")) f = crc_bytewise;
    else if (!strcmp(impl, "slice8")) f = crc_slice8;
#ifdef RW_HW
    else if (!strcmp(impl, "hw") && crc_hw_supported()) f = crc_hw;
#endif
    *ok = f != nullptr;
    return f ? ~f(0xFFFFFFFFu, data, (size_t)n) : 0;
}

}  // extern "C"
