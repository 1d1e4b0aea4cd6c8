// CRC32C (Castagnoli) — packet checksum backend.
//
// The reference checksums every 512-byte chunk of the data-transfer stream with
// CRC32C (DataChecksum in hadoop-common, written from BlockReceiver.java:924-986).
//
// Two routines, one entry.  `hdrf_crc32c` runs on the CPU's own CRC32C
// instruction (SSE4.2) where the CPU reports it: three interleaved streams of
// `_mm_crc32_u64` (one stream is latency-bound at 8 bytes every 3 cycles), in
// blocks of 3 x 8 KiB and then 3 x 256 B, the streams' CRCs combined exactly
// by zero-shift tables (the operator "append n zero bytes" is linear over
// GF(2); the scheme of Mark Adler's crc32c.c), what is left single-stream and
// the last bytes one at a time.  The path depends on `len` and on the CPU and
// on nothing else; it is chosen once, when the library is loaded.
// `hdrf_crc32c_table`, the slice-by-8 table loop, is the fallback where the
// CPU lacks the instruction (or the target is not x86-64) and the oracle the
// tests hold the other to.

#include <cstdint>
#include <cstring>

#if defined(__x86_64__)
#include <nmmintrin.h>
#define HDRF_CRC32C_HW 1
#endif

namespace {

struct Tables {
  uint32_t t[8][256];
  Tables() {
    const uint32_t poly = 0x82F63B78u;  // reflected Castagnoli
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++) c = (c >> 1) ^ (poly & (0u - (c & 1)));
      t[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++)
      for (int s = 1; s < 8; s++)
        t[s][i] = (t[s - 1][i] >> 8) ^ t[0][t[s - 1][i] & 0xFF];
  }
};
const Tables T;

uint32_t crc32c_table(uint32_t crc, const uint8_t *data, uint64_t len) {
  crc = ~crc;
  while (len >= 8) {
    uint64_t v;
    memcpy(&v, data, 8);
    v ^= crc;  // little-endian assumption (x86-64 / TPU hosts)
    crc = T.t[7][v & 0xFF] ^ T.t[6][(v >> 8) & 0xFF] ^ T.t[5][(v >> 16) & 0xFF] ^
          T.t[4][(v >> 24) & 0xFF] ^ T.t[3][(v >> 32) & 0xFF] ^
          T.t[2][(v >> 40) & 0xFF] ^ T.t[1][(v >> 48) & 0xFF] ^
          T.t[0][(v >> 56) & 0xFF];
    data += 8;
    len -= 8;
  }
  while (len--) crc = (crc >> 8) ^ T.t[0][(crc ^ *data++) & 0xFF];
  return ~crc;
}

typedef uint32_t (*crc_fn)(uint32_t, const uint8_t *, uint64_t);

#ifdef HDRF_CRC32C_HW

const uint64_t LONG_BLOCK = 8192, SHORT_BLOCK = 256;

// z[k][b]: the register that holds byte b at position k, after `n` zero
// bytes have gone through it.  Built from the images of the 32 single bits.
struct ZeroShift {
  uint32_t z[4][256];
  explicit ZeroShift(uint64_t n) {
    uint32_t bit[32];
    for (int b = 0; b < 32; b++) {
      uint32_t c = 1u << b;
      for (uint64_t i = 0; i < n; i++) c = (c >> 8) ^ T.t[0][c & 0xFF];
      bit[b] = c;
    }
    for (int k = 0; k < 4; k++)
      for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = 0;
        for (int b = 0; b < 8; b++)
          if (i >> b & 1) c ^= bit[8 * k + b];
        z[k][i] = c;
      }
  }
  uint32_t operator()(uint32_t c) const {
    return z[0][c & 0xFF] ^ z[1][(c >> 8) & 0xFF] ^ z[2][(c >> 16) & 0xFF] ^
           z[3][c >> 24];
  }
};
const ZeroShift Z_LONG(LONG_BLOCK), Z_SHORT(SHORT_BLOCK);

// Three streams of `block` bytes each, side by side; returns the register
// after all 3 * block bytes.
template <uint64_t BLOCK>
__attribute__((target("sse4.2"))) inline uint64_t three_streams(
    uint64_t c0, const uint8_t *p, const ZeroShift &z) {
  uint64_t c1 = 0, c2 = 0, a, b, c;
  for (const uint8_t *end = p + BLOCK; p < end; p += 8) {
    memcpy(&a, p, 8);
    memcpy(&b, p + BLOCK, 8);
    memcpy(&c, p + 2 * BLOCK, 8);
    c0 = _mm_crc32_u64(c0, a);
    c1 = _mm_crc32_u64(c1, b);
    c2 = _mm_crc32_u64(c2, c);
  }
  c0 = z((uint32_t)c0) ^ c1;
  return z((uint32_t)c0) ^ c2;
}

__attribute__((target("sse4.2"))) uint32_t crc32c_sse42(
    uint32_t crc, const uint8_t *p, uint64_t len) {
  uint64_t c = (uint32_t)~crc, v;
  for (; len >= 3 * LONG_BLOCK; p += 3 * LONG_BLOCK, len -= 3 * LONG_BLOCK)
    c = three_streams<LONG_BLOCK>(c, p, Z_LONG);
  for (; len >= 3 * SHORT_BLOCK; p += 3 * SHORT_BLOCK, len -= 3 * SHORT_BLOCK)
    c = three_streams<SHORT_BLOCK>(c, p, Z_SHORT);
  for (; len >= 8; p += 8, len -= 8) {
    memcpy(&v, p, 8);
    c = _mm_crc32_u64(c, v);
  }
  uint32_t r = (uint32_t)c;
  while (len--) r = _mm_crc32_u8(r, *p++);
  return ~r;
}

crc_fn choose() {
  __builtin_cpu_init();
  return __builtin_cpu_supports("sse4.2") ? crc32c_sse42 : crc32c_table;
}
const crc_fn CRC = choose();

#else

const crc_fn CRC = crc32c_table;

#endif

}  // namespace

extern "C" {

uint32_t hdrf_crc32c(uint32_t crc, const uint8_t *data, uint64_t len) {
  return CRC(crc, data, len);
}

uint32_t hdrf_crc32c_table(uint32_t crc, const uint8_t *data, uint64_t len) {
  return crc32c_table(crc, data, len);
}

// Which routine `hdrf_crc32c` runs in this process: "sse42x3" or "table".
const char *hdrf_crc32c_backend() {
  return CRC == crc32c_table ? "table" : "sse42x3";
}

// Batch: CRC32C of each `chunk_size` slice of data (last may be short),
// writing one u32 per slice. Used for per-packet checksum arrays.
void hdrf_crc32c_chunks(const uint8_t *data, uint64_t len, uint64_t chunk_size,
                        uint32_t *out) {
  uint64_t n = 0;
  for (uint64_t off = 0; off < len; off += chunk_size)
    out[n++] = hdrf_crc32c(0, data + off,
                           (len - off < chunk_size) ? len - off : chunk_size);
}

// A run of data-transfer packets in one call (proto/datatransfer.py PKT_HDR:
// [u32 len][u64 seqno][u8 flags][u32 crc32c(payload)], packed little-endian,
// then the payload).  Walks the whole packets staged in buf[0, len): each
// payload is summed, compared with its header's CRC32C and copied to
// out[out_off...), where the block is read from afterwards.  Stops, with
// ret = {bytes consumed, bytes the next call needs staged, why}:
//   0  at a partial packet (ret[1] = its whole length, or a header's)
//   1  after a packet that carries FLAG_LAST (0x1)
//   2  at a checksum mismatch: the packet's header is at index n, nothing of
//      it is copied or consumed
//   3  at a payload that does not fit under out_cap (its header at index n)
//   4  with max_pkts unpacked and at least one more whole packet staged
// Returns n, the packets unpacked; their header fields are in the arrays.
uint64_t hdrf_unpack_packets(const uint8_t *buf, uint64_t len, uint8_t *out,
                             uint64_t out_off, uint64_t out_cap,
                             uint64_t max_pkts, uint64_t *seqnos,
                             uint32_t *lens, uint8_t *flags, uint32_t *crcs,
                             uint64_t *ret) {
  const uint64_t HDR = 17;
  uint64_t pos = 0, n = 0, need = HDR, why = 0;
  while (len - pos >= HDR) {
    const uint8_t *h = buf + pos;
    uint32_t ln, crc;
    uint64_t seq;
    memcpy(&ln, h, 4);
    memcpy(&seq, h + 4, 8);
    memcpy(&crc, h + 13, 4);
    if (len - pos < HDR + ln) {
      need = HDR + ln;
      break;
    }
    need = 0;
    if (n == max_pkts) {
      why = 4;
      break;
    }
    seqnos[n] = seq;
    lens[n] = ln;
    flags[n] = h[12];
    crcs[n] = crc;
    if (out_off + ln > out_cap) {
      why = 3;
      break;
    }
    if (hdrf_crc32c(0, h + HDR, ln) != crc) {
      why = 2;
      break;
    }
    memcpy(out + out_off, h + HDR, ln);
    out_off += ln;
    pos += HDR + ln;
    n++;
    if (h[12] & 0x1) {
      why = 1;
      break;
    }
    need = HDR;
  }
  ret[0] = pos;
  ret[1] = need;
  ret[2] = why;
  return n;
}

}  // extern "C"
