// CRC32C (Castagnoli) — packet checksum backend.
//
// The reference checksums every 512-byte chunk of the data-transfer stream with
// CRC32C (DataChecksum in hadoop-common, written from BlockReceiver.java:924-986).
// Slice-by-8 table-driven implementation.

#include <cstdint>
#include <cstring>

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

}  // namespace

extern "C" {

uint32_t hdrf_crc32c(uint32_t crc, const uint8_t *data, uint64_t len) {
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
