// Byte-range gather for the dedup commit path.
//
// Role-equivalent of the reference's storer-thread byte shuffling
// (DataDeduplicator.java:652-845 threadedStorer: per-chunk ByteBuffer
// slices copied into container buffers).  The Python half used to build a
// list of per-chunk memoryviews and b"".join them — ~1.2 s per 512 MiB of
// TeraGen-density chunks on the 1-vCPU DataNode host; this single memcpy
// loop replaces that.

#include <cstdint>
#include <cstring>

extern "C" {

// Concatenate n [starts[i], starts[i]+lens[i]) ranges of src into dst.
// Returns total bytes written.  Caller sizes dst >= sum(lens).  Ranges that
// lie end to start in src (every range of a block whose chunks are all new)
// move as one memcpy a run.
uint64_t hdrf_gather_ranges(const uint8_t *src, uint64_t n,
                            const uint64_t *starts, const uint64_t *lens,
                            uint8_t *dst) {
  uint64_t at = 0;
  for (uint64_t i = 0; i < n; i++) {
    const uint64_t start = starts[i];
    uint64_t len = lens[i];
    while (i + 1 < n && starts[i + 1] == start + len) len += lens[++i];
    memcpy(dst + at, src + start, len);
    at += len;
  }
  return at;
}

}  // extern "C"
