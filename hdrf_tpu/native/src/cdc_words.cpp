// Cut selection over the device's candidate words (ops/resident.py reads
// them back from _prep): the rule of hdrf_cdc_select (cdc.cpp) without
// expanding the words into positions. Linked after every other source (see
// the Makefile), so adding it moves none of the library's hot loops.

#include <cstdint>

extern "C" {

// hdrf_cdc_select's rule over the candidate bitmap as the device returns it:
// sparse (word index, word value) pairs, word indices strictly ascending, bit
// b of word w the cut-point 32*w + b + 1. Each chunk looks up its first
// candidate at or after `lo` directly in the words, so the cost is
// O(words + chunks) however dense the candidates are (a zero run makes every
// position one) -- never a pass over every set bit. Candidates past `len`
// (a padded tail's) are never chosen: they exceed every `hi`.
uint64_t hdrf_cdc_select_words(const uint32_t *idx, const uint32_t *vals,
                               uint64_t nwords, uint64_t len,
                               uint64_t min_chunk, uint64_t max_chunk,
                               uint64_t *out_cuts, uint64_t cap) {
  uint64_t start = 0, n = 0, wi = 0;
  if (min_chunk == 0) min_chunk = 1;
  while (start < len && n < cap) {
    uint64_t lo = start + min_chunk;
    uint64_t hi = start + max_chunk;
    if (hi > len) hi = len;
    // bit lo - 1 is cut-point lo; lo only grows, so the cursor only advances
    uint64_t w = (lo - 1) >> 5;
    while (wi < nwords && idx[wi] < w) wi++;
    uint64_t cut = hi;
    for (uint64_t j = wi; j < nwords; j++) {
      uint32_t v = vals[j];
      if (idx[j] == w) v &= ~0u << ((lo - 1) & 31);
      if (v) {
        uint64_t p = uint64_t(idx[j]) * 32 + __builtin_ctz(v) + 1;
        if (p <= hi) cut = p;
        break;
      }
    }
    out_cuts[n++] = cut;
    start = cut;
  }
  return n;
}

}  // extern "C"
