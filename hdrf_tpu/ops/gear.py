"""Gear rolling-hash CDC candidate scan on TPU.

TPU-native reformulation of the reference's sequential byte scan
(DataDeduplicator.chunking(), DataDeduplicator.java:264-307). The sequential
recurrence ``h = (h << 1) + G[b]`` unrolls to a windowed sum

    h[i] = sum_{k=0}^{31} G[b[i-k]] << k   (mod 2^32)

which is computable for *every* position at once by log-doubling: with
``A_m[i] = sum_{k<m} G[b[i-k]] << k`` (window m),

    A_{2m}[i] = A_m[i] + (A_m[i-m] << m)

so five elementwise shift+add+(array roll) steps produce the full window-32
hash for all positions — pure VPU work, no sequential dependence. Candidate
cut-points are positions where ``(h & mask) == 0``; the tiny sequential min/max
selection over the sparse candidates runs on the host (native.cdc_select),
sharing the exact semantics of the CPU baseline (native/src/cdc.cpp).

The gear byte-mixing function is arithmetic — ``G[b] = fmix32(b * 0x9E3779B1)``
(murmur3 finalizer) — rather than a lookup table, because a 256-entry gather
scalarizes on TPU (~10 ns/element, measured), while fmix32 is 6 elementwise VPU
ops across all positions at once. The C++ side (native/src/cdc.cpp) pre-tabulates
the same function; equality is asserted in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WINDOW = 32  # bytes contributing to the hash: h[i] covers b[i-31..i]

# Window-warmup convention shared by every candidate producer (this module,
# ops/cdc_pallas.py, native hdrf_gear_candidates): the first WINDOW-1
# positions hold partial-window hashes and can never be cuts, so the
# smallest admissible 1-based cut position is WINDOW.  Pinned by a shared
# test vector in tests/test_cdc_pallas.py.
MIN_CANDIDATE_POS1 = WINDOW


def skip_ahead_threshold(min_chunk: int) -> int:
    """Smallest pos1 a candidate must reach to ever be SELECTABLE under a
    ``min_chunk`` floor.  Every selection window opens at
    ``prev_cut + min_chunk`` (native/src/cdc.cpp:74-92's ``lo``) and
    ``prev_cut >= 0``, so a candidate below
    ``max(MIN_CANDIDATE_POS1, min_chunk)`` is dead on arrival regardless of
    block content.  The skip-ahead kernels (ops/cdc_pallas.py) and the mesh
    plane (parallel/sharded.py) mask such candidates out of candidate
    generation up front — provably cut-identical, because the frontier scan
    could never have picked them.  The XLA scan here stays verbatim: it is
    the all-geometry bit-identity oracle."""
    return max(MIN_CANDIDATE_POS1, int(min_chunk))


def _fmix32_np(z: np.ndarray) -> np.ndarray:
    z = z.astype(np.uint32)
    z ^= z >> np.uint32(16)
    z = (z * np.uint32(0x85EBCA6B)) & np.uint32(0xFFFFFFFF)
    z ^= z >> np.uint32(13)
    z = (z * np.uint32(0xC2B2AE35)) & np.uint32(0xFFFFFFFF)
    z ^= z >> np.uint32(16)
    return z


@functools.cache
def gear_table_np() -> np.ndarray:
    """256-entry uint32 gear table, bit-identical to native hdrf_gear_table()."""
    with np.errstate(over="ignore"):
        return _fmix32_np(np.arange(256, dtype=np.uint32) * np.uint32(0x9E3779B1))


def _gear_map(block_u8: jax.Array) -> jax.Array:
    """G[b] per byte, computed arithmetically (no gather)."""
    z = block_u8.astype(jnp.uint32) * np.uint32(0x9E3779B1)
    z = z ^ (z >> np.uint32(16))
    z = z * np.uint32(0x85EBCA6B)
    z = z ^ (z >> np.uint32(13))
    z = z * np.uint32(0xC2B2AE35)
    z = z ^ (z >> np.uint32(16))
    return z


def _doubling_hashes(t: jax.Array) -> jax.Array:
    """All-position window-32 gear hashes from the mapped byte values ``t``.

    t: uint32[N] where t[i] = G[b[i]]. Returns uint32[N]; positions i < 31 hold
    partial-window values (never used: candidates require p >= 32).
    """
    a = t
    m = 1
    while m < WINDOW:
        # a[i] += a[i-m] << m ; out-of-range reads as 0 (zero-pad shift).
        shifted = jnp.concatenate([jnp.zeros((m,), a.dtype), a[:-m]])
        a = a + (shifted << np.uint32(m))
        m *= 2
    return a


_PACK_ROW = 256  # mask bits packed per matmul row -> 32 output bytes


def candidate_bitmap_words(block_u8: jax.Array, mask: jax.Array,
                           pos1_base: jax.Array | None = None,
                           n_valid: jax.Array | None = None) -> jax.Array:
    """Packed all-position candidate bitmap of a resident block.

    The one implementation of the gear-scan hot path, shared by the
    single-chip scan (_candidate_words), the device-resident pipeline
    (ops/resident._prep), the seq-sharded scan (parallel/sharded), and the
    graft entry.  block_u8: u8[n], n % _PACK_ROW == 0.  ``pos1_base`` offsets
    the 1-based positions for shards of a larger block (uint32 scalar).
    ``n_valid`` (uint32 scalar, traced) is the block's true length where the
    array carries pad: no position past it is a candidate, so a zero pad of
    any size (gear(0) == 0: every position in one would be) adds no bitmap
    word; zeros inside the true length stay the candidates they are.
    Returns u32[n/32] little-endian bitmap words (bit k of word w = position
    32w + k is a candidate cut *end*, i.e. cut-point = bit index + 1).
    """
    n = block_u8.shape[0]
    t = _gear_map(block_u8)
    h = _doubling_hashes(t)
    pos1 = jnp.arange(1, n + 1, dtype=jnp.uint32)
    if pos1_base is not None:
        pos1 = pos1 + pos1_base
    is_cand = ((h & mask) == 0) & (pos1 >= MIN_CANDIDATE_POS1)
    if n_valid is not None:
        is_cand &= pos1 <= n_valid
    return pack_bitmap_words(is_cand)


def pack_bitmap_words(is_cand: jax.Array) -> jax.Array:
    """bool[n] -> little-endian u32[n/32] bitmap via the MXU pack matmul
    (exact in f32: per-byte bit sums stay < 2^8).  n % _PACK_ROW == 0."""
    m = is_cand.astype(jnp.float32).reshape(-1, _PACK_ROW)
    packed = jnp.dot(m, jnp.asarray(_pack_weights()),
                     preferred_element_type=jnp.float32)
    # u8 bitcast combine (little-endian), not astype(u32)+strided gather:
    # the (M, 4) u32 intermediate tiles as minor-dim-4 -> 128 lanes (32x
    # memory) when XLA materializes it at batch scale.
    b = packed.astype(jnp.uint8).reshape(-1, 4)
    return jax.lax.bitcast_convert_type(b, jnp.uint32)


@functools.cache
def _pack_weights() -> np.ndarray:
    """Block-diagonal (256, 32) f32: output byte j sums bits 8j..8j+7 weighted
    2^k. Bit sums stay < 2^8 so f32 accumulation is exact; the matmul runs on
    the MXU, which is the fast path for this reduction shape on TPU."""
    w = np.zeros((_PACK_ROW, _PACK_ROW // 8), dtype=np.float32)
    for i in range(_PACK_ROW):
        w[i, i // 8] = float(1 << (i % 8))
    return w


@functools.partial(jax.jit, static_argnames=("cap",))
def _candidate_words(block: jax.Array, mask: jax.Array, cap: int):
    """Sparse candidate bitmap as nonzero u32 words.

    The full bitmap is n/8 bytes — too much for the D2H path — and a flat nonzero over n bools is several
    slow passes. Instead: pack bits to bytes with an MXU matmul (exact in f32),
    combine to u32 words, then nonzero over the n/32 words (sparse at real CDC
    densities). D2H is O(candidates): word indices + word values + count.
    """
    n = block.shape[0]
    pad = (-n) % _PACK_ROW
    words = candidate_bitmap_words(jnp.pad(block, (0, pad)), mask)
    nz = words != 0
    (idx,) = jnp.nonzero(nz, size=cap, fill_value=words.shape[0])
    vals = jnp.take(words, idx, fill_value=0)
    return idx.astype(jnp.uint32), vals, jnp.sum(nz.astype(jnp.int32))


def _words_to_positions(idx: np.ndarray, vals: np.ndarray, n: int) -> np.ndarray:
    """Bit positions from sparse (word_index, word_value) pairs, host side."""
    if idx.size == 0:
        return np.empty(0, dtype=np.uint64)
    # unpackbits over the sparse words only: (k, 32) bits, little-endian.
    bits = np.unpackbits(vals[:, None].astype(">u4").view(np.uint8).reshape(-1, 4)[:, ::-1],
                         axis=1, bitorder="little")
    wi, bi = np.nonzero(bits)
    pos = idx[wi].astype(np.uint64) * 32 + bi + 1  # cut-point = bit index + 1
    pos.sort()
    return pos[pos <= n]


def gear_candidates_jax(data: bytes | np.ndarray, mask: int) -> np.ndarray:
    """Candidate cut-points via the XLA scan; same contract as
    native.gear_candidates."""
    a = np.frombuffer(data, dtype=np.uint8) if not isinstance(data, np.ndarray) else data
    n = a.size
    if n == 0:
        return np.empty(0, dtype=np.uint64)
    nwords = (n + _PACK_ROW - 1) // _PACK_ROW * (_PACK_ROW // 32)
    density_bits = bin(mask & 0xFFFFFFFF).count("1")
    cap = min(nwords, max(1024, (n >> max(density_bits - 2, 0)) + 1024))
    # device_put streams via DMA; jnp.asarray takes a literal path (measured
    # ~25x slower for 128 MB on the earlier shared dev box).
    block = jax.device_put(a)
    m = jnp.uint32(mask & 0xFFFFFFFF)
    idx, vals, count = _candidate_words(block, m, cap)
    if int(count) > cap:  # dense-candidate retry with exact capacity
        idx, vals, count = _candidate_words(block, m, int(count))
    k = int(count)
    return _words_to_positions(np.asarray(idx)[:k], np.asarray(vals)[:k], n)


def cdc_chunk_jax(data: bytes | np.ndarray, mask: int, min_chunk: int,
                  max_chunk: int) -> np.ndarray:
    """TPU candidate scan + host min/max selection; bit-identical cuts to
    native.cdc_chunk (asserted in tests/test_ops.py)."""
    from hdrf_tpu import native

    a = np.frombuffer(data, dtype=np.uint8) if not isinstance(data, np.ndarray) else data
    cand = gear_candidates_jax(a, mask)
    return native.cdc_select(cand, a.size, min_chunk, max_chunk)
