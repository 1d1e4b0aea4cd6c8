"""Plain reference for the reduction a configuration states: content-defined
chunking, SHA-256 fingerprints, exact dedup and the LZ4 block decode.

Imports nothing of the program and takes no table from it.  The format is the
configuration's to state (``perfbench/configs/<name>.json``, key ``cdc``):

- gear function ``G[b] = fmix32(b * 0x9E3779B1)`` (murmur3 finaliser);
- rolling hash ``h = (h << 1) + G[byte]`` in 32 bits, so the hash before
  position ``p`` is a function of bytes ``[p-32, p)`` alone;
- ``p`` (32 <= p <= len) is a candidate where ``h & mask == 0``; the mask has
  ``mask_bits`` bits spread from bit 31 downwards every ``32 // mask_bits``;
- a chunk starting at ``s`` ends at the first candidate in
  ``[s + min_chunk, min(s + max_chunk, len)]``, else at that upper end.

The hash is computed by window doubling, ``S_2w(p) = S_w(p) + (S_w(p-w) << w)``,
in numpy, fingerprints by ``hashlib`` and LZ4 blocks by pyarrow's ``lz4_raw``
codec: three implementations that share no line with the code under test.
"""

from __future__ import annotations

import bisect
import hashlib

import numpy as np

_PIECE = 1 << 20   # bytes hashed per numpy pass: the buffers stay in cache


def gear_table() -> np.ndarray:
    z = (np.arange(256, dtype=np.uint64) * 0x9E3779B1) & 0xFFFFFFFF
    z ^= z >> 16
    z = (z * 0x85EBCA6B) & 0xFFFFFFFF
    z ^= z >> 13
    z = (z * 0xC2B2AE35) & 0xFFFFFFFF
    z ^= z >> 16
    return z.astype(np.uint32)


def spread_mask(mask_bits: int) -> int:
    mask, step, pos = 0, 32 // max(mask_bits, 1), 31
    for _ in range(mask_bits):
        mask |= 1 << pos
        pos -= step
        if pos < 0:
            pos = 30
    return mask & 0xFFFFFFFF


def candidates(buf: np.ndarray, mask: int) -> np.ndarray:
    """Every cut candidate ``p`` of ``buf`` (uint8), ascending."""
    table, n, out = gear_table(), buf.size, []
    m = np.uint32(mask)
    # three buffers reused by every piece: fresh pages are slow to touch
    a = np.empty(_PIECE + 31, np.uint32)
    b = np.empty_like(a)
    tmp = np.empty_like(a)
    for lo in range(0, n, _PIECE):
        hi = min(lo + _PIECE, n)
        start = max(lo - 31, 0)            # 31 bytes of history for the window
        k = hi - start
        s, t = a[:k], b[:k]
        np.take(table, buf[start:hi], out=s)          # S_1 at each byte
        w = 1
        while w < 32:
            np.left_shift(s[:k - w], np.uint32(w), out=tmp[:k - w])
            np.add(s[w:], tmp[:k - w], out=t[w:])
            t[:w] = s[:w]
            s, t, w = t, s, 2 * w
        # s[i] is the hash of the 32 bytes ending at byte start+i (fewer at
        # the very start of the buffer, where no candidate is allowed)
        first = max(lo, 31) - start        # byte index of the first p-1 owned
        np.bitwise_and(s[first:], m, out=tmp[:k - first])
        hit = np.flatnonzero(tmp[:k - first] == 0)
        out.append(hit + (start + first + 1))
    return np.concatenate(out) if out else np.empty(0, np.int64)


def cuts(buf: np.ndarray, cdc: dict) -> list[int]:
    """Exclusive chunk ends covering ``[0, len(buf)]``."""
    cand = candidates(buf, spread_mask(cdc["mask_bits"])).tolist()
    lo_min, hi_max, n = max(cdc["min_chunk"], 1), cdc["max_chunk"], buf.size
    out, start = [], 0
    while start < n:
        hi = min(start + hi_max, n)
        i = bisect.bisect_left(cand, start + lo_min)
        cut = cand[i] if i < len(cand) and cand[i] <= hi else hi
        out.append(cut)
        start = cut
    return out


def chunk_table(buf: np.ndarray, cdc: dict, block: int) -> tuple[dict, int]:
    """``{sha256 digest: chunk length}`` of ``buf`` chunked block by block (a
    block is chunked alone, as a DataNode sees it), and the chunk count."""
    table: dict[bytes, int] = {}
    total = 0
    view = memoryview(np.ascontiguousarray(buf))
    for off in range(0, buf.size, block):
        ends = cuts(buf[off:off + block], cdc)
        total += len(ends)
        start = 0
        for end in ends:
            table.setdefault(
                hashlib.sha256(view[off + start:off + end]).digest(),
                end - start)
            start = end
    return table, total


def chunk_tables(bufs, cdc: dict, block: int) -> tuple[dict, int]:
    """:func:`chunk_table` over several files' bytes, merged."""
    table: dict[bytes, int] = {}
    total = 0
    for buf in bufs:
        t, n = chunk_table(np.frombuffer(buf, np.uint8), cdc, block)
        total += n
        for d, ln in t.items():
            table.setdefault(d, ln)
    return table, total


def lz4_block_decode(payload: bytes, usize: int) -> bytes:
    import pyarrow as pa

    out = pa.Codec("lz4_raw").decompress(payload, decompressed_size=usize)
    return out.to_pybytes()
