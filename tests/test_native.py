"""Tests for libhdrf_native.so: SHA-256, Gear-CDC, LZ4 block codec, CRC32C.

Cross-implementation oracles: hashlib for SHA-256, a pure-Python LZ4 block
decoder for format conformance, numpy recomputation for gear candidates, and
fused-vs-two-phase CDC equivalence.
"""

import hashlib
import os
import zlib

import numpy as np
import pytest

from hdrf_tpu import native

RNG = np.random.default_rng(7)


def lz4_decompress_pyref(src: bytes) -> bytes:
    """Pure-Python LZ4 block decoder — format conformance oracle."""
    out = bytearray()
    i = 0
    while i < len(src):
        token = src[i]; i += 1
        litlen = token >> 4
        if litlen == 15:
            while True:
                b = src[i]; i += 1
                litlen += b
                if b != 255:
                    break
        out += src[i:i + litlen]; i += litlen
        if i >= len(src):
            break
        offset = src[i] | (src[i + 1] << 8); i += 2
        matchlen = token & 0xF
        if matchlen == 15:
            while True:
                b = src[i]; i += 1
                matchlen += b
                if b != 255:
                    break
        matchlen += 4
        assert 0 < offset <= len(out)
        for _ in range(matchlen):
            out.append(out[-offset])
    return bytes(out)


def gear_hash_pyref(data: np.ndarray, table: np.ndarray) -> np.ndarray:
    """Rolling gear hash value after each byte, h = (h<<1) + G[b] (mod 2^32)."""
    h = np.uint64(0)
    out = np.empty(len(data), dtype=np.uint32)
    g = table.astype(np.uint64)
    for i, b in enumerate(data):
        h = ((h << np.uint64(1)) + g[b]) & np.uint64(0xFFFFFFFF)
        out[i] = h
    return out


# ------------------------------------------------------------------ SHA-256

def test_sha256_vs_hashlib():
    for n in [0, 1, 55, 56, 63, 64, 65, 1000, 1 << 16]:
        data = RNG.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert native.sha256(data) == hashlib.sha256(data).digest(), n


def test_sha256_batch():
    data = RNG.integers(0, 256, 1 << 16, dtype=np.uint8)
    offs = np.array([0, 100, 5000, 65535], dtype=np.uint64)
    lens = np.array([100, 4900, 60000, 1], dtype=np.uint64)
    got = native.sha256_batch(data, offs, lens)
    for i in range(len(offs)):
        want = hashlib.sha256(data[int(offs[i]):int(offs[i]) + int(lens[i])].tobytes()).digest()
        assert got[i].tobytes() == want


# ------------------------------------------------------------------ CDC

def test_gear_candidates_vs_pyref():
    data = RNG.integers(0, 256, 4096, dtype=np.uint8)
    table = native.gear_table()
    mask = 0xFF000000  # 8 bits -> ~16 candidates in 4 KiB
    hashes = gear_hash_pyref(data, table)
    want = [p + 1 for p in range(len(data)) if p + 1 >= 32 and (hashes[p] & mask) == 0]
    got = native.gear_candidates(data, mask).tolist()
    assert got == want


def test_cdc_fused_equals_two_phase():
    mask = 0xFFF00000 >> 8  # 12 effective bits
    for n in [0, 10, 100, 5000, 1 << 18]:
        data = RNG.integers(0, 256, n, dtype=np.uint8)
        cand = native.gear_candidates(data, mask)
        cuts_a = native.cdc_select(cand, n, 512, 8192).tolist()
        cuts_b = native.cdc_chunk(data, mask, 512, 8192).tolist()
        assert cuts_a == cuts_b, (n, cuts_a[:5], cuts_b[:5])


def test_cdc_chunk_invariants():
    data = RNG.integers(0, 256, 1 << 18, dtype=np.uint8)
    min_c, max_c = 512, 8192
    cuts = native.cdc_chunk(data, 0x3FF, min_c, max_c)
    assert cuts[-1] == len(data)
    sizes = np.diff(np.concatenate([[0], cuts]))
    assert (sizes <= max_c).all()
    assert (sizes[:-1] >= min_c).all()  # final chunk may be short


def test_cdc_content_defined_shift_invariance():
    """Inserting bytes at the front only perturbs boundaries near the edit."""
    data = RNG.integers(0, 256, 1 << 17, dtype=np.uint8)
    shifted = np.concatenate([RNG.integers(0, 256, 97, dtype=np.uint8), data])
    cuts_a = set(native.cdc_chunk(data, 0x1FFF, 2048, 65536).tolist())
    cuts_b = {c - 97 for c in native.cdc_chunk(shifted, 0x1FFF, 2048, 65536).tolist()}
    # The tail boundaries must re-align despite the insertion.
    tail_a = {c for c in cuts_a if c > (1 << 16)}
    assert len(tail_a & cuts_b) / max(len(tail_a), 1) > 0.8


def test_cdc_empty_and_tiny():
    assert native.cdc_chunk(b"", 0xFF, 64, 1024).tolist() == []
    assert native.cdc_chunk(b"x" * 10, 0xFF, 64, 1024).tolist() == [10]
    assert native.cdc_chunk(b"x" * 2000, 0xFF, 64, 1024).tolist() == [1024, 2000]


# ------------------------------------------------------------------ LZ4

@pytest.mark.parametrize("kind", ["random", "zeros", "text", "repeats", "tiny", "empty"])
def test_lz4_roundtrip(kind):
    if kind == "random":
        data = RNG.integers(0, 256, 1 << 16, dtype=np.uint8).tobytes()
    elif kind == "zeros":
        data = b"\x00" * (1 << 16)
    elif kind == "text":
        data = (b"the quick brown fox jumps over the lazy dog. " * 2000)
    elif kind == "repeats":
        data = bytes(range(256)) * 300
    elif kind == "tiny":
        data = b"abc"
    else:
        data = b""
    comp = native.lz4_compress(data)
    assert native.lz4_decompress(comp, len(data)) == data
    if data:
        assert lz4_decompress_pyref(comp) == data  # format conformance
    if kind in ("zeros", "text", "repeats"):
        assert len(comp) < len(data) // 3


def test_lz4_compresses_zeros_hard():
    data = b"\x00" * (1 << 20)
    comp = native.lz4_compress(data)
    assert len(comp) < 5000


def test_lz4_rejects_garbage():
    with pytest.raises(RuntimeError):
        native.lz4_decompress(b"\xff\xff\xff\xff\x00", 100)


# ------------------------------------------------------------------ CRC32C

def test_crc32c_known_vectors():
    # RFC 3720 test vector: 32 bytes of zeros -> 0x8A9136AA
    assert native.crc32c(b"\x00" * 32) == 0x8A9136AA
    assert native.crc32c(b"123456789") == 0xE3069283


def test_crc32c_chunks():
    data = RNG.integers(0, 256, 2000, dtype=np.uint8)
    out = native.crc32c_chunks(data, 512)
    assert len(out) == 4
    for i in range(4):
        assert out[i] == native.crc32c(data[i * 512:(i + 1) * 512])


def test_crc32c_incremental():
    data = os.urandom(1000)
    c1 = native.crc32c(data)
    # zlib.crc32 is CRC32 (IEEE), not CRC32C — just ensure ours differs from a
    # wrong-poly implementation and is stable.
    assert c1 == native.crc32c(data)
    assert c1 != zlib.crc32(data)


# hdrf_crc32c (the dispatching entry: the CPU's instruction over three
# interleaved streams, blocks of 3 x 8 KiB then 3 x 256 B, the rest one
# stream) against hdrf_crc32c_table (the slice-by-8 loop it replaced), bit
# for bit.  On a CPU without the instruction both are the table loop and
# every case still passes.
_CRC_LONG, _CRC_SHORT = 3 * 8192, 3 * 256
_CRC_DATA = np.random.default_rng(36).integers(
    0, 256, (4 << 20) + 64, dtype=np.uint8)


def _crc_same(a, crc=0, want=None):
    got = native.crc32c(a, crc)
    assert got == native.crc32c_table(a, crc)
    assert want is None or got == want
    return got


def _crc_parts(parts):
    """A buffer summed in ``parts`` pieces of unequal length, each piece's
    sum carried into the next, equals the whole — by either routine."""
    a = _CRC_DATA[3:3 + 2 * _CRC_LONG + _CRC_SHORT + 13]
    edges = [a.size * k * k // (parts * parts) for k in range(parts + 1)]
    crc = 0
    for lo, hi in zip(edges, edges[1:]):
        crc = _crc_same(a[lo:hi], crc)
    assert crc == _crc_same(a)


def _crc_chunks(chunk):
    a = _CRC_DATA[5:5 + 3 * chunk + chunk // 3 + 1]    # a short last chunk
    out = native.crc32c_chunks(a, chunk)
    assert out.tolist() == [native.crc32c_table(a[o:o + chunk])
                            for o in range(0, a.size, chunk)]
    assert len(out) == 4


_CRC_LENGTHS = [*range(65),
                *(b + d for b in (_CRC_SHORT, 2 * _CRC_SHORT, _CRC_LONG,
                                  _CRC_LONG + _CRC_SHORT, 2 * _CRC_LONG)
                  for d in (-9, -1, 0, 1, 9)),
                64 << 10, 1 << 20, (4 << 20) + 17]
_CRC_CASES = {f"len-{n}": (lambda n=n: _crc_same(_CRC_DATA[:n]))
              for n in _CRC_LENGTHS}
_CRC_CASES.update({
    f"start-{o}": (lambda o=o: [
        _crc_same(_CRC_DATA[o:o + n])
        for n in (5, 64, _CRC_SHORT + 3, _CRC_LONG + _CRC_SHORT + 11,
                  64 << 10)])
    for o in range(1, 8)})
_CRC_CASES.update({
    "parts-2": lambda: _crc_parts(2),
    "parts-5": lambda: _crc_parts(5),
    "chunks-64KiB": lambda: _crc_chunks(64 << 10),
    "chunks-1MiB": lambda: _crc_chunks(1 << 20),
    # RFC 3720 B.4: 32 bytes of zeros, of ones, ascending, descending
    "vector-zeros": lambda: _crc_same(bytes(32), want=0x8A9136AA),
    "vector-ones": lambda: _crc_same(b"\xff" * 32, want=0x62A8AB43),
    "vector-ascending": lambda: _crc_same(bytes(range(32)), want=0x46DD794E),
    "vector-descending":
        lambda: _crc_same(bytes(range(31, -1, -1)), want=0x113FDB5C),
    "vector-digits": lambda: _crc_same(b"123456789", want=0xE3069283),
})


@pytest.mark.parametrize("case", list(_CRC_CASES))
def test_crc32c_equals_the_table_loop(case):
    _CRC_CASES[case]()


def test_crc32c_backend_is_what_the_cpu_reports():
    backend = native.crc32c_backend()
    assert backend in ("sse42x3", "table")
    assert native.crc32c_hw() == (backend == "sse42x3")
    try:
        with open("/proc/cpuinfo") as f:
            flags = [ln.split(":", 1)[1].split() for ln in f
                     if ln.startswith("flags")]
    except OSError:
        return
    if flags:       # x86 lists its flags; another target runs the table loop
        assert backend == ("sse42x3" if "sse4_2" in flags[0] else "table")
    else:
        assert backend == "table"


def test_gear_candidates_dense_mask_no_truncation():
    """mask=0 makes every position>=32 a candidate; wrapper must not truncate."""
    data = RNG.integers(0, 256, 1 << 14, dtype=np.uint8)
    cand = native.gear_candidates(data, 0x0)
    assert len(cand) == (1 << 14) - 31
    assert cand[0] == 32 and cand[-1] == 1 << 14


def test_sha256_batch_bounds_check():
    data = RNG.integers(0, 256, 100, dtype=np.uint8)
    with pytest.raises(ValueError):
        native.sha256_batch(data, np.array([90], dtype=np.uint64),
                            np.array([20], dtype=np.uint64))


def _ranges(kind: str, size: int):
    """(starts, lens) over a buffer of ``size`` bytes."""
    if kind == "contiguous":        # every range starts where the last ended
        cuts = np.sort(RNG.choice(np.arange(1, size), 40, replace=False))
        edges = np.concatenate([[0], cuts, [size]])
        return edges[:-1], np.diff(edges)
    if kind == "fragmented":        # gaps, and some ranges out of order
        starts = RNG.permutation(np.arange(0, size - 64, 97))[:50]
        return starts, RNG.integers(0, 64, starts.size)
    if kind == "runs":              # runs of adjacent ranges between gaps
        starts, lens, at = [], [], 0
        while at + 200 < size:
            for ln in RNG.integers(1, 40, RNG.integers(1, 5)):
                starts.append(at)
                lens.append(int(ln))
                at += int(ln)
            at += int(RNG.integers(1, 30))
        return np.array(starts), np.array(lens)
    assert kind == "empty"
    return np.array([], np.uint64), np.array([], np.uint64)


@pytest.mark.parametrize("dest", ["exact", "roomy", "bytearray", "slice"])
@pytest.mark.parametrize("kind", ["contiguous", "fragmented", "runs", "empty"])
def test_gather_ranges_vs_numpy(kind, dest):
    data = RNG.integers(0, 256, 5000, dtype=np.uint8)
    starts, lens = _ranges(kind, data.size)
    want = b"".join(data[int(s):int(s) + int(n)].tobytes()
                    for s, n in zip(starts, lens))
    # "slice": the store's shape, a destination part-way into a larger buffer
    before, room = {"exact": (0, 0), "roomy": (0, 333), "bytearray": (0, 7),
                    "slice": (129, 64)}[dest]
    whole = (bytearray(b"\xee" * (len(want) + room)) if dest == "bytearray"
             else np.full(before + len(want) + room, 0xEE, np.uint8))
    out = whole if dest == "bytearray" else whole[before:]  # a view
    got = native.gather_ranges(data, starts, lens, out=out)
    # filled in place, and not a byte before it or past the ranges' total
    assert bytes(whole[:before]) == b"\xee" * before
    assert bytes(out[:len(want)]) == want
    assert bytes(out[len(want):]) == b"\xee" * room
    assert np.shares_memory(got, np.frombuffer(whole, np.uint8)) or not want
    assert got.tobytes() == want


def test_gather_ranges_checks_its_destination():
    data = RNG.integers(0, 256, 1000, dtype=np.uint8)
    starts, lens = [0, 500], [100, 200]
    with pytest.raises(ValueError, match="smaller"):
        native.gather_ranges(data, starts, lens, out=np.empty(299, np.uint8))
    with pytest.raises(ValueError, match="read-only"):
        native.gather_ranges(data, starts, lens, out=bytes(300))
    with pytest.raises(ValueError):             # not C-contiguous
        native.gather_ranges(data, starts, lens,
                             out=np.empty(600, np.uint8)[::2])
    with pytest.raises(ValueError, match="exceeds"):
        native.gather_ranges(data, [900], [101], out=np.empty(200, np.uint8))
