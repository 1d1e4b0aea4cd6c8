"""ctypes bindings to libhdrf_native.so.

The native library plays the role of the reference's native layer:
libnayuki-native-hashes.so (JNI SHA, utilities.java:98-137), JNI codec backends
(snappy-java / hadoop-lzo), and the hot CDC scan loop
(DataDeduplicator.chunking(), DataDeduplicator.java:264-307).

Built on demand from ``src/*.cpp`` with g++ if the .so is missing or stale —
the moral equivalent of the reference installing its prebuilt jar from
``hadoop-hdfs/pom.xml:245-255``, but from source.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libhdrf_native.so")

_lib: ctypes.CDLL | None = None
_lock = threading.Lock()

_u8p = ctypes.POINTER(ctypes.c_uint8)
_i32p = ctypes.POINTER(ctypes.c_int32)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_u64p = ctypes.POINTER(ctypes.c_uint64)


def _build() -> None:
    subprocess.run(["make", "-s", "-C", _DIR], check=True,
                   capture_output=True, text=True)


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        srcs = [os.path.join(_DIR, "src", f) for f in os.listdir(os.path.join(_DIR, "src"))
                if f.endswith(".cpp")]
        if not os.path.exists(_SO) or any(os.path.getmtime(s) > os.path.getmtime(_SO)
                                          for s in srcs):
            _build()
        lib = ctypes.CDLL(_SO)

        lib.hdrf_sha256.argtypes = [_u8p, ctypes.c_uint64, _u8p]
        lib.hdrf_sha256_batch.argtypes = [_u8p, _u64p, _u64p, ctypes.c_uint64, _u8p]
        lib.hdrf_gear_table.argtypes = [_u32p]
        lib.hdrf_gear_candidates.argtypes = [_u8p, ctypes.c_uint64, ctypes.c_uint32,
                                             _u64p, ctypes.c_uint64]
        lib.hdrf_gear_candidates.restype = ctypes.c_uint64
        lib.hdrf_cdc_select.argtypes = [_u64p, ctypes.c_uint64, ctypes.c_uint64,
                                        ctypes.c_uint64, ctypes.c_uint64, _u64p,
                                        ctypes.c_uint64]
        lib.hdrf_cdc_select.restype = ctypes.c_uint64
        lib.hdrf_cdc_chunk.argtypes = [_u8p, ctypes.c_uint64, ctypes.c_uint32,
                                       ctypes.c_uint64, ctypes.c_uint64, _u64p,
                                       ctypes.c_uint64]
        lib.hdrf_cdc_chunk.restype = ctypes.c_uint64
        lib.hdrf_lz4_compress_bound.argtypes = [ctypes.c_uint64]
        lib.hdrf_lz4_compress_bound.restype = ctypes.c_uint64
        lib.hdrf_lz4_compress.argtypes = [_u8p, ctypes.c_uint64, _u8p, ctypes.c_uint64]
        lib.hdrf_lz4_compress.restype = ctypes.c_uint64
        lib.hdrf_lz4_compress_tail.argtypes = [
            _u8p, ctypes.c_uint64, _u8p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
        lib.hdrf_lz4_compress_tail.restype = ctypes.c_uint64
        lib.hdrf_lz4_decompress.argtypes = [_u8p, ctypes.c_uint64, _u8p, ctypes.c_uint64]
        lib.hdrf_lz4_decompress.restype = ctypes.c_uint64
        lib.hdrf_lz4_unpack_records.argtypes = [
            _u32p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_uint64, _i32p, _u32p]
        lib.hdrf_lz4_unpack_records.restype = ctypes.c_uint64
        lib.hdrf_lz4_emit.argtypes = [_u8p, ctypes.c_uint64, _i32p, _u32p,
                                      ctypes.c_uint64, _u8p, ctypes.c_uint64]
        lib.hdrf_lz4_emit.restype = ctypes.c_uint64
        lib.hdrf_crc32c.argtypes = [ctypes.c_uint32, _u8p, ctypes.c_uint64]
        lib.hdrf_crc32c.restype = ctypes.c_uint32
        lib.hdrf_crc32c_table.argtypes = lib.hdrf_crc32c.argtypes
        lib.hdrf_crc32c_table.restype = ctypes.c_uint32
        lib.hdrf_crc32c_backend.restype = ctypes.c_char_p
        lib.hdrf_chacha20_xor.argtypes = [_u8p, _u8p, ctypes.c_uint32, _u8p,
                                          ctypes.c_uint64, _u8p]
        lib.hdrf_aead_seal.argtypes = [_u8p, _u8p, _u8p, ctypes.c_uint64,
                                       _u8p, ctypes.c_uint64, _u8p]
        lib.hdrf_aead_open.argtypes = [_u8p, _u8p, _u8p, ctypes.c_uint64,
                                       _u8p, ctypes.c_uint64, _u8p]
        lib.hdrf_aead_open.restype = ctypes.c_int
        lib.hdrf_crc32c_chunks.argtypes = [_u8p, ctypes.c_uint64, ctypes.c_uint64, _u32p]
        lib.hdrf_gather_ranges.argtypes = [_u8p, ctypes.c_uint64, _u64p,
                                           _u64p, _u8p]
        lib.hdrf_gather_ranges.restype = ctypes.c_uint64
        # addresses, not typed pointers: PacketUnpacker converts its arrays
        # once a stream and not once a call
        lib.hdrf_unpack_packets.argtypes = (
            [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p]
            + [ctypes.c_uint64] * 3 + [ctypes.c_void_p] * 5)
        lib.hdrf_unpack_packets.restype = ctypes.c_uint64
        _lib = lib
        return lib


def _as_u8(buf: bytes | bytearray | memoryview | np.ndarray) -> np.ndarray:
    if isinstance(buf, np.ndarray):
        if buf.dtype != np.uint8 or not buf.flags.c_contiguous:
            raise ValueError("expected C-contiguous uint8 array")
        return buf
    return np.frombuffer(buf, dtype=np.uint8)


def _ptr(a: np.ndarray, typ):  # noqa: ANN001
    return a.ctypes.data_as(typ)


# ---------------------------------------------------------------- public API


def sha256(data: bytes | np.ndarray) -> bytes:
    a = _as_u8(data)
    out = np.empty(32, dtype=np.uint8)
    _load().hdrf_sha256(_ptr(a, _u8p), a.size, _ptr(out, _u8p))
    return out.tobytes()


def sha256_batch(data: bytes | np.ndarray, offsets: np.ndarray,
                 lengths: np.ndarray) -> np.ndarray:
    """Hash n sub-ranges of `data`; returns (n, 32) uint8 digests."""
    a = _as_u8(data)
    offs = np.ascontiguousarray(offsets, dtype=np.uint64)
    lens = np.ascontiguousarray(lengths, dtype=np.uint64)
    if offs.shape != lens.shape:
        raise ValueError("offsets/lengths shape mismatch")
    if offs.size and int((offs + lens).max()) > a.size:
        raise ValueError("chunk range exceeds data buffer")
    n = offs.size
    out = np.empty((n, 32), dtype=np.uint8)
    _load().hdrf_sha256_batch(_ptr(a, _u8p), _ptr(offs, _u64p), _ptr(lens, _u64p),
                              n, _ptr(out, _u8p))
    return out


def gear_table() -> np.ndarray:
    out = np.empty(256, dtype=np.uint32)
    _load().hdrf_gear_table(_ptr(out, _u32p))
    return out


def gear_candidates(data: bytes | np.ndarray, mask: int) -> np.ndarray:
    a = _as_u8(data)
    cap = max(a.size // 8, 1024)
    out = np.empty(cap, dtype=np.uint64)
    n = _load().hdrf_gear_candidates(_ptr(a, _u8p), a.size, mask & 0xFFFFFFFF,
                                     _ptr(out, _u64p), cap)
    if n > cap:  # dense-candidate mask (few effective bits): retry exact-sized
        out = np.empty(n, dtype=np.uint64)
        n = _load().hdrf_gear_candidates(_ptr(a, _u8p), a.size, mask & 0xFFFFFFFF,
                                         _ptr(out, _u64p), n)
    return out[:n].copy()


def cdc_select(candidates: np.ndarray, length: int, min_chunk: int,
               max_chunk: int) -> np.ndarray:
    cand = np.ascontiguousarray(candidates, dtype=np.uint64)
    cap = length // max(min_chunk, 1) + 2
    out = np.empty(cap, dtype=np.uint64)
    n = _load().hdrf_cdc_select(_ptr(cand, _u64p), cand.size, length, min_chunk,
                                max_chunk, _ptr(out, _u64p), cap)
    return out[:n].copy()


def cdc_chunk(data: bytes | np.ndarray, mask: int, min_chunk: int,
              max_chunk: int) -> np.ndarray:
    """Sequential CPU chunker: cut-points (exclusive ends) for the whole buffer."""
    a = _as_u8(data)
    cap = a.size // max(min_chunk, 1) + 2
    out = np.empty(cap, dtype=np.uint64)
    n = _load().hdrf_cdc_chunk(_ptr(a, _u8p), a.size, mask & 0xFFFFFFFF, min_chunk,
                               max_chunk, _ptr(out, _u64p), cap)
    return out[:n].copy()


def lz4_compress(data: bytes | np.ndarray) -> bytes:
    a = _as_u8(data)
    if a.size == 0:
        return b""
    cap = _load().hdrf_lz4_compress_bound(a.size)
    out = np.empty(cap, dtype=np.uint8)
    n = _load().hdrf_lz4_compress(_ptr(a, _u8p), a.size, _ptr(out, _u8p), cap)
    if n == 0:
        raise RuntimeError("lz4 compression failed")
    return out[:n].tobytes()


def lz4_compress_tail(data: bytes | np.ndarray) -> tuple[bytes, int, int]:
    """lz4_compress plus (tail_token_offset, tail_literal_count) of the
    stream's final literals-only sequence — what the parallel segmented
    compressor's stitcher needs (ops/lz4_tpu.lz4_stitch)."""
    a = _as_u8(data)
    if a.size == 0:
        return b"", 0, 0
    cap = _load().hdrf_lz4_compress_bound(a.size)
    out = np.empty(cap, dtype=np.uint8)
    toff = ctypes.c_uint64()
    tlit = ctypes.c_uint64()
    n = _load().hdrf_lz4_compress_tail(_ptr(a, _u8p), a.size, _ptr(out, _u8p),
                                       cap, ctypes.byref(toff),
                                       ctypes.byref(tlit))
    if n == 0:
        raise RuntimeError("lz4 compression failed")
    return out[:n].tobytes(), toff.value, tlit.value


def lz4_emit(data: bytes | np.ndarray, positions: np.ndarray,
             delta_len: np.ndarray) -> bytes:
    """Greedy-parse + serialize an LZ4 block from externally discovered match
    records (the host half of the TPU LZ4 path; see hdrf_lz4_emit).  Records
    are (position, (offset << 16) | est_len), sorted by position."""
    a = _as_u8(data)
    if a.size == 0:
        return b""
    ps = np.ascontiguousarray(positions, dtype=np.int32)
    dl = np.ascontiguousarray(delta_len, dtype=np.uint32)
    if ps.shape != dl.shape:
        raise ValueError("positions/delta_len shape mismatch")
    cap = _load().hdrf_lz4_compress_bound(a.size)
    out = np.empty(cap, dtype=np.uint8)
    n = _load().hdrf_lz4_emit(_ptr(a, _u8p), a.size, _ptr(ps, _i32p),
                              _ptr(dl, _u32p), ps.size, _ptr(out, _u8p), cap)
    if n == 0:
        raise RuntimeError("lz4 emit failed")
    return out[:n].tobytes()


def lz4_unpack_records(row: np.ndarray, p3: int, nv: int, stride: int,
                       esc_slots: int):
    """Decode the packed device record readback (see hdrf_lz4_unpack_records
    and the ops/lz4_tpu._match_scan_impl layout docstring) into the
    (positions, (offset << 16) | len) arrays lz4_emit consumes.  ``row`` is
    the u32 body AFTER the 4-word header.  Returns (pos i32[nrec],
    dl u32[nrec], nrec); nrec < nv means an escape lane overflowed on
    device and the tail was not decodable."""
    r = np.ascontiguousarray(row, dtype=np.uint32)
    if r.size < p3 + p3 // 4 + 2 * esc_slots:
        raise ValueError("packed record row too short")
    if not 0 <= nv <= p3:
        raise ValueError("invalid record count")
    pos = np.empty(nv, dtype=np.int32)
    dl = np.empty(nv, dtype=np.uint32)
    nrec = _load().hdrf_lz4_unpack_records(
        _ptr(r, _u32p), p3, nv, stride, esc_slots,
        _ptr(pos, _i32p), _ptr(dl, _u32p))
    return pos[:nrec], dl[:nrec], int(nrec)


def lz4_decompress(data: bytes | np.ndarray, decompressed_size: int,
                   out=None) -> bytes | np.ndarray:
    """``out`` (a writable bytes-like or C-contiguous uint8 array of at
    least ``decompressed_size``: a decoded container's buffer, kept and
    reused by its owner) receives the bytes where it lies and the filled
    part of it is returned; without it, fresh ``bytes`` — one more copy of
    the whole output, made with the interpreter held."""
    a = _as_u8(data)
    if decompressed_size == 0:
        return b"" if out is None else _as_u8(out)[:0]
    dst = (np.empty(decompressed_size, dtype=np.uint8) if out is None
           else _as_u8(out))
    if not dst.flags.writeable:
        raise ValueError("destination is read-only")
    if dst.size < decompressed_size:
        raise ValueError("destination smaller than the decompressed size")
    n = _load().hdrf_lz4_decompress(_ptr(a, _u8p), a.size, _ptr(dst, _u8p),
                                    decompressed_size)
    if n != decompressed_size:
        raise RuntimeError(f"lz4 decompression failed: got {n}, want {decompressed_size}")
    return dst.tobytes() if out is None else dst[:decompressed_size]


def chacha20_xor(key: bytes, nonce: bytes, data: bytes | np.ndarray,
                 counter: int = 1) -> bytes:
    """Raw ChaCha20 keystream XOR (RFC 8439)."""
    assert len(key) == 32 and len(nonce) == 12
    a = _as_u8(data)
    out = np.empty(a.size, dtype=np.uint8)
    _load().hdrf_chacha20_xor(_ptr(np.frombuffer(key, np.uint8), _u8p),
                              _ptr(np.frombuffer(nonce, np.uint8), _u8p),
                              counter, _ptr(a, _u8p), a.size, _ptr(out, _u8p))
    return out.tobytes()


def aead_seal(key: bytes, nonce: bytes, aad: bytes,
              plaintext: bytes | np.ndarray) -> bytes:
    """ChaCha20-Poly1305 seal: ciphertext || 16-byte tag."""
    assert len(key) == 32 and len(nonce) == 12
    a = _as_u8(plaintext)
    ad = np.frombuffer(aad, np.uint8) if aad else np.empty(0, np.uint8)
    out = np.empty(a.size + 16, dtype=np.uint8)
    _load().hdrf_aead_seal(_ptr(np.frombuffer(key, np.uint8), _u8p),
                           _ptr(np.frombuffer(nonce, np.uint8), _u8p),
                           _ptr(ad, _u8p), ad.size, _ptr(a, _u8p), a.size,
                           _ptr(out, _u8p))
    return out.tobytes()


def aead_open(key: bytes, nonce: bytes, aad: bytes,
              sealed: bytes | np.ndarray) -> bytes | None:
    """ChaCha20-Poly1305 open; None if authentication fails."""
    assert len(key) == 32 and len(nonce) == 12
    a = _as_u8(sealed)
    if a.size < 16:
        return None
    ad = np.frombuffer(aad, np.uint8) if aad else np.empty(0, np.uint8)
    out = np.empty(a.size - 16, dtype=np.uint8)
    ok = _load().hdrf_aead_open(_ptr(np.frombuffer(key, np.uint8), _u8p),
                                _ptr(np.frombuffer(nonce, np.uint8), _u8p),
                                _ptr(ad, _u8p), ad.size, _ptr(a, _u8p),
                                a.size - 16, _ptr(out, _u8p))
    return out.tobytes() if ok else None


def crc32c(data: bytes | np.ndarray, crc: int = 0) -> int:
    a = _as_u8(data)
    return _load().hdrf_crc32c(crc & 0xFFFFFFFF, _ptr(a, _u8p), a.size)


def crc32c_table(data: bytes | np.ndarray, crc: int = 0) -> int:
    """The slice-by-8 table loop: what ``crc32c`` runs where the CPU lacks
    the instruction, and the oracle the tests hold ``crc32c`` to."""
    a = _as_u8(data)
    return _load().hdrf_crc32c_table(crc & 0xFFFFFFFF, _ptr(a, _u8p), a.size)


def crc32c_backend() -> str:
    """The routine ``crc32c`` (and with it ``crc32c_chunks`` and the packet
    unpacker's verify) runs in this process, chosen once at load from what
    the CPU reports: ``"sse42x3"`` (the CRC32C instruction, three
    interleaved streams) or ``"table"``."""
    return _load().hdrf_crc32c_backend().decode()


def crc32c_hw() -> int:
    """1 / 0: the value of gauge ``crc32c_hw`` (registry ``native``), which
    the DataNode and the worker set once at start."""
    return int(crc32c_backend() != "table")


def crc32c_chunks(data: bytes | np.ndarray, chunk_size: int) -> np.ndarray:
    a = _as_u8(data)
    n = (a.size + chunk_size - 1) // chunk_size
    out = np.empty(max(n, 1), dtype=np.uint32)
    _load().hdrf_crc32c_chunks(_ptr(a, _u8p), a.size, chunk_size, _ptr(out, _u32p))
    return out[:n]


class PacketUnpacker:
    """``hdrf_unpack_packets`` with the arrays it reads and fills held: the
    staged wire bytes (``stage``) and, per unpacked packet, ``seqnos``,
    ``lens``, ``flags`` and ``crcs``.  One call walks the whole
    data-transfer packets in ``stage[:have]``, verifies each payload's
    CRC32C against its header's and copies it to ``out`` at ``out_off``."""

    PARTIAL, LAST, MISMATCH, OUT_FULL, MORE = range(5)

    def __init__(self, stage_bytes: int, max_pkts: int = 1024):
        self.max_pkts = max_pkts
        self.seqnos = np.empty(max_pkts, np.uint64)
        self.lens = np.empty(max_pkts, np.uint32)
        self.flags = np.empty(max_pkts, np.uint8)
        self.crcs = np.empty(max_pkts, np.uint32)
        self._ret = np.zeros(3, np.uint64)
        self._fixed = tuple(a.ctypes.data for a in (
            self.seqnos, self.lens, self.flags, self.crcs, self._ret))
        self._fn = _load().hdrf_unpack_packets
        self._out = None
        self.grow_stage(stage_bytes)

    def grow_stage(self, nbytes: int, keep: int = 0) -> None:
        """A stage of ``nbytes``, its first ``keep`` bytes carried over."""
        stage = np.empty(nbytes, np.uint8)
        if keep:
            stage[:keep] = self.stage[:keep]
        self.stage, self._stage_addr = stage, stage.ctypes.data

    def __call__(self, have: int, out: np.ndarray,
                 out_off: int) -> tuple[int, int, int, int]:
        """Returns ``(n, used, need, why)``: packets unpacked, bytes of the
        stage consumed, bytes the next call needs staged once the rest has
        moved to the front (0: call again as it is), and one of the
        constants above.  At MISMATCH and OUT_FULL the header fields of the
        packet that stopped the run are at index ``n``."""
        if out is not self._out:
            self._out, self._out_addr = _as_u8(out), out.ctypes.data
        if have > self.stage.size or out_off > out.size:
            raise ValueError("staged bytes or offset beyond the buffer")
        n = self._fn(self._stage_addr, have, self._out_addr, out_off,
                     out.size, self.max_pkts, *self._fixed)
        used, need, why = self._ret.tolist()
        return n, used, need, why


def gather_ranges(data: bytes | np.ndarray, starts: np.ndarray,
                  lens: np.ndarray, out) -> np.ndarray:
    """Concatenate [start, start+len) ranges of ``data`` into ``out`` —
    the commit path's chunk-byte shuffle (threadedStorer's per-chunk
    ByteBuffer copies, DataDeduplicator.java:652-845) in one native pass.
    ``out`` (a writable bytes-like or C-contiguous uint8 array of at least
    the ranges' total: the open container's buffer) receives the bytes
    where it lies; the filled part of it is returned."""
    a = _as_u8(data)
    ss = np.ascontiguousarray(starts, dtype=np.uint64)
    ls = np.ascontiguousarray(lens, dtype=np.uint64)
    if ss.shape != ls.shape:
        raise ValueError("starts/lens shape mismatch")
    if ss.size and int((ss + ls).max()) > a.size:
        raise ValueError("range exceeds data buffer")
    total = int(ls.sum())
    out = _as_u8(out)
    if not out.flags.writeable:
        raise ValueError("destination is read-only")
    if out.size < total:
        raise ValueError("destination smaller than the ranges' total")
    _load().hdrf_gather_ranges(_ptr(a, _u8p), ss.size, _ptr(ss, _u64p),
                               _ptr(ls, _u64p), _ptr(out, _u8p))
    return out[:total]
