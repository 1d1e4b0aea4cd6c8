"""Shared block-codec helpers (one place for codec name -> implementation).

Used by both the container store's seal stage (the reference's LZ4-on-rollover,
DataDeduplicator.java:770-781) and the compress-only reduction schemes (the
reference's stream-codec modes, BlockReceiver.java:822-866).
"""

from __future__ import annotations

import zlib

CODEC_IDS = {"none": 0, "lz4": 1, "zstd": 2, "gzip": 3, "snappy": 4}
CODEC_NAMES = {v: k for k, v in CODEC_IDS.items()}


def compress(codec: str, data: bytes) -> bytes:
    if codec == "lz4":
        from hdrf_tpu import native

        return native.lz4_compress(data)
    if codec == "zstd":
        import zstandard

        return zstandard.ZstdCompressor(level=1).compress(data)
    if codec == "gzip":
        return zlib.compress(data, 1)
    if codec == "snappy":
        import snappy  # optional dep (the reference's mode 0; absent -> gated)

        return snappy.compress(data)
    if codec == "none":
        return data
    raise KeyError(f"unknown codec {codec!r}")


def decompress_into(codec: str, data, usize: int, out):
    """``decompress`` into ``out`` (a C-contiguous uint8 array of at least
    ``usize``); returns ``out[:usize]``.  LZ4 — the container codec the
    read path meets — lands there directly; the rest decode as they do and
    are copied in."""
    if codec == "lz4":
        from hdrf_tpu import native

        return native.lz4_decompress(data, usize, out=out)
    import numpy as np

    plain = np.frombuffer(decompress(codec, data, usize), np.uint8)
    if plain.size != usize:
        raise RuntimeError(f"{codec} decompression: got {plain.size}, "
                           f"want {usize}")
    out[:usize] = plain
    return out[:usize]


def decompress(codec: str, data: bytes, usize: int) -> bytes:
    if codec == "lz4":
        from hdrf_tpu import native

        return native.lz4_decompress(data, usize)
    if codec == "zstd":
        import zstandard

        return zstandard.ZstdDecompressor().decompress(data, max_output_size=usize)
    if codec == "gzip":
        return zlib.decompress(data)
    if codec == "snappy":
        import snappy

        return snappy.decompress(data)
    if codec == "none":
        return data
    raise KeyError(f"unknown codec {codec!r}")


def available(codec: str) -> bool:
    try:
        compress(codec, b"x")
        return True
    except (ImportError, KeyError):
        return False
