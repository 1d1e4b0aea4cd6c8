"""Backend dispatch for the reduction hot ops (CDC scan + fingerprinting).

The reference hardwires its hot loops: the CDC byte scan is a sequential Java
loop (DataDeduplicator.chunking(), DataDeduplicator.java:264-307) and hashing
goes through JNI to libnayuki (utilities.java:98-137).  Here both ops have two
interchangeable backends with identical outputs (asserted in tests/test_ops.py):

- ``native``: C++ via ctypes (hdrf_tpu/native) — the CPU baseline the >=4x
  BASELINE target is measured against, and the correctness oracle.
- ``tpu``:    JAX/XLA device programs (hdrf_tpu/ops/gear.py, sha256.py) — the
  all-position Gear candidate scan and lane-parallel SHA-256.

``auto`` resolves to ``tpu`` when an accelerator is attached.
"""

from __future__ import annotations

import threading

import numpy as np

from hdrf_tpu.config import CdcConfig
from hdrf_tpu.utils import metrics as _metrics
from hdrf_tpu.utils import profiler as _profiler

# Op-level accounting at the dispatch boundary (per-dispatch device
# accounting lives in utils/device_ledger.py, fed by the ops modules).
_M = _metrics.registry("ops_dispatch")


def resolve_backend(backend: str) -> str:
    """``auto`` -> ``tpu`` when a TPU is attached, else ``native``.  Asking
    initialises JAX's backend in this process, so only a process that may
    own the device asks (a DataNode that fronts a worker does not), and a
    chip that is present but cannot be had raises here rather than
    demoting the process to the host codec."""
    if backend != "auto":
        return backend
    import jax

    return "tpu" if jax.devices()[0].platform == "tpu" else "native"


def gear_mask(cdc: CdcConfig) -> int:
    """Boundary mask with ``mask_bits`` effective bits -> avg chunk 2^mask_bits.
    Bits are spread across the 32-bit hash (FastCDC observation: spread masks
    judge more of the window than low-contiguous ones)."""
    bits, mask, step = cdc.mask_bits, 0, 32 // max(cdc.mask_bits, 1)
    pos = 31
    for _ in range(bits):
        mask |= 1 << pos
        pos -= step
        if pos < 0:
            pos = 30
    return mask & 0xFFFFFFFF


def chunk_cuts(data: bytes | np.ndarray, cdc: CdcConfig,
               backend: str = "native") -> np.ndarray:
    """Exclusive chunk end offsets covering [0, len(data)]."""
    from hdrf_tpu import native

    mask = gear_mask(cdc)
    if backend == "tpu":
        from hdrf_tpu.ops import gear

        return gear.cdc_chunk_jax(data, mask, cdc.min_chunk, cdc.max_chunk)
    return native.cdc_chunk(data, mask, cdc.min_chunk, cdc.max_chunk)


def fingerprints(data: bytes | np.ndarray, cuts: np.ndarray,
                 backend: str = "native") -> np.ndarray:
    """(n_chunks, 32) SHA-256 digests of the chunks delimited by ``cuts``."""
    if backend == "tpu":
        from hdrf_tpu.ops import sha256 as sha_tpu

        return sha_tpu.fingerprint_chunks(data, cuts)
    from hdrf_tpu import native

    starts = np.concatenate([[0], cuts[:-1]]).astype(np.uint64)
    lens = (cuts - starts).astype(np.uint64)
    return native.sha256_batch(data, starts, lens)


_resident_cache: dict = {}
_mesh_cache: list = []
_mesh_plane_mesh_cache: list = []


def mesh_plane_enabled() -> bool:
    """Mesh-sharded batched lz4 seals (``block_compress_batch``):
    ``HDRF_MESH_PLANE=1``."""
    import os

    return os.environ.get("HDRF_MESH_PLANE", "") == "1"


def _mesh_plane_mesh():
    """Flat ('data'=n, 'seq'=1) mesh over every attached device — the
    block-data-parallel layout of the mesh reduction plane (one block per
    lane, fingerprint space partitioned over 'data').  None below 2 devices:
    the serial ResidentReducer is strictly better there."""
    if not _mesh_plane_mesh_cache:
        import jax

        from hdrf_tpu.parallel.sharded import make_mesh

        devs = jax.devices()
        _mesh_plane_mesh_cache.append(
            make_mesh(n_data=len(devs), n_seq=1, devices=devs)
            if len(devs) > 1 else None)
    return _mesh_plane_mesh_cache[0]


def _multichip_mesh():
    """The flat ('data'=1, 'seq'=n) mesh over every attached device, built
    once — the serving path's multi-chip form engages automatically when
    more than one device is present."""
    if not _mesh_cache:
        import jax

        from hdrf_tpu.parallel.sharded import make_mesh

        devs = jax.devices()
        _mesh_cache.append(make_mesh(n_data=1, n_seq=len(devs),
                                     devices=devs)
                           if len(devs) > 1 else None)
    return _mesh_cache[0]


def chunk_and_fingerprint(data: bytes | np.ndarray, cdc: CdcConfig,
                          backend: str = "native"):
    """(cuts, digests) in one call — THE entry point for the write path.

    On the TPU backend this routes through ops.resident.ResidentReducer so
    the block crosses to HBM once and the gather/SHA read the resident image
    (the naive chunk_cuts+fingerprints composition re-uploads the block per
    stage).  With MULTIPLE devices attached, the block instead runs the
    sharded pipeline (parallel/sharded.reduce_sharded): seq-parallel
    candidate scan with ICI halo exchange + chunk-parallel SHA lanes over
    every chip.  The native path is the CPU baseline pair of calls.
    """
    from hdrf_tpu.reduction import accounting

    nbytes = len(data) if isinstance(data, (bytes, bytearray)) else data.nbytes
    _M.incr(f"reduce_{backend}_total")
    _M.incr(f"reduce_{backend}_bytes", nbytes)
    # Effective-geometry gauges: under the adaptive controller the cdc
    # object mutates between calls, and this is the one funnel every
    # reduction passes through.
    accounting.note_geometry(cdc)
    if backend == "tpu":
        mesh = _multichip_mesh()
        if mesh is not None:
            from hdrf_tpu.parallel.sharded import reduce_sharded

            return reduce_sharded(data, cdc, mesh)
        from hdrf_tpu.ops.cdc_pallas import cdc_pallas_mode, cdc_skip_ahead
        from hdrf_tpu.ops.resident import ResidentReducer

        # The fused-CDC mode and scan variant are part of the key: a
        # reducer pins both at construction (jit-cache coherence), so
        # flipping HDRF_CDC_PALLAS / HDRF_CDC_SKIP_AHEAD mid-process — or
        # an adaptive-controller retune mutating ``cdc`` — must select a
        # different reducer, not mutate one.
        key = (cdc.mask_bits, cdc.min_chunk, cdc.max_chunk,
               cdc_pallas_mode(), cdc_skip_ahead())
        r = _resident_cache.get(key)
        if r is None:
            r = _resident_cache[key] = ResidentReducer(
                cdc, fused_mode=key[3], skip_ahead=key[4])
        return r.reduce(data)
    # Native CDC+SHA run synchronously on the host, so they are a host
    # phase; the jax paths above must NOT be wrapped here — their wall time
    # is dominated by blocking device waits the ledger already attributes
    # as device_wait, and a host phase would misclassify that overlap.
    with _profiler.phase("reduce_compute"):
        cuts = chunk_cuts(data, cdc, backend)
        return cuts, fingerprints(data, cuts, backend)


_tpu_lz4 = None
_tpu_lz4_lock = threading.Lock()


def block_compress(codec: str, data: bytes, backend: str = "native") -> bytes:
    """Codec dispatch for the entropy stage (container seal / compress-only
    schemes).  ``lz4`` on the TPU backend runs match discovery on device
    (ops/lz4_tpu.py — the north star's compression kernel); every other
    codec/backend pair uses the host codec path.  Output is format-identical
    either way (standard LZ4 block), so readers never care who compressed."""
    global _tpu_lz4
    _M.incr(f"compress_{backend}_total")
    _M.incr(f"compress_{backend}_bytes", len(data))
    if codec == "lz4" and backend == "tpu":
        return _lz4_device().compress(data)
    from hdrf_tpu.utils import codec as codecs

    return codecs.compress(codec, data)


def _lz4_device():
    global _tpu_lz4
    if _tpu_lz4 is None:
        with _tpu_lz4_lock:
            if _tpu_lz4 is None:
                from hdrf_tpu.ops.lz4_tpu import TpuLz4

                _tpu_lz4 = TpuLz4()
    return _tpu_lz4


def block_decompress_batch(codec_names: list, blobs: list, usizes: list,
                           outs: list, backend: str = "native") -> list:
    """Batched decode dispatch for the read coalescer
    (server/read_plane.py): one call decodes a whole coalesced window of
    sealed-container payloads, each into its buffer of ``outs`` (uint8
    arrays of at least the payload's ``usize`` — the container store's
    own, reused) and gives back the filled views.  LZ4 decode is
    byte-serial in its output dependence (ops/reconstruct.py:1-30), so
    the decode itself always runs the host oracle — the same one that
    verifies the TPU compressor's output (ops/lz4_tpu.py:63); this surface
    is the grouped DISPATCH seam, mirroring block_compress_batch's shape
    so per-window accounting lands in one place and a future device
    decoder slots in without touching callers."""
    _M.incr(f"decompress_{backend}_total", len(blobs))
    _M.incr(f"decompress_{backend}_bytes", sum(usizes))
    from hdrf_tpu.utils import codec as codecs

    return [codecs.decompress_into(c, b, u, o)
            for c, b, u, o in zip(codec_names, blobs, usizes, outs)]


def block_compress_batch(codec: str, datas: list,
                         backend: str = "native") -> list:
    """Batched codec dispatch: equal-length lz4 payloads on the TPU backend
    run as ONE device program with one grouped record readback
    (TpuLz4.compress_many) — the transport-latency lever for multi-container
    seals, where per-container dispatch+readback round trips dominate.
    Everything else degrades to per-item block_compress."""
    if codec == "lz4" and backend == "tpu":
        _M.incr(f"compress_{backend}_total", len(datas))
        _M.incr(f"compress_{backend}_bytes", sum(len(d) for d in datas))
        if mesh_plane_enabled():
            mesh = _mesh_plane_mesh()
            if mesh is not None:
                from hdrf_tpu.parallel.sharded import (
                    lz4_compress_many_sharded,
                )

                return lz4_compress_many_sharded(_lz4_device(), datas, mesh)
        return _lz4_device().compress_many(datas)
    return [block_compress(codec, d, backend) for d in datas]
