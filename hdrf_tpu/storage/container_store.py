"""Chunk-container store: append-only container files with seal-on-rollover.

Re-expression of the reference's chunk store (threadedStorer,
DataDeduplicator.java:652-845): chunks append to flat files
``<chunkDir>/<containerID>`` up to 32 MB (DataNode.java:434 ``maxSize=2^25``),
and a container is LZ4-compressed when it rolls over
(DataDeduplicator.java:770-781).  Reads group chunks by container, decompress
sealed containers, and slice chunks out (DataConstructor.threadedConstructor,
DataConstructor.java:430-567, open-container fast path :482-490).

Differences by design:

- **Lanes, not threads-with-bit-tricks.** The reference namespaces container
  ids with a 2-bit writer-thread field packed into 3 bytes
  (utilities.java:36-75).  Here container ids are a flat monotonic counter;
  concurrency comes from N independent *lanes*, each owning one open container
  and its own lock.
- **Sealed-ness is self-describing**: ``<cid>.raw`` (open) vs ``<cid>.sealed``
  (codec-framed), no external state needed to read.
- **Compaction exists** (the reference can never reclaim dead chunks).
"""

from __future__ import annotations

import contextlib
import os
import queue
import struct
import threading
from dataclasses import dataclass

import numpy as np

from hdrf_tpu.utils import codec as codecs
from hdrf_tpu.utils import fault_injection, metrics, profiler

_M = metrics.registry("container_store")


def cache_hit_ratio() -> float:
    """Decoded-container LRU hit ratio over the process's cumulative
    ``cache_hit``/``cache_miss`` counters (0.0 before any probe) — the
    /prom + /health gauge ROADMAP item 1 asks for (the counters existed
    since the true-LRU landed but were never surfaced as a ratio)."""
    hits, misses = _M.counter("cache_hit"), _M.counter("cache_miss")
    total = hits + misses
    return hits / total if total else 0.0


def _gauge_hit_ratio() -> None:
    _M.gauge("cache_hit_ratio", cache_hit_ratio())

_SEAL_HDR = struct.Struct("<IQI")  # magic, usize, codec id
_SEAL_MAGIC = 0x48435452  # "RTCH"
# Open (.raw) containers carry a same-width placeholder header so sealing an
# incompressible container is a header stamp + rename, not a data rewrite.
# The distinct magic makes a mis-framed file a loud error, never a silent
# 16-byte shift of every chunk.
_RAW_MAGIC = 0x48435257  # "WRCH"


def _pread_exact(fd: int, n: int, off: int) -> bytes:
    """``n`` bytes of ``fd`` at ``off``, fewer only where the file ends.
    One ``pread`` may return fewer bytes than asked short of the end (the
    kernel stops a copy it cannot finish), so the read goes on from where
    it stopped until the range is read or a call returns nothing."""
    data = os.pread(fd, n, off)
    if len(data) in (0, n):
        return data
    parts = [data]
    got = len(data)
    while got < n:
        part = os.pread(fd, n - got, off + got)
        if not part:
            break
        parts.append(part)
        got += len(part)
    return b"".join(parts)


class _Decoded:
    """One decoded sealed container of the LRU: ``buf[:size]`` is its
    payload (a reused buffer's rest is another container's).  ``pins``
    counts the readers copying out of it right now, under the store's
    ``_cache_lock``: the buffer goes back for the next decode only when the
    LRU has let the entry go AND nobody is pinned to it."""

    __slots__ = ("cid", "buf", "size", "pins")

    def __init__(self, cid: int, buf: np.ndarray, size: int) -> None:
        self.cid, self.buf, self.size, self.pins = cid, buf, size, 1


@dataclass
class _Lane:
    lock: threading.Lock
    container_id: int = -1
    size: int = 0
    fh: object | None = None
    # the open container's memory, allocated whole: ``buffer[:size]`` is the
    # container, the rest is room (never read; a reused buffer's is stale)
    buffer: np.ndarray | None = None

    def view(self) -> memoryview:
        """The container's bytes where they lie (under ``lock``)."""
        return memoryview(self.buffer)[:self.size]


class ContainerStore:
    """Append-only chunk containers with compress-on-seal and compaction."""

    def __init__(self, directory: str, container_size: int = 1 << 25,
                 lanes: int = 4, codec: str = "lz4", cache_containers: int = 4,
                 compress_fn=None, on_roll=None, fsync: bool = False,
                 id_base: int = 0, compress_batch_fn=None):
        """``compress_fn`` overrides the seal-time compressor while keeping
        the frame codec id (the TPU LZ4 stage produces format-identical
        output, so readers decode with the stock codec either way).
        ``compress_batch_fn(list) -> list`` is its grouped
        form: when set, ``flush_open`` seals all open lanes through ONE
        call (one device program + one grouped readback on the TPU
        backend) instead of a compressor round trip per lane.
        ``on_roll(cid, payload)`` observes each container's full
        uncompressed payload at seal time (the lane's buffer itself) — the
        hook an async seal pipeline hangs off, sparing a disk read-back.
        All three are handed a ``memoryview`` of the lane's own buffer,
        ``buffer[:size]``, not a copy, and may give back any bytes-like.
        Who may hold that memory, and until when: the lane until its
        container rolls over; from then the hooks, the seal queue and
        ``seal()``, and nobody else — the lane opens in another buffer.
        When ``seal()`` has returned and ``on_seal`` has run, the buffer
        goes to the store's free list and a later container is written
        over it.  So the hooks must not write to what they are handed, and
        must not keep it (nor a view or a zero-copy array of it) past
        their return — a hook that needs the bytes later copies them
        (``bytes(payload)``), as ``bench.py``'s does; what they give back
        must not alias it unless it IS it (a codec that cannot shrink the
        bytes)."""
        self._dir = directory
        os.makedirs(directory, exist_ok=True)
        self._container_size = container_size
        self._codec = codec
        self._compress_fn = compress_fn
        self._compress_batch_fn = compress_batch_fn
        self._on_roll = on_roll
        # fsync policy for container DATA (HDFS parity: block data is not
        # fsync'd on finalize — replication is the durability story; see
        # ReductionConfig.fsync_containers).  Seal-time writes of NEW files
        # still fsync regardless (rename barrier).
        self._fsync = fsync
        # observer for container deletion (compaction/GC): lets a device
        # reconstructor drop its stale HBM image
        self._on_delete = None
        # observer for container RETIREMENT (delete OR quarantine): the
        # read plane's decoded-chunk cache drops entries sliced from the
        # container.  Separate from _on_delete because quarantine keeps the
        # container logically present (no HBM/EC teardown) yet its bytes
        # must never serve again, cached slices included.
        self._on_retire = None
        # EC cold tier hooks (storage/stripe_store.py): when a sealed file
        # is gone because the container was demoted to stripes,
        # ``_stripe_fallback(cid)`` returns the reconstructed sealed FILE
        # bytes (header + compressed payload) or None, and
        # ``_stripe_probe(cid)`` returns the uncompressed payload size
        # recorded in the striping manifest (for has_container) or None.
        self._stripe_fallback = None
        self._stripe_probe = None
        self._alloc_lock = threading.Lock()
        # ``id_base`` namespaces this store's container ids (multi-volume
        # DNs: vol_id << CID_SHIFT — the same trick the reference uses to
        # namespace container ids by writer thread, the 2-bit threadID
        # field packed into its 3-byte ids at utilities.java:36-75), so
        # one DN-wide chunk index can route any cid to its volume.
        self._id_base = id_base
        # Bytes on disk of every ``.raw`` / ``.sealed`` file of the
        # directory, by (cid, suffix), and their sum: the store is its
        # directory's only writer, so ``physical_bytes`` and
        # ``container_sizes`` answer from what it wrote (a walk is a
        # ``stat`` a container file, each one the interpreter let go and
        # won back — under every block's commit and every heartbeat).
        # ``_sizes_lock`` guards both and is never held across I/O.
        self._sizes_lock = threading.Lock()
        self._sizes: dict[tuple[int, str], int] = {}
        self._physical = 0
        self._next_id = max(self._scan_dir(), id_base)
        self._lanes = [_Lane(threading.Lock()) for _ in range(lanes)]
        self._rr = 0
        # Sealed containers' buffers (``container_size`` each), for the next
        # ``_open_locked``: a container's pages are faulted in once a
        # process and not once a container.  Under ``_alloc_lock`` with
        # ``_sealing``, the buffers handed to a seal and not yet back.
        self._free: list[np.ndarray] = []
        self._sealing = 0
        # Tiny LRU of decompressed sealed containers (read amplification guard;
        # the reference re-decompresses the whole container per read), and
        # the buffers it has let go (``_give_back_locked``), both under
        # ``_cache_lock``.
        self._cache: dict[int, _Decoded] = {}
        self._cache_cap = cache_containers
        self._decoded_free: list[np.ndarray] = []
        self._cache_lock = threading.Lock()
        # Async seal stage (enable_async_seals): rollover compression moves
        # off the appending thread onto one worker; None = inline seals.
        self._seal_q: queue.Queue | None = None
        self._seal_thread: threading.Thread | None = None
        self._seal_exc: BaseException | None = None

    def _scan_dir(self) -> int:
        """The one walk of the directory, at open: the next container id
        (past every name a previous process left, ``.tmp`` and ``.quar``
        too) and the size of what it left (``.raw`` and ``.sealed``
        only)."""
        _M.incr("dir_walks")
        mx = -1
        for name in os.listdir(self._dir):
            stem, _, suffix = name.partition(".")
            if not stem.isdigit():
                continue
            mx = max(mx, int(stem))
            if suffix in ("raw", "sealed"):
                size = os.path.getsize(os.path.join(self._dir, name))
                self._sizes[int(stem), suffix] = size
                self._physical += size
        return mx + 1

    def _note_sizes(self, cid: int, **sizes: int | None) -> None:
        """What the store just did to ``<cid>.raw`` / ``<cid>.sealed``, as
        ``raw=`` / ``sealed=``: the bytes the file now holds, or None once
        it is gone.  One step for a reader, whatever it names."""
        with self._sizes_lock:
            for suffix, size in sizes.items():
                self._physical -= self._sizes.pop((cid, suffix), 0)
                if size is not None:
                    self._sizes[cid, suffix] = size
                    self._physical += size

    def _note_lane(self, lane: _Lane) -> None:
        """Under ``lane.lock``, after a write to the lane's file: header +
        every byte of the container so far.  A lane without a file counts
        nothing until its seal writes one."""
        if lane.fh is not None:
            self._note_sizes(lane.container_id,
                             raw=_SEAL_HDR.size + lane.size)

    def _raw_path(self, cid: int) -> str:
        return os.path.join(self._dir, f"{cid}.raw")

    def _sealed_path(self, cid: int) -> str:
        return os.path.join(self._dir, f"{cid}.sealed")

    # -------------------------------------------------------------- writing

    def append_chunks(self, chunks: list[bytes], on_seal=None,
                      sync: bool = True) -> list[tuple[int, int, int]]:
        """Append chunks to one lane's open container; returns
        (container_id, offset, length) per chunk (a chunk is any bytes-like
        whose items are single bytes).  ``on_seal(cid)`` fires after
        a rollover compresses+seals a container (index notification).
        ``sync=False`` skips the fsync — the batched commit pipeline calls
        ``sync_lanes()`` once per group instead, BEFORE the covering index
        commit (same durability ordering, amortized)."""
        if not chunks:  # fully-deduplicated block: nothing new to store
            return []
        with self._alloc_lock:
            lane = self._lanes[self._rr % len(self._lanes)]
            self._rr += 1
        out: list[tuple[int, int, int]] = []
        with lane.lock:
            written = lane.size  # buffer[written:size] is not in the file yet

            def drain():
                if lane.fh is not None and lane.size > written:
                    lane.fh.write(lane.buffer[written:lane.size])
                    self._note_lane(lane)

            for chunk in chunks:
                if lane.buffer is None or (
                        lane.size + len(chunk) > self._container_size and lane.size > 0):
                    if lane.buffer is not None:
                        drain()  # before rollover seals the container
                        self._seal_locked(lane, on_seal)
                    self._open_locked(lane)
                    written = 0
                off = lane.size
                if off + len(chunk) > lane.buffer.size:
                    self._oversize_locked(lane, len(chunk))
                lane.buffer[off:off + len(chunk)] = np.frombuffer(
                    chunk, np.uint8)
                lane.size += len(chunk)
                out.append((lane.container_id, off, len(chunk)))
            # One write per batch, not per chunk (measured: per-chunk writes
            # were ~25% of the whole ingest host cost at 8 KiB avg chunks).
            drain()
            if lane.fh is not None:
                lane.fh.flush()
                if sync and self._fsync:
                    os.fsync(lane.fh.fileno())
        _M.incr("chunks_appended", len(chunks))
        return out

    def append_ranges(self, data, starts, lens, on_seal=None,
                      sync: bool = True) -> list[tuple[int, int, int]]:
        """``append_chunks`` for chunks that are RANGES of one buffer (the
        dedup commit's shape): byte movement runs as one native
        gather_ranges per container segment, straight into the lane's
        buffer, and the raw file is written from a view of it — a new byte
        is copied once into the open container and once to the page cache
        (it was four copies, two of them holding the interpreter, three
        into pages nobody had touched).  Rollover semantics identical to
        append_chunks: a chunk that doesn't fit seals the open container
        first; an oversized chunk lands alone in an empty one."""
        from hdrf_tpu import native

        n = int(len(starts))
        if n == 0:
            return []
        starts = np.ascontiguousarray(starts, dtype=np.uint64)
        lens = np.ascontiguousarray(lens, dtype=np.uint64)
        with self._alloc_lock:
            lane = self._lanes[self._rr % len(self._lanes)]
            self._rr += 1
        out_cid = np.empty(n, np.int64)
        out_off = np.empty(n, np.int64)
        csum = np.concatenate([[0], np.cumsum(lens, dtype=np.int64)])
        with lane.lock:
            i = 0
            while i < n:
                if lane.buffer is None:
                    self._open_locked(lane)
                cap = self._container_size - lane.size
                j = int(np.searchsorted(csum, csum[i] + cap,
                                        side="right")) - 1
                if j <= i:
                    if lane.size > 0:
                        self._seal_locked(lane, on_seal)
                        self._open_locked(lane)
                        continue
                    j = i + 1
                    self._oversize_locked(lane, int(lens[i]))
                end = lane.size + int(csum[j] - csum[i])
                seg = lane.buffer[lane.size:end]
                native.gather_ranges(data, starts[i:j], lens[i:j], out=seg)
                if lane.fh is not None:
                    lane.fh.write(seg)
                out_cid[i:j] = lane.container_id
                out_off[i:j] = lane.size + (csum[i:j] - csum[i])
                lane.size = end
                self._note_lane(lane)
                i = j
            if lane.fh is not None:
                lane.fh.flush()
                if sync and self._fsync:
                    os.fsync(lane.fh.fileno())
        _M.incr("chunks_appended", n)
        return list(zip(out_cid.tolist(), out_off.tolist(), lens.tolist()))

    def sync_lanes(self) -> None:
        """Flush (and, under the fsync policy, fsync) every open lane — the
        group-commit durability barrier.  A no-op in memory-resident mode,
        where open containers reach disk once, at seal."""
        for lane in self._lanes:
            with lane.lock:
                if lane.fh is not None:
                    lane.fh.flush()
                    if self._fsync:
                        os.fsync(lane.fh.fileno())

    def _open_locked(self, lane: _Lane) -> None:
        with self._alloc_lock:
            cid = self._next_id
            self._next_id += 1
            buf = self._free.pop() if self._free else None
        _M.incr("lane_buffer_allocs" if buf is None else "lane_buffer_reuses")
        lane.container_id = cid
        lane.size = 0
        # never waits for a seal to give one back: none free, allocate
        # (``np.empty``: no fill, so a page is first touched by the bytes
        # that land on it, inside the native gather)
        lane.buffer = (np.empty(self._container_size, np.uint8)
                       if buf is None else buf)
        # Write-through WITHOUT fsync (unless the strict policy is on):
        # process death loses nothing (the page cache survives), OS-crash
        # durability comes from replication — HDFS's own block-data story.
        # Raw files are unlinked at seal, so under steady rollover their
        # data blocks are mostly never written back at all (ext4 ordered
        # mode skips deleted data): container bytes effectively hit the
        # platter once, compressed.
        lane.fh = open(self._raw_path(cid), "wb")
        # Placeholder header: chunk data starts at _SEAL_HDR.size, so sealing
        # an incompressible (or codec "none") container is a header stamp +
        # rename instead of a full data rewrite (measured: the rewrite was
        # ~35% of ingest host cost for codec "none").
        lane.fh.write(_SEAL_HDR.pack(_RAW_MAGIC, 0, 0))
        self._note_lane(lane)

    def _oversize_locked(self, lane: _Lane, need: int) -> None:
        """A chunk larger than ``container_size`` lands alone in an empty
        container: that one gets a buffer of the chunk's size, and the
        free list gets back the one nothing has seen."""
        assert lane.size == 0 and need > lane.buffer.size
        with self._alloc_lock:
            self._reuse_locked(lane.buffer)
        lane.buffer = np.empty(need, np.uint8)
        _M.incr("lane_buffer_allocs")

    def _reuse_locked(self, buf: np.ndarray) -> None:
        """Under ``_alloc_lock``: a buffer nothing can see any more, for a
        later ``_open_locked``.  The list keeps no more than the lane count
        plus the seals still in flight (what a burst allocated goes as its
        seals finish), and only buffers of ``container_size``."""
        if buf.size == self._container_size:
            self._free.append(buf)
        del self._free[len(self._lanes) + self._sealing:]

    def _seal_payload(self, cid: int, payload: memoryview, had_raw: bool,
                      on_seal, comp) -> None:
        """``seal`` + ``on_seal`` of a rolled-over lane's container (inline
        or on the seal thread), and then — nothing can see it any more —
        its buffer back to the free list.  A seal that raised keeps the
        buffer out of it.

        ``seal`` is the container's covering span (its count is the
        store's count of containers sealed, its CPU what the seal costs
        the thread that runs it); the thread's spans between its ends
        (``seal_send`` / ``seal_wait`` / ``seal_write``, ``seal_index``)
        are the container's own timeline."""
        sealed = False
        with profiler.cpu_phase("seal"):
            try:
                self.seal(cid, data=payload, have_raw=had_raw, comp=comp)
                if on_seal is not None:
                    with profiler.phase("seal_index"):
                        on_seal(cid)
                sealed = True
            finally:
                with self._alloc_lock:
                    self._sealing -= 1
                    if sealed:
                        self._reuse_locked(payload.obj)

    def _seal_locked(self, lane: _Lane, on_seal, comp=None) -> None:
        had_raw = lane.fh is not None
        if had_raw:
            lane.fh.close()
        # the lane's buffer spares the seal a full read-back of the file
        # (measured ~10% of ingest host cost at 32 MiB containers).  It is
        # handed on where it lies — a copy here is 32 MiB of fresh pages
        # under the lane's lock, on the commit thread — and dropped from
        # the lane below: ``_open_locked`` starts the next container in
        # another buffer, and this one is free again after its seal
        payload = lane.view()
        if self._on_roll is not None:
            self._on_roll(lane.container_id, payload)
        with self._alloc_lock:
            self._sealing += 1
        if self._seal_q is not None:
            # Async stage: hand the payload to the seal worker and return —
            # the appending (commit) thread never pays the compressor.  Safe
            # because sealed-ness is self-describing: the raw file stays
            # readable (read_container's raw fallback) until the worker's
            # seal renames it, and the cid is retired from the lane HERE, so
            # no later append can touch it.
            self._seal_q.put((profiler.mark(), lane.container_id, payload,
                              had_raw, on_seal, comp))
            _M.incr("async_seals")
        else:
            self._seal_payload(lane.container_id, payload, had_raw, on_seal,
                               comp)
        lane.fh = None
        lane.buffer = None

    def seal(self, cid: int, data=None, have_raw: bool | None = None,
             comp=None) -> None:
        """Compress a raw container into the sealed format (the rollover LZ4
        pass, DataDeduplicator.java:770-781).  ``data`` carries the
        container's chunk bytes when the caller already holds them (a view
        of the lane's buffer, any bytes-like); otherwise they are read from
        the raw file.
        ``have_raw=False`` (memory-resident lane) writes the sealed file
        directly — there is no raw file to stamp or remove.  ``comp`` is
        the already-compressed payload when the caller ran the compressor
        itself (the grouped flush_open seal)."""
        # ``seal_write`` spans cover the file work only (this interpreter's
        # seconds); the compressor between them has its own phases
        # (``seal_send`` / ``seal_wait`` when it is the worker's).
        raw = self._raw_path(cid)
        if have_raw is None:
            have_raw = os.path.exists(raw)
        if have_raw:
            with open(raw, "r+b") as f:
                with profiler.phase("seal_write"):
                    magic = _SEAL_HDR.unpack(f.read(_SEAL_HDR.size))[0]
                    if magic != _RAW_MAGIC:
                        raise IOError(
                            f"container {cid}: bad raw magic {magic:#x}")
                    if data is None:
                        data = f.read()
                fault_injection.point("container.seal")
                if comp is None:
                    comp = self._compress(data)
                if len(comp) >= len(data):
                    # Incompressible or codec "none": stamp the placeholder
                    # header in place and rename — no data copy.  The fsync
                    # (forcing the full container's writeback NOW) follows
                    # the block-data durability policy.
                    with profiler.phase("seal_write"):
                        f.seek(0)
                        f.write(_SEAL_HDR.pack(_SEAL_MAGIC, len(data),
                                               codecs.CODEC_IDS["none"]))
                        f.flush()
                        if self._fsync:
                            os.fsync(f.fileno())
                        os.replace(raw, self._sealed_path(cid))
                    self._note_sizes(cid, raw=None,
                                     sealed=_SEAL_HDR.size + len(data))
                    _M.incr("sealed")
                    return
        else:
            assert data is not None, "memory-resident seal needs the payload"
            fault_injection.point("container.seal")
            if comp is None:
                comp = self._compress(data)
        codec = self._codec if len(comp) < len(data) else "none"
        out = comp if len(comp) < len(data) else data
        tmp = self._sealed_path(cid) + ".tmp"
        with profiler.phase("seal_write"):
            with open(tmp, "wb") as f:
                f.write(_SEAL_HDR.pack(_SEAL_MAGIC, len(data),
                                       codecs.CODEC_IDS[codec]))
                f.write(out)
                f.flush()
                if self._fsync:
                    os.fsync(f.fileno())
            os.replace(tmp, self._sealed_path(cid))
            self._note_sizes(cid, sealed=_SEAL_HDR.size + len(out))
            if have_raw:
                os.unlink(raw)
                self._note_sizes(cid, raw=None)
        _M.incr("sealed")

    def _compress(self, data):
        if self._codec == "none":
            return data
        if self._compress_fn is not None:
            return self._compress_fn(data)
        return codecs.compress(self._codec, data)

    def flush_open(self, on_seal=None) -> None:
        """Seal every open lane (shutdown/test hook).

        With ``compress_batch_fn`` set, every sealable lane's payload is
        compressed through ONE batched call before sealing — on the TPU
        backend that is a single device program plus one grouped record
        readback instead of a dispatch/readback round trip per lane."""
        with contextlib.ExitStack() as stack:
            sealable = []
            for lane in self._lanes:
                stack.enter_context(lane.lock)
                if lane.buffer is not None and lane.size > 0:
                    sealable.append(lane)
                elif lane.buffer is not None:
                    if lane.fh is not None:
                        lane.fh.close()
                        os.unlink(self._raw_path(lane.container_id))
                        self._note_sizes(lane.container_id, raw=None)
                        lane.fh = None
                    with self._alloc_lock:  # opened, empty: nothing saw it
                        self._reuse_locked(lane.buffer)
                    lane.buffer = None
            comps = None
            if (self._compress_batch_fn is not None and len(sealable) > 1
                    and self._codec != "none"):
                comps = self._compress_batch_fn(
                    [l.view() for l in sealable])
                _M.incr("batch_seals", len(sealable))
            for lane, comp in zip(sealable, comps or [None] * len(sealable)):
                self._seal_locked(lane, on_seal, comp=comp)
        self.drain_seals()

    # --------------------------------------------------------- async sealing

    def enable_async_seals(self) -> None:
        """Move rollover compression off the appending thread onto a single
        seal worker (the write pipeline's commit stage must not stall on an
        unlucky 32 MiB compress).  Idempotent.  Durability is unchanged:
        the raw file persists (and serves reads) until the worker's sealed
        file is in place, exactly the ordering ``seal`` already guarantees
        for concurrent readers."""
        if self._seal_q is not None:
            return
        self._seal_q = queue.Queue()
        self._seal_thread = threading.Thread(
            target=self._seal_worker, name="container-seal", daemon=True)
        self._seal_thread.start()

    def _seal_worker(self) -> None:
        while True:
            item = self._seal_q.get()
            if item is None:
                self._seal_q.task_done()
                return
            t_put, *args = item
            try:
                # the container's wait for this thread: from the
                # rollover's ``put`` to here, where its ``seal`` begins
                profiler.record_span("seal_queue", t_put, profiler.mark())
                self._seal_payload(*args)
            except BaseException as e:  # noqa: BLE001 — re-raised at drain
                self._seal_exc = e
            finally:
                self._seal_q.task_done()

    def drain_seals(self) -> None:
        """Barrier: every enqueued async seal is on disk (or its error is
        raised here).  No-op with async seals disabled."""
        if self._seal_q is None:
            return
        # a caller waiting for the queue to empty: in a benchmark's
        # window, the tail after the last ack
        with profiler.phase("seal_drain"):
            self._seal_q.join()
        if self._seal_exc is not None:
            exc, self._seal_exc = self._seal_exc, None
            raise exc

    def close_async_seals(self) -> None:
        """Drain, then stop the seal worker (shutdown hook)."""
        if self._seal_q is None:
            return
        self.drain_seals()
        self._seal_q.put(None)
        self._seal_thread.join()
        self._seal_q = None
        self._seal_thread = None

    # -------------------------------------------------------------- reading

    def _cache_pin(self, cid: int) -> _Decoded | None:
        """The decoded container, pinned for the caller's copy-out (paired
        with ``_cache_unpin``), or None."""
        with self._cache_lock:
            # true LRU: re-insert on hit so eviction drops the least
            # RECENTLY used container, not the oldest insertion (FIFO
            # evicted the hottest container under cyclic read sets)
            entry = self._cache.pop(cid, None)
            if entry is not None:
                self._cache[cid] = entry
                entry.pins += 1
        _M.incr("cache_miss" if entry is None else "cache_hit")
        _gauge_hit_ratio()
        return entry

    def _cache_unpin(self, entry: _Decoded) -> None:
        with self._cache_lock:
            entry.pins -= 1
            if entry.pins == 0 and self._cache.get(entry.cid) is not entry:
                self._give_back_locked(entry.buf)

    def _cache_drop_locked(self, cid: int) -> None:
        """Under ``_cache_lock``: out of the LRU (evicted, replaced or
        retired).  Readers pinned to it finish on the bytes they have."""
        entry = self._cache.pop(cid, None)
        if entry is not None and entry.pins == 0:
            self._give_back_locked(entry.buf)

    def _give_back_locked(self, buf: np.ndarray) -> None:
        """Under ``_cache_lock``: a decoded container's buffer nobody can
        see any more, for the next decode — as many as the LRU holds, so a
        miss decodes into pages this process has touched before (a fresh
        32 MiB costs its 8 192 first-touch faults every time, and its
        ``munmap`` when dropped)."""
        if (buf.size == self._container_size
                and len(self._decoded_free) < self._cache_cap):
            self._decoded_free.append(buf)

    def _decode_buffer(self, usize: int) -> np.ndarray:
        if usize > self._container_size:    # an oversize chunk's container
            return np.empty(usize, np.uint8)
        with self._cache_lock:
            if self._decoded_free:
                return self._decoded_free.pop()
        return np.empty(self._container_size, np.uint8)

    def _cache_insert_pinned(self, cid: int, buf: np.ndarray,
                             size: int) -> _Decoded:
        entry = _Decoded(cid, buf, size)
        with self._cache_lock:
            self._cache_drop_locked(cid)   # two readers decoded it at once
            self._cache[cid] = entry
            while len(self._cache) > self._cache_cap:
                self._cache_drop_locked(next(iter(self._cache)))
                _M.incr("cache_evict")
        return entry

    def _open_lane(self, cid: int) -> _Lane | None:
        """The lane whose open container ``cid`` may be.  A peek without
        the lanes' locks (a reader of a sealed container must not queue
        behind a writer's append for the answer): ids only grow and an
        index entry exists only once its bytes are in a container, so a
        ``cid`` that no lane shows now is open in none later; the caller
        looks again under the lane's lock."""
        for lane in self._lanes:
            if lane.container_id == cid:
                return lane
        return None

    @staticmethod
    def _check_raw_header(cid: int, f) -> None:
        """The header of a file opened under its raw name.  A container the
        codec could not shrink is sealed by stamping that very file's
        header and renaming it (``seal``): a reader that opened it a moment
        before the rename holds the sealed file, whose payload is the raw
        bytes where they were — as good as the raw file it asked for."""
        magic, _, codec_id = _SEAL_HDR.unpack(f.read(_SEAL_HDR.size))
        if magic != _RAW_MAGIC and not (
                magic == _SEAL_MAGIC
                and codec_id == codecs.CODEC_IDS["none"]):
            raise IOError(f"container {cid}: bad raw magic {magic:#x}")

    def _read_open(self, cid: int, lo: int = 0,
                   hi: int | None = None) -> bytes | None:
        """Bytes ``[lo, hi)`` (``hi`` None: to its end) of a container that
        is not sealed yet — the no-decompress sources: copied out of its
        lane's buffer under the lane's lock (only what the read wants: the
        writer's next append waits for that lock, and the buffer takes
        another container's bytes after its seal) or, once rolled over and
        still in the seal queue, read from its raw file
        (DataConstructor.java:482-490's skip-decompress path).  None when
        the container is sealed (or gone)."""
        lane = self._open_lane(cid)
        if lane is not None:
            with lane.lock:
                if lane.container_id == cid and lane.buffer is not None:
                    end = lane.size if hi is None else hi
                    if end > lane.size:
                        raise IOError(f"container {cid}: range ends at {end}, "
                                      f"the open container at {lane.size}")
                    return lane.buffer[lo:end].tobytes()
        try:
            # Open without an exists() pre-check: a concurrent seal unlinks
            # the raw file only *after* the sealed file is in place, so on
            # ENOENT the sealed path is guaranteed readable.
            with open(self._raw_path(cid), "rb") as f:
                self._check_raw_header(cid, f)
                if hi is None:
                    f.seek(_SEAL_HDR.size + lo)
                    return f.read()
                data = _pread_exact(f.fileno(), hi - lo, _SEAL_HDR.size + lo)
                if len(data) != hi - lo:
                    held = os.fstat(f.fileno()).st_size - _SEAL_HDR.size
                    raise IOError(f"container {cid}: raw file ends inside "
                                  f"[{lo}, {hi}): it holds {held} bytes")
        except FileNotFoundError:
            return None
        return data

    def _sealed_parse(self, cid: int) -> tuple[str, int, memoryview]:
        """(codec name, uncompressed size, compressed payload) of the
        sealed container — the decode deferred so the read coalescer can
        run a whole window's payloads through one batched dispatch."""
        try:
            with open(self._sealed_path(cid), "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            # Demoted to the EC cold tier: the sealed file was replaced by
            # k+m stripes.  The DN-installed fallback reassembles the exact
            # sealed-file bytes from any k survivors (degraded read path).
            if self._stripe_fallback is None:
                raise
            blob = self._stripe_fallback(cid)
            if blob is None:
                raise
        magic, usize, codec_id = _SEAL_HDR.unpack(blob[:_SEAL_HDR.size])
        if magic != _SEAL_MAGIC:
            raise IOError(f"container {cid}: bad magic {magic:#x}")
        return (codecs.CODEC_NAMES[codec_id], usize,
                memoryview(blob)[_SEAL_HDR.size:])

    def _decode_pinned(self, cids: list[int],
                       decompress_batch=None) -> list[_Decoded]:
        """Sealed containers read, decoded into buffers of the store's own
        and put in the LRU; each comes back pinned.  The payloads run
        through ONE ``decompress_batch(codec_names, blobs, usizes, outs)``
        call when given (the read coalescer passes
        ops/dispatch.block_decompress_batch)."""
        from hdrf_tpu.reduction import accounting

        with profiler.phase("container_load"):
            parsed = [self._sealed_parse(cid) for cid in cids]
        bufs = [self._decode_buffer(usize) for _, usize, _ in parsed]
        with profiler.phase("container_decode"):
            if decompress_batch is not None:
                decompress_batch([p[0] for p in parsed],
                                 [p[2] for p in parsed],
                                 [p[1] for p in parsed], bufs)
            else:
                for (codec_name, usize, payload), buf in zip(parsed, bufs):
                    codecs.decompress_into(codec_name, payload, usize, buf)
        out = []
        for cid, (_, usize, _), buf in zip(cids, parsed, bufs):
            accounting.record_container_decode(usize)
            out.append(self._cache_insert_pinned(cid, buf, usize))
        return out

    def read_container(self, cid: int) -> bytes:
        """Full uncompressed container bytes (open or sealed), the caller's
        own copy."""
        entry = self._cache_pin(cid)
        if entry is None:
            data = self._read_open(cid)
            if data is not None:
                from hdrf_tpu.reduction import accounting

                accounting.record_container_decode(len(data))
                return data
            (entry,) = self._decode_pinned([cid])
        try:
            return entry.buf[:entry.size].tobytes()
        finally:
            self._cache_unpin(entry)

    def read_chunks(self, locs: list[tuple[int, int, int]],
                    decompress_batch=None) -> list[bytes]:
        """Fetch many chunks, grouping by container so each container is read
        and decompressed once (quickBuildMT's grouping,
        DataConstructor.java:375-395).  A decoded container never leaves
        the store: the wanted ranges are copied out of it while it is
        pinned, so its buffer can take the next decode once the LRU has let
        it go.  Of an open container only the extent the chunks span is
        copied.  ``decompress_batch`` as in ``_decode_pinned``."""
        from hdrf_tpu.reduction import accounting

        by_cid: dict[int, list[int]] = {}
        for i, (cid, _, _) in enumerate(locs):
            by_cid.setdefault(cid, []).append(i)
        out: list[bytes | None] = [None] * len(locs)
        sealed: list[int] = []
        for cid, idxs in by_cid.items():
            entry = self._cache_pin(cid)
            if entry is not None:
                try:
                    self._copy_out(entry, locs, idxs, out)
                finally:
                    self._cache_unpin(entry)
                continue
            lo = min(locs[i][1] for i in idxs)
            hi = max(locs[i][1] + locs[i][2] for i in idxs)
            with profiler.phase("container_load"):
                data = self._read_open(cid, lo, hi)
            if data is None:
                sealed.append(cid)
                continue
            accounting.record_container_decode(len(data))
            with profiler.phase("chunk_copy"):
                for i in idxs:
                    _, off, ln = locs[i]
                    out[i] = data[off - lo:off - lo + ln]
        if sealed:
            entries = self._decode_pinned(sealed, decompress_batch)
            try:
                for entry in entries:
                    self._copy_out(entry, locs, by_cid[entry.cid], out)
            finally:
                for entry in entries:
                    self._cache_unpin(entry)
        return out  # type: ignore[return-value]

    def _copy_out(self, entry: _Decoded, locs: list, idxs: list[int],
                  out: list) -> None:
        """The chunks ``idxs`` of ``locs`` out of a pinned decoded
        container, each a ``bytes`` of its own."""
        with profiler.phase("chunk_copy"):
            buf, size = entry.buf, entry.size
            for i in idxs:
                _, off, ln = locs[i]
                if off + ln > size:     # past it lie another's bytes
                    raise IOError(
                        f"container {entry.cid}: chunk [{off}, +{ln}) "
                        f"ends past its {size} bytes")
                out[i] = buf[off:off + ln].tobytes()

    # ----------------------------------------------------------- compaction

    def copy_live(self, cid: int, live: dict[bytes, tuple[int, int]],
                  on_seal=None) -> dict[bytes, tuple[int, int, int]]:
        """Copy a container's *live* chunks into the current open lane.
        ``live`` maps fingerprint -> (offset, len) within ``cid``.  Returns
        fingerprint -> new (cid, off, len).

        Compaction protocol (crash-safe ordering): ``copy_live`` (bytes
        durable in new container) -> ``ChunkIndex.record_moves`` (index commit)
        -> ``delete_container(cid)``.  A crash before the index commit leaves
        only orphan copies; the old container is deleted strictly after the
        index stops referencing it."""
        data = self.read_container(cid)
        hashes = list(live.keys())
        chunks = [data[off:off + ln] for off, ln in (live[h] for h in hashes)]
        new_locs = self.append_chunks(chunks, on_seal=on_seal)
        return dict(zip(hashes, new_locs))

    def sealed_file_bytes(self, cid: int) -> bytes | None:
        """Raw sealed FILE bytes (header + compressed payload) — the EC
        cold tier's striping unit (stripe_store.py encodes exactly these
        bytes, so reassembly needs no re-compression).  None when the
        container is open or already striped."""
        try:
            with open(self._sealed_path(cid), "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None

    def drop_sealed_file(self, cid: int) -> int:
        """Unlink just the sealed file (EC demotion: the stripes + manifest
        now carry the bytes).  Unlike delete_container this keeps the LRU
        entry (the decompressed payload is still valid) and does NOT fire
        ``_on_delete`` — the container remains logically present.  Returns
        bytes freed."""
        path = self._sealed_path(cid)
        try:
            size = os.path.getsize(path)
            os.unlink(path)
        except OSError:
            return 0
        self._note_sizes(cid, sealed=None)
        return size

    def quarantine(self, cid: int) -> int:
        """Rename the container's files aside (``.quar`` suffix) so it can
        never be served again — a scrub-confirmed corrupt container must
        not satisfy another read, across restarts included
        (markBlockAsCorrupt's never-serve guarantee applied to the shared
        container).  A rename, not an unlink: the corrupt bytes stay on
        disk for forensics and are censused as
        ``garbage_bytes|class=quarantined`` until GC reclaims them.  Does
        NOT fire ``_on_delete`` (the container remains logically present;
        re-replication restores its blocks elsewhere).  Returns bytes
        quarantined."""
        moved = 0
        for suffix, p in (("raw", self._raw_path(cid)),
                          ("sealed", self._sealed_path(cid))):
            try:
                size = os.path.getsize(p)
                os.rename(p, p + ".quar")
            except OSError:
                continue
            self._note_sizes(cid, **{suffix: None})
            moved += size
        with self._cache_lock:
            self._cache_drop_locked(cid)
        if self._on_retire is not None:
            self._on_retire(cid)
        return moved

    def delete_container(self, cid: int) -> None:
        for p in (self._raw_path(cid), self._sealed_path(cid)):
            if os.path.exists(p):
                os.unlink(p)
        self._note_sizes(cid, raw=None, sealed=None)
        with self._cache_lock:
            self._cache_drop_locked(cid)
        if self._on_retire is not None:
            self._on_retire(cid)
        if self._on_delete is not None:
            self._on_delete(cid)

    def has_container(self, cid: int, need_bytes: int = 0) -> bool:
        """True if the container's bytes are reachable AND cover at least
        ``need_bytes`` of payload.  The extent check matters: the typical
        fsync_containers=False crash artifact is a TRUNCATED raw file (the
        un-fsync'd tail lost to writeback), not a missing one.  Sources:
        an open lane's memory image, the raw file (size minus header), or
        the sealed file (uncompressed size from its fsync'd header)."""
        for lane in self._lanes:
            with lane.lock:
                if lane.container_id == cid and lane.buffer is not None:
                    return lane.size >= need_bytes
        try:
            sz = os.path.getsize(self._raw_path(cid))
            return sz - _SEAL_HDR.size >= need_bytes
        except OSError:
            pass
        try:
            with open(self._sealed_path(cid), "rb") as f:
                hdr = f.read(_SEAL_HDR.size)
                if len(hdr) < _SEAL_HDR.size:
                    return False
                magic, usize, _codec = _SEAL_HDR.unpack(hdr)
                return magic == _SEAL_MAGIC and usize >= need_bytes
        except OSError:
            pass
        if self._stripe_probe is not None:
            usize = self._stripe_probe(cid)
            if usize is not None:  # striped: manifest records payload size
                return usize >= need_bytes
        return False

    def container_ids(self) -> list[int]:
        _M.incr("dir_walks")    # the scrubber's census; on no block's path
        ids = set()
        for name in os.listdir(self._dir):
            stem = name.split(".")[0]
            if stem.isdigit() and (name.endswith(".raw") or name.endswith(".sealed")):
                ids.add(int(stem))
        return sorted(ids)

    def physical_bytes(self) -> int:
        """Bytes on disk of the ``.raw`` and ``.sealed`` files, from the
        store's record of its own writes: no directory walk."""
        with self._sizes_lock:
            return self._physical

    def container_sizes(self) -> dict[int, int]:
        """cid -> bytes on disk (raw + sealed forms summed) — the
        denominator of the utilization accounting
        (reduction/accounting.py:utilization_hist).  From the same record:
        no ``stat``, no file opened."""
        with self._sizes_lock:
            sizes = list(self._sizes.items())
        out: dict[int, int] = {}
        for (cid, _), size in sizes:
            out[cid] = out.get(cid, 0) + size
        return out
