"""Chunk-granular read-serving plane: range resolution, a DN-wide decoded-
chunk cache, and coalesced container decodes.

Re-expression of the reference read path one layer above the container
store.  DataConstructor.java's hash-list fetch (:222-235) and metadata
batch lookup + group-by-container (quickBuildMT, DataConstructor.java
:360-417) become an explicit :class:`ChunkPlan` — the position→chunk-range
resolver that lets ``read_logical(offset, length)`` touch ONLY the
containers overlapping the requested range (the reference always
materializes the full block, BlockSender.java:612-623).  The decoded-chunk
LRU has no reference counterpart: the reference re-decompresses whole
containers per read (threadedConstructor, DataConstructor.java:430-567)
and caches nothing chunk-shaped, so a hot dedup'd chunk shared by many
files pays a container decode on every file that touches it.  Here the
cache is keyed by FINGERPRINT, so hits serve cross-file exactly as far as
dedup reached, and a hit books zero decode bytes in the read-amplification
ledger (reduction/accounting.py:118 record_container_decode never fires) —
the compounding win ROADMAP item 1 chases.

The :class:`ReadCoalescer` is a group-commit discipline for reads (bounded
admission, a short window, drain up to ``depth`` requests, lead-timeline
binding with mirrored spans): concurrent readers' container-decode misses
group into ONE
``ops/dispatch.block_decompress_batch`` call per window, so a container
wanted by N readers decodes once and the per-call dispatch overhead
amortizes across the group.  LZ4 decode itself is byte-serial host work by
design (ops/reconstruct.py:1-30) — the batch surface is the grouped
DISPATCH seam a future device decoder slots into, not a pretend TPU
decoder; on this 1-vCPU host the honest wins are decode-once-per-container
and fewer dispatch round trips (PERF_NOTES.md round 4).  At depth 1 / on
the non-TPU backend the coalescer decodes inline on the caller's thread —
bit-identical results, no extra hops.  What crosses this plane is chunks,
never containers: a request is the ``(container, offset, length)`` of every
chunk it misses, the store copies those out of its decoded containers
(``ContainerStore.read_chunks``, which records the load, decode and copy
phases) and a whole decoded container never leaves it.  Reads still
attribute ≥95% of wall through the PR 11 read timelines: the worker binds
the lead reader's timeline for the real spans and mirrors the window to
every other member as ``container_decode``; the reader-side wait is its
own ``decode_wait`` transport phase.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field

from hdrf_tpu.ops import dispatch
from hdrf_tpu.utils import metrics, profiler, qos

_M = metrics.registry("read_plane")


def chunk_cache_hit_ratio() -> float:
    """Decoded-chunk cache hit ratio over the process's cumulative
    ``chunk_cache_hit``/``chunk_cache_miss`` counters (0.0 before any
    probe) — the /prom + /health gauge, the chunk-granular sibling of
    storage/container_store.py:38 cache_hit_ratio."""
    hits, misses = _M.counter("chunk_cache_hit"), _M.counter("chunk_cache_miss")
    total = hits + misses
    return hits / total if total else 0.0


def _gauge_hit_ratio() -> None:
    _M.gauge("chunk_cache_hit_ratio", chunk_cache_hit_ratio())


# ------------------------------------------------------- chunk-range plans


@dataclass
class ChunkPlan:
    """A resolved read: which chunks, from which containers, land where.

    ``wanted[i]`` is the (container_id, offset, length) of the i-th needed
    chunk, ``hashes[i]`` its fingerprint (the chunk-cache key), and
    ``spans[i]`` the (out_at, src_lo, n) scatter into the output buffer —
    the same three-list shape DedupScheme.reconstruct built inline before
    this plane existed (reduction/dedup.py:289)."""

    block_id: int
    offset: int
    end: int
    logical_len: int
    wanted: list = field(default_factory=list)   # (cid, off, len) per chunk
    hashes: list = field(default_factory=list)   # fingerprint per chunk
    spans: list = field(default_factory=list)    # (out_at, src_lo, n)

    @property
    def out_len(self) -> int:
        return max(self.end - self.offset, 0)

    def containers(self) -> list:
        """Distinct containers the plan touches, in first-use order."""
        return list(dict.fromkeys(cid for cid, _, _ in self.wanted))


def resolve_chunk_plan(index, block_id: int, offset: int = 0,
                       length: int = -1) -> ChunkPlan:
    """Position→chunk-range resolution over the chunk index: the chunks
    overlapping [offset, offset+length), found by position
    (``ChunkIndex.block_range``: the block's hash list is not walked, only
    the overlapping chunks are looked up) (quickBuildMT's
    group-by-container lookup, DataConstructor.java:360-417, with the
    range cut the reference never does).  ``length=-1`` means to EOF;
    a zero-length / past-EOF request resolves to an empty plan.  Raises
    KeyError for an unindexed block and IOError for a chunk missing from
    the index or a length-sum mismatch (index corruption)."""
    found = index.block_range(block_id, offset, length)
    if found is None:
        raise KeyError(f"block {block_id} not in chunk index")
    logical_len, pos, chunks = found
    end = logical_len if length < 0 else min(offset + length, logical_len)
    plan = ChunkPlan(block_id=block_id, offset=offset, end=end,
                     logical_len=logical_len)
    for h, loc in chunks:
        c_start, c_len = pos, loc.length
        pos += c_len
        lo = max(offset, c_start) - c_start
        hi = min(end, c_start + c_len) - c_start
        plan.wanted.append((loc.container_id, loc.offset, loc.length))
        plan.hashes.append(h)
        plan.spans.append((max(offset, c_start) - offset, lo, hi - lo))
    return plan


# ------------------------------------------------------ decoded-chunk LRU


class ChunkCache:
    """Byte-budgeted true-LRU of decoded chunks keyed by fingerprint.

    Sits ABOVE the decoded-container LRU (container_store.py:120): a hit
    here never reaches ``read_container``, so no decode bytes book in the
    read-amplification ledger and the hit serves any file that dedup'd the
    chunk.  Each entry remembers the container it was sliced from so a
    quarantine/delete invalidation (scrubber interplay) can drop exactly
    the entries whose backing bytes are gone."""

    def __init__(self, capacity_bytes: int):
        self._cap = max(int(capacity_bytes), 0)
        self._lock = threading.Lock()
        self._data: dict[bytes, bytes] = {}      # fp -> chunk (LRU order)
        self._cid_of: dict[bytes, int] = {}      # fp -> source container
        self._by_cid: dict[int, set] = {}        # cid -> {fp, ...}
        self._bytes = 0

    @property
    def capacity(self) -> int:
        return self._cap

    @property
    def bytes_used(self) -> int:
        return self._bytes

    def get(self, fp: bytes) -> bytes | None:
        return self.get_many([fp])[0]

    def get_many(self, fps: list) -> list:
        """One probe a fingerprint (None for a miss) under ONE hold of the
        lock, the counters moved once: a plan probes a hundred and more."""
        out = []
        with self._lock:
            for fp in fps:
                data = self._data.pop(fp, None)
                if data is not None:
                    # true LRU: re-insert on hit (same discipline as the
                    # container LRU — FIFO evicts the hottest under cycles)
                    self._data[fp] = data
                out.append(data)
        hits = len(out) - out.count(None)
        if hits:
            _M.incr("chunk_cache_hit", hits)
        if len(out) > hits:
            _M.incr("chunk_cache_miss", len(out) - hits)
        _gauge_hit_ratio()
        return out

    def put(self, fp: bytes, data: bytes, cid: int) -> None:
        self.put_many([(fp, data, cid)])

    def put_many(self, items: list) -> None:
        """``(fp, data, cid)`` each, under one hold of the lock."""
        if self._cap <= 0:
            return  # disabled
        evicted = 0
        with self._lock:
            for fp, data, cid in items:
                if len(data) > self._cap:
                    continue  # a chunk that would evict everything
                if fp in self._data:
                    self._drop_locked(fp)
                self._data[fp] = data
                self._cid_of[fp] = cid
                self._by_cid.setdefault(cid, set()).add(fp)
                self._bytes += len(data)
                while self._bytes > self._cap:
                    self._drop_locked(next(iter(self._data)))
                    evicted += 1
            _M.gauge("chunk_cache_bytes", self._bytes)
        if evicted:
            _M.incr("chunk_cache_evict", evicted)

    def _drop_locked(self, fp: bytes) -> None:
        data = self._data.pop(fp, None)
        if data is None:
            return
        self._bytes -= len(data)
        cid = self._cid_of.pop(fp)
        peers = self._by_cid.get(cid)
        if peers is not None:
            peers.discard(fp)
            if not peers:
                del self._by_cid[cid]

    def invalidate_container(self, cid: int) -> int:
        """Drop every cached chunk sliced from ``cid`` — wired to the
        store's quarantine/delete retirement hook so a scrub-condemned or
        compacted-away container can never serve another chunk from this
        cache.  Returns entries dropped."""
        with self._lock:
            fps = list(self._by_cid.get(cid, ()))
            for fp in fps:
                self._drop_locked(fp)
            if fps:
                _M.incr("chunk_cache_invalidated", len(fps))
                _M.gauge("chunk_cache_bytes", self._bytes)
        return len(fps)


# ---------------------------------------------------------- read coalescer


class _Req:
    __slots__ = ("locs", "future", "timeline", "tenant")

    def __init__(self, locs: list, future: Future, timeline,
                 tenant: str | None = None) -> None:
        self.locs = locs
        self.future = future
        self.timeline = timeline
        self.tenant = tenant


class ReadCoalescer:
    """Bounded batching of container-decode misses: concurrent readers'
    misses that land within one ``read_batch_window_ms`` window decode
    through ONE grouped ``block_decompress_batch`` dispatch on the
    coalescer's thread, each distinct container once.  Admission is
    bounded by the ``read_max_inflight`` semaphore.  ``batched=False``
    (depth 1 / non-TPU backend) decodes inline on the caller's thread."""

    def __init__(self, containers, window_ms: float = 2.0,
                 max_inflight: int = 16, depth: int = 8,
                 backend: str = "native", batched: bool | None = None,
                 qos_ctrl=None):
        self._containers = containers
        self._window_s = max(window_ms, 0.0) / 1000.0
        self._depth = max(depth, 1)
        self._backend = backend
        self._qos = qos_ctrl
        self._sem = threading.BoundedSemaphore(max(max_inflight, 1))
        # weighted-fair dequeue across tenants (utils/qos.py FairQueue) —
        # a flooding tenant's queued decode groups cannot starve a light
        # tenant's (the coalescer window still batches across lanes)
        self._q = qos.FairQueue()
        self._thread: threading.Thread | None = None
        if batched is None:
            batched = backend == "tpu" and window_ms > 0 and max_inflight > 1
        if batched:
            self._thread = threading.Thread(target=self._loop,
                                            name="read-plane", daemon=True)
            self._thread.start()

    def _decomp(self, codec_names, blobs, usizes, outs):
        return dispatch.block_decompress_batch(codec_names, blobs, usizes,
                                               outs, self._backend)

    def fetch(self, locs: list, timeline=None,
              tenant: str | None = None) -> list:
        """Decoded chunk bytes, one per ``locs`` entry (``(cid, off,
        len)``): the store copies them out of its decoded containers
        (``ContainerStore.read_chunks`` records the load, decode and copy
        phases), a whole container never leaves it.  Blocks at the
        admission bound; in batched mode the call parks on the group's
        future while the worker decodes under the lead member's timeline.
        Sheds (qos.ShedError) BEFORE acquiring a permit when the ambient
        tenant is over rate or the deadline cannot cover the estimate."""
        if tenant is None:
            tenant = qos.current_tenant()
        # unattributed callers (scrub, EC reconstruction, compaction) are
        # internal housekeeping — never shed them, only client traffic
        if self._qos is not None and tenant is not None:
            self._qos.admit(tenant, "read")
        with profiler.phase("read_admit"):
            admitted = self._sem.acquire(timeout=300)
        if not admitted:
            raise TimeoutError("read plane admission timeout")
        try:
            if self._thread is None:
                _M.incr("inline_decodes")
                return self._containers.read_chunks(
                    locs, decompress_batch=self._decomp)
            fut: Future = Future()
            self._q.put(_Req(list(locs), fut,
                             timeline or profiler.current_timeline(),
                             tenant))
            with profiler.phase("decode_wait"):
                return fut.result(timeout=300)
        finally:
            self._sem.release()

    def close(self) -> None:
        if self._thread is not None:
            self._q.put(None)
            self._thread.join()
            self._thread = None

    def _loop(self) -> None:
        while True:
            req = self._q.get()
            if req is None:
                return
            group = [req]
            deadline = time.monotonic() + self._window_s
            stopping = False
            while len(group) < self._depth:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remain)
                except queue.Empty:
                    break
                if nxt is None:
                    stopping = True
                    break
                group.append(nxt)
            self._serve(group)
            if stopping:
                return

    def _serve(self, group: list) -> None:
        locs = [loc for r in group for loc in r.locs]
        lead = group[0].timeline
        t0 = profiler.mark()
        try:
            # the lead reader's timeline is ambient for the real load /
            # decode / copy spans; the shared window is mirrored to the
            # rest below
            with profiler.bind_timeline(lead):
                chunks = self._containers.read_chunks(
                    locs, decompress_batch=self._decomp)
        except BaseException as e:  # noqa: BLE001 — readers unwrap
            for r in group:
                if not r.future.done():
                    r.future.set_exception(e)
            return
        t1 = profiler.mark()
        _M.incr("read_batches")
        _M.observe("read_batch_containers", len({loc[0] for loc in locs}))
        if len(group) > 1:
            _M.incr("coalesced_reads", len(group))
        at = 0
        for i, r in enumerate(group):
            if r.timeline is not None and i > 0:
                r.timeline.add_span("container_decode", t0, t1, 0)
            r.future.set_result(chunks[at:at + len(r.locs)])
            at += len(r.locs)


# ------------------------------------------------------------- the facade


class ReadPlane:
    """The DN's chunk-granular serving engine: plan → cache → coalescer.

    ``fetch_chunks(plan)`` probes the decoded-chunk cache per fingerprint,
    groups the misses by container, decodes those containers through the
    coalescer (once each, batched across concurrent readers), slices the
    missed chunks out and back-fills the cache.  Per-plan decode fan-out is
    exported as ``containers_decoded_per_read`` — the acceptance gauge that
    a range read touches exactly the containers overlapping its range."""

    def __init__(self, containers, chunk_cache_mb: float = 8,
                 window_ms: float = 2.0, max_inflight: int = 16,
                 backend: str = "native", batched: bool | None = None,
                 qos_ctrl=None):
        self.cache = ChunkCache(int(chunk_cache_mb * (1 << 20)))
        self.coalescer = ReadCoalescer(containers, window_ms=window_ms,
                                       max_inflight=max_inflight,
                                       backend=backend, batched=batched,
                                       qos_ctrl=qos_ctrl)
        self._containers = containers

    def attach_store(self, containers) -> None:
        """Install the cache-invalidation hook on the store (quarantine or
        delete retires a container → its cached chunks drop)."""
        containers._on_retire = self.cache.invalidate_container

    def fetch_chunks(self, plan: ChunkPlan) -> list:
        """Decoded chunk bytes, one per ``plan.wanted`` entry."""
        with profiler.phase("cache_probe"):
            out = self.cache.get_many(plan.hashes)
        misses = [i for i, data in enumerate(out) if data is None]
        decoded = 0
        if misses:
            locs = [plan.wanted[i] for i in misses]
            decoded = len({loc[0] for loc in locs})
            chunks = self.coalescer.fetch(locs)
            for i, chunk in zip(misses, chunks):
                out[i] = chunk
            with profiler.phase("cache_probe"):     # the back-fill
                self.cache.put_many([(plan.hashes[i], out[i],
                                      plan.wanted[i][0]) for i in misses])
        _M.incr("plans_served")
        _M.incr("containers_fetched", decoded)
        _M.observe("containers_decoded_per_read", decoded)
        return out

    def close(self) -> None:
        self.coalescer.close()
