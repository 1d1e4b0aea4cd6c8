"""Read-plane serving engine: position→chunk-range resolver edges, the
DN-wide decoded-chunk cache (zero decode bytes on hit, cross-file hits,
byte-budget eviction, retirement invalidation), the read coalescer, and
hedged replica reads — plus the PR's acceptance assertions (range reads
decode exactly the overlapping containers; chunk-cache reads beat the
full-decode baseline on read amplification)."""

import threading

import numpy as np
import pytest

from hdrf_tpu.client.filesystem import HdrfClient
from hdrf_tpu.config import ClientConfig, ReductionConfig
from hdrf_tpu.index.chunk_index import ChunkIndex
from hdrf_tpu.reduction import scheme as schemes
from hdrf_tpu.reduction.scheme import ReductionContext
from hdrf_tpu.server.read_plane import (ChunkCache, ReadCoalescer, ReadPlane,
                                        resolve_chunk_plan)
from hdrf_tpu.storage.container_store import ContainerStore
from hdrf_tpu.utils import metrics

_RP = metrics.registry("read_plane")
_ACC = metrics.registry("reduction_accounting")
_CL = metrics.registry("client")


def _phys() -> int:
    """Decoded-container bytes booked against the dedup_lz4 scheme — the
    read-amplification ledger's physical side (a chunk-cache hit must
    leave this untouched)."""
    return _ACC.counter("read_physical_bytes__dedup_lz4")


def make_ctx(tmp_path, *, container_size: int = 1 << 18,
             cache_containers: int = 4, with_plane: bool = True,
             chunk_cache_mb: float = 8.0, window_ms: float = 0.0,
             batched=None, mask_bits: int = 10, min_chunk: int = 256,
             max_chunk: int = 8192) -> ReductionContext:
    cfg = ReductionConfig()
    cfg.cdc.mask_bits = mask_bits
    cfg.cdc.min_chunk = min_chunk
    cfg.cdc.max_chunk = max_chunk
    containers = ContainerStore(str(tmp_path / "containers"),
                                container_size=container_size, lanes=2,
                                cache_containers=cache_containers)
    ctx = ReductionContext(
        config=cfg, containers=containers,
        index=ChunkIndex(str(tmp_path / "index")), backend="native")
    if with_plane:
        rp = ReadPlane(containers, chunk_cache_mb=chunk_cache_mb,
                       window_ms=window_ms, backend="native", batched=batched)
        rp.attach_store(containers)
        ctx.read_plane = rp
    return ctx


def _chunk_starts(ctx, block_id: int) -> list:
    """Logical start offset of every chunk in the block, from the index
    (the ground truth the resolver walks)."""
    entry = ctx.index.get_block(block_id)
    locmap = ctx.index.lookup_chunks(list(set(entry.hashes)))
    starts, pos = [], 0
    for h in entry.hashes:
        starts.append(pos)
        pos += locmap[h].length
    return starts


# The 7 standard corpora (tests/test_cdc_pallas.py::_corpora, copied
# verbatim — the test_mesh_plane.py precedent) drive the bit-identity
# sweep; (mask, mn, mx) map onto CdcConfig via mask.bit_count().
def _corpora():
    rng = np.random.default_rng(7)
    text = rng.integers(97, 123, size=200_000, dtype=np.uint8)
    yield "random", rng.integers(0, 256, 150_000, dtype=np.uint8), \
        0x1FFF, 2048, 65536
    yield "text-low-entropy", text, 0x1FFF, 2048, 65536
    # sparse mask -> candidate droughts -> forced max-chunk runs
    yield "forced-max-runs", rng.integers(0, 256, 120_000, dtype=np.uint8), \
        0xFFFFFF, 512, 4096
    # dense mask + tiny limits: every-word candidates, lo>hi edge traffic
    yield "dense", rng.integers(0, 256, 30_000, dtype=np.uint8), 0x7, 8, 64
    # block tail shorter than min_chunk: final cut is the short remainder
    yield "tail-short-chunk", rng.integers(0, 256, 65536 + 37,
                                           dtype=np.uint8), \
        0x1FFF, 2048, 65536
    # one supertile exactly / less than one supertile
    yield "single-tile", rng.integers(0, 256, 65536, dtype=np.uint8), \
        0x3FF, 256, 8192
    yield "sub-tile", rng.integers(0, 256, 300, dtype=np.uint8), 0x3F, 16, 128


def _blob(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


# ------------------------------------------------------------ the resolver


class TestResolver:
    def test_zero_length_and_past_eof(self, tmp_path):
        ctx = make_ctx(tmp_path)
        s = schemes.get("dedup_lz4")
        data = _blob(3, 50_000)
        s.reduce(1, data, ctx)
        for off, ln in [(1000, 0), (len(data), -1), (len(data) + 5, 100)]:
            plan = resolve_chunk_plan(ctx.index, 1, off, ln)
            assert plan.out_len == 0 and not plan.wanted
            assert s.reconstruct(1, b"", len(data), ctx, off, ln) == b""

    def test_unknown_block_raises(self, tmp_path):
        ctx = make_ctx(tmp_path)
        with pytest.raises(KeyError):
            resolve_chunk_plan(ctx.index, 404)

    def test_offset_exactly_on_cut_boundary(self, tmp_path):
        ctx = make_ctx(tmp_path)
        s = schemes.get("dedup_lz4")
        data = _blob(4, 80_000)
        s.reduce(2, data, ctx)
        starts = _chunk_starts(ctx, 2)
        assert len(starts) >= 3
        cut = starts[2]  # an interior cut boundary
        plan = resolve_chunk_plan(ctx.index, 2, cut, 100)
        # the preceding chunk must NOT be touched: the first wanted chunk
        # begins at the cut itself (src_lo == 0)
        assert plan.spans[0] == (0, 0, min(100, plan.out_len))
        assert s.reconstruct(2, b"", len(data), ctx, cut, 100) \
            == data[cut:cut + 100]

    def test_tail_read_open_length(self, tmp_path):
        ctx = make_ctx(tmp_path)
        s = schemes.get("dedup_lz4")
        data = _blob(5, 60_000)
        s.reduce(3, data, ctx)
        plan = resolve_chunk_plan(ctx.index, 3, len(data) - 777, -1)
        assert plan.out_len == 777
        assert s.reconstruct(3, b"", len(data), ctx, len(data) - 777, -1) \
            == data[-777:]

    def test_span_across_container_seal_boundary(self, tmp_path):
        # 64 KiB containers force a multi-container block; a range
        # straddling the seal boundary must touch exactly the two
        # adjacent containers.
        ctx = make_ctx(tmp_path, container_size=1 << 16)
        s = schemes.get("dedup_lz4")
        data = _blob(6, 300_000)
        s.reduce(4, data, ctx)
        full = resolve_chunk_plan(ctx.index, 4)
        assert len(full.containers()) >= 2
        edge = next(i for i in range(1, len(full.wanted))
                    if full.wanted[i][0] != full.wanted[i - 1][0])
        boundary = full.spans[edge][0]  # logical start of the first chunk
        plan = resolve_chunk_plan(ctx.index, 4, boundary - 16, 32)
        assert plan.containers() == [full.wanted[edge - 1][0],
                                     full.wanted[edge][0]]
        assert s.reconstruct(4, b"", len(data), ctx, boundary - 16, 32) \
            == data[boundary - 16:boundary + 16]

    def test_pre_resolved_plan_is_honored(self, tmp_path):
        ctx = make_ctx(tmp_path)
        s = schemes.get("dedup_lz4")
        data = _blob(8, 40_000)
        s.reduce(5, data, ctx)
        plan = resolve_chunk_plan(ctx.index, 5, 1000, 2000)
        assert s.reconstruct(5, b"", len(data), ctx, plan=plan) \
            == data[1000:3000]

    @pytest.mark.parametrize("name,a,mask,mn,mx", list(_corpora()),
                             ids=[c[0] for c in _corpora()])
    def test_range_bit_identity(self, tmp_path, name, a, mask, mn, mx):
        ctx = make_ctx(tmp_path, container_size=1 << 16,
                       mask_bits=mask.bit_count(), min_chunk=mn,
                       max_chunk=mx)
        s = schemes.get("dedup_lz4")
        data = a.tobytes()
        s.reduce(9, data, ctx)
        assert s.reconstruct(9, b"", len(data), ctx) == data
        n = len(data)
        ranges = [(0, 10), (0, -1), (n // 3, n // 3), (n - 7, -1),
                  (n // 2, 1), (1, n - 2)]
        ranges += [(c, 64) for c in _chunk_starts(ctx, 9)[:3]]
        for off, ln in ranges:
            end = n if ln < 0 else min(off + ln, n)
            assert s.reconstruct(9, b"", len(data), ctx, off, ln) \
                == data[off:end], (name, off, ln)


# -------------------------------------------- acceptance: decode fan-out


class TestRangeDecodesOnlyOverlap:
    def test_single_container_span_decodes_one(self, tmp_path):
        # chunk cache OFF and container LRU OFF so every read's decode
        # fan-out is observable in containers_fetched / physical bytes
        ctx = make_ctx(tmp_path, container_size=1 << 16, cache_containers=0,
                       chunk_cache_mb=0)
        s = schemes.get("dedup_lz4")
        data = _blob(10, 300_000)
        s.reduce(6, data, ctx)
        full = resolve_chunk_plan(ctx.index, 6)
        assert len(full.containers()) >= 2
        f0, p0, phys0 = (_RP.counter("containers_fetched"),
                         _RP.counter("plans_served"), _phys())
        assert s.reconstruct(6, b"", len(data), ctx, 100, 64) \
            == data[100:164]
        assert _RP.counter("plans_served") - p0 == 1
        assert _RP.counter("containers_fetched") - f0 == 1
        phys_range = _phys() - phys0
        phys1 = _phys()
        assert s.reconstruct(6, b"", len(data), ctx) == data
        phys_full = _phys() - phys1
        # the ≤1-container range decoded strictly less than the full block
        assert 0 < phys_range < phys_full


# ------------------------------------------------------ decoded-chunk LRU


class TestChunkCacheSemantics:
    def test_hit_books_zero_decode_bytes(self, tmp_path):
        ctx = make_ctx(tmp_path, cache_containers=0)
        s = schemes.get("dedup_lz4")
        data = _blob(11, 120_000)
        s.reduce(7, data, ctx)
        assert s.reconstruct(7, b"", len(data), ctx) == data  # warm
        h0, f0, phys0 = (_RP.counter("chunk_cache_hit"),
                         _RP.counter("containers_fetched"), _phys())
        assert s.reconstruct(7, b"", len(data), ctx) == data
        assert _phys() == phys0                       # ZERO decode bytes
        assert _RP.counter("containers_fetched") == f0
        assert _RP.counter("chunk_cache_hit") > h0

    def test_cross_file_dedup_hit(self, tmp_path):
        # same content under a DIFFERENT block id: dedup maps both hash
        # lists onto the same chunks, so reading file B after file A is
        # pure cache hits — zero decode bytes booked for B.
        ctx = make_ctx(tmp_path, cache_containers=0)
        s = schemes.get("dedup_lz4")
        data = _blob(12, 100_000)
        s.reduce(1, data, ctx)
        s.reduce(2, data, ctx)
        assert s.reconstruct(1, b"", len(data), ctx) == data  # warm via A
        h0, phys0 = _RP.counter("chunk_cache_hit"), _phys()
        assert s.reconstruct(2, b"", len(data), ctx) == data  # read B
        assert _phys() == phys0
        assert _RP.counter("chunk_cache_hit") > h0

    def test_byte_budget_eviction_order(self):
        cache = ChunkCache(1000)
        e0 = _RP.counter("chunk_cache_evict")
        cache.put(b"a" * 32, b"x" * 400, cid=1)
        cache.put(b"b" * 32, b"y" * 400, cid=1)
        assert cache.get(b"a" * 32) is not None  # recency bump: a is MRU
        cache.put(b"c" * 32, b"z" * 400, cid=2)  # over budget -> evict LRU
        assert _RP.counter("chunk_cache_evict") - e0 == 1
        assert cache.get(b"b" * 32) is None      # b was LRU, not a
        assert cache.get(b"a" * 32) == b"x" * 400
        assert cache.get(b"c" * 32) == b"z" * 400
        assert cache.bytes_used <= cache.capacity

    def test_disabled_and_oversized(self):
        off = ChunkCache(0)
        off.put(b"f" * 32, b"data", cid=1)
        assert off.get(b"f" * 32) is None and off.bytes_used == 0
        small = ChunkCache(10)
        small.put(b"g" * 32, b"x" * 11, cid=1)  # would evict everything
        assert small.get(b"g" * 32) is None and small.bytes_used == 0

    def test_quarantine_invalidates_cached_chunks(self, tmp_path):
        ctx = make_ctx(tmp_path, container_size=1 << 16)
        s = schemes.get("dedup_lz4")
        data = _blob(13, 300_000)
        s.reduce(8, data, ctx)
        assert s.reconstruct(8, b"", len(data), ctx) == data  # warm
        cache = ctx.read_plane.cache
        assert cache.bytes_used > 0
        plan = resolve_chunk_plan(ctx.index, 8)
        victim = plan.containers()[0]
        inv0 = _RP.counter("chunk_cache_invalidated")
        ctx.containers.quarantine(victim)
        assert _RP.counter("chunk_cache_invalidated") > inv0
        for fp, (cid, _, _) in zip(plan.hashes, plan.wanted):
            if cid == victim:
                assert cache.get(fp) is None  # retired bytes never served

    def test_delete_invalidates_cached_chunks(self, tmp_path):
        ctx = make_ctx(tmp_path, container_size=1 << 16)
        s = schemes.get("dedup_lz4")
        data = _blob(14, 300_000)
        s.reduce(9, data, ctx)
        assert s.reconstruct(9, b"", len(data), ctx) == data
        cache = ctx.read_plane.cache
        plan = resolve_chunk_plan(ctx.index, 9)
        victim = plan.containers()[-1]
        before = cache.bytes_used
        ctx.containers.delete_container(victim)
        assert cache.bytes_used < before
        for fp, (cid, _, _) in zip(plan.hashes, plan.wanted):
            if cid == victim:
                assert cache.get(fp) is None

    def test_read_amp_strictly_below_full_decode_baseline(self, tmp_path):
        # the PR's headline acceptance: repeated reads through the chunk
        # cache book strictly fewer physical bytes than the same reads
        # through the full-decode path (container LRU off on both sides —
        # the fleet-scale working set where containers don't fit the LRU)
        data = _blob(15, 150_000)
        s = schemes.get("dedup_lz4")
        costs = {}
        for mode, with_plane in (("plane", True), ("baseline", False)):
            ctx = make_ctx(tmp_path / mode, cache_containers=0,
                           with_plane=with_plane)
            s.reduce(1, data, ctx)
            phys0 = _phys()
            for _ in range(3):
                assert s.reconstruct(1, b"", len(data), ctx) == data
            costs[mode] = _phys() - phys0
        assert 0 < costs["plane"] < costs["baseline"]


# --------------------------------------------------------- read coalescer


class TestCoalescer:
    def _commit(self, tmp_path, seed=16, n=300_000):
        """(ctx, the block's chunk locations, the chunks' own bytes)."""
        ctx = make_ctx(tmp_path, container_size=1 << 16, with_plane=False)
        schemes.get("dedup_lz4").reduce(1, _blob(seed, n), ctx)
        locs = resolve_chunk_plan(ctx.index, 1).wanted
        want = [ctx.containers.read_container(cid)[off:off + ln]
                for cid, off, ln in locs]
        return ctx, locs, want

    def test_inline_fallback_on_native_backend(self, tmp_path):
        ctx, locs, want = self._commit(tmp_path)
        co = ReadCoalescer(ctx.containers, window_ms=2.0, backend="native")
        assert co._thread is None  # non-TPU backend: no worker spun up
        i0 = _RP.counter("inline_decodes")
        # chunks of the first two containers, out of order
        pick = [i for i, loc in enumerate(locs)
                if loc[0] in {locs[0][0], locs[-1][0]}][::-1]
        assert len({locs[i][0] for i in pick}) == 2
        chunks = co.fetch([locs[i] for i in pick])
        assert _RP.counter("inline_decodes") - i0 == 1
        assert chunks == [want[i] for i in pick]
        assert all(type(c) is bytes for c in chunks)
        co.close()

    def test_batched_groups_concurrent_readers(self, tmp_path):
        ctx, locs, want = self._commit(tmp_path)
        co = ReadCoalescer(ctx.containers, window_ms=300.0, max_inflight=8,
                           batched=True)
        try:
            b0, c0 = (_RP.counter("read_batches"),
                      _RP.counter("coalesced_reads"))
            barrier = threading.Barrier(2)
            results = [None, None]
            # two readers, overlapping ranges of the first container
            asks = [[0, 1, 2], [2, 1]]

            def reader(i):
                barrier.wait()
                results[i] = co.fetch([locs[k] for k in asks[i]])

            ts = [threading.Thread(target=reader, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            # both landed in ONE window: one batch, both members coalesced
            assert _RP.counter("read_batches") - b0 == 1
            assert _RP.counter("coalesced_reads") - c0 == 2
            # each member got its own chunks back, in its own order
            assert results[0] == [want[k] for k in asks[0]]
            assert results[1] == [want[k] for k in asks[1]]
        finally:
            co.close()

    def test_batched_propagates_errors(self, tmp_path):
        ctx, _, _ = self._commit(tmp_path)
        co = ReadCoalescer(ctx.containers, window_ms=1.0, batched=True)
        try:
            with pytest.raises(Exception):
                co.fetch([(987654, 0, 16)])  # no such container
        finally:
            co.close()


# ------------------------------------------------------ hedged replica reads


def _hedge_client(**cfg_kw) -> HdrfClient:
    cfg = ClientConfig(short_circuit=False, **cfg_kw)
    return HdrfClient(("127.0.0.1", 1), config=cfg, name="hedge-test")


def _binfo():
    return {"block_id": 42, "token": None,
            "locations": [{"addr": ("10.0.0.1", 1001)},
                          {"addr": ("10.0.0.2", 1002)}]}


class TestHedgedReads:
    def test_hedge_fires_on_primary_failure(self):
        c = _hedge_client(read_hedge_floor_s=5.0)

        def fake_read(addr, block_id, offset, length, token=None):
            if addr[0] == "10.0.0.1":
                raise ConnectionError("primary down")
            return b"replica-bytes"

        c._read_from = fake_read
        f0, w0 = (_CL.counter("read_hedges_fired"),
                  _CL.counter("read_hedge_wins"))
        assert c._read_block(_binfo(), 0, -1) == b"replica-bytes"
        # fail-fast: the hedge launched immediately, well before the 5 s
        # deadline, and the hedge leg won
        assert _CL.counter("read_hedges_fired") - f0 == 1
        assert _CL.counter("read_hedge_wins") - w0 == 1

    def test_hedge_fires_on_slow_primary(self):
        c = _hedge_client(read_hedge_floor_s=0.05)
        release = threading.Event()

        def fake_read(addr, block_id, offset, length, token=None):
            if addr[0] == "10.0.0.1":
                release.wait(timeout=10)  # primary stalls past the deadline
                return b"slow-primary"
            return b"fast-hedge"

        c._read_from = fake_read
        w0 = _CL.counter("read_hedge_wins")
        try:
            assert c._read_block(_binfo(), 0, -1) == b"fast-hedge"
        finally:
            release.set()
        assert _CL.counter("read_hedge_wins") - w0 == 1

    def test_primary_win_is_not_a_hedge_win(self):
        c = _hedge_client(read_hedge_floor_s=5.0)
        c._read_from = lambda *a, **k: b"primary"
        f0, w0 = (_CL.counter("read_hedges_fired"),
                  _CL.counter("read_hedge_wins"))
        assert c._read_block(_binfo(), 0, -1) == b"primary"
        assert _CL.counter("read_hedges_fired") == f0
        assert _CL.counter("read_hedge_wins") == w0

    def test_disabled_restores_serial_failover(self):
        c = _hedge_client(hedged_reads=False)
        calls = []

        def fake_read(addr, block_id, offset, length, token=None):
            calls.append(addr)
            if len(calls) == 1:
                raise ConnectionError("first replica down")
            return b"serial"

        c._read_from = fake_read
        f0 = _CL.counter("read_hedges_fired")
        assert c._read_block(_binfo(), 0, -1) == b"serial"
        assert calls == [("10.0.0.1", 1001), ("10.0.0.2", 1002)]
        assert _CL.counter("read_hedges_fired") == f0

    def test_all_locations_failed(self):
        c = _hedge_client(read_hedge_floor_s=0.01)

        def fake_read(addr, block_id, offset, length, token=None):
            raise ConnectionError(f"{addr} down")

        c._read_from = fake_read
        with pytest.raises(IOError, match="all 2 locations failed"):
            c._read_block(_binfo(), 0, -1)
