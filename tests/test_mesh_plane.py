"""Mesh-sharded reduction plane: MeshReducer/ShardedBucketTable
(parallel/sharded.py) against the native oracle and the product's dedup
path (ISSUE 9 tentpole).

Everything runs on the conftest-provided 8-virtual-device XLA:CPU mesh.
Pinned here: bit-identity of the one-dispatch mesh step vs the native
C++ oracle (native/src/cdc.cpp:16-62 + sha256.cpp:8-150) across the 7
standard CDC corpora (tests/test_cdc_pallas.py::_corpora — same
generator seed/order, the shared fixture contract), the device-ledger
shape (one mesh step == ONE "sharded.step" enqueue, zero per-chunk host
round-trips in the probe), stale-bucket safety (false positive resolved
by the authoritative index re-check, false negative degrades to a
compactable duplicate append — never corruption; the
"sharded.bucket_refresh" fault point re-queues on failure), the
ContainerStore true-LRU decode cache, and a mesh step over blocks of
mixed sizes.
"""

import numpy as np
import pytest

from hdrf_tpu import native
from hdrf_tpu.config import CdcConfig, ReductionConfig
from hdrf_tpu.index.chunk_index import ChunkIndex
from hdrf_tpu.parallel import sharded
from hdrf_tpu.reduction import scheme as schemes
from hdrf_tpu.reduction.scheme import ReductionContext
from hdrf_tpu.storage.container_store import ContainerStore
from hdrf_tpu.utils import device_ledger, fault_injection, metrics


def _corpora():
    """The 7 standard CDC corpora — generator params copied verbatim from
    tests/test_cdc_pallas.py::_corpora (seed 7, text drawn FIRST: draw
    order is part of the corpus identity)."""
    rng = np.random.default_rng(7)
    text = rng.integers(97, 123, size=200_000, dtype=np.uint8)
    yield "random", rng.integers(0, 256, 150_000, dtype=np.uint8), \
        0x1FFF, 2048, 65536
    yield "text-low-entropy", text, 0x1FFF, 2048, 65536
    yield "forced-max-runs", rng.integers(0, 256, 120_000, dtype=np.uint8), \
        0xFFFFFF, 512, 4096
    yield "dense", rng.integers(0, 256, 30_000, dtype=np.uint8), 0x7, 8, 64
    yield "tail-short-chunk", rng.integers(0, 256, 65536 + 37,
                                           dtype=np.uint8), \
        0x1FFF, 2048, 65536
    yield "single-tile", rng.integers(0, 256, 65536, dtype=np.uint8), \
        0x3FF, 256, 8192
    yield "sub-tile", rng.integers(0, 256, 300, dtype=np.uint8), 0x3F, 16, 128


def _oracle(a: np.ndarray, mask: int, mn: int, mx: int):
    a = np.ascontiguousarray(a)
    cuts = native.cdc_chunk(a, mask, mn, mx)
    starts = np.concatenate([[0], cuts[:-1]]).astype(np.uint64)
    digs = native.sha256_batch(a, starts, (cuts - starts).astype(np.uint64))
    return cuts, digs


def _mesh_reducer(mask: int, mn: int, mx: int, **kw) -> sharded.MeshReducer:
    cdc = CdcConfig(mask_bits=max(bin(mask).count("1"), 1),
                    min_chunk=mn, max_chunk=mx)
    mesh = sharded.make_mesh(n_data=8, n_seq=1)
    return sharded.MeshReducer(cdc, mesh, mask=mask, **kw)


@pytest.mark.parametrize("name,a,mask,mn,mx", list(_corpora()),
                         ids=[c[0] for c in _corpora()])
def test_mesh_step_bit_identical_to_oracle(name, a, mask, mn, mx):
    """The fused CDC->SHA->probe mesh step must be bit-identical to the
    serial native oracle on every corpus — a mixed-size group (full block
    + a truncated sibling), so lane binning, per-device digest-row
    reconstruction, and mesh-width padding all engage."""
    r = _mesh_reducer(mask, mn, mx)
    group = [a, np.ascontiguousarray(a[: max(len(a) // 2, 1)])]
    res = r.reduce_many(group)
    assert len(res) == len(group)
    for blk, (cuts, digs, probe) in zip(group, res):
        ref_cuts, ref_digs = _oracle(blk, mask, mn, mx)
        np.testing.assert_array_equal(cuts, ref_cuts)
        np.testing.assert_array_equal(digs, ref_digs)
        assert probe == frozenset()   # empty bucket table: no hits


def test_mesh_matches_serial_resident_reducer():
    """Cross-check against the serial single-device path itself (not just
    the shared native oracle): the ResidentReducer oracle the config knob
    keeps verbatim must agree with the mesh plane chunk-for-chunk."""
    from hdrf_tpu.ops.resident import ResidentReducer

    cdc = CdcConfig(mask_bits=10, min_chunk=256, max_chunk=4096)
    rng = np.random.default_rng(21)
    a = rng.integers(0, 256, 50_000, dtype=np.uint8)
    serial = ResidentReducer(cdc, fused_mode="off")
    s_cuts, s_digs = serial.reduce(a)
    mesh = sharded.make_mesh(n_data=8, n_seq=1)
    m_cuts, m_digs, _probe = \
        sharded.MeshReducer(cdc, mesh).reduce_many([a])[0]
    np.testing.assert_array_equal(m_cuts, np.asarray(s_cuts))
    np.testing.assert_array_equal(m_digs, np.asarray(s_digs))


def _enqueues_after(last_id: int):
    return [e for e in device_ledger.events_snapshot()
            if e["id"] > last_id and e["kind"] == "enqueue"]


def _last_id() -> int:
    evs = device_ledger.events_snapshot()
    return evs[-1]["id"] if evs else 0


class TestOneDispatchPerStep:
    def test_one_ledger_dispatch_per_mesh_step(self):
        """A coalesced group of 8 blocks = ONE "sharded.step" enqueue —
        no resident.* dispatch chain, no per-block programs (the ISSUE 9
        acceptance's device-ledger evidence, pinned)."""
        r = _mesh_reducer(0x3FF, 256, 4096)
        rng = np.random.default_rng(5)
        group = [rng.integers(0, 256, 20_000, np.uint8) for _ in range(8)]
        r.reduce_many(group)                      # warm: jit compile
        id0 = _last_id()
        steps0 = metrics.registry("mesh_plane").counter("steps")
        jobs = r.submit_many(group)
        r.finish_many(jobs)
        enq = _enqueues_after(id0)
        assert [e["op"] for e in enq] == ["sharded.step"], enq
        assert metrics.registry("mesh_plane").counter("steps") == steps0 + 1

    def test_probe_negative_skips_host_lookup_entirely(self, tmp_path):
        """Zero per-chunk host round-trips when the bucket probe voted all
        chunks unknown: dedup_commit's index walk runs over the EMPTY
        probe-positive set, not the chunk list."""
        from hdrf_tpu.reduction.dedup import dedup_commit

        index = ChunkIndex(str(tmp_path / "index"))
        containers = ContainerStore(str(tmp_path / "c"), lanes=2)
        looked_up: list[int] = []
        orig = index.lookup_chunks

        def counting(hashes):
            looked_up.append(len(hashes))
            return orig(hashes)

        index.lookup_chunks = counting
        rng = np.random.default_rng(9)
        data = rng.integers(0, 256, 60_000, np.uint8).tobytes()
        cuts, digs = _oracle(np.frombuffer(data, np.uint8), 0x3FF, 256, 4096)
        uniq = len({digs[i].tobytes() for i in range(len(digs))})
        m0 = metrics.registry("dedup").counter("probe_skipped_lookups")
        n, new, _ = dedup_commit(1, data, cuts, digs, index, containers,
                                 probe=frozenset())
        assert n == len(cuts) and new == uniq     # all committed as new
        assert sum(looked_up) == 0                # zero per-chunk walks
        assert metrics.registry("dedup").counter(
            "probe_skipped_lookups") == m0 + uniq


class TestStaleBucketSafety:
    def _ctx(self, tmp_path) -> ReductionContext:
        cfg = ReductionConfig()
        cfg.cdc.mask_bits = 10
        cfg.cdc.min_chunk = 256
        cfg.cdc.max_chunk = 8192
        return ReductionContext(
            config=cfg,
            containers=ContainerStore(str(tmp_path / "containers"),
                                      container_size=1 << 18, lanes=2),
            index=ChunkIndex(str(tmp_path / "index")),
            backend="native")

    def test_false_positive_resolved_by_host_recheck(self, tmp_path):
        """A stale/collided bucket entry flags an UNKNOWN chunk as a hit:
        the authoritative index lookup returns None, the chunk commits as
        new, and the block reads back bit-identical."""
        ctx = self._ctx(tmp_path)
        s = schemes.get("dedup_lz4")
        data = bytes(np.random.default_rng(11).integers(
            0, 256, 80_000, np.uint8))
        arr = np.frombuffer(data, np.uint8)
        cuts, digs = _oracle(arr, 0x3FF, 256, 8192)
        fp0 = metrics.registry("dedup").counter("probe_false_positive")
        # every chunk falsely flagged possibly-known
        probe = frozenset(digs[i].tobytes() for i in range(len(digs)))
        s.reduce_with(7, data, cuts, digs, ctx, probe=probe)
        assert metrics.registry("dedup").counter(
            "probe_false_positive") == fp0 + len(probe)
        assert s.reconstruct(7, b"", len(data), ctx) == data

    def test_false_negative_appends_never_corrupts(self, tmp_path):
        """A stale table misses KNOWN chunks: they re-append (orphan
        container bytes) but commit_block's first-commit-wins keeps the
        original locations — dedup quality degrades, data never does."""
        ctx = self._ctx(tmp_path)
        s = schemes.get("dedup_lz4")
        data = bytes(np.random.default_rng(12).integers(
            0, 256, 80_000, np.uint8))
        arr = np.frombuffer(data, np.uint8)
        cuts, digs = _oracle(arr, 0x3FF, 256, 8192)
        s.reduce_with(1, data, cuts, digs, ctx)       # authoritative commit
        unique0 = ctx.index.stats()["unique_chunk_bytes"]
        uniq = len({digs[i].tobytes() for i in range(len(digs))})
        stale0 = metrics.registry("dedup").counter("probe_stale_appends")
        # same content again, bucket table stale: probe misses everything
        s.reduce_with(2, data, cuts, digs, ctx, probe=frozenset())
        assert metrics.registry("dedup").counter(
            "probe_stale_appends") == stale0 + uniq
        # first commit won: no new unique bytes despite the re-append
        assert ctx.index.stats()["unique_chunk_bytes"] == unique0
        assert s.reconstruct(1, b"", len(data), ctx) == data
        assert s.reconstruct(2, b"", len(data), ctx) == data

    def test_refresh_failure_requeues_and_recovers(self):
        """A failed device refresh (fault point "sharded.bucket_refresh")
        leaves the step probing the STALE table — old verdicts hold, the
        pending rows re-queue, and the next healthy flush lands them."""
        r = _mesh_reducer(0x3FF, 256, 4096)
        rng = np.random.default_rng(3)
        a = rng.integers(0, 256, 40_000, np.uint8)
        _cuts, digs, probe = r.reduce_many([a])[0]
        assert probe == frozenset()
        half = [digs[i].tobytes() for i in range(0, len(digs), 2)]
        r.table.note_new(half)
        _c, _d, probe2 = r.reduce_many([a])[0]
        assert probe2 == frozenset(half)
        # host mirror agrees with the on-mesh verdicts
        hm = r.table.host_probe(digs)
        assert {i for i in np.nonzero(hm)[0]} == \
            {i for i in range(len(digs)) if digs[i].tobytes() in probe2}
        rest = [digs[i].tobytes() for i in range(1, len(digs), 2)]
        r.table.note_new(rest)
        fails0 = metrics.registry("mesh_plane").counter(
            "bucket_refresh_failures")

        def boom(**_kw):
            raise RuntimeError("refresh transport down")

        with fault_injection.inject("sharded.bucket_refresh", boom):
            _c, _d, probe3 = r.reduce_many([a])[0]
        assert probe3 == frozenset(half), "stale table must keep verdicts"
        assert metrics.registry("mesh_plane").counter(
            "bucket_refresh_failures") == fails0 + 1
        _c, _d, probe4 = r.reduce_many([a])[0]   # healthy flush: re-queued
        assert probe4 == frozenset(d.tobytes() for d in digs)


class TestContainerCacheLru:
    def _store(self, tmp_path, cap: int) -> ContainerStore:
        return ContainerStore(str(tmp_path / "c"), container_size=4096,
                              lanes=1, cache_containers=cap)

    def test_hit_refreshes_recency(self, tmp_path):
        """True LRU, not FIFO: a hit moves the container to most-recent,
        so cyclic re-reads of the hot container survive inserts that
        would have evicted the OLDEST-INSERTED entry."""
        store = self._store(tmp_path, cap=2)
        cids = []
        for i in range(3):          # 3 sealed single-chunk containers
            cid, _off, _ln = store.append_chunks([bytes([i]) * 3000])[0]
            store.flush_open()
            cids.append(cid)
        m = metrics.registry("container_store")
        h0, mi0, ev0 = (m.counter("cache_hit"), m.counter("cache_miss"),
                        m.counter("cache_evict"))
        store.read_container(cids[0])            # miss -> cache [0]
        store.read_container(cids[1])            # miss -> cache [0, 1]
        store.read_container(cids[0])            # HIT -> recency [1, 0]
        store.read_container(cids[2])            # miss, evicts 1 (LRU)
        assert m.counter("cache_hit") == h0 + 1
        assert m.counter("cache_miss") == mi0 + 3
        assert m.counter("cache_evict") == ev0 + 1
        h1 = m.counter("cache_hit")
        store.read_container(cids[0])            # still cached: FIFO would
        assert m.counter("cache_hit") == h1 + 1  # have evicted 0, not 1


class TestMixedSizeCoalescer:
    def test_mesh_reducer_handles_mixed_size_group(self):
        """One mesh step over blocks of different lengths: per-block
        true_n drives cut selection, so padding to the group max never
        leaks into cuts or digests."""
        r = _mesh_reducer(0x3FF, 256, 4096)
        rng = np.random.default_rng(17)
        group = [rng.integers(0, 256, n, np.uint8)
                 for n in (20_000, 9_999, 33_333, 300)]
        for blk, (cuts, digs, _p) in zip(group, r.reduce_many(group)):
            ref_cuts, ref_digs = _oracle(blk, 0x3FF, 256, 4096)
            np.testing.assert_array_equal(cuts, ref_cuts)
            np.testing.assert_array_equal(digs, ref_digs)
