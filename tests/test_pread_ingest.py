"""Positional readers beside a writer on one DataNode (PR 33): the deployment
of the benchmark's ``teragen-1dn.pread-ingest`` cell at a small size — blocks
of 1 MiB that roll a 256 KiB container four times, TeraGen rows from the
benchmark's generator — and the pieces of the read path that let the two
live together: a read copies only the chunks it wants out of a decoded
container that stays inside the store (pinned while it is copied from, its
buffer reused once the LRU has let it go), out of an open container only
their extent under the lane's lock; a block's chunk plan comes from
positions kept with its index entry; and each step has a phase of its own.

Reference seam: DataConstructor.java:430-567 re-decompresses a whole
container for every read on the reading thread; BlockSender.java:612-623
materialises the whole block.

Everything here stops by counts (files written, appends made), never by
seconds.
"""

import os
import threading

import numpy as np
import pytest

from hdrf_tpu import native
from hdrf_tpu.index.chunk_index import ChunkIndex
from hdrf_tpu.server.read_plane import ChunkCache, resolve_chunk_plan
from hdrf_tpu.storage.container_store import ContainerStore
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.utils import codec as codecs
from hdrf_tpu.utils import metrics, profiler

BLOCK = 1 << 20
CONTAINER = 256 << 10
SEED = 2**31 + 33
CDC = {"mask_bits": 13, "min_chunk": 2048, "max_chunk": 65536}
READ_PHASES = ("read_admit", "container_load", "container_decode",
               "chunk_copy")


def _rows(perfbench_file, client: int, k: int) -> bytes:
    """File ``k`` of ``client`` as the benchmark's clients make it."""
    teragen = perfbench_file("generators/teragen.py")
    return teragen.Source({"file_bytes": BLOCK}, SEED, client).file(
        k).tobytes()


# ------------------------------------------------- (a) the mix, end to end


@pytest.mark.parametrize("readers", [1, 4])
def test_readers_loop_while_a_writer_writes(readers, perfbench_file):
    """``readers`` clients read random ranges of four files from set-up
    until a fifth client has written six files of its own.  Every read
    equals the generated bytes, every reader completed a read, the
    writer's files read back whole, and the DataNode's index is the plain
    reference's chunk table of everything written."""
    ref = perfbench_file("reference/chunking.py")
    shared = [_rows(perfbench_file, 0, j) for j in range(4)]
    mine = [_rows(perfbench_file, 9, k) for k in range(6)]
    done = threading.Event()
    counts, bad, errors = [0] * readers, [], []

    with MiniCluster(n_datanodes=1, replication=1, block_size=BLOCK,
                     container_size=CONTAINER, tpu_worker=True,
                     worker_backend="native") as mc:
        dn = mc.datanodes[0]
        with mc.client("setup") as c:
            for j, data in enumerate(shared):
                c.write(f"/shared/f{j}", data, scheme="dedup_lz4")
        dn.containers.drain_seals()

        def reader(idx: int) -> None:
            rng = np.random.default_rng([SEED, idx])
            try:
                with mc.client(f"reader-{idx}") as c:
                    while not done.is_set() or counts[idx] == 0:
                        j = int(rng.integers(0, len(shared)))
                        n = int(rng.integers(1, 200_000))
                        off = int(rng.integers(0, BLOCK - n + 1))
                        got = c.read(f"/shared/f{j}", offset=off, length=n)
                        if got != shared[j][off:off + n]:
                            bad.append((j, off, n))
                        counts[idx] += 1
            except Exception as e:  # noqa: BLE001 — judged below
                errors.append(f"reader {idx}: {type(e).__name__}: {e}")

        def writer() -> None:
            try:
                with mc.client("writer") as c:
                    for k, data in enumerate(mine):
                        c.write(f"/writer/f{k}", data, scheme="dedup_lz4")
            except Exception as e:  # noqa: BLE001 — judged below
                errors.append(f"writer: {type(e).__name__}: {e}")
            finally:
                done.set()

        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(readers)]
        threads.append(threading.Thread(target=writer))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        assert errors == [] and bad == []
        assert all(n >= 1 for n in counts), counts
        with mc.client("readback") as c:
            for k, data in enumerate(mine):
                assert c.read(f"/writer/f{k}") == data
        table, _ = ref.chunk_tables(shared + mine, CDC, BLOCK)
        found = dn.index.lookup_chunks(list(table))
        assert all(loc is not None and loc.length == table[d]
                   for d, loc in found.items())
        stats = dn.index.stats()
        assert stats["chunks"] == len(table)
        assert stats["unique_chunk_bytes"] == sum(table.values())
        assert metrics.registry("block_sender").counter("read_sheds") == 0


# ------------------------- (b) a range in an open container, its lane rolling


def _chunks_of(rng, n: int):
    lens = rng.integers(500, 6000, n)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    data = rng.integers(0, 256, int(lens.sum()), dtype=np.uint8)
    return data, starts, lens


@pytest.mark.parametrize("async_seals", [False, True])
def test_open_container_reads_while_its_lane_appends_and_rolls(tmp_path,
                                                               async_seals):
    """One lane, 64 KiB containers: a writer appends forty batches (each
    rolls the lane several times, its buffers coming back from their seals
    and taken again), while two readers read the newest chunks — those of
    the open container and of the ones just rolled over — again and again.
    Every read returns the appended bytes; some were served from the open
    lane, and buffers were reused meanwhile."""
    store = ContainerStore(str(tmp_path / "c"), container_size=64 << 10,
                           lanes=1, codec="lz4", cache_containers=2)
    if async_seals:
        store.enable_async_seals()
    reg = metrics.registry("container_store")
    reuses0 = reg.counter("lane_buffer_reuses")
    known: list = []          # (loc, bytes), appended after the append
    done = threading.Event()
    stats = {"reads": 0, "open": 0}
    bad, errors = [], []

    def writer() -> None:
        rng = np.random.default_rng(5)
        try:
            for _ in range(40):
                data, starts, lens = _chunks_of(rng, 60)
                locs = store.append_ranges(data, starts, lens)
                raw = data.tobytes()
                known.extend((loc, raw[s:s + ln]) for loc, s, ln
                             in zip(locs, starts.tolist(), lens.tolist()))
        except Exception as e:  # noqa: BLE001 — judged below
            errors.append(f"writer: {type(e).__name__}: {e}")
        finally:
            done.set()

    def reader(idx: int) -> None:
        rng = np.random.default_rng([6, idx])
        n = 0
        try:
            while not done.is_set() or n < 50:
                if not known:
                    continue
                top = len(known)
                first = max(top - int(rng.integers(1, 40)), 0)
                pick = known[first:top]
                opened = store._open_lane(pick[-1][0][0]) is not None
                got = store.read_chunks([loc for loc, _ in pick])
                if got != [want for _, want in pick]:
                    bad.append(pick[0][0])
                n += 1
                stats["reads"] += 1
                stats["open"] += opened
        except Exception as e:  # noqa: BLE001 — judged below
            errors.append(f"reader {idx}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert errors == [] and bad == []
    assert stats["open"] > 0, stats
    assert reg.counter("lane_buffer_reuses") > reuses0
    # the whole history once more, sealed or open, through both entries
    store.drain_seals()
    locs = [loc for loc, _ in known]
    assert store.read_chunks(locs) == [want for _, want in known]
    cid, off, ln = locs[0]
    assert store.read_container(cid)[off:off + ln] == known[0][1]
    store.close_async_seals()


def test_an_open_container_gives_only_the_wanted_extent(tmp_path):
    """Of an open container a read copies the extent its chunks span, under
    the lane's lock, and books that — not the whole container."""
    from hdrf_tpu.reduction import accounting

    store = ContainerStore(str(tmp_path / "c"), container_size=1 << 20,
                           lanes=1, codec="lz4")
    data, starts, lens = _chunks_of(np.random.default_rng(8), 100)
    locs = store.append_ranges(data, starts, lens)
    raw = data.tobytes()
    with accounting.read_scope("dedup_lz4"):
        before = accounting.snapshot()["counters"]
        got = store.read_chunks(locs[10:13])
        after = accounting.snapshot()["counters"]
    assert got == [raw[s:s + ln] for s, ln in
                   zip(starts[10:13].tolist(), lens[10:13].tolist())]
    moved = {k: after[k] - before.get(k, 0) for k in after
             if after[k] != before.get(k, 0)}
    assert list(moved.values()) == [int(lens[10:13].sum())], moved
    with pytest.raises(IOError):      # a range past what was appended
        store.read_chunks([(locs[0][0], int(lens.sum()) - 10, 64)])


# ----------------------------------- the decoded containers stay in the store


def _sealed_store(tmp_path, containers: int, cache: int):
    store = ContainerStore(str(tmp_path / "c"), container_size=64 << 10,
                           lanes=1, codec="lz4", cache_containers=cache)
    rng = np.random.default_rng(11)
    known = []
    while len({loc[0] for loc, _ in known}) <= containers:
        data, starts, lens = _chunks_of(rng, 12)
        raw = data.tobytes()
        known.extend((loc, raw[s:s + ln]) for loc, s, ln in zip(
            store.append_ranges(data, starts, lens), starts.tolist(),
            lens.tolist()))
    store.flush_open()
    return store, known


class TestDecodedContainers:
    def test_a_miss_decodes_into_a_buffer_the_lru_let_go(self, tmp_path):
        store, known = _sealed_store(tmp_path, 5, cache=2)
        cids = list(dict.fromkeys(loc[0] for loc, _ in known))
        for cid in cids[:2]:                     # fill the LRU
            store.read_chunks([loc for loc, _ in known if loc[0] == cid])
        held = {id(e.buf) for e in store._cache.values()}
        assert len(held) == 2 and store._decoded_free == []
        for cid in cids[2:5]:                    # three evictions
            pick = [(loc, want) for loc, want in known if loc[0] == cid]
            assert store.read_chunks([loc for loc, _ in pick]) == \
                [want for _, want in pick]
        # two buffers were enough for all five containers
        assert {id(e.buf) for e in store._cache.values()} == held
        assert list(store._cache) == cids[3:5]

    def test_a_pinned_container_keeps_its_bytes_until_unpinned(self,
                                                               tmp_path):
        store, known = _sealed_store(tmp_path, 4, cache=1)
        cids = list(dict.fromkeys(loc[0] for loc, _ in known))
        first = [(loc, want) for loc, want in known if loc[0] == cids[0]]
        store.read_chunks([first[0][0]])
        entry = store._cache_pin(cids[0])        # a reader mid-copy
        assert entry is not None and entry.pins == 1
        for cid in cids[1:4]:                    # evicts it, decodes thrice
            store.read_chunks([loc for loc, _ in known if loc[0] == cid])
        assert cids[0] not in store._cache
        assert all(e.buf is not entry.buf for e in store._cache.values())
        assert not any(b is entry.buf for b in store._decoded_free)
        for (_, off, ln), want in first:         # still this container's
            assert entry.buf[off:off + ln].tobytes() == want
        store._decoded_free.clear()              # the next decodes took them
        store._cache_unpin(entry)
        assert [b is entry.buf for b in store._decoded_free] == [True]

    def test_a_chunk_past_the_container_is_refused_not_served(self,
                                                              tmp_path):
        """A reused buffer holds another container's bytes past this
        one's size: an index entry that points there is corruption."""
        store, known = _sealed_store(tmp_path, 2, cache=2)
        cid = known[0][0][0]
        size = max(off + ln for (c, off, ln), _ in known if c == cid)
        with pytest.raises(IOError):
            store.read_chunks([(cid, size - 8, 64)])
        assert all(e.pins == 0 for e in store._cache.values())

    def test_read_container_gives_the_caller_its_own_bytes(self, tmp_path):
        store, known = _sealed_store(tmp_path, 2, cache=1)
        (cid, off, ln), want = known[0]
        whole = store.read_container(cid)
        assert type(whole) is bytes and whole[off:off + ln] == want
        other = next(loc[0] for loc, _ in known if loc[0] != cid)
        store.read_container(other)              # reuses the buffer
        assert whole[off:off + ln] == want

    @pytest.mark.parametrize("codec", ["lz4", "none", "gzip"])
    def test_decompress_into_lands_where_it_is_told(self, codec):
        data = bytes(range(256)) * 300
        comp = codecs.compress(codec, data)
        out = np.full(len(data) + 100, 7, np.uint8)
        view = codecs.decompress_into(codec, comp, len(data), out)
        assert view.tobytes() == data and view.base is out
        assert out[len(data):].tolist() == [7] * 100
        if codec == "lz4":
            with pytest.raises(ValueError):
                native.lz4_decompress(comp, len(data), out=out[:100])
            assert native.lz4_decompress(comp, len(data)) == data


# ------------------------------------------ a block's plan, found by position


class TestBlockRange:
    @pytest.fixture(scope="class")
    def index(self, tmp_path_factory):
        idx = ChunkIndex(str(tmp_path_factory.mktemp("idx")))
        rng = np.random.default_rng(3)
        lens = rng.integers(2048, 65536, 200).tolist()
        hashes = [bytes([i % 256, i // 256]) * 16 for i in range(200)]
        hashes[150] = hashes[20]                 # a chunk the block repeats
        lens[150] = lens[20]
        new, at = {}, 0
        for h, ln in zip(hashes, lens):
            if h not in new:
                new[h] = (at // 100_000, at % 100_000, ln)
                at += ln
        idx.commit_block(7, sum(lens), hashes, new)
        return idx, hashes, lens

    @staticmethod
    def _walk(hashes, lens, locs, offset, end):
        """The plan as the whole-list walk made it."""
        wanted, spans, pos = [], [], 0
        for h, ln in zip(hashes, lens):
            start, pos = pos, pos + ln
            if start >= end or start + ln <= offset:
                continue
            lo, hi = max(offset, start) - start, min(end, start + ln) - start
            loc = locs[h]
            wanted.append((loc.container_id, loc.offset, loc.length))
            spans.append((max(offset, start) - offset, lo, hi - lo))
        return wanted, spans

    def test_the_plan_equals_the_walk_at_every_kind_of_range(self, index):
        idx, hashes, lens = index
        total = sum(lens)
        locs = idx.lookup_chunks(hashes)
        ends = np.cumsum(lens)
        rng = np.random.default_rng(4)
        ranges = [(0, -1), (0, 1), (total - 1, 1), (total - 1, 50),
                  (int(ends[9]), int(lens[10])),         # one whole chunk
                  (int(ends[9]) - 1, int(lens[10]) + 2),  # and its neighbours
                  (int(ends[148]), 100_000)]             # over the repeat
        ranges += [(int(rng.integers(0, total)), int(rng.integers(1, 300_000)))
                   for _ in range(60)]
        for offset, length in ranges:
            end = total if length < 0 else min(offset + length, total)
            plan = resolve_chunk_plan(idx, 7, offset, length)
            wanted, spans = self._walk(hashes, lens, locs, offset, end)
            assert (plan.wanted, plan.spans) == (wanted, spans)
            assert (plan.end, plan.logical_len) == (end, total)
            assert sum(n for _, _, n in plan.spans) == plan.out_len

    def test_empty_and_unknown(self, index):
        idx, _, lens = index
        assert resolve_chunk_plan(idx, 7, sum(lens), 10).wanted == []
        assert resolve_chunk_plan(idx, 7, 5, 0).out_len == 0
        with pytest.raises(KeyError):
            resolve_chunk_plan(idx, 8)

    def test_a_recommit_brings_its_own_positions(self, index):
        idx, hashes, lens = index
        assert idx.block_range(7, 10, 10)[0] == sum(lens)
        idx.commit_block(9, lens[0] + lens[1], hashes[:2], {})
        assert idx.block_range(9, lens[0], 5) == (
            lens[0] + lens[1], lens[0],
            [(hashes[1], idx.chunk_location(hashes[1]))])
        idx.commit_block(9, lens[2], hashes[2:3], {})    # the block rewritten
        assert idx.block_range(9, 0, -1) == (
            lens[2], 0, [(hashes[2], idx.chunk_location(hashes[2]))])

    def test_lengths_that_do_not_sum_are_index_corruption(self, index):
        idx, hashes, lens = index
        idx.commit_block(11, lens[0] + 1, hashes[:1], {})
        with pytest.raises(IOError):
            resolve_chunk_plan(idx, 11, 0, 10)


def test_the_chunk_cache_probes_and_fills_a_plan_at_once():
    reg = metrics.registry("read_plane")
    cache = ChunkCache(10_000)
    hit0, miss0 = (reg.counter("chunk_cache_hit"),
                   reg.counter("chunk_cache_miss"))
    assert cache.get_many([b"a", b"b"]) == [None, None]
    cache.put_many([(b"a", b"x" * 4000, 1), (b"b", b"y" * 4000, 2),
                    (b"huge", b"z" * 10_001, 3)])
    assert cache.get_many([b"b", b"huge", b"a"]) == [b"y" * 4000, None,
                                                     b"x" * 4000]
    assert (reg.counter("chunk_cache_hit") - hit0,
            reg.counter("chunk_cache_miss") - miss0) == (2, 3)
    cache.put_many([(b"c", b"w" * 4000, 2)])     # evicts the LRU: b
    assert cache.get_many([b"a", b"b", b"c"]) == [b"x" * 4000, None,
                                                  b"w" * 4000]
    assert cache.bytes_used == 8000
    assert cache.invalidate_container(2) == 1 and cache.get(b"c") is None


# ------------------------------------------- (c) the read's phases partition


@pytest.fixture(scope="module")
def one_read():
    """A ranged read of a sealed container through a served DataNode: the
    finished read timeline and the ring's spans of its window."""
    data = np.random.default_rng(12).integers(
        0, 256, BLOCK, dtype=np.uint8).tobytes()
    with MiniCluster(n_datanodes=1, replication=1, block_size=BLOCK,
                     container_size=CONTAINER) as mc:
        dn = mc.datanodes[0]
        with mc.client("phases") as c:
            c.write("/p", data, scheme="dedup_lz4")
            dn.containers.drain_seals()
            profiler.reset()
            t0 = profiler.mark()
            assert c.read("/p", offset=12_345, length=100_000) == \
                data[12_345:112_345]
            # the handler files its timeline after the last packet
            assert dn.await_xceivers()
            t1 = profiler.mark()
        (tl,) = [t for t in profiler.read_timelines_snapshot()
                 if t["nbytes"] == 100_000]
        yield {"timeline": tl, "spans": profiler.window_spans(t0, t1)}


class TestReadPhases:
    @pytest.mark.parametrize("name", READ_PHASES + ("read_serve",
                                                    "index_lookup",
                                                    "cache_probe",
                                                    "net_send"))
    def test_a_sealed_read_leaves_the_phase(self, one_read, name):
        assert any(p == name and b > a
                   for p, a, b in one_read["timeline"]["spans"])

    def test_the_phases_partition_the_read(self, one_read):
        """Exclusive seconds by phase sum to the attributed wall, the
        finer phases lie inside the covering span and take their seconds
        out of it, and nothing is counted twice."""
        tl = one_read["timeline"]
        prof = tl["profile"]
        wall = prof["wall_s"]
        assert sum(prof["phases"].values()) == pytest.approx(
            wall - prof["classes"]["idle"])
        (serve,) = [(a, b) for p, a, b in tl["spans"] if p == "read_serve"]
        (send,) = [(a, b) for p, a, b in tl["spans"] if p == "net_send"]
        # The two covering spans follow each other inside the timeline and
        # every other span lies inside the first, so what no phase names
        # is exactly the three seams around them (the timeline's opening,
        # the step from service to send, its filing) — however long a busy
        # machine makes a seam.  (This held ``attributed_frac`` to 0.95
        # once: one lost interpreter switch is more than 5 % of a
        # millisecond's read under six test workers.)
        assert tl["t0"] <= serve[0] and serve[1] <= send[0] \
            and send[1] <= tl["t1"]
        assert all(p in ("read_serve", "net_send")
                   or (serve[0] <= a and b <= serve[1])
                   for p, a, b in tl["spans"])
        assert prof["classes"]["idle"] == pytest.approx(
            wall - (serve[1] - serve[0]) - (send[1] - send[0]), abs=1e-6)
        assert prof["attributed_frac"] == pytest.approx(
            1.0 - prof["classes"]["idle"] / wall)
        inner = [(p, a, b) for p, a, b in tl["spans"]
                 if p in READ_PHASES + ("index_lookup", "cache_probe")]
        assert all(serve[0] <= a and b <= serve[1] for _, a, b in inner)
        # the inner spans of one thread do not overlap each other ...
        inner.sort(key=lambda sp: sp[1])
        assert all(x[2] <= y[1] for x, y in zip(inner, inner[1:]))
        # ... so the covering span owns exactly what they leave of it
        own = (serve[1] - serve[0]) - sum(b - a for _, a, b in inner)
        assert prof["phases"].get("read_serve", 0.0) == pytest.approx(
            own, abs=1e-6)
        for name in READ_PHASES:
            spans = [b - a for p, a, b in inner if p == name]
            assert prof["phases"][name] == pytest.approx(sum(spans),
                                                         abs=1e-6)

    def test_one_span_a_container_or_a_read_never_one_a_chunk(self,
                                                              one_read):
        # a 100 000-byte range of a 1 MiB block in 256 KiB containers:
        # a dozen chunks in at most two containers
        for name in READ_PHASES:
            n = sum(1 for sp in one_read["spans"] if sp[0] == name)
            assert 1 <= n <= 4, (name, n)

    @pytest.mark.parametrize("name", READ_PHASES)
    def test_nested_in_the_covering_span_the_finer_phase_wins(self, name):
        prof = profiler.profile_spans(
            [("read_serve", 0.0, 1.0), (name, 0.25, 0.75),
             ("index_lookup", 0.0, 0.125)], 0.0, 1.0)
        assert prof["phases"] == pytest.approx(
            {"index_lookup": 0.125, name: 0.5, "read_serve": 0.375})
        assert prof["classes"]["host_busy"] == pytest.approx(1.0)


# -------------------------------------------- the benchmark's reader of reads


class TestClientReadsReader:
    OPS = ([{"kind": "read", "ok": True, "bytes": 1_000_000,
             "t0": 10.0, "t1": 10.0 + 0.01 * k} for k in range(1, 21)]
           + [{"kind": "read", "ok": False, "bytes": 1_000_000,
               "t0": 0.0, "t1": 9.0},
              {"kind": "write", "ok": True, "bytes": 1 << 27,
               "t0": 0.0, "t1": 3.0}])

    def _read(self, perfbench_file, metric: str, src: dict):
        import json

        with open(os.path.join(perfbench_file.root, "layers",
                               metric + ".json")) as f:
            layer = json.load(f)
        assert layer["metric"] == metric
        return perfbench_file(f"readers/{layer['reader']}.py").read(
            src, layer["params"])

    def test_it_is_the_arithmetic_of_the_end_to_end_metrics(
            self, perfbench_file):
        src = {"ops": self.OPS, "window_s": 4.0}
        assert self._read(perfbench_file, "client.read_mb_s", src) == \
            pytest.approx(20 * 1_000_000 / 1e6 / 4.0)
        # run.py's percentile: the value at round(0.95 * (n - 1))
        assert self._read(perfbench_file, "client.read_p95_ms", src) == \
            pytest.approx(190.0)

    @pytest.mark.parametrize("metric", ["client.read_mb_s",
                                        "client.read_p95_ms"])
    def test_a_window_without_a_read_is_nothing_to_read(self, perfbench_file,
                                                        metric):
        writes = [op for op in self.OPS if op["kind"] == "write"]
        assert self._read(perfbench_file, metric,
                          {"ops": writes, "window_s": 4.0}) is None
        assert self._read(perfbench_file, metric,
                          {"ops": [], "window_s": 4.0}) is None


class TestTheCellsConfiguration:
    """``dfsio-pread-1dn`` is TestDFSIO's random reads on ``teragen-1dn``'s
    DataNode: the cell's writer is ``teragen-1dn.ingest-1w``'s only as long
    as the two files state one cluster, one CDC geometry and one data
    source, and no guarantee of the write cells is dropped for the reads."""

    @staticmethod
    def _configs(perfbench_file):
        import json

        def load(name):
            with open(os.path.join(perfbench_file.root, "configs",
                                   name + ".json")) as f:
                return json.load(f)
        return load("dfsio-pread-1dn"), load("teragen-1dn")

    @pytest.mark.parametrize("group", ["cluster", "cdc", "data"])
    def test_it_runs_on_teragen_1dns_deployment(self, perfbench_file, group):
        mine, base = self._configs(perfbench_file)
        assert mine[group] == base[group]

    def test_it_keeps_every_guarantee_and_adds_the_reads(self,
                                                         perfbench_file):
        mine, base = self._configs(perfbench_file)
        n = len(base["guarantees"])
        assert mine["guarantees"][:n] == base["guarantees"]
        assert len(mine["guarantees"]) > n
        assert mine["source"] != base["source"]

    def test_the_cell_runs_it(self, perfbench_file):
        import json

        with open(os.path.join(os.path.dirname(perfbench_file.root),
                               "BENCHMARK.json")) as f:
            bench = json.load(f)
        (cell,) = [w for w in bench["workloads"]
                   if w["name"] == "teragen-1dn.pread-ingest"]
        (cfg,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
        mine, _ = self._configs(perfbench_file)
        assert cfg["file"] == "perfbench/configs/dfsio-pread-1dn.json"
        assert (cfg["name"], cfg["source"]) == (mine["name"], mine["source"])
        assert sorted(cfg["reduced"]) == sorted(mine["reduced"])
        assert (cell["traffic"], cell["chips"]) == ("pread-ingest", 1)
