"""ContainerStore + ReplicaStore behavior."""

import os

import pytest

from hdrf_tpu.storage.container_store import (_RAW_MAGIC, _SEAL_HDR,
                                              ContainerStore)
from hdrf_tpu.storage.replica_store import ReplicaStore
from hdrf_tpu.utils import metrics


def _buffer_counts() -> tuple[int, int]:
    """(``lane_buffer_allocs``, ``lane_buffer_reuses``) of this process."""
    m = metrics.registry("container_store")
    return m.counter("lane_buffer_allocs"), m.counter("lane_buffer_reuses")


class TestContainerStore:
    def test_append_and_read(self, tmp_path):
        cs = ContainerStore(str(tmp_path), container_size=1 << 20, lanes=1)
        chunks = [b"a" * 100, b"b" * 200, b"c" * 300]
        locs = cs.append_chunks(chunks)
        assert [ln for _, _, ln in locs] == [100, 200, 300]
        assert cs.read_chunks(locs) == chunks

    def test_rollover_seals_with_compression(self, tmp_path):
        sealed = []
        cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1, codec="lz4")
        locs1 = cs.append_chunks([b"x" * 600], on_seal=sealed.append)
        locs2 = cs.append_chunks([b"y" * 600], on_seal=sealed.append)  # rollover
        assert sealed == [locs1[0][0]]
        assert locs2[0][0] != locs1[0][0]
        # sealed container readable (decompress path), open one raw
        assert cs.read_chunks(locs1) == [b"x" * 600]
        assert cs.read_chunks(locs2) == [b"y" * 600]
        assert os.path.exists(tmp_path / f"{locs1[0][0]}.sealed")
        assert os.path.exists(tmp_path / f"{locs2[0][0]}.raw")

    def test_incompressible_stored_raw_frame(self, tmp_path):
        cs = ContainerStore(str(tmp_path), container_size=100, lanes=1, codec="lz4")
        data = os.urandom(90)
        locs = cs.append_chunks([data])
        cs.flush_open()
        assert cs.read_chunks(locs) == [data]

    def test_lanes_are_independent_containers(self, tmp_path):
        cs = ContainerStore(str(tmp_path), container_size=1 << 20, lanes=2)
        l1 = cs.append_chunks([b"a" * 10])
        l2 = cs.append_chunks([b"b" * 10])
        assert l1[0][0] != l2[0][0]  # round-robin to distinct lanes
        assert cs.read_chunks(l1 + l2) == [b"a" * 10, b"b" * 10]

    def test_id_allocation_survives_restart(self, tmp_path):
        cs = ContainerStore(str(tmp_path), lanes=1)
        locs = cs.append_chunks([b"z" * 10])
        cs.flush_open()
        cs2 = ContainerStore(str(tmp_path), lanes=1)
        locs2 = cs2.append_chunks([b"w" * 10])
        assert locs2[0][0] > locs[0][0]
        assert cs2.read_chunks(locs) == [b"z" * 10]

    def test_compaction_protocol(self, tmp_path):
        cs = ContainerStore(str(tmp_path), container_size=1 << 20, lanes=1)
        locs = cs.append_chunks([b"a" * 100, b"dead" * 25, b"b" * 50])
        cs.flush_open()
        cid = locs[0][0]
        live = {b"h1" * 16: (locs[0][1], locs[0][2]),
                b"h2" * 16: (locs[2][1], locs[2][2])}
        moves = cs.copy_live(cid, live)
        assert set(moves) == set(live)
        # Old container still present until the index commit lands...
        assert os.path.exists(tmp_path / f"{cid}.sealed")
        cs.delete_container(cid)  # ...then dropped (after record_moves)
        assert not os.path.exists(tmp_path / f"{cid}.sealed")
        new_locs = [moves[b"h1" * 16], moves[b"h2" * 16]]
        assert cs.read_chunks(new_locs) == [b"a" * 100, b"b" * 50]

    def test_zstd_codec(self, tmp_path):
        pytest.importorskip("zstandard",
                            reason="zstandard module not installed")
        cs = ContainerStore(str(tmp_path), container_size=100, lanes=1, codec="zstd")
        locs = cs.append_chunks([b"q" * 90])
        cs.flush_open()
        assert cs.read_chunks(locs) == [b"q" * 90]


class TestSealHandOff:
    """A rolled-over lane's buffer goes to the seal where it lies
    (``_seal_locked``): a view of the lane's own memory, no copy on the
    committing thread; nothing the lane does afterwards reaches it until its
    seal has returned, and then a later container takes it."""

    @staticmethod
    def _store(tmp_path, seen, async_seals, gate=None, **kw):
        def compress_fn(data):
            from hdrf_tpu.utils import codec as codecs

            if gate is not None:
                assert gate.wait(30), "the test never opened the gate"
            seen.append((type(data), bytes(data), data.obj))
            return codecs.compress("lz4", data)

        cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1,
                            codec="lz4", compress_fn=compress_fn, **kw)
        if async_seals:
            cs.enable_async_seals()
        return cs

    @pytest.mark.parametrize("async_seals", [False, True],
                             ids=["inline", "async"])
    @pytest.mark.parametrize("append", ["chunks", "ranges"])
    def test_the_seal_gets_the_lanes_buffer_and_the_lane_a_new_one(
            self, tmp_path, async_seals, append):
        import threading

        import numpy as np

        seen, rolled = [], []
        # async: the seal is held until the lane has moved on, so which
        # buffer the lane opens next does not hang on a race
        gate = threading.Event() if async_seals else None
        cs = self._store(tmp_path, seen, async_seals, gate=gate,
                         on_roll=lambda cid, p: rolled.append(
                             (cid, type(p), bytes(p), p.obj)))
        lane = cs._lanes[0]

        def put(blob: bytes):
            if append == "chunks":
                return cs.append_chunks([blob[:400], blob[400:]])
            return cs.append_ranges(np.frombuffer(blob, np.uint8),
                                    [0, 400], [400, len(blob) - 400])

        first, second = b"x" * 300 + b"y" * 400, b"z" * 650
        allocs, reuses = _buffer_counts()
        locs1 = put(first)
        open_buf = lane.buffer
        assert open_buf.size == 1000 and lane.size == len(first)
        assert lane.view().obj is open_buf and bytes(lane.view()) == first
        locs2 = put(second)                       # rolls the first over
        later = cs.append_chunks([b"w" * 100])    # same lane
        if async_seals:
            # its seal has not returned: the lane is in another buffer
            assert lane.buffer is not open_buf
            assert _buffer_counts() == (allocs + 2, reuses)
            gate.set()
        else:
            # its seal returned before the lane opened again: taken back
            assert lane.buffer is open_buf
            assert _buffer_counts() == (allocs + 1, reuses + 1)
        cs.drain_seals()
        # the seal saw the lane's own memory, no copy, and as much of it as
        # was the container
        (kind, sealed_bytes, handed), = seen
        assert kind is memoryview and handed is open_buf
        assert rolled == [(locs1[0][0], memoryview, first, open_buf)]
        # what was appended before the rollover, and only that
        assert sealed_bytes == first
        assert bytes(lane.view()) == second + b"w" * 100
        if async_seals:
            assert len(cs._free) == 1 and cs._free[0] is open_buf
        assert cs.read_container(locs1[0][0]) == first
        assert cs.read_chunks(locs2 + later) == \
            [second[:400], second[400:], b"w" * 100]
        assert os.path.exists(tmp_path / f"{locs1[0][0]}.sealed")
        cs.close_async_seals()

    @pytest.mark.parametrize("batch", [False, True],
                             ids=["one-by-one", "compress_batch_fn"])
    def test_flush_open_hands_every_lane_on(self, tmp_path, batch):
        from hdrf_tpu.utils import codec as codecs

        single, grouped = [], []

        def batch_fn(datas):
            grouped.append([(type(d), d.obj, len(d)) for d in datas])
            return [codecs.compress("lz4", d) for d in datas]

        def one(data):
            single.append((type(data), data.obj, len(data)))
            return codecs.compress("lz4", data)

        cs = ContainerStore(str(tmp_path), container_size=1 << 20, lanes=3,
                            codec="lz4", compress_fn=one,
                            compress_batch_fn=batch_fn if batch else None)
        chunks = [b"a" * 5000, b"b" * 7000, b"c" * 100]
        locs = [cs.append_chunks([c])[0] for c in chunks]
        bufs = [lane.buffer for lane in cs._lanes]
        # each lane's own memory, as much of it as is the container
        want = [(memoryview, b, len(c)) for b, c in zip(bufs, chunks)]
        cs.flush_open()
        if batch:
            assert grouped == [want] and not single
        else:
            assert single == want and not grouped
        assert [bytes(b[:len(c)]) for b, c in zip(bufs, chunks)] == chunks
        assert all(lane.buffer is None for lane in cs._lanes)
        # the tails' buffers come back like any sealed container's
        assert sorted(map(id, cs._free)) == sorted(map(id, bufs))
        assert cs.read_chunks(locs) == chunks
        for cid, _, _ in locs:
            assert os.path.exists(tmp_path / f"{cid}.sealed")

    def test_a_memory_resident_lane_seals_from_its_buffer(self, tmp_path):
        """``have_raw=False``: no raw file to stamp or remove, the sealed
        file is written from the lane's buffer alone."""
        seen = []
        cs = self._store(tmp_path, seen, async_seals=False)
        locs = cs.append_chunks([b"m" * 900])
        lane = cs._lanes[0]
        lane.fh.close()
        os.unlink(tmp_path / f"{locs[0][0]}.raw")
        lane.fh = None
        cs.append_chunks([b"n" * 900])            # rolls the first over
        assert seen[0][0] is memoryview and seen[0][1] == b"m" * 900
        assert os.path.exists(tmp_path / f"{locs[0][0]}.sealed")
        assert not os.path.exists(tmp_path / f"{locs[0][0]}.raw")
        assert cs.read_chunks(locs) == [b"m" * 900]

    def test_an_incompressible_buffer_stamps_the_raw_file(self, tmp_path):
        """The compressor's answer as long as its input (a ``bytearray``
        from the worker's reply, say): header stamp and rename, no rewrite."""
        cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1,
                            codec="lz4",
                            compress_fn=lambda d: bytearray(d) + b"!")
        data = os.urandom(900)
        locs = cs.append_chunks([data])
        cs.append_chunks([b"k" * 900])
        codec, usize, payload = cs._sealed_parse(locs[0][0])
        assert (codec, usize, payload) == ("none", 900, data)
        assert cs.read_chunks(locs) == [data]

    def test_a_reader_of_the_open_lane_gets_a_copy(self, tmp_path):
        cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1)
        locs = cs.append_chunks([b"r" * 300])
        got = cs.read_container(locs[0][0])
        cs.append_chunks([b"s" * 300])
        assert type(got) is bytes and got == b"r" * 300


def _layout_oracle(chunks: list[bytes], size: int, first_cid: int,
                   open_bytes: bytes = b""):
    """The plain rule of the container layout, nothing of the store's: a
    chunk that does not fit seals the open container first, an oversized
    one lands alone in an empty one.  Returns the triples and every
    container's bytes (a ``b"".join``), the last of them the open one."""
    cid, parts, fill = first_cid, [open_bytes], len(open_bytes)
    locs, containers = [], {}
    for c in chunks:
        if fill + len(c) > size and fill > 0:
            containers[cid] = b"".join(parts)
            cid, parts, fill = cid + 1, [], 0
        locs.append((cid, fill, len(c)))
        parts.append(c)
        fill += len(c)
    containers[cid] = b"".join(parts)
    return locs, containers


# (what, container_size, the chunk lengths of each call in turn)
_LAYOUTS = [
    ("contiguous", 1000, [[100, 200, 300], [150, 50]]),
    ("fragmented", 1000, [[100, 200, 300], [150, 50]]),
    ("rollover-inside-one-call", 1000, [[400, 400, 400, 300, 500, 600]]),
    ("exact-fit", 1000, [[600, 400], [1000], [1]]),
    ("oversized-chunk", 1000, [[300], [2500, 10], [1001]]),
    ("oversized-first", 1000, [[4000], [200]]),
]


class TestLaneBuffer:
    """The open container's buffer: allocated whole, filled in place, written
    to the raw file from a view, and taken again after its seal."""

    @pytest.mark.parametrize("append", ["chunks", "ranges"])
    @pytest.mark.parametrize("what,size,calls", _LAYOUTS,
                             ids=[c[0] for c in _LAYOUTS])
    def test_appends_match_the_join_oracle(self, tmp_path, append, what,
                                           size, calls):
        """Triples, raw-file bytes and sealed files against a ``b"".join``:
        the raw file holds every appended byte when the append returns,
        before any seal."""
        import numpy as np

        from hdrf_tpu.utils import codec as codecs

        rng = np.random.default_rng(len(what))
        cs = ContainerStore(str(tmp_path), container_size=size, lanes=1,
                            codec="lz4")
        first_cid, open_bytes, all_locs, all_chunks = 0, b"", [], []
        sealed = {}
        for lens in calls:
            # half-compressible bytes, so some containers seal to LZ4 frames
            chunks = [bytes(rng.integers(0, 4, n, dtype=np.uint8))
                      for n in lens]
            if append == "chunks":
                locs = cs.append_chunks(chunks)
            else:
                gap = 0 if what == "contiguous" else 7
                block, starts = bytearray(), []
                for c in chunks:
                    block += b"\xff" * gap
                    starts.append(len(block))
                    block += c
                locs = cs.append_ranges(np.frombuffer(block, np.uint8),
                                        starts, lens)
            want, containers = _layout_oracle(chunks, size, first_cid,
                                              open_bytes)
            assert locs == want
            assert all(type(v) is int for loc in locs for v in loc)
            first_cid, open_bytes = list(containers.items())[-1]
            sealed.update(list(containers.items())[:-1])
            # the open container: in the lane, and in its raw file already
            lane = cs._lanes[0]
            assert lane.container_id == first_cid
            assert bytes(lane.view()) == open_bytes
            assert cs.has_container(first_cid, len(open_bytes))
            assert not cs.has_container(first_cid, len(open_bytes) + 1)
            with open(tmp_path / f"{first_cid}.raw", "rb") as f:
                assert f.read() == \
                    _SEAL_HDR.pack(_RAW_MAGIC, 0, 0) + open_bytes
            all_locs += locs
            all_chunks += chunks
        cs.flush_open()
        sealed[first_cid] = open_bytes
        assert cs.container_ids() == sorted(sealed)
        for cid, want_bytes in sealed.items():
            codec, usize, payload = cs._sealed_parse(cid)
            assert usize == len(want_bytes)
            assert codecs.decompress(codec, payload, usize) == want_bytes
        assert cs.read_chunks(all_locs) == all_chunks

    def test_a_parked_seal_keeps_its_buffer_to_itself(self, tmp_path):
        """The compressor parked while two further containers roll over:
        what it then reads is still its own container, no buffer is out
        twice at once, and the counters read what the sequence implies."""
        import threading

        from hdrf_tpu.utils import codec as codecs

        gate, parked = threading.Event(), threading.Event()
        seen, out = [], []             # out: buffers a seal may still see

        def compress_fn(data):
            parked.set()
            assert gate.wait(30), "the test never opened the gate"
            seen.append(bytes(data))
            return codecs.compress("lz4", data)

        def on_seal(cid):
            out.pop(0)                 # seals finish in the order queued

        cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1,
                            codec="lz4", compress_fn=compress_fn,
                            on_roll=lambda cid, p: out.append(p.obj))
        cs.enable_async_seals()
        lane = cs._lanes[0]
        allocs, reuses = _buffer_counts()
        locs, held = [], []
        for fill in b"abcd":           # b, c, d each roll the one before
            locs += cs.append_chunks([bytes([fill]) * 700], on_seal=on_seal)
            assert all(lane.buffer is not b for b in out + held)
            held.append(lane.buffer)
        assert parked.wait(30)
        # a parked, b and c queued behind it, d open: four buffers, none free
        assert len(out) == 3 and not cs._free
        assert _buffer_counts() == (allocs + 4, reuses)
        gate.set()
        cs.drain_seals()
        assert seen == [bytes([f]) * 700 for f in b"abc"]
        # the list keeps lanes + seals in flight (1 + 2, 1 + 1, 1 + 0):
        # what the burst allocated went as its seals finished
        assert len(cs._free) == 1 and cs._free[0] is held[0]
        for fill in b"ef":             # two more rollovers: both reuse
            locs += cs.append_chunks([bytes([fill]) * 700], on_seal=on_seal)
            assert all(lane.buffer is not b for b in out)
            cs.drain_seals()
        assert _buffer_counts() == (allocs + 4, reuses + 2)
        cs.flush_open(on_seal=on_seal)
        assert not out
        assert cs.read_chunks(locs) == [bytes([f]) * 700 for f in b"abcdef"]
        for (cid, _, _), fill in zip(locs, b"abcdef"):
            codec, usize, payload = cs._sealed_parse(cid)
            assert codecs.decompress(codec, payload, usize) == \
                bytes([fill]) * 700
        cs.close_async_seals()

    def test_appenders_and_the_seal_thread_share_the_free_list(self, tmp_path):
        """More appending threads than cores against one seal thread, the
        interpreter switching every few microseconds: a buffer taken while
        its seal still reads it would change under the compressor."""
        import sys
        import threading
        import time
        import zlib

        import numpy as np

        from hdrf_tpu.utils import codec as codecs

        torn = []

        def compress_fn(data):
            before = zlib.crc32(data)
            out = codecs.compress("lz4", data)
            time.sleep(0.0005)
            if zlib.crc32(data) != before:
                torn.append(len(data))
            return out

        cs = ContainerStore(str(tmp_path), container_size=4096, lanes=2,
                            codec="lz4", compress_fn=compress_fn)
        cs.enable_async_seals()
        n_threads = (os.cpu_count() or 4) + 2
        wrote: list[list] = [[] for _ in range(n_threads)]
        deadline = time.monotonic() + 1.5

        def writer(k: int):
            rng = np.random.default_rng(k)
            while time.monotonic() < deadline and len(wrote[k]) < 400:
                block = rng.integers(0, 8, 3000, dtype=np.uint8)
                starts = [0, 700, 1500]
                lens = [int(x) for x in rng.integers(1, 700, 3)]
                for loc, s, n in zip(cs.append_ranges(block, starts, lens),
                                     starts, lens):
                    wrote[k].append((loc, block[s:s + n].tobytes()))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=writer, args=(k,))
                       for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
            cs.flush_open()
        finally:
            sys.setswitchinterval(old)
        assert not torn
        assert len(cs._free) <= len(cs._lanes)      # no seal in flight now
        pairs = [p for w in wrote for p in w]
        assert len(pairs) >= 3 * n_threads
        assert cs.read_chunks([loc for loc, _ in pairs]) == \
            [b for _, b in pairs]
        cs.close_async_seals()


def _delta(seed: int, n: int) -> tuple:
    """A relayed block's shape: ``n`` chunks of 2-16 KiB back to back in
    one buffer, ``(buf, starts, lens)``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(2 << 10, (16 << 10) + 1, n)
    buf = rng.integers(0, 256, int(lens.sum()), np.uint8)
    return buf, np.cumsum(lens) - lens, lens


class TestReadAfterRollover:
    """A chunk read back straight after the ``append_ranges`` that rolled
    its container over, while that container waits in the seal queue: its
    bytes come out of the ``.raw`` file, every one of them."""

    CONTAINER = 64 << 10

    @staticmethod
    def _chunks(buf, starts, lens) -> list[bytes]:
        return [buf[s:s + n].tobytes() for s, n in zip(starts, lens)]

    def test_every_chunk_of_a_rolled_container_reads_from_its_raw_file(
            self, tmp_path):
        import threading

        from hdrf_tpu.utils import codec as codecs

        go = threading.Event()

        def stalled(data):
            go.wait(30)
            return codecs.compress("lz4", data)

        cs = ContainerStore(str(tmp_path), container_size=self.CONTAINER,
                            lanes=2, codec="lz4", compress_fn=stalled)
        cs.enable_async_seals()
        try:
            buf, starts, lens = _delta(39, 60)          # some 9 containers
            locs = cs.append_ranges(buf, starts, lens)
            cids = sorted({cid for cid, _, _ in locs})
            assert len(cids) > 3
            # every container but the lane's open one rolled over and is
            # still a .raw file: no seal has run
            assert all((tmp_path / f"{c}.raw").exists() for c in cids)
            assert not any((tmp_path / f"{c}.sealed").exists() for c in cids)
            assert cs.read_chunks(locs) == self._chunks(buf, starts, lens)
            # the whole of each container in one read, as a push asks
            for c in cids[:-1]:
                ends = [o + n for cid, o, n in locs if cid == c]
                assert max(ends) > self.CONTAINER - (16 << 10)
        finally:
            go.set()
            cs.close_async_seals()
        assert cs.read_chunks(locs) == self._chunks(buf, starts, lens)

    def test_appenders_read_back_what_they_rolled_while_the_seals_lag(
            self, tmp_path):
        """Six threads, each appending a delta and reading it back at once,
        against one seal thread that takes a few ms a container."""
        import threading
        import time

        from hdrf_tpu.utils import codec as codecs

        def slow(data):
            time.sleep(0.003)
            return codecs.compress("lz4", data)

        cs = ContainerStore(str(tmp_path), container_size=self.CONTAINER,
                            lanes=4, codec="lz4", compress_fn=slow)
        cs.enable_async_seals()
        bad: list = []

        def relay(k: int):
            for r in range(12):
                buf, starts, lens = _delta(1000 * k + r, 25)
                locs = cs.append_ranges(buf, starts, lens)
                try:
                    if cs.read_chunks(locs) != self._chunks(buf, starts,
                                                             lens):
                        bad.append((k, r, "bytes"))
                except IOError as e:
                    bad.append((k, r, str(e)))

        threads = [threading.Thread(target=relay, args=(k,))
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        cs.close_async_seals()
        assert not any(t.is_alive() for t in threads)
        assert not bad

    @pytest.mark.parametrize("most", [1, 4096, 65_521])
    def test_a_read_the_kernel_cuts_short_goes_on(self, tmp_path,
                                                  monkeypatch, most):
        """``pread`` may return fewer bytes than asked short of the file's
        end: the store reads on from there, not calling it the end."""
        buf, starts, lens = _delta(7, 30)
        cs = ContainerStore(str(tmp_path), container_size=1 << 20, lanes=1)
        locs = cs.append_ranges(buf, starts, lens)
        cs._lanes[0].container_id = -1     # read it as a rolled container
        real = os.pread
        monkeypatch.setattr(os, "pread",
                            lambda fd, n, off: real(fd, min(n, most), off))
        assert cs.read_chunks(locs) == self._chunks(buf, starts, lens)

    def test_a_raw_file_that_is_short_says_how_short(self, tmp_path):
        buf, starts, lens = _delta(8, 10)
        cs = ContainerStore(str(tmp_path), container_size=1 << 20, lanes=1)
        locs = cs.append_ranges(buf, starts, lens)
        cs._lanes[0].container_id = -1
        (cid,) = {c for c, _, _ in locs}
        total = int(lens.sum())
        os.truncate(tmp_path / f"{cid}.raw", _SEAL_HDR.size + total - 5)
        with pytest.raises(IOError, match=rf"ends inside \[0, {total}\): "
                                          rf"it holds {total - 5} bytes"):
            cs.read_chunks(locs)


@pytest.fixture
def sealed_three(tmp_path):
    """Four 700-byte appends to one 1 000-byte lane with async seals: three
    containers sealed (the compressor leaves the hop's two spans, as
    ``WorkerClient.compress`` does), then ``drain_seals()`` from here.
    Everything the phase clock recorded meanwhile."""
    import threading
    import time

    from hdrf_tpu.utils import codec as codecs
    from hdrf_tpu.utils import profiler

    def compress_fn(data):
        with profiler.phase("seal_send"):
            time.sleep(0.001)
        with profiler.phase("seal_wait"):
            out = codecs.compress("lz4", data)
        return out

    indexed = []
    profiler.reset()
    cum0 = profiler.cumulative()
    t0 = profiler.mark()
    cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1,
                        codec="lz4", compress_fn=compress_fn)
    cs.enable_async_seals()
    locs = []
    for fill in b"abcd":
        locs += cs.append_chunks([bytes([fill]) * 700],
                                 on_seal=indexed.append)
    cs.drain_seals()
    out = {"spans": profiler.window_spans(t0, float("inf")),
           "t0": t0, "t1": profiler.mark(),
           "seal_tid": cs._seal_thread.ident,
           "caller_tid": threading.get_ident(), "indexed": indexed,
           "cids": [cid for cid, _, _ in locs[:3]],
           "cum": {k: v - cum0.get(k, 0.0)
                   for k, v in profiler.cumulative().items()}}
    cs.close_async_seals()
    return out


INNER = ("seal_send", "seal_wait", "seal_write", "seal_index")


class TestSealSpans:
    """The seal pipeline on the phase clock (PR 35): a container's wait in
    the queue, its covering ``seal`` span with the sealing thread's CPU,
    the index hook inside it, and the caller's wait in ``drain_seals``."""

    def _named(self, rec, name):
        return sorted((sp for sp in rec["spans"] if sp[0] == name),
                      key=lambda sp: sp[1])

    def test_one_seal_span_a_container_with_its_threads_cpu(self,
                                                            sealed_three):
        seals = self._named(sealed_three, "seal")
        assert len(seals) == 3 and sealed_three["indexed"] == \
            sealed_three["cids"]
        for sp in seals:
            assert len(sp) == 5 and sp[3] == sealed_three["seal_tid"]
            # CPU under its wall (two clocks: a hair of slack)
            assert 0.0 <= sp[4] <= sp[2] - sp[1] + 1e-4
        # one thread: the seals do not overlap
        assert all(a[2] <= b[1] for a, b in zip(seals, seals[1:]))

    def test_seal_queue_ends_where_its_seal_begins(self, sealed_three):
        queued = self._named(sealed_three, "seal_queue")
        seals = self._named(sealed_three, "seal")
        assert len(queued) == 3
        queued.sort(key=lambda sp: sp[2])
        for k, (q, sl) in enumerate(zip(queued, seals)):
            assert len(q) == 4 and q[3] == sealed_three["seal_tid"]
            assert q[1] <= q[2] <= sl[1]
            # put by the rollover on the appending thread, taken only
            # after the seal before it has ended
            assert k == 0 or q[2] >= seals[k - 1][2]

    @pytest.mark.parametrize("name", INNER)
    def test_the_inner_spans_lie_inside_their_seal_on_one_thread(
            self, sealed_three, name):
        seals = self._named(sealed_three, "seal")
        inner = self._named(sealed_three, name)
        assert inner and all(sp[3] == sealed_three["seal_tid"]
                             for sp in inner)
        for sl in seals:
            mine = [sp for sp in inner if sl[1] <= sp[1] and sp[2] <= sl[2]]
            assert len(mine) >= 1, (name, sl)
            assert name == "seal_write" or len(mine) == 1
        assert all(any(sl[1] <= sp[1] and sp[2] <= sl[2] for sl in seals)
                   for sp in inner)

    def test_seal_drain_is_the_callers(self, sealed_three):
        (drain,) = self._named(sealed_three, "seal_drain")
        assert drain[3] == sealed_three["caller_tid"] != \
            sealed_three["seal_tid"]
        # it waited for the last seal to end
        assert drain[2] >= self._named(sealed_three, "seal")[-1][2]

    def test_none_of_them_is_in_the_partition(self, sealed_three):
        from hdrf_tpu.utils import profiler

        rec = sealed_three
        prof = profiler.profile_spans(rec["spans"], rec["t0"], rec["t1"])
        assert not {"seal", "seal_queue", "seal_index",
                    "seal_drain"} & set(prof["phases"])
        bare = [sp for sp in rec["spans"]
                if profiler.phase_class(sp[0]) != profiler.COVER]
        again = profiler.profile_spans(bare, rec["t0"], rec["t1"])
        assert again["phases"] == prof["phases"]
        assert again["classes"] == prof["classes"]
        assert prof["inclusive"]["seal"]["count"] == 3
        assert prof["inclusive"]["seal_queue"]["count"] == 3
        assert prof["inclusive"]["seal_index"]["count"] == 3
        assert "cpu_s" in prof["inclusive"]["seal"]

    def test_what_seal_alone_owns_is_its_wall_less_the_inner_spans(
            self, sealed_three):
        """The closure PERF.md section 5 reads: ``seal`` = the four inner
        names' inclusive seconds + its own self seconds."""
        wall = {n: sum(sp[2] - sp[1] for sp in self._named(sealed_three, n))
                for n in INNER + ("seal",)}
        own = sealed_three["cum"]["seal"]
        assert own >= 0.0
        assert wall["seal"] == pytest.approx(
            sum(wall[n] for n in INNER) + own, abs=1e-6)

    def test_an_inline_seal_is_the_appending_threads(self, tmp_path):
        import threading

        from hdrf_tpu.utils import profiler

        profiler.reset()
        cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1,
                            codec="lz4")
        cs.append_chunks([b"x" * 700], on_seal=lambda cid: None)
        cs.append_chunks([b"y" * 700], on_seal=lambda cid: None)
        cs.drain_seals()                # no queue: nothing to wait for
        names = [sp[0] for sp in profiler.window_spans(0.0, float("inf"))]
        assert names.count("seal") == 1 and names.count("seal_index") == 1
        assert "seal_queue" not in names and "seal_drain" not in names
        (seal,) = [sp for sp in profiler.window_spans(0.0, float("inf"))
                   if sp[0] == "seal"]
        assert seal[3] == threading.get_ident() and len(seal) == 5


class TestReplicaStore:
    def test_rbw_to_finalized(self, tmp_path):
        rs = ReplicaStore(str(tmp_path))
        w = rs.create_rbw(42, gen_stamp=7)
        w.write(b"hello")
        w.write(b"world")
        meta = w.finalize(logical_len=10, scheme="direct", checksums=[123])
        assert meta.physical_len == 10 and meta.logical_len == 10
        assert rs.length(42) == 10
        assert rs.read_data(42) == b"helloworld"
        assert rs.block_report() == [(42, 7, 10)]

    def test_reduced_block_zero_physical_is_consistent(self, tmp_path):
        rs = ReplicaStore(str(tmp_path))
        w = rs.create_rbw(1)
        meta = w.finalize(logical_len=128 * 1024, scheme="dedup_lz4")
        assert meta.physical_len == 0
        assert rs.length(1) == 128 * 1024  # logical, from metadata
        assert rs.scan() == []  # NOT flagged corrupt (vs DirectoryScanner.java:437)

    def test_scan_detects_real_problems(self, tmp_path):
        rs = ReplicaStore(str(tmp_path))
        w = rs.create_rbw(5)
        w.write(b"x" * 100)
        w.finalize(logical_len=100, scheme="direct")
        # Truncate the data file behind the store's back.
        with open(rs.data_path(5), "wb") as f:
            f.write(b"x" * 40)
        problems = rs.scan()
        assert len(problems) == 1 and "physical length 40" in problems[0]

    def test_recovery_drops_orphan_rbw(self, tmp_path):
        rs = ReplicaStore(str(tmp_path))
        w = rs.create_rbw(9)
        w.write(b"partial")  # crash: no finalize
        rs2 = ReplicaStore(str(tmp_path))
        assert rs2.get_meta(9) is None
        assert not os.path.exists(tmp_path / "rbw" / "blk_9")

    def test_recovery_loads_finalized(self, tmp_path):
        rs = ReplicaStore(str(tmp_path))
        w = rs.create_rbw(3)
        w.write(b"abc")
        w.finalize(logical_len=3, scheme="lz4", checksums=[1, 2])
        rs2 = ReplicaStore(str(tmp_path))
        m = rs2.get_meta(3)
        assert m.scheme == "lz4" and m.checksums == [1, 2]

    def test_duplicate_create_rejected(self, tmp_path):
        rs = ReplicaStore(str(tmp_path))
        rs.create_rbw(1).finalize(logical_len=0, scheme="direct")
        with pytest.raises(FileExistsError):
            rs.create_rbw(1)

    def test_delete(self, tmp_path):
        rs = ReplicaStore(str(tmp_path))
        w = rs.create_rbw(8)
        w.write(b"data")
        w.finalize(logical_len=4, scheme="direct")
        rs.delete(8)
        assert rs.get_meta(8) is None
        assert rs.block_ids() == []
        assert rs.scan() == []


def _walked(directory) -> tuple[int, dict[int, int]]:
    """(bytes, cid -> bytes) of the ``.raw`` and ``.sealed`` files, by a
    walk of the test's own: what ``physical_bytes`` / ``container_sizes``
    read before the store kept a record."""
    sizes: dict[int, int] = {}
    for name in os.listdir(directory):
        stem, _, suffix = name.partition(".")
        if suffix in ("raw", "sealed"):
            sizes[int(stem)] = sizes.get(int(stem), 0) + os.path.getsize(
                os.path.join(directory, name))
    return sum(sizes.values()), sizes


def _record_is_the_directory(cs: ContainerStore) -> dict[int, int]:
    total, sizes = _walked(cs._dir)
    assert cs.physical_bytes() == total
    assert cs.container_sizes() == sizes
    return sizes


_HDR = _SEAL_HDR.size


def _rolls_and_seals_compressible(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=1, codec="lz4")
    assert same(cs) == {}
    (cid, _, _), = cs.append_chunks([b"x" * 600])
    assert same(cs) == {cid: _HDR + 600}
    # the first range rolls container 0 over, the second container 1
    locs = cs.append_ranges(b"y" * 1200, [0, 600], [600, 600])
    sizes = same(cs)
    assert sizes[locs[1][0]] == _HDR + 600          # the open one, whole
    assert 0 < sizes[cid] < _HDR + 600              # sealed, and smaller
    assert not os.path.exists(os.path.join(d, f"{cid}.raw"))
    cs.flush_open()
    assert len(same(cs)) == 3


def _incompressible_is_stamped_and_renamed(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=1, codec="lz4")
    (cid, _, _), = cs.append_chunks([os.urandom(700)])
    cs.append_chunks([os.urandom(700)])             # rolls the first over
    assert same(cs)[cid] == _HDR + 700
    assert os.path.exists(os.path.join(d, f"{cid}.sealed"))
    cs.flush_open()
    assert sorted(same(cs).values()) == [_HDR + 700] * 2


def _flush_open_unlinks_an_opened_empty_lane(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=2, codec="lz4")
    cs.append_chunks([b"a" * 100])
    lane = cs._lanes[1]
    with lane.lock:                 # what an append that then raised leaves
        cs._open_locked(lane)
    cs.sync_lanes()                 # the placeholder header reaches the file
    assert same(cs)[lane.container_id] == _HDR
    cs.flush_open()
    assert len(same(cs)) == 1


def _an_oversize_chunk_lands_alone(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=1, codec="none")
    (cid, _, _), = cs.append_chunks([b"o" * 5000])
    assert same(cs) == {cid: _HDR + 5000}
    data = os.urandom(400) + b"p" * 3000
    locs = cs.append_ranges(data, [0, 400], [400, 3000])
    sizes = same(cs)
    assert sizes[locs[1][0]] == _HDR + 3000 and len(sizes) == 3
    cs.flush_open()
    same(cs)


def _delete_container_and_copy_live(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=1, codec="lz4")
    (cid, off, ln), = cs.append_chunks([b"l" * 700])
    (open_cid, _, _), = cs.append_chunks([b"m" * 700])
    moved = cs.copy_live(cid, {b"h" * 32: (off, ln)})   # rolls the open one
    assert len(same(cs)) == 3
    cs.delete_container(cid)
    assert cid not in same(cs)
    cs.delete_container(moved[b"h" * 32][0])        # an open lane's file
    assert list(same(cs)) == [open_cid]


def _quarantine_moves_the_bytes_out(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=1, codec="lz4")
    (cid, _, _), = cs.append_chunks([b"q" * 700])
    cs.append_chunks([b"r" * 700])
    before = same(cs)
    assert cs.quarantine(cid) == before.pop(cid)
    assert same(cs) == before
    assert cs.quarantine(cid) == 0                  # nothing left to move
    assert same(cs) == before
    assert os.path.exists(os.path.join(d, f"{cid}.sealed.quar"))


def _drop_sealed_file_keeps_the_rest(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=1, codec="lz4")
    (cid, _, _), = cs.append_chunks([b"s" * 700])
    cs.append_chunks([b"t" * 700])
    before = same(cs)
    assert cs.drop_sealed_file(cid) == before.pop(cid)
    assert same(cs) == before
    assert cs.drop_sealed_file(cid) == 0
    assert same(cs) == before


def _async_seals_then_drain(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=2, codec="lz4")
    cs.enable_async_seals()
    try:
        for i in range(12):
            cs.append_chunks([bytes([i]) * 300, os.urandom(300)])
            cs.append_ranges(b"z" * 900, [0, 450], [450, 450])
        cs.drain_seals()
        assert len(same(cs)) > 8
        cs.flush_open()
        same(cs)
    finally:
        cs.close_async_seals()


def _a_direct_seal_without_a_raw_file(d, same):
    cs = ContainerStore(d, container_size=1000, lanes=1, codec="lz4")
    cs.seal(41, data=b"d" * 500, have_raw=False)
    assert 0 < same(cs)[41] < _HDR + 500
    noise = os.urandom(500)
    cs.seal(42, data=noise, have_raw=False)
    assert same(cs)[42] == _HDR + 500
    cs.seal(42, data=b"e" * 500, have_raw=False)    # over the file it wrote
    assert 0 < same(cs)[42] < _HDR + 500 and len(same(cs)) == 2


def _reopened_over_what_another_left(d, same):
    first = ContainerStore(d, container_size=1000, lanes=2, codec="lz4")
    (sealed_cid, _, _), = first.append_chunks([b"u" * 700])
    (stray_cid, _, _), = first.append_chunks([os.urandom(300)])
    first.append_chunks([b"v" * 700])               # lane 0 rolls, 2 opens
    left = same(first)
    assert len(left) == 3
    # a crash between the seal's replace and its unlink leaves both forms;
    # .tmp and .quar files are no container's bytes
    with open(os.path.join(d, f"{sealed_cid}.raw"), "wb") as f:
        f.write(_SEAL_HDR.pack(_RAW_MAGIC, 0, 0) + b"u" * 700)
    for name in ("90.sealed.tmp", "91.raw.quar"):
        with open(os.path.join(d, name), "wb") as f:
            f.write(b"junk" * 10)
    cs = ContainerStore(d, container_size=1000, lanes=2, codec="lz4")
    sizes = same(cs)
    assert sizes[sealed_cid] == left[sealed_cid] + _HDR + 700
    assert sizes[stray_cid] == _HDR + 300
    (cid, _, _), = cs.append_chunks([b"w" * 10])
    assert cid == 92 and same(cs)[cid] == _HDR + 10
    cs.seal(stray_cid)                              # read back from its file
    assert same(cs)[stray_cid] == _HDR + 300
    assert os.path.exists(os.path.join(d, f"{stray_cid}.sealed"))
    assert cs.quarantine(sealed_cid) == sizes[sealed_cid]   # both forms
    assert sealed_cid not in same(cs)


@pytest.mark.parametrize("drive", [
    _rolls_and_seals_compressible,
    _incompressible_is_stamped_and_renamed,
    _flush_open_unlinks_an_opened_empty_lane,
    _an_oversize_chunk_lands_alone,
    _delete_container_and_copy_live,
    _quarantine_moves_the_bytes_out,
    _drop_sealed_file_keeps_the_rest,
    _async_seals_then_drain,
    _a_direct_seal_without_a_raw_file,
    _reopened_over_what_another_left,
], ids=lambda f: f.__name__.lstrip("_"))
def test_the_size_record_follows_every_operation(tmp_path, drive):
    """``physical_bytes`` / ``container_sizes`` answer from the store's
    record of its own writes; after each operation that creates, grows,
    renames or removes a container file the record equals a walk of the
    directory."""
    drive(str(tmp_path), _record_is_the_directory)


def test_sizes_are_answered_without_touching_the_directory(tmp_path,
                                                           monkeypatch):
    m = metrics.registry("container_store")
    walks = m.counter("dir_walks")
    first = ContainerStore(str(tmp_path), container_size=1000, lanes=1,
                           codec="lz4")
    first.append_chunks([b"a" * 700])
    cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1,
                        codec="lz4")
    assert m.counter("dir_walks") == walks + 2      # one an open, no more
    total, sizes = _walked(str(tmp_path))

    def refused(*a, **kw):
        raise AssertionError("the store looked at its directory")

    monkeypatch.setattr(os, "listdir", refused)
    monkeypatch.setattr(os, "scandir", refused)
    monkeypatch.setattr(os.path, "getsize", refused)
    monkeypatch.setattr(os, "stat", refused)
    assert cs.physical_bytes() == total and cs.container_sizes() == sizes
    cs.append_chunks([b"b" * 700])
    cs.append_ranges(b"c" * 1400, [0, 700], [700, 700])     # seals two
    cs.flush_open()
    sizes = cs.container_sizes()
    assert len(sizes) == 4 and cs.physical_bytes() == sum(sizes.values())
    assert m.counter("dir_walks") == walks + 2
    with pytest.raises(AssertionError, match="looked at its directory"):
        cs.container_ids()                          # the one that still walks
    monkeypatch.undo()
    assert (cs.physical_bytes(), sizes) == _walked(str(tmp_path))


def test_size_accounting_tolerates_a_file_sealed_under_it(tmp_path):
    """The race that is left now that no reader walks the directory: one
    thread appends and rolls containers over to the seal thread, which
    swaps ``.raw`` sizes for ``.sealed`` ones, while another asks
    ``physical_bytes`` / ``container_sizes`` as the heartbeat does.  No call
    raises, no update is lost: after ``drain_seals`` both equal the
    directory.  (The walk raced a seal's rename between its ``listdir`` and
    its ``getsize`` and killed the heartbeat thread on the v5e host.)"""
    import sys
    import threading
    import time

    cs = ContainerStore(str(tmp_path), container_size=1000, lanes=2,
                        codec="lz4")
    cs.enable_async_seals()
    errors: list[BaseException] = []
    done = threading.Event()
    reads = [0]

    def write():
        try:
            for i in range(150):
                cs.append_chunks([bytes([i]) * 400, os.urandom(350)])
                cs.append_ranges(b"k" * 800, [0, 400], [400, 400])
        except BaseException as e:  # noqa: BLE001 — shown below
            errors.append(e)
        finally:
            done.set()

    def read():
        try:
            while not done.is_set():
                total, sizes = cs.physical_bytes(), cs.container_sizes()
                assert total >= 0 and all(v > 0 for v in sizes.values())
                reads[0] += 1
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=write), threading.Thread(target=read),
               threading.Thread(target=read)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
    finally:
        sys.setswitchinterval(interval)
    try:
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert reads[0] > 0
        cs.drain_seals()
        assert len(_record_is_the_directory(cs)) > 100
    finally:
        cs.close_async_seals()
