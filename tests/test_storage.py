"""ContainerStore + ReplicaStore behavior."""

import os

import pytest

from hdrf_tpu.storage.container_store import ContainerStore
from hdrf_tpu.storage.replica_store import ReplicaStore


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
    """A rolled-over lane's ``bytearray`` goes to the seal as it is
    (``_seal_locked``): no copy on the committing thread, and nothing the
    lane does afterwards reaches it."""

    @staticmethod
    def _store(tmp_path, seen, async_seals, **kw):
        def compress_fn(data):
            from hdrf_tpu.utils import codec as codecs

            seen.append((type(data), bytes(data), data))
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
        import numpy as np

        seen, rolled = [], []
        cs = self._store(tmp_path, seen, async_seals,
                         on_roll=lambda cid, p: rolled.append((cid, p)))

        def put(blob: bytes):
            if append == "chunks":
                return cs.append_chunks([blob[:400], blob[400:]])
            return cs.append_ranges(np.frombuffer(blob, np.uint8),
                                    [0, 400], [400, len(blob) - 400])

        first, second = b"x" * 300 + b"y" * 400, b"z" * 650
        locs1 = put(first)
        open_image = cs._lanes[0].image
        locs2 = put(second)                       # rolls the first over
        later = cs.append_chunks([b"w" * 100])    # same lane, new buffer
        cs.drain_seals()
        (kind, sealed_bytes, handed), = seen
        assert kind is bytearray and handed is open_image
        assert rolled == [(locs1[0][0], open_image)]
        assert rolled[0][1] is open_image
        # what was appended before the rollover, and only that
        assert sealed_bytes == first and bytes(handed) == first
        assert cs._lanes[0].image is not handed
        assert bytes(cs._lanes[0].image) == second + b"w" * 100
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
            grouped.append([type(d) for d in datas])
            return [codecs.compress("lz4", d) for d in datas]

        def one(data):
            single.append(type(data))
            return codecs.compress("lz4", data)

        cs = ContainerStore(str(tmp_path), container_size=1 << 20, lanes=3,
                            codec="lz4", compress_fn=one,
                            compress_batch_fn=batch_fn if batch else None)
        chunks = [b"a" * 5000, b"b" * 7000, b"c" * 100]
        locs = [cs.append_chunks([c])[0] for c in chunks]
        images = [lane.image for lane in cs._lanes]
        cs.flush_open()
        if batch:
            assert grouped == [[bytearray] * 3] and not single
        else:
            assert single == [bytearray] * 3 and not grouped
        assert [bytes(i) for i in images] == chunks      # left as they were
        assert all(lane.image is None for lane in cs._lanes)
        assert cs.read_chunks(locs) == chunks
        for cid, _, _ in locs:
            assert os.path.exists(tmp_path / f"{cid}.sealed")

    def test_a_memory_resident_lane_seals_from_its_buffer(self, tmp_path):
        """``have_raw=False``: no raw file to stamp or remove, the sealed
        file is written from the ``bytearray`` alone."""
        seen = []
        cs = self._store(tmp_path, seen, async_seals=False)
        locs = cs.append_chunks([b"m" * 900])
        lane = cs._lanes[0]
        lane.fh.close()
        os.unlink(tmp_path / f"{locs[0][0]}.raw")
        lane.fh = None
        cs.append_chunks([b"n" * 900])            # rolls the first over
        assert seen[0][0] is bytearray and seen[0][1] == b"m" * 900
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


def test_size_accounting_tolerates_a_file_sealed_under_it(tmp_path,
                                                          monkeypatch):
    """physical_bytes / container_sizes stat files a concurrent seal may
    rename away between listdir and getsize (the heartbeat's stats call
    raced exactly so on the v5e host and killed the heartbeat thread)."""
    import os

    from hdrf_tpu.storage.container_store import ContainerStore

    cs = ContainerStore(str(tmp_path), container_size=1000, lanes=1,
                        codec="lz4")
    cs.append_chunks([b"a" * 600])
    cs.append_chunks([b"b" * 600])          # rolls: 0.sealed + 1.raw
    whole = cs.physical_bytes()
    real = os.path.getsize

    def racing(path):
        if path.endswith(".raw"):
            raise FileNotFoundError(path)
        return real(path)

    monkeypatch.setattr(os.path, "getsize", racing)
    assert 0 < cs.physical_bytes() < whole
    assert list(cs.container_sizes()) == [0]
