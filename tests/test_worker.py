"""Co-located reduction worker: a SEPARATE process serving the DN's hot
ops over the streaming protocol (the BASELINE.json north-star deployment:
BlockReceiver streams block packets to the worker; bytes land in HBM).

On the CPU test mesh the worker backend auto-resolves to native — the
plumbing (process boundary, streaming ingest, completion flow, fallback)
is identical; the real-chip variant is chip_smoke.py, run through the chip
tool (its CPU rehearsal is tests/test_chip_smoke.py)."""

from __future__ import annotations

import random

import numpy as np
import pytest

from hdrf_tpu.config import CdcConfig
from hdrf_tpu.ops.dispatch import gear_mask
from hdrf_tpu.server.reduction_worker import (_STRIDE, ReductionWorker,
                                              WorkerClient, WorkerError,
                                              spawn_local_worker)
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.testing.wire import PiecedSocket, frame_packets
from hdrf_tpu.utils import metrics

RNG = np.random.default_rng(51)


def _bytes(n):
    return RNG.integers(0, 256, size=n, dtype=np.uint8).tobytes()


PKT = 64 * 1024

# Where a flipped bit lies in a 64 KiB payload, by the part of
# ``hdrf_crc32c``'s interleaved path that sums it (native/src/crc32c.cpp):
# two blocks of 3 x 8 KiB, then twenty-one of 3 x 256 B, then 256 bytes one
# stream; "payload" is the offset the older cases flipped.
CRC_FLIPS = {"payload": 100,
             "long-0": 24576 + 77, "long-1": 24576 + 8192 + 77,
             "long-2": 24576 + 16384 + 77,
             "short-0": 49152 + 5, "short-1": 49152 + 256 + 5,
             "short-2": 49152 + 512 + 5,
             "tail": PKT - 3}


def _oracle(data: bytes, cdc: CdcConfig):
    """Whole-buffer cuts and digests from the native codecs."""
    from hdrf_tpu import native

    if not data:
        return np.empty(0, np.int64), np.empty((0, 32), np.uint8)
    buf = np.frombuffer(data, np.uint8)
    cuts = native.cdc_chunk(buf, gear_mask(cdc), cdc.min_chunk,
                            cdc.max_chunk)
    starts = np.concatenate([[0], cuts[:-1]]).astype(np.uint64)
    return cuts.astype(np.int64), native.sha256_batch(
        buf, starts, (cuts - starts).astype(np.uint64))


class TestWorkerProtocol:
    @pytest.fixture(scope="class")
    def worker(self):
        w = ReductionWorker(backend="native").start()
        yield w
        w.stop()

    def test_reduce_matches_oracle(self, worker):
        cdc = CdcConfig()
        data = _bytes(300_000)
        c = WorkerClient(worker.addr)
        cuts, digs = c.reduce(data, cdc)
        want_cuts, want_digs = _oracle(data, cdc)
        np.testing.assert_array_equal(cuts, want_cuts)
        np.testing.assert_array_equal(digs, want_digs)
        c.close()

    def test_streaming_matches_whole(self, worker):
        cdc = CdcConfig()
        data = _bytes(500_000)
        c = WorkerClient(worker.addr)
        whole = c.reduce(data, cdc)
        pkts = [data[i:i + 64 * 1024] for i in range(0, len(data), 64 * 1024)]
        streamed = c.reduce_stream(iter(pkts), cdc)
        np.testing.assert_array_equal(whole[0], streamed[0])
        np.testing.assert_array_equal(whole[1], streamed[1])
        c.close()

    def test_compress_roundtrip(self, worker):
        from hdrf_tpu import native

        data = (b"the quick brown fox " * 5000)[:80_000]
        c = WorkerClient(worker.addr)
        comp = c.compress("lz4", data)
        assert native.lz4_decompress(comp, len(data)) == data
        c.close()

    def test_compress_batch_roundtrip(self, worker):
        from hdrf_tpu import native

        datas = [(b"lorem ipsum dolor " * 4000)[:60_000], _bytes(30_000),
                 b"\x00" * 50_000]
        c = WorkerClient(worker.addr)
        outs = c.compress_batch("lz4", datas)
        assert len(outs) == len(datas)
        for d, comp in zip(datas, outs):
            assert native.lz4_decompress(comp, len(d)) == d
        # batch must equal the per-item op byte for byte
        assert outs == [c.compress("lz4", d) for d in datas]
        c.close()

    def test_ping_and_stats(self, worker):
        c = WorkerClient(worker.addr)
        assert c.ping()["backend"] == "native"
        before = c.stats()["blocks_reduced"]
        c.reduce(_bytes(10_000), CdcConfig())
        assert c.stats()["blocks_reduced"] == before + 1
        c.close()


    def test_ping_carries_the_device(self, worker):
        """A parent that stays off JAX reports the device from ping: a
        native worker owns none (and never initialises JAX to find out)."""
        c = WorkerClient(worker.addr)
        assert c.ping()["device"] == {"platform": None, "kind": None,
                                      "count": 0}
        c.close()

    def test_device_report_of_the_device_path(self):
        """backend="tpu" in-process on the CPU mesh (only main() insists on
        a chip): ping names what JAX runs on, and the report carries
        per-op awaited dispatches, the ledger and the LZ4 counters."""
        w = ReductionWorker(backend="tpu").start()
        try:
            c = WorkerClient(w.addr)
            dev = c.ping()["device"]
            assert dev["platform"] == "cpu" and dev["count"] >= 1
            # the ledger is process-wide: count what this reduce adds
            before = c.device_report()
            c.reduce(_bytes(200_000), CdcConfig())
            rep = c.device_report()
            assert rep["device"] == dev and rep["backend"] == "tpu"
            assert rep["ledger"]["dispatch_total"] >= \
                before["ledger"]["dispatch_total"] + 2
            assert rep["ops"]["resident.prep"]["n"] == \
                before["ops"].get("resident.prep", {"n": 0})["n"] + 1
            assert rep["ops"]["resident.sha"]["mean_ms"] > 0
            assert "box" not in rep and isinstance(rep["lz4"], dict)
            st = c.stats()
            assert st["reduce_s"] > 0 and st["ingest_s"] >= 0
            c.close()
        finally:
            w.stop()


def _packets(data: bytes, sizes=(PKT,)):
    """``data`` cut into parts of ``sizes``, cycled."""
    out, off, i = [], 0, 0
    while off < len(data):
        out.append(data[off:off + sizes[i % len(sizes)]])
        off += len(out[-1])
        i += 1
    return out


def _with_crcs(parts):
    from hdrf_tpu import native

    return [(p, native.crc32c(p)) for p in parts]


# name -> (bytes, parts of reduce_stream, frames, segments): a frame leaves
# when a stride is pending and one more, the last, when the iterator ends
# (it is counted only if it carries bytes)
UNEVEN = (1, 70_000, 3, 1 << 20, PKT, 5_000_000, 17)
WIRE_CASES = {
    "empty": (0, lambda d: [], 0, 0),
    "one-byte": (1, _packets, 1, 1),
    "packet-less-one": (PKT - 1, _packets, 1, 1),
    "one-stride": (_STRIDE, _packets, 1, 64),
    "stride-plus-one": (_STRIDE + 1, _packets, 2, 65),
    "three-strides-and-a-tail": (3 * _STRIDE + 1000, _packets, 4, 193),
    "carried-crcs": (_STRIDE + 1, lambda d: _with_crcs(_packets(d)), 2, 65),
    "uneven-carried": (2 * _STRIDE + 12_345,
                       lambda d: _with_crcs(_packets(d, UNEVEN)), None, None),
    "uneven-summed-here": (2 * _STRIDE + 12_345,
                           lambda d: _packets(d, UNEVEN), None, None),
    # the one-part path of WorkerClient.reduce: cut into stride segments,
    # one a frame
    "one-part": (3 * _STRIDE + 1000, lambda d: [d], 4, 4),
}


class TestStrideWire:
    """The reduce op's upload leg carries strides (proto/datatransfer.py):
    whatever the parts, both backends answer what the whole buffer gives."""

    @pytest.fixture(scope="class", params=["native", "tpu"])
    def client(self, request):
        w = ReductionWorker(backend=request.param).start()
        c = WorkerClient(w.addr)
        yield c
        c.close()
        w.stop()

    @pytest.mark.parametrize("case", sorted(WIRE_CASES))
    def test_streamed_equals_the_whole_buffer_oracle(self, client, case):
        n, cut, frames, segments = WIRE_CASES[case]
        data = _bytes(n)
        parts = cut(data)
        assert b"".join(p[0] if isinstance(p, tuple) else p
                        for p in parts) == data
        cdc = CdcConfig()
        before = client.stats()
        cuts, digs = client.reduce_stream(iter(parts), cdc)
        after = client.stats()
        want_cuts, want_digs = _oracle(data, cdc)
        np.testing.assert_array_equal(cuts, want_cuts)
        np.testing.assert_array_equal(digs, want_digs)
        got = (after["hop_frames"] - before["hop_frames"],
               after["hop_packets"] - before["hop_packets"])
        if frames is not None:
            assert got == (frames, segments)
        else:
            # a carried part stays whole, one summed here is cut at a stride
            assert got[0] >= 2 and got[1] >= len(parts)

    @pytest.mark.parametrize("damage", ["crc"] + sorted(CRC_FLIPS))
    def test_an_altered_frame_is_refused(self, client, damage):
        """A bit of a payload (wherever the interleaved CRC32C sums it) or
        a carried CRC changed on the way: the worker answers with an error
        that names the segment, like any worker failure, and goes on
        serving."""
        data = _bytes(_STRIDE + 5 * PKT)
        parts = _with_crcs(_packets(data))
        victim, crc = parts[66]              # in the second, last frame
        if damage == "crc":
            parts[66] = (victim, crc ^ 0x10)
        else:
            at = CRC_FLIPS[damage]
            parts[66] = (victim[:at] + bytes([victim[at] ^ 1])
                         + victim[at + 1:], crc)
        with pytest.raises(WorkerError,
                           match="stride segment 2 of 5: checksum mismatch"):
            client.reduce_stream(iter(parts), CdcConfig())
        cuts, _ = client.reduce(data, CdcConfig())
        assert int(cuts[-1]) == len(data)


class TestBlockLengthLadder:
    """A stream of files of different lengths through the device backend
    (jax on the CPU here): each block lands at its rung of the block-length
    ladder, so the worker meets a ``_prep`` shape a rung and not a file
    (``prep_shapes``), counts the zeros it added (``bytes_padded``), and
    answers what the whole buffer gives."""

    @pytest.fixture(scope="class")
    def client(self):
        w = ReductionWorker(backend="tpu").start()
        c = WorkerClient(w.addr)
        yield c
        c.close()
        w.stop()

    def test_forty_files_of_the_cell_meet_rungs_not_files(self, client,
                                                          slive_files):
        from hdrf_tpu.ops.resident import block_rung

        files = [f.tobytes() for f in slive_files(40)]
        rungs = [block_rung(len(f)) for f in files]
        cdc = CdcConfig()
        before = client.stats()
        for data in files:
            cuts, digs = client.reduce_stream(iter(_packets(data)), cdc)
            want_cuts, want_digs = _oracle(data, cdc)
            np.testing.assert_array_equal(cuts, want_cuts)
            np.testing.assert_array_equal(digs, want_digs)
        after = client.stats()
        took = {k: after[k] - before.get(k, 0) for k in after}
        assert took["blocks_reduced"] == 40
        assert took["bytes_reduced"] == sum(map(len, files))
        assert took["bytes_padded"] == sum(rungs) - sum(map(len, files))
        assert 1 <= took["prep_shapes"] <= len(set(rungs)) <= 5
        assert took["prep_retries"] == 0
        # the same lengths again: every shape has been met
        for data in files[:5]:
            client.reduce(data, cdc)
        assert client.stats()["prep_shapes"] == after["prep_shapes"]

    @pytest.mark.parametrize("n, frames", [
        (_STRIDE, 1),               # a rung, one full stride: as it is
        (_STRIDE + 1, 2),           # a stride and a tail up to 6 MiB
        (2 * _STRIDE + 12_345, 3),  # two strides and a tail up to 12 MiB
        (70_000, 1)])               # under a stride: one upload at 1 MiB
    def test_a_block_lands_at_its_rung(self, client, n, frames):
        from hdrf_tpu.ops.resident import block_rung

        data = _bytes(n)
        before = client.stats()
        cuts, digs = client.reduce_stream(iter(_packets(data)), CdcConfig())
        after = client.stats()
        want_cuts, want_digs = _oracle(data, CdcConfig())
        np.testing.assert_array_equal(cuts, want_cuts)
        np.testing.assert_array_equal(digs, want_digs)
        assert after["hop_frames"] - before["hop_frames"] == frames
        assert after["bytes_padded"] - before["bytes_padded"] == \
            block_rung(n) - n

    def test_a_capacity_retry_counts_its_shape_where_it_ran(self):
        """A zero-dense block overflows ``_prep``'s first shot and runs it
        again at a higher capacity: both programs count in that op, and the
        next block, dispatched at the remembered rung, meets nothing new."""
        a = np.frombuffer(_bytes(600_000), np.uint8).copy()
        a[50_000:450_000] = 0
        w = ReductionWorker(backend="tpu").start()
        try:
            c = WorkerClient(w.addr)
            seen = [c.stats().get("prep_shapes", 0)]
            for _ in range(2):
                cuts, digs = c.reduce(a.tobytes(), CdcConfig())
                seen.append(c.stats()["prep_shapes"])
            retries = c.stats()["prep_retries"]
            c.close()
        finally:
            w.stop()
        want_cuts, want_digs = _oracle(a.tobytes(), CdcConfig())
        np.testing.assert_array_equal(cuts, want_cuts)
        np.testing.assert_array_equal(digs, want_digs)
        assert seen == [0, 2, 2] and retries >= 1

    def test_a_native_worker_has_neither_counter(self):
        w = ReductionWorker(backend="native").start()
        try:
            c = WorkerClient(w.addr)
            c.reduce(_bytes(70_000), CdcConfig())
            stats = c.stats()
            c.close()
        finally:
            w.stop()
        assert "bytes_padded" not in stats and "prep_shapes" not in stats


# ------------------------------------------- the seal's wire (compress ops)

SEG = 1 << 20
CONTAINER = 32 << 20


def _teragen(n: int) -> bytes:
    """TeraGen rows, as the benchmark's north-star cell writes them."""
    import chip_smoke

    return chip_smoke.teragen_rows(RNG, n).tobytes()


def _frames_and_segments(sizes) -> tuple[int, int]:
    """What the upload leg sends for payloads of ``sizes``: frames of one
    stride, none across two payloads, in segments of 1 MiB."""
    frames = segments = 0
    for n in sizes:
        for off in range(0, n, _STRIDE):
            frames += 1
            segments += -(-min(_STRIDE, n - off) // SEG)
    return frames, segments


# name -> a payload of the compress op
SEAL_PAYLOADS = {
    "empty": lambda: b"",
    "shorter-than-a-segment": lambda: _teragen(70_001),
    "no-multiple-of-segment-or-frame": lambda: _teragen(_STRIDE + 2 * SEG
                                                        + 12_345),
    "teragen-container": lambda: _teragen(CONTAINER),
    "incompressible-container": lambda: _bytes(CONTAINER),
    # a lane hands over its bytearray, and a view is as good
    "bytearray": lambda: bytearray(_teragen(3 * SEG + 5)),
    "memoryview": lambda: memoryview(_teragen(_STRIDE)),
}
SEAL_BATCHES = {
    "unequal-and-empty": lambda: [_teragen(_STRIDE + 17), b"", _bytes(30_000),
                                  bytearray(_teragen(2 * SEG)), b""],
    "all-empty": lambda: [b"", b""],
    "no-member": lambda: [],
    "equal": lambda: [_teragen(SEG + 1), _teragen(SEG + 1)],
}


def _flip_in_flight(monkeypatch, frame: int, only_thread: str | None = None):
    """One byte of the ``frame``-th stride frame that carries bytes changes
    between the sender's sum and the wire (on every thread, or the named
    one only)."""
    import threading

    from hdrf_tpu.proto import datatransfer as dt

    real, seen = dt.write_stride, [0]

    def write_stride(sock, segs, crcs, last=False):
        mine = only_thread in (None, threading.current_thread().name)
        if mine and segs:
            seen[0] += 1
            if seen[0] == frame + 1:
                bad = bytearray(segs[-1])
                bad[len(bad) // 2] ^= 0x20
                segs = [*segs[:-1], bad]
        return real(sock, segs, crcs, last)

    monkeypatch.setattr(dt, "write_stride", write_stride)


class TestSealWire:
    """``compress`` / ``compress_batch``: the payload goes up in stride
    frames of views of the caller's buffer and lands in one buffer of the
    stated size; the answer comes back raw behind a frame of lengths."""

    @pytest.fixture(scope="class", params=["native", "tpu"])
    def client(self, request):
        w = ReductionWorker(backend=request.param).start()
        c = WorkerClient(w.addr)
        yield c
        c.close()
        w.stop()

    @pytest.fixture(scope="class")
    def native_client(self):
        w = ReductionWorker(backend="native").start()
        c = WorkerClient(w.addr)
        yield c
        c.close()
        w.stop()

    @pytest.mark.parametrize("case", sorted(SEAL_PAYLOADS))
    def test_compress_round_trip(self, native_client, case):
        from hdrf_tpu.utils import codec as codecs

        data = SEAL_PAYLOADS[case]()
        before = native_client.stats()
        comp = native_client.compress("lz4", data)
        after = native_client.stats()
        assert codecs.decompress("lz4", comp, len(data)) == bytes(data)
        # the same encoder as in process: the sealed files do not change
        assert comp == codecs.compress("lz4", data)
        if case == "incompressible-container":
            assert len(comp) >= len(data)
        assert (after["seal_frames"] - before["seal_frames"],
                after["seal_segments"] - before["seal_segments"]) == \
            _frames_and_segments([len(data)])
        assert after["compress_jobs"] == before["compress_jobs"] + 1

    def test_compress_round_trip_on_the_device_path(self, client):
        """Both backends read the same wire: views of the landed buffer go
        to ``block_compress`` whichever encoder it picks."""
        from hdrf_tpu.utils import codec as codecs

        data = _teragen(2 * SEG + 999)
        comp = client.compress("lz4", data)
        assert codecs.decompress("lz4", comp, len(data)) == data

    @pytest.mark.parametrize("case", sorted(SEAL_BATCHES))
    def test_compress_batch_round_trip(self, native_client, case):
        from hdrf_tpu.utils import codec as codecs

        datas = SEAL_BATCHES[case]()
        before = native_client.stats()
        outs = native_client.compress_batch("lz4", datas)
        after = native_client.stats()
        assert len(outs) == len(datas)
        for d, comp in zip(datas, outs):
            assert codecs.decompress("lz4", comp, len(d)) == bytes(d)
        assert outs == [native_client.compress("lz4", d) for d in datas]
        assert (after["seal_frames"] - before["seal_frames"],
                after["seal_segments"] - before["seal_segments"]) == \
            _frames_and_segments([len(d) for d in datas])
        assert after["compress_jobs"] == before["compress_jobs"] + len(datas)

    def test_the_seal_has_a_stage_of_its_own(self, native_client):
        """A compress op's read and verify are ``seal_ingest_s``; the
        reduce op's ``packet_verify_s`` and ``ingest_wait_s`` do not grow."""
        native_client.reduce(_bytes(2 * SEG), CdcConfig())
        before = native_client.stats()
        native_client.compress("lz4", _teragen(_STRIDE + SEG))
        native_client.compress_batch("lz4", [_teragen(SEG), _teragen(99)])
        after = native_client.stats()
        assert after["seal_ingest_s"] > before.get("seal_ingest_s", 0.0)
        for stage in ("packet_verify_s", "ingest_wait_s"):
            assert after[stage] == before[stage]
        assert (after["hop_frames"], after["hop_packets"]) == \
            (before["hop_frames"], before["hop_packets"])

    @pytest.mark.parametrize("op,frame", [("compress", 0), ("compress", 1),
                                          ("compress_batch", 2)])
    def test_a_byte_flipped_in_flight_is_an_error_frame(
            self, native_client, monkeypatch, op, frame):
        """Whichever frame it is in, the worker reads the stream to its
        end, answers an error frame (``WorkerError`` here) and serves the
        next op on a connection that is still in step."""
        data = _teragen(2 * _STRIDE + 5)
        _flip_in_flight(monkeypatch, frame)
        with pytest.raises(WorkerError, match="checksum mismatch"):
            if op == "compress":
                native_client.compress("lz4", data)
            else:
                native_client.compress_batch("lz4", [data[:SEG], data])
        monkeypatch.undo()
        from hdrf_tpu.utils import codec as codecs

        comp = native_client.compress("lz4", data)
        assert codecs.decompress("lz4", comp, len(data)) == data

    def test_more_bytes_than_stated_hang_up(self, native_client):
        """A stream longer than the request said cannot be landed: the
        worker hangs up, which the client reports as a worker failure."""
        import socket

        from hdrf_tpu import native
        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.proto.rpc import send_frame

        s = socket.create_connection(native_client._addr, timeout=10)
        try:
            send_frame(s, {"op": "compress", "codec": "lz4", "size": 10})
            seg = _bytes(11)
            dt.write_stride(s, [seg], [native.crc32c(seg)], last=True)
            assert s.recv(1) == b""
        finally:
            s.close()

    def test_fewer_bytes_than_stated_are_an_error_frame(self, native_client):
        import socket

        from hdrf_tpu import native
        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.proto.rpc import recv_frame, send_frame

        s = socket.create_connection(native_client._addr, timeout=10)
        try:
            send_frame(s, {"op": "compress", "codec": "lz4", "size": 10})
            seg = _bytes(9)
            dt.write_stride(s, [seg], [native.crc32c(seg)], last=True)
            assert recv_frame(s)["error"] == "ValueError"
        finally:
            s.close()


# ------------------------------------------------------ the run reader (PR 28)


def _stream(sizes, flags=None, base=0):
    """A block's packets: payloads of ``sizes``; ``flags`` by index, the
    last packet FLAG_LAST."""
    flags = dict(flags or {})
    flags[len(sizes) - 1] = flags.get(len(sizes) - 1, 0) | 0x1
    return [(base + i, _bytes(n), flags.get(i, 0))
            for i, n in enumerate(sizes)]


# what iter_packets_crc's callers sent, and what they did not
RUN_STREAMS = {
    # a client's block: full packets, a short one, the empty last trailer
    "block": lambda: _stream([PKT, PKT, PKT, 12_345, 0]),
    # bytes in the last packet, an empty packet mid-stream, a flush marker
    "odd": lambda: _stream([1, 0, 70_000, 17, 3], {1: 0x2, 2: 0x4}, base=9),
    "trailer-only": lambda: _stream([0]),
    "a-window-of-small": lambda: _stream([100] * 40 + [0]),
}
RUN_PIECES = {
    "1": [1], "17": [17], "65553": [PKT + 17], "whole": [1 << 30],
    "random-a": random.Random(28).sample(range(1, 200_000), 64),
    "random-b": random.Random(82).sample(range(1, 3_000), 64),
}


def _read_runs(wire, sizes, capacity=1 << 20):
    from hdrf_tpu.proto import datatransfer as dt

    out = dt.BlockBuffer(capacity)
    sock = PiecedSocket(wire, sizes)
    return list(dt.iter_packet_runs(sock, out)), out, sock


class TestRunReader:
    """``dt.iter_packet_runs`` (the DataNode's reduced-write ingest) against
    ``dt.read_packet_crc`` on the same bytes, and ``hdrf_unpack_packets``
    against a parse in Python."""

    @pytest.mark.parametrize("pieces", sorted(RUN_PIECES))
    @pytest.mark.parametrize("stream", sorted(RUN_STREAMS))
    def test_runs_hold_what_the_packet_reader_reads(self, stream, pieces):
        from hdrf_tpu.proto import datatransfer as dt

        packets = RUN_STREAMS[stream]()
        wire = frame_packets(packets)
        one = PiecedSocket(wire, RUN_PIECES[pieces])
        want = [dt.read_packet_crc(one) for _ in packets]
        assert [(q, d, f) for q, d, f, _ in want] == packets
        runs, out, sock = _read_runs(wire, RUN_PIECES[pieces])
        got = [(int(q), int(n), int(f), int(c)) for r in runs
               for q, n, f, c in zip(r.seqnos, r.lens, r.flags, r.crcs)]
        assert got == [(q, len(d), f, c) for q, d, f, c in want]
        # the payloads lie back to back in the block's buffer, run after run
        assert bytes(out.view()) == b"".join(d for _, d, _ in packets)
        edges = [0] + [r.end for r in runs]
        assert [(r.start, r.end) for r in runs] == list(zip(edges, edges[1:]))
        assert all(r.end - r.start == int(r.lens.sum()) for r in runs)
        assert 1 <= len(runs) <= len(packets)
        if pieces == "whole":       # all had arrived: one recv, one run
            assert (len(runs), sock.calls) == (1, 1)
        if pieces == "1":           # nothing ever waits behind a packet
            assert len(runs) == len(packets)
        assert sock.calls <= one.calls      # never a recv more than before

    @pytest.mark.parametrize("damage", ["crc"] + sorted(CRC_FLIPS))
    @pytest.mark.parametrize("k", [0, 3, 6])
    def test_a_mismatch_ends_the_run_before_it(self, k, damage):
        """Packet ``k`` of a run altered on the way — its carried CRC, or a
        bit of its payload wherever the interleaved CRC32C sums it: the
        packets before it are a run (they may be acked), nothing of ``k`` is
        copied, then the reader raises what ``read_packet_crc`` raises,
        naming ``k``'s seqno."""
        from hdrf_tpu.proto import datatransfer as dt

        packets = _stream([PKT] * 7 + [0], base=40)
        seq, data, fl = packets[k]
        at = len(frame_packets(packets[:k]))
        wire = bytearray(frame_packets(packets))
        wire[at + (13 if damage == "crc"
                   else dt.PKT_HDR.size + CRC_FLIPS[damage])] ^= 0x20
        out = dt.BlockBuffer(1 << 20)
        runs = dt.iter_packet_runs(PiecedSocket(bytes(wire), [1 << 30]), out)
        if k:
            run = next(runs)
            assert run.seqnos.tolist() == [q for q, _, _ in packets[:k]]
        with pytest.raises(IOError, match=f"packet {seq}: checksum mismatch"):
            next(runs)
        assert bytes(out.view()) == b"".join(d for _, d, _ in packets[:k])
        one = PiecedSocket(bytes(wire), [1 << 30])
        with pytest.raises(IOError, match=f"packet {seq}: checksum mismatch"):
            for _ in packets:
                dt.read_packet_crc(one)

    def test_a_packet_larger_than_the_stage_and_a_block_than_its_buffer(self):
        """Both grow: a 5 MiB packet (the stage is a stride and a header)
        into a buffer sized for less; a view taken before stays good."""
        from hdrf_tpu.proto import datatransfer as dt

        packets = _stream([1000, 5 << 20, 0])
        out = dt.BlockBuffer(4096)
        runs = dt.iter_packet_runs(PiecedSocket(frame_packets(packets), [1 << 20]), out)
        first = next(runs)
        early = out.view(first.start, first.end)
        rest = list(runs)
        assert [q for r in [first] + rest for q in r.seqnos.tolist()] == \
            [0, 1, 2]
        assert bytes(early) == packets[0][1]
        assert bytes(out.view()) == packets[0][1] + packets[1][1]

    def test_a_closed_stream_is_a_connection_error(self):
        from hdrf_tpu.proto import datatransfer as dt

        wire = frame_packets(_stream([1000, 1000, 0]))[:-5]
        with pytest.raises(ConnectionError):
            list(dt.iter_packet_runs(PiecedSocket(wire, [700]),
                                     dt.BlockBuffer(4096)))

    # have (bytes staged, None = all), out_cap, max_pkts -> what stops it
    UNPACK_CASES = {
        "zero-length-run": (0, 1 << 20, 64, "PARTIAL"),
        "partial-header": (10, 1 << 20, 64, "PARTIAL"),
        "partial-payload": (17 + 999, 1 << 20, 64, "PARTIAL"),
        "whole-then-partial-header": (2 * 1017 + 16, 1 << 20, 64, "PARTIAL"),
        "whole-then-partial-payload": (3 * 1017 - 1, 1 << 20, 64, "PARTIAL"),
        "empty-last-trailer": (None, 1 << 20, 64, "LAST"),
        "more-than-the-arrays-hold": (None, 1 << 20, 2, "MORE"),
        "out-too-small": (None, 2500, 64, "OUT_FULL"),
    }

    @staticmethod
    def _parse(buf: bytes, out_cap: int, max_pkts: int):
        """``hdrf_unpack_packets`` in Python: (fields, payload, used, need,
        why)."""
        import struct

        from hdrf_tpu import native

        hdr = struct.Struct("<IQBI")
        pos, fields, payload, need, why = 0, [], b"", hdr.size, "PARTIAL"
        while len(buf) - pos >= hdr.size:
            ln, seq, fl, crc = hdr.unpack_from(buf, pos)
            if len(buf) - pos < hdr.size + ln:
                need = hdr.size + ln
                break
            need = 0
            if len(fields) == max_pkts:
                why = "MORE"
                break
            body = buf[pos + hdr.size:pos + hdr.size + ln]
            if len(payload) + ln > out_cap:
                why = "OUT_FULL"
                break
            if native.crc32c(body) != crc:
                why = "MISMATCH"
                break
            fields.append((seq, ln, fl, crc))
            payload += body
            pos += hdr.size + ln
            if fl & 0x1:
                why = "LAST"
                break
            need = hdr.size
        return fields, payload, pos, need, why

    @pytest.mark.parametrize("case", sorted(UNPACK_CASES))
    def test_the_native_unpack_equals_a_parse_in_python(self, case):
        from hdrf_tpu import native

        have, out_cap, max_pkts, why = self.UNPACK_CASES[case]
        # three data packets, the empty last trailer, and bytes after it
        # that a run must leave alone
        wire = frame_packets(_stream([1000, 1000, 1000, 0])) + b"\xff" * 40
        have = len(wire) if have is None else have
        unpack = native.PacketUnpacker(len(wire) + 1, max_pkts)
        unpack.stage[:have] = np.frombuffer(wire[:have], np.uint8)
        out = np.zeros(out_cap, np.uint8)
        n, used, need, got = unpack(have, out, 0)
        fields, payload, w_used, w_need, w_why = self._parse(
            wire[:have], out_cap, max_pkts)
        assert w_why == why and got == getattr(unpack, why)
        assert (n, used, need) == (len(fields), w_used, w_need)
        assert list(zip(unpack.seqnos[:n].tolist(), unpack.lens[:n].tolist(),
                        unpack.flags[:n].tolist(),
                        unpack.crcs[:n].tolist())) == fields
        assert out[:len(payload)].tobytes() == payload
        assert not out[len(payload):].any()         # and not a byte more
        if why == "OUT_FULL":       # the packet that did not fit, by name
            assert (int(unpack.seqnos[n]), int(unpack.lens[n])) == (2, 1000)

    @pytest.mark.parametrize("flip", sorted(CRC_FLIPS))
    def test_a_flipped_bit_stops_the_unpack_and_the_stride_verify(self, flip):
        """One bit of packet 2's 64 KiB payload, wherever the interleaved
        CRC32C sums it: ``PacketUnpacker`` stops with MISMATCH at that
        packet's header with nothing of it copied or consumed, and
        ``dt.verify_stride`` over the same payloads raises ``ValueError``
        naming segment 2."""
        from hdrf_tpu import native
        from hdrf_tpu.proto import datatransfer as dt

        packets = _stream([PKT] * 4 + [0])
        wire = bytearray(frame_packets(packets))
        one = dt.PKT_HDR.size + PKT
        wire[2 * one + dt.PKT_HDR.size + CRC_FLIPS[flip]] ^= 0x04
        unpack = native.PacketUnpacker(len(wire), 64)
        unpack.stage[:len(wire)] = np.frombuffer(bytes(wire), np.uint8)
        out = np.zeros(5 * PKT, np.uint8)
        n, used, need, why = unpack(len(wire), out, 0)
        assert (n, used, why) == (2, 2 * one, unpack.MISMATCH)
        assert int(unpack.seqnos[n]) == 2
        assert out[:2 * PKT].tobytes() == packets[0][1] + packets[1][1]
        assert not out[2 * PKT:].any()

        body = np.frombuffer(b"".join(d for _, d, _ in packets[:4]),
                             np.uint8).copy()
        body[2 * PKT + CRC_FLIPS[flip]] ^= 0x04
        lens = np.full(4, PKT, np.uint32)
        crcs = np.array([native.crc32c_table(d) for _, d, _ in packets[:4]],
                        np.uint32)
        with pytest.raises(ValueError, match="stride segment 2 of 4"):
            dt.verify_stride(body, lens, crcs)
        crcs[2] = native.crc32c_table(body[2 * PKT:3 * PKT])
        dt.verify_stride(body, lens, crcs)      # the damaged bytes' own sum


class TestWorkerProcess:
    def test_tpu_backend_without_a_chip_refuses_to_start(self, capfd):
        """--backend tpu on a host where JAX finds no TPU: non-zero exit,
        the reason on stderr, and spawn_local_worker lets that stderr
        through instead of demoting to the host codec in silence."""
        with pytest.raises(RuntimeError, match=r"rc=3"):
            spawn_local_worker(backend="tpu")
        err = capfd.readouterr().err
        assert "--backend tpu but JAX reports platform 'cpu'" in err

    def test_resolve_backend_raises_what_jax_raises(self, monkeypatch):
        """auto no longer swallows: a chip that is present but cannot be
        had is an error, not the native backend."""
        import jax

        from hdrf_tpu.ops import dispatch

        def busy():
            raise RuntimeError("TPU is already in use")

        monkeypatch.setattr(jax, "devices", busy)
        with pytest.raises(RuntimeError, match="already in use"):
            dispatch.resolve_backend("auto")
        assert dispatch.resolve_backend("native") == "native"

    def test_spawn_real_process(self):
        proc, addr = spawn_local_worker(backend="native")
        try:
            c = WorkerClient(addr)
            assert c.ping()["ok"]
            cuts, digs = c.reduce(_bytes(100_000), CdcConfig())
            assert int(cuts[-1]) == 100_000 and digs.shape[1] == 32
            c.close()
        finally:
            proc.terminate()
            proc.wait(timeout=5)


class TestClusterWithWorker:
    def test_datanode_fronting_a_worker_never_asks_jax(self, tmp_path,
                                                       monkeypatch):
        """backend="auto" (the config default) + a configured worker: the
        DN's own backend is native WITHOUT resolve_backend -> jax.devices()
        — the worker is the one process that owns the chip."""
        from hdrf_tpu.config import DataNodeConfig, NameNodeConfig
        from hdrf_tpu.ops import dispatch
        from hdrf_tpu.server.datanode import DataNode
        from hdrf_tpu.server.namenode import NameNode

        w = ReductionWorker(backend="native").start()
        monkeypatch.setattr(
            dispatch, "resolve_backend",
            lambda b: pytest.fail("a DN with a worker resolved its backend"))
        nn = NameNode(NameNodeConfig(meta_dir=str(tmp_path / "nn"),
                                     replication=1)).start()
        cfg = DataNodeConfig(data_dir=str(tmp_path / "dn"))
        assert cfg.reduction.backend == "auto"
        cfg.reduction.worker_addr = list(w.addr)
        dn = DataNode(cfg, nn.addr, dn_id="dn-w").start()
        try:
            assert dn.reduction_ctx.backend == "native"
            assert dn.coded.backend == "native"
            # both daemons say which CRC32C routine their process runs
            from hdrf_tpu import native
            from hdrf_tpu.utils import metrics

            hw = metrics.registry("native").snapshot()["gauges"]["crc32c_hw"]
            assert hw == w.stats()["crc32c_hw"] == native.crc32c_hw()
        finally:
            dn.stop()
            nn.stop()
            w.stop()

    def test_a_default_datanode_seals_off_thread_and_groups_its_commits(
            self):
        """What the write pipeline's depth option used to arm is on in
        every default DataNode, with a worker or without: container
        seals run on the store's seal thread, and the index's WAL
        group-commit window is ``group_commit_window_ms`` (2 ms) with
        ``ChunkIndex``'s own bound of 8 entries a window."""
        w = ReductionWorker(backend="native").start()
        try:
            for kw in ({}, {"reduction_overrides":
                            {"worker_addr": list(w.addr)}}):
                with MiniCluster(n_datanodes=1, replication=1,
                                 block_size=1 << 20, **kw) as mc:
                    dn = mc.datanodes[0]
                    assert (dn.reduction_ctx.worker is not None) == bool(kw)
                    stores = [v.containers for v in dn.volumes._alive()]
                    assert stores and all(
                        s._seal_thread is not None
                        and s._seal_thread.is_alive() for s in stores)
                    assert dn.index._group_window_s == 0.002
                    assert dn.index._group_max == 8
        finally:
            w.stop()

    def test_out_of_process_reduction_e2e(self):
        """The MiniCluster flag the VERDICT asked for: every dedup write
        flows DN -> worker process; the worker's stats prove it served."""
        with MiniCluster(n_datanodes=2, replication=2, block_size=1 << 20,
                         tpu_worker=True) as mc:
            wc = WorkerClient(mc._worker_addr)
            assert wc.ping()["ok"]
            data = _bytes(1_500_000) + _bytes(200_000) * 2
            with mc.client("w") as c:
                c.write("/w/f", data, scheme="dedup_lz4")
                assert c.read("/w/f") == data
                c.write("/w/g", data[:300_000], scheme="dedup_lz4")
                assert c.read("/w/g") == data[:300_000]
            st = wc.stats()
            assert st["blocks_reduced"] >= 3  # every dedup block offloaded
            wc.close()

    def test_the_datanode_sums_a_served_packet_once(self, monkeypatch):
        """The hop carries the client's CRC: for a block of P packets the
        DataNode verifies P (and the empty last packet) inside the run
        reader's native unpack, counted once each by ``recv_packets``, and
        calls ``native.crc32c`` for none of them, neither to verify nor to
        forward.  The client writes from this process too, so calls are
        told apart by their caller."""
        import collections
        import sys

        from hdrf_tpu import native

        by = collections.Counter()
        real = native.crc32c

        def counting(data, crc=0):
            by[sys._getframe(1).f_code.co_name] += 1
            return real(data, crc)

        data = _bytes(1_000_000)
        packets = -(-len(data) // PKT)
        with MiniCluster(n_datanodes=1, replication=1, block_size=1 << 20,
                         tpu_worker=True, worker_backend="native") as mc:
            wc = WorkerClient(mc._worker_addr)
            recv = metrics.registry("block_receiver")
            with mc.client("w") as c:
                before = wc.stats()
                seen = recv.counter("recv_packets")
                monkeypatch.setattr(native, "crc32c", counting)
                c.write("/crc/f", data, scheme="dedup_lz4")
                monkeypatch.undo()
                after = wc.stats()
                seen = recv.counter("recv_packets") - seen
                assert c.read("/crc/f") == data
            wc.close()
        assert after["blocks_reduced"] == before["blocks_reduced"] + 1
        assert after["hop_packets"] - before["hop_packets"] == packets
        assert seen == packets + 1                      # the DataNode's
        assert by["write_packet"] == packets + 1        # the client's own
        path = {"iter_packet_runs", "_admit_runs", "stream", "reduce_stream",
                "send", "write_stride", "_sendmsg_all"}
        assert not path & set(by), dict(by)

    def test_worker_death_falls_back_in_process(self):
        """Kill the worker mid-cluster: writes keep succeeding via the
        in-process fallback (availability over offload)."""
        with MiniCluster(n_datanodes=1, replication=1, block_size=1 << 20,
                         tpu_worker=True) as mc:
            data = _bytes(400_000)
            second = data[:100_000] + _bytes(50_000)
            with mc.client("w") as c:
                c.write("/f1", data, scheme="dedup_lz4")
                mc._worker_proc.terminate()
                mc._worker_proc.wait(timeout=5)
                c.write("/f2", second, scheme="dedup_lz4")
                assert c.read("/f2") == second
                assert c.read("/f1") == data


    def test_a_seal_damaged_in_flight_falls_back_to_the_host_codec(
            self, monkeypatch):
        """A byte of a container changes on its way to the worker: the
        worker refuses it, the DataNode counts ``worker_fallbacks`` and
        seals with the host codec — the same file the worker's encoder
        would have written."""
        import os

        from hdrf_tpu.utils import codec as codecs

        reg = metrics.registry("datanode")
        with MiniCluster(n_datanodes=1, replication=1, block_size=1 << 20,
                         tpu_worker=True, worker_backend="native",
                         reduction_overrides={"container_size": 256 << 10}
                         ) as mc:
            dn = mc.datanodes[0]
            data = _teragen(1 << 20)
            before = reg.counter("worker_fallbacks")
            _flip_in_flight(monkeypatch, 0, only_thread="container-seal")
            with mc.client("w") as c:
                c.write("/f", data, scheme="dedup_lz4")
                dn.containers.drain_seals()
                assert reg.counter("worker_fallbacks") == before + 1
                monkeypatch.undo()
                store = dn.volumes.volumes[0].containers
                sealed = sorted(n for n in os.listdir(store._dir)
                                if n.endswith(".sealed"))
                assert len(sealed) >= 2
                for name in sealed:
                    cid = int(name.split(".")[0])
                    codec, usize, payload = store._sealed_parse(cid)
                    raw = codecs.decompress(codec, payload, usize)
                    assert codec == "lz4" and \
                        bytes(payload) == codecs.compress("lz4", raw)
                assert c.read("/f") == data
