"""Control RPC + data-transfer framing."""

import socket
import threading

import numpy as np
import pytest

from hdrf_tpu import native
from hdrf_tpu.proto import datatransfer as dt
from hdrf_tpu.proto.rpc import RpcClient, RpcError, RpcServer


class EchoService:
    def rpc_add(self, a, b):
        return a + b

    def rpc_boom(self):
        raise ValueError("kapow")

    def rpc_echo(self, **kw):
        return kw


@pytest.fixture
def server():
    srv = RpcServer("127.0.0.1", 0, EchoService(), "test").start()
    yield srv
    srv.stop()


class TestRpc:
    def test_roundtrip(self, server):
        with RpcClient(server.addr) as c:
            assert c.call("add", a=2, b=3) == 5

    def test_error_roundtrip(self, server):
        with RpcClient(server.addr) as c:
            with pytest.raises(RpcError) as ei:
                c.call("boom")
            assert ei.value.error == "ValueError" and "kapow" in ei.value.message

    def test_unknown_method(self, server):
        with RpcClient(server.addr) as c:
            with pytest.raises(RpcError) as ei:
                c.call("nope")
            assert ei.value.error == "NoSuchMethod"

    def test_binary_and_nested_payloads(self, server):
        with RpcClient(server.addr) as c:
            out = c.call("echo", blob=b"\x00\xff" * 100, nested={"a": [1, 2]})
            assert out["blob"] == b"\x00\xff" * 100
            assert out["nested"] == {"a": [1, 2]}

    def test_concurrent_clients(self, server):
        errs = []

        def worker(n):
            try:
                with RpcClient(server.addr) as c:
                    for i in range(50):
                        assert c.call("add", a=n, b=i) == n + i
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(n,)) for n in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs

    def test_reconnect_after_server_restart(self, server):
        c = RpcClient(server.addr)
        assert c.call("add", a=1, b=1) == 2
        c._sock.close()  # simulate broken connection
        with pytest.raises((ConnectionError, OSError)):
            c.call("add", a=1, b=1)
        assert c.call("add", a=2, b=2) == 4  # auto-reconnect on next call
        c.close()


class TestDataTransfer:
    def _pair(self):
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        cli = socket.create_connection(srv.getsockname())
        conn, _ = srv.accept()
        srv.close()
        return cli, conn

    def test_packet_roundtrip(self):
        a, b = self._pair()
        dt.write_packet(a, 7, b"hello", last=False)
        dt.write_packet(a, 8, b"", last=True)
        assert dt.read_packet(b) == (7, b"hello", False)
        assert dt.read_packet(b) == (8, b"", True)
        a.close(), b.close()

    def test_checksum_detects_corruption(self):
        a, b = self._pair()
        hdr = dt.PKT_HDR.pack(5, 1, 0, 12345)  # wrong crc
        a.sendall(hdr + b"hello")
        with pytest.raises(IOError, match="checksum"):
            dt.read_packet(b)
        a.close(), b.close()

    def test_stream_and_collect(self):
        a, b = self._pair()
        data = bytes(range(256)) * 1000
        n = dt.stream_bytes(a, data, packet_size=4096)
        # full data packets + partial tail packet + empty LAST trailer
        import math
        assert n == math.ceil(len(data) / 4096) + 1
        assert dt.collect_packets(b) == data
        a.close(), b.close()

    @pytest.mark.parametrize("window", [1, 4, 16])
    def test_stream_bytes_acked_keeps_the_window(self, window):
        """At most ``window`` packets are ever un-acked, and the LAST ack
        (the one carrying pipeline status) is what comes back — a receiver
        that acks a packet only as it consumes it never sees the sender
        more than ``window`` ahead."""
        a, b = self._pair()
        data = bytes(range(256)) * 400           # 25 packets of 4096
        seen = {"max_ahead": 0, "data": b""}

        def receiver():
            got = acked = 0
            for seqno, pkt, last in dt.iter_packets(b):
                got += 1
                seen["max_ahead"] = max(seen["max_ahead"], got - acked)
                seen["data"] += pkt
                dt.send_ack(b, seqno,
                            dt.ACK_ERROR if last else dt.ACK_SUCCESS)
                acked += 1

        t = threading.Thread(target=receiver)
        t.start()
        out = dt.stream_bytes_acked(a, data, 4096, window)
        t.join(timeout=10)
        assert not t.is_alive()
        assert out == (25, dt.ACK_ERROR)         # the trailer's ack
        assert seen["data"] == data
        assert seen["max_ahead"] == 1            # receiver acks as it reads
        a.close(), b.close()

    def test_stream_bytes_acked_blocks_at_the_window(self):
        """A receiver that withholds acks stops the sender after ``window``
        packets instead of letting it run the whole block ahead."""
        a, b = self._pair()
        a.settimeout(0.5)
        with pytest.raises(socket.timeout):
            dt.stream_bytes_acked(a, b"x" * 4096 * 50, 4096, 3)
        b.settimeout(0.5)
        n = 0
        try:
            while True:
                dt.read_packet(b)
                n += 1
        except socket.timeout:
            pass
        assert n == 3
        a.close(), b.close()

    def test_op_header_roundtrip(self):
        a, b = self._pair()
        dt.send_op(a, dt.WRITE_BLOCK, block_id=5, targets=[{"addr": ["h", 1]}])
        op, fields = dt.recv_op(b)
        assert op == dt.WRITE_BLOCK and fields["block_id"] == 5
        a.close(), b.close()

    def test_acks(self):
        a, b = self._pair()
        dt.send_ack(a, 42, dt.ACK_SUCCESS)
        dt.send_ack(a, 43, dt.ACK_ERROR)
        assert dt.read_ack(b) == (42, dt.ACK_SUCCESS)
        assert dt.read_ack(b) == (43, dt.ACK_ERROR)
        a.close(), b.close()


# chunk lengths of a delta, from a seeded generator
_DELTAS = {
    # unequal chunks of 2-64 KiB over two frames and a tail: some cross a
    # frame's end
    "straddling": lambda rng: rng.integers(2 << 10, (64 << 10) + 1,
                                           300).tolist(),
    # 64 chunks of 64 KiB fill a frame exactly: none crosses
    "on-the-boundary": lambda rng: [64 << 10] * 128,
    "one-chunk": lambda rng: [5000],
    # every chunk known: no frame with bytes, the trailer alone
    "empty": lambda rng: [],
}


def _delta(case: str) -> list[bytes]:
    rng = np.random.default_rng(2**31 + 39)
    return [rng.integers(0, 256, n, np.uint8).tobytes()
            for n in _DELTAS[case](rng)]


class TestStrideFrames:
    """The mirror leg's wire for bytes the sender holds: ``write_frames``
    of ``chunk_frames`` / ``frames_of`` on one end, ``read_frames`` into
    one buffer on the other."""

    @pytest.mark.parametrize("case", sorted(_DELTAS))
    def test_a_chunk_delta_round_trips_one_sum_call_a_frame_each_end(
            self, case, monkeypatch):
        chunks = _delta(case)
        total = sum(map(len, chunks))
        calls = {"crc32c": 0, "crc32c_chunks": 0}
        for name in calls:
            real = getattr(native, name)

            def counted(*a, _real=real, _name=name, **kw):
                calls[_name] += 1
                return _real(*a, **kw)
            monkeypatch.setattr(native, name, counted)
        a, b = socket.socketpair()
        seen = []
        t = threading.Thread(target=dt.write_frames, args=(
            a, dt.chunk_frames(chunks), lambda k, n: seen.append((k, n))))
        t.start()
        buf, nframes, nsegs = dt.read_frames(b, total, "mirror_recv")
        t.join(timeout=10)
        a.close(), b.close()
        assert buf.tobytes() == b"".join(chunks)
        frames = -(-total // dt.STRIDE)
        assert nframes == frames and nsegs == -(-total // dt.SEGMENT)
        assert seen == [(k, min(dt.STRIDE, total - k * dt.STRIDE))
                        for k in range(frames)] + [(frames, 0)]
        # one native sum a frame on each end, none a chunk
        assert calls == {"crc32c": 0, "crc32c_chunks": 2 * frames}
        ends = set(np.cumsum([len(c) for c in chunks]).tolist())
        crossed = set(range(dt.STRIDE, total, dt.STRIDE)) - ends
        assert bool(crossed) == (case == "straddling")

    @pytest.mark.parametrize("k", [0, 1, 2])
    def test_a_flipped_byte_in_frame_k_raises_and_hands_nothing_on(self, k):
        chunks = _delta("straddling")
        frames = list(dt.chunk_frames(chunks))
        assert len(frames) == 3
        a, b = socket.socketpair()

        def send():
            try:
                for j, frame in enumerate(frames):
                    crcs = native.crc32c_chunks(frame, dt.SEGMENT).tolist()
                    buf = bytearray(frame)
                    if j == k:
                        buf[len(buf) // 2] ^= 0x40
                    dt.write_stride(a, [buf[o:o + dt.SEGMENT] for o in
                                        range(0, len(buf), dt.SEGMENT)],
                                    crcs)
                dt.write_stride(a, [], [], last=True)
            except OSError:
                pass            # the reader hung up, as a relay does

        t = threading.Thread(target=send)
        t.start()
        with pytest.raises(ValueError, match=f"frame {k}: .*checksum mismatch"):
            dt.read_frames(b, sum(map(len, chunks)), "mirror_recv")
        b.close()
        t.join(timeout=10)
        a.close()

    @pytest.mark.parametrize("stated,error", [(+1, ValueError),
                                              (-1, IOError)])
    def test_a_stream_that_is_not_the_stated_size_raises(self, stated, error):
        data = bytes(range(256)) * 20_000           # 5 MB: two frames
        a, b = socket.socketpair()

        def send():
            try:
                dt.write_frames(a, dt.frames_of(data))
            except OSError:
                pass            # the reader hung up at the long frame

        t = threading.Thread(target=send)
        t.start()
        with pytest.raises(error, match="stated|left of the size"):
            dt.read_frames(b, len(data) + stated, "mirror_recv")
        b.close()
        t.join(timeout=10)
        a.close()
