"""Bulk data-transfer protocol: framed packet streaming on a raw TCP socket.

Modeled on the reference's ``DataTransferProtocol``
(hadoop-hdfs-client/.../datatransfer/DataTransferProtocol.java:42): a
connection carries one op — an op header, then for WRITE/READ a run of framed
packets with per-packet checksums, with acks flowing back on the same socket
(BlockReceiver's PacketResponder, BlockReceiver.java:1509).

Wire layout:

- Op header: one msgpack frame ``[op_name, fields_dict]`` (length-prefixed via
  proto.rpc.send_frame).  ``fields["_trace"]`` resumes a client span
  server-side (Receiver.java:94-98 continueTraceSpan).
- Packet:    ``[u32 data_len][u64 seqno][u8 flags][u32 crc32c(data)]`` + data
  (the reference's PacketHeader: 64 KB default payload, crc per checksum chunk;
  here one crc32c per packet — checksum chunking for range reads lives in
  BlockMeta.checksums).
- Ack:       ``[u64 seqno][u8 status]`` per packet, status 0 = SUCCESS; for
  pipelines the ack aggregates downstream status (worst wins), the analog of
  PipelineAck.
- Stride:    ``[u32 nseg][u8 flags]`` + nseg x ``[u32 len][u32 crc32c]`` + the
  segments back to back.  The upload legs of the DataNode -> reduction-worker
  ops (server/reduction_worker.py) and the DN -> DN reduced mirror leg
  (server/block_receiver.py).  ``reduce``: one frame per device upload
  stride instead of one per client packet; a segment is a client packet
  carried with the CRC32C its producer computed for it (the DataNode
  verified it before the ack and does not compute it again).  Bytes the
  sender already holds (``write_frames``): frames of ``STRIDE`` (4 MiB),
  segments of ``SEGMENT`` (1 MiB) summed in one native call a frame, landed
  by the receiver in one buffer of the size the sender stated
  (``read_frames``) — ``compress`` / ``compress_batch`` carry a sealed
  container's bytes so, the mirror leg a block's chunk delta (the chunks'
  lengths in a frame ahead, the chunks back to back, one crossing a frame's
  end split between two) or a stored block.  Either way the receiver checks
  every byte against its origin's sum, one native call a frame.
  ``FLAG_LAST`` ends the stream; the last frame may hold no segment.
- Raw reply: one msgpack frame (a header that states lengths) and the
  payloads behind it as they are, no msgpack around them
  (``send_with_payloads`` / ``recv_payload``): the worker's answer to a
  compress op.  An error is a plain msgpack frame, as everywhere.

Readers of the packet stream.  ``read_packet_crc`` and the iterators over
it take a packet at a time: two ``recv``s, one CRC32C call and two copies
each (the direct write path, which needs a barrier at every FLUSH/SYNC
packet, and reads).  ``iter_packet_runs`` — the DataNode's
reduced-write ingest, ``BlockReceiver.receive_reduced`` — takes a RUN:
every whole packet that has already arrived, in one ``recv_into``, then
one native call (``hdrf_unpack_packets``) that parses the headers,
verifies each payload against its header's CRC32C and copies it once to
the block's buffer (``BlockBuffer``), where the block is read from
afterwards.  It adapts to what it observes — the bytes already in the
socket — so a receiver ahead of its sender sees runs of one and a busy one
sees its clients' windows.  Nothing changes on the wire: the receiver
answers one ``ACK`` a packet, in order, each after its packet's verify
(a run's acks leave in one write), the last after the commit; a sender
with a window of one completes as before.

Ops (Receiver.java:101-135 op dispatch analog): WRITE_BLOCK, READ_BLOCK,
TRANSFER_BLOCK, COPY_BLOCK, BLOCK_CHECKSUM — dispatched by the DataNode's
xceiver loop.
"""

from __future__ import annotations

import contextlib
import socket
import struct
from typing import Any, Iterable, Iterator, NamedTuple

import numpy as np

from hdrf_tpu import native
from hdrf_tpu.proto.rpc import (pack_frame, recv_exact, recv_frame,
                                send_frame)
from hdrf_tpu.utils import profiler, retry, tracing

PKT_HDR = struct.Struct("<IQBI")
FLAG_LAST = 0x1
# hflush/hsync markers (DFSOutputStream.java:573 hflush / :580 hsync; the
# reference rides syncBlock on the packet header, PacketHeader.java): a
# FLUSH-flagged packet makes the receiver expose the prefix to readers
# (visible length) before acking; SYNC additionally fsyncs the replica.
FLAG_FLUSH = 0x2
FLAG_SYNC = 0x4

ACK = struct.Struct("<QB")
ACK_SUCCESS = 0
ACK_ERROR = 1
# Admission shed (utils/qos.py ShedError crossing the write wire): the DN
# refused the block AT ADMISSION — retryable, nothing was stored.  Shed
# acks repurpose the seqno field to carry the retry-after hint in
# MILLISECONDS (the 8-byte slot is wasted on a refusal; the reference's
# PipelineAck rides ECN/restart hints in spare header fields the same way).
ACK_SHED = 2

DEFAULT_PACKET = 64 * 1024

# Op names (DataTransferProtocol.java op codes)
WRITE_BLOCK = "write_block"
READ_BLOCK = "read_block"
TRANSFER_BLOCK = "transfer_block"
COPY_BLOCK = "copy_block"
BLOCK_CHECKSUM = "block_checksum"
# EC cold-tier stripe ops (server/ec_tier.py; DN-protocol trust — stripe
# ops never carry client bytes).  STRIPE_CODED_READ is the coded-exchange
# sibling of STRIPE_READ: the request carries a per-DN chain plan plus
# negotiation fields (``accept_enc`` — may the response ship LZ4'd payloads
# with per-item ``enc`` flags?), so a peer that predates the op simply
# books unknown_ops and answers nothing — the caller's recv fails and it
# falls back to plain STRIPE_READ legs, byte-identical results either way.
STRIPE_READ = "stripe_read"
STRIPE_WRITE = "stripe_write"
STRIPE_CODED_READ = "stripe_coded_read"


def secure_socket(sock: socket.socket, token: dict | None, encrypt: bool):
    """Wrap a freshly connected data socket with the AEAD record layer when
    encryption is on (security.client_handshake, keyed by the block token —
    the datatransfer/sasl analog).  Returns the socket to use for the op."""
    if not encrypt:
        return sock
    from hdrf_tpu import security

    if not token or not token.get("sig"):
        raise PermissionError("data-transfer encryption requires block "
                              "tokens (dfs.block.access.token.enable)")
    return security.client_handshake(sock, token)


def send_op(sock: socket.socket, op: str, **fields: Any) -> None:
    tr = tracing.current_context()
    if tr is not None:
        fields["_trace"] = list(tr)
    # remaining deadline budget rides the op header beside _trace (the
    # receiving DN rebinds it around its handler — datanode._xceive)
    hdr = retry.remaining_header()
    if hdr is not None:
        fields[retry.DEADLINE_KEY] = hdr
    send_frame(sock, [op, fields])


def recv_op(sock: socket.socket) -> tuple[str, dict]:
    op, fields = recv_frame(sock)
    return op, fields


def write_packet(sock: socket.socket, seqno: int, data: bytes,
                 last: bool = False, flags: int = 0) -> None:
    flags |= FLAG_LAST if last else 0
    sock.sendall(PKT_HDR.pack(len(data), seqno, flags, native.crc32c(data)))
    if data:
        sock.sendall(data)


def read_packet_crc(sock: socket.socket) -> tuple[int, bytes, int, int]:
    """Returns (seqno, data, flags, crc32c); raises IOError on checksum
    mismatch — the receiver-side verify the reference does per checksum
    chunk.  The CRC is the sender's own, just verified: a receiver that
    passes the packet on (the DataNode's hop to the reduction worker)
    carries it instead of computing it again."""
    ln, seqno, flags, crc = PKT_HDR.unpack(recv_exact(sock, PKT_HDR.size))
    data = recv_exact(sock, ln) if ln else b""
    # its own phase: a caller that times the whole call as a socket wait
    # (the DataNode's ``recv``, the worker's ``ingest_wait``) would book
    # this compute as transport.  Laps, one span a stride: a span a packet
    # doubled what the receiving interpreter records
    t0 = profiler.mark()
    ok = native.crc32c(data) == crc
    profiler.lap("packet_verify", t0)
    if flags & FLAG_LAST:
        profiler.flush_laps()
    if not ok:
        raise IOError(f"packet {seqno}: checksum mismatch")
    return seqno, data, flags, crc


def read_packet_ex(sock: socket.socket) -> tuple[int, bytes, int]:
    return read_packet_crc(sock)[:3]


def read_packet(sock: socket.socket) -> tuple[int, bytes, bool]:
    seqno, data, flags, _crc = read_packet_crc(sock)
    return seqno, data, bool(flags & FLAG_LAST)


def iter_packets(sock: socket.socket) -> Iterator[tuple[int, bytes, bool]]:
    while True:
        seqno, data, last = read_packet(sock)
        yield seqno, data, last
        if last:
            return


def iter_packets_ex(sock: socket.socket) -> Iterator[tuple[int, bytes, int]]:
    """Flag-preserving packet run iterator (the write path needs FLUSH/SYNC
    markers; readers of whole runs use iter_packets)."""
    while True:
        seqno, data, flags = read_packet_ex(sock)
        yield seqno, data, flags
        if flags & FLAG_LAST:
            return


class BlockBuffer:
    """One block's payload, landed once and read from where it landed.
    ``np.empty`` pages cost nothing until written, so ``capacity`` may be
    the deployment's block size whatever the block holds; a stream that
    sends more moves to a buffer twice the size (views of the old one stay
    good: they hold it, and its bytes do not change)."""

    def __init__(self, capacity: int):
        self.arr = np.empty(max(capacity, 1), np.uint8)
        self.size = 0

    def reserve(self, need: int) -> None:
        if need > self.arr.size:
            arr = np.empty(max(need, 2 * self.arr.size), np.uint8)
            arr[:self.size] = self.arr[:self.size]
            self.arr = arr      # swapped in whole: a reader sees old or new

    def view(self, start: int = 0, end: int | None = None) -> memoryview:
        return memoryview(self.arr)[start:self.size if end is None else end]


class PacketRun(NamedTuple):
    """Whole packets that had arrived together, verified: their header
    fields as arrays, and the range ``[start, end)`` of the block's buffer
    where their payloads lie back to back, in order."""
    seqnos: np.ndarray
    lens: np.ndarray
    flags: np.ndarray
    crcs: np.ndarray
    start: int
    end: int


# the run reader's staging buffer: the hop's stride and a header, more than
# a client's window of packets fills; a larger packet grows it
_STAGE = (4 << 20) + PKT_HDR.size


def iter_packet_runs(sock: socket.socket,
                     out: BlockBuffer) -> Iterator[PacketRun]:
    """The write stream a run at a time.  A run is every whole packet that
    has already arrived: ONE ``recv_into`` of whatever the socket holds (it
    returns with the first byte and never waits for a count; a packet still
    in pieces takes another), then ONE native call that parses the staged
    headers, verifies each payload against its header's CRC32C and copies
    it to ``out`` (``native.PacketUnpacker``), then the trailing partial
    packet moves to the front.  A run ends at the last whole packet staged
    or at ``FLAG_LAST``; a run of one is what a receiver ahead of its
    sender sees.  A mismatch raises what ``read_packet_crc`` raises, after
    the run of the packets before it.  The verify and its copy are one
    ``packet_verify`` lap a run."""
    unpack = native.PacketUnpacker(_STAGE)
    have, need = 0, PKT_HDR.size
    while True:
        view = memoryview(unpack.stage)
        while have < need:
            r = sock.recv_into(view[have:], len(view) - have)
            if r == 0:
                raise ConnectionError("peer closed connection")
            have += r
        t0 = profiler.mark()
        n, used, need, why = unpack(have, out.arr, out.size)
        profiler.lap("packet_verify", t0)
        if why == unpack.LAST:
            profiler.flush_laps()
        if n:
            start, out.size = out.size, out.size + int(unpack.lens[:n].sum())
            yield PacketRun(unpack.seqnos[:n].copy(), unpack.lens[:n].copy(),
                            unpack.flags[:n].copy(), unpack.crcs[:n].copy(),
                            start, out.size)
        if why == unpack.LAST:
            return
        if why == unpack.MISMATCH:
            raise IOError(f"packet {int(unpack.seqnos[n])}: "
                          "checksum mismatch")
        if why == unpack.OUT_FULL:
            out.reserve(out.size + int(unpack.lens[n]))
        have -= used
        if have:
            unpack.stage[:have] = unpack.stage[used:used + have]
        if need > unpack.stage.size:
            unpack.grow_stage(need, keep=have)


def send_ack(sock: socket.socket, seqno: int, status: int = ACK_SUCCESS) -> None:
    sock.sendall(ACK.pack(seqno, status))


_ACK_DT = np.dtype([("seqno", "<u8"), ("status", "u1")])    # = ACK, packed


def pack_acks(seqnos: np.ndarray, status: int = ACK_SUCCESS) -> bytes:
    """One ``ACK`` a seqno, in order, as ``send_ack`` would write them."""
    acks = np.empty(len(seqnos), _ACK_DT)
    acks["seqno"], acks["status"] = seqnos, status
    return acks.tobytes()


def read_ack(sock: socket.socket) -> tuple[int, int]:
    seqno, status = ACK.unpack(recv_exact(sock, ACK.size))
    return seqno, status


def stream_bytes(sock: socket.socket, data: bytes,
                 packet_size: int = DEFAULT_PACKET, base_seqno: int = 0,
                 throttle=None) -> int:
    """Packetize ``data`` onto the socket, ending with an empty LAST packet
    (the reference's zero-payload trailer that carries lastPacketInBlock).
    Returns the number of packets sent.  ``throttle(nbytes)`` is invoked
    before each packet when given (DataTransferThrottler's per-packet
    gating in BlockSender.sendPacket)."""
    seqno = base_seqno
    for off in range(0, len(data), packet_size):
        pkt = data[off:off + packet_size]
        if throttle is not None:
            throttle(len(pkt))
        write_packet(sock, seqno, pkt)
        seqno += 1
    write_packet(sock, seqno, b"", last=True)
    return seqno - base_seqno + 1


def stream_bytes_acked(sock: socket.socket, data: bytes, packet_size: int,
                       window: int) -> tuple[int, int]:
    """``stream_bytes`` for a peer that answers every packet with an ack:
    at most ``window`` packets are ever outstanding (the DataStreamer /
    ResponseProcessor window, DataStreamer.java:655), and the LAST ack —
    the one that carries the pipeline status — is returned as
    ``(seqno_field, status)``.  Sending a whole 128 MiB block before
    reading any ack left ~2000 twelve-byte segments queued on the sender's
    receive side; on the v5e host they then arrived at ~14 ms apiece (my
    chip runs, PR 22: 30 s per block in ``read_ack`` with no thread on the
    DataNode still working for it)."""
    window = max(1, window)
    sent = acked = 0
    last = (0, ACK_SUCCESS)
    for off in range(0, len(data), packet_size):
        write_packet(sock, sent, data[off:off + packet_size])
        sent += 1
        while sent - acked >= window:
            last = read_ack(sock)
            acked += 1
    write_packet(sock, sent, b"", last=True)
    sent += 1
    while acked < sent:
        last = read_ack(sock)
        acked += 1
    return last


def fetch_block(addr: tuple, block_id: int, offset: int = 0,
                length: int = -1, timeout: float = 60,
                token: dict | None = None, encrypt: bool = False) -> bytes:
    """One-shot READ_BLOCK: connect, request [offset, offset+length), collect
    the packet run, length-check.  Shared by the EC degraded-read path
    (client/striped.py) and DN reconstruction fan-in (server/datanode.py)."""
    from hdrf_tpu.proto.rpc import recv_frame

    sock = socket.create_connection(addr, timeout=timeout)
    try:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock = secure_socket(sock, token, encrypt)
        send_op(sock, READ_BLOCK, block_id=block_id, offset=offset,
                length=length, token=token)
        hdr = recv_frame(sock)
        if hdr["status"] != 0:
            if hdr.get("error") == "ShedError":
                from hdrf_tpu.utils import qos

                raise qos.ShedError(
                    f"datanode shed: {hdr.get('message', '')}",
                    retry_after_s=float(hdr.get("retry_after_s") or 0.0))
            raise IOError(f"datanode error: {hdr['error']}: "
                          f"{hdr.get('message', '')}")
        data = collect_packets(sock)
        if len(data) != hdr["length"]:
            raise IOError(f"short read: {len(data)} != {hdr['length']}")
        return data
    finally:
        sock.close()


def collect_packets(sock: socket.socket, ack_sock: socket.socket | None = None,
                    on_packet=None) -> bytes:
    """Receive a full packet run; optionally ack each packet on ``ack_sock``
    and/or forward via ``on_packet(seqno, data, last)`` (mirroring hook)."""
    parts: list[bytes] = []
    for seqno, data, last in iter_packets(sock):
        parts.append(data)
        if on_packet is not None:
            on_packet(seqno, data, last)
        if ack_sock is not None:
            send_ack(ack_sock, seqno)
    return b"".join(parts)


# ----------------------------------------------------------- stride frames

STRIDE_HDR = struct.Struct("<IB")
_IOV_MAX = 512      # buffers per sendmsg (the kernel takes 1024)


def _sendmsg_all(sock: socket.socket, bufs: list) -> None:
    """``sendall`` for a list of buffers: scatter-gather, no join."""
    i, n = 0, len(bufs)
    while i < n:
        sent = sock.sendmsg(bufs[i:i + _IOV_MAX])
        while i < n and sent >= len(bufs[i]):    # wholly sent (or empty)
            sent -= len(bufs[i])
            i += 1
        if sent:                                 # a short write: the rest
            bufs[i] = memoryview(bufs[i])[sent:]


def write_stride(sock: socket.socket, segs: list, crcs: list[int],
                 last: bool = False) -> None:
    """One stride frame: the header and ``segs`` (bytes-likes, each with
    its CRC32C in ``crcs``) leave in one ``sendmsg``."""
    table = [v for seg, crc in zip(segs, crcs) for v in (len(seg), crc)]
    hdr = struct.pack(f"<IB{len(table)}I", len(segs),
                      FLAG_LAST if last else 0, *table)
    _sendmsg_all(sock, [hdr, *segs])


def _recv_all_into(sock: socket.socket, buf) -> None:
    """Fill ``buf`` (any writable buffer) from the socket, in place."""
    view, got = memoryview(buf), 0
    while got < len(view):
        r = sock.recv_into(view[got:], len(view) - got, socket.MSG_WAITALL)
        if r == 0:
            raise ConnectionError("peer closed connection")
        got += r


def read_stride(sock: socket.socket, out: np.ndarray | None = None
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray, bool]:
    """Receive one stride frame, unverified: ``(buf, lens, crcs, last)``
    with the segments back to back in ``buf``, a fresh ``uint8`` array the
    caller may hand on as it is (a device upload may still be reading the
    one before) — or, given ``out``, the front of that array: a caller who
    knows the stream's size lands every frame in one buffer.  A frame
    longer than ``out`` is an IOError before a byte of it is read: the
    stream cannot go on, and whoever serves it hangs up."""
    nseg, flags = STRIDE_HDR.unpack(recv_exact(sock, STRIDE_HDR.size))
    table = np.frombuffer(recv_exact(sock, 8 * nseg), "<u4").reshape(-1, 2)
    lens, crcs = table[:, 0], table[:, 1]
    size = int(lens.sum(dtype=np.int64))
    if out is None:
        buf = np.empty(size, np.uint8)
    elif size > out.size:
        raise IOError(f"stride of {size} bytes: {out.size} left of the "
                      "size the stream stated")
    else:
        buf = out[:size]
    _recv_all_into(sock, buf)
    return buf, lens, crcs, bool(flags & FLAG_LAST)


def verify_stride(buf: np.ndarray, lens: np.ndarray,
                  crcs: np.ndarray) -> None:
    """Every segment of a stride against its carried CRC32C.  A mismatch
    raises ValueError, not IOError: the bytes are wrong, the connection is
    not, and the worker answers an error frame for it instead of hanging
    up.  Equal segments (all but a shorter last: every stride of 64 KiB
    client packets) are one native call."""
    if not len(lens):
        return
    seg = int(lens[0])
    if (lens[:-1] == seg).all() and 0 < lens[-1] <= seg:
        ok = native.crc32c_chunks(buf, seg) == crcs
    else:
        ends = np.cumsum(lens, dtype=np.int64)
        ok = np.array([native.crc32c(buf[e - n:e]) == c
                       for e, n, c in zip(ends.tolist(), lens.tolist(),
                                          crcs.tolist())])
    if not ok.all():
        raise ValueError(f"stride segment {int(np.argmin(ok))} of "
                         f"{len(lens)}: checksum mismatch")


# A stream of bytes the sender already holds (a sealed container on the hop
# to the worker, a chunk delta or a stored block on the mirror leg): frames
# of ``STRIDE`` bytes, the reduce op's upload stride, each cut into segments
# of ``SEGMENT`` with a CRC32C each (the packet wire's granularity of the
# check), the sums of a frame from one native call.
STRIDE = 4 << 20
SEGMENT = 1 << 20


def frames_of(buf) -> list[memoryview]:
    """``buf`` (any contiguous bytes-like) as views of ``STRIDE`` bytes,
    the last shorter; none for an empty ``buf``."""
    view = memoryview(buf).cast("B")
    return [view[o:o + STRIDE] for o in range(0, len(view), STRIDE)]


def chunk_frames(chunks: list) -> Iterator[bytes | memoryview]:
    """``chunks`` (bytes-likes) back to back, cut into frames of ``STRIDE``
    bytes, the last shorter: one join a frame (a frame inside one chunk is
    a view of it), a chunk that crosses a frame's end split between the
    two."""
    lens = np.fromiter(map(len, chunks), np.int64, len(chunks))
    ends = np.cumsum(lens)
    total = int(ends[-1]) if len(ends) else 0
    for lo in range(0, total, STRIDE):
        hi = min(lo + STRIDE, total)
        i = int(np.searchsorted(ends, lo, "right"))   # first past ``lo``
        j = int(np.searchsorted(ends, hi, "left"))    # holds byte hi - 1
        a, b = lo - int(ends[i] - lens[i]), hi - int(ends[j] - lens[j])
        if i == j:
            yield memoryview(chunks[i])[a:b]
            continue
        parts = chunks[i:j + 1]
        parts[0], parts[-1] = memoryview(parts[0])[a:], \
            memoryview(parts[-1])[:b]
        yield b"".join(parts)


def write_frame(sock: socket.socket, frame, last: bool = False) -> None:
    """``frame`` (a contiguous bytes-like of at most ``STRIDE`` bytes) as
    one stride frame: segments are views of ``SEGMENT`` bytes of it, their
    CRC32Cs one native call, all in one ``sendmsg``."""
    view = memoryview(frame).cast("B")
    write_stride(sock, [view[o:o + SEGMENT]
                        for o in range(0, len(view), SEGMENT)],
                 native.crc32c_chunks(view, SEGMENT).tolist(), last)


def write_frames(sock: socket.socket, frames: Iterable,
                 before=None) -> None:
    """Each of ``frames`` as a stride frame (``write_frame``), then an
    empty ``FLAG_LAST`` one.  ``before(k, nbytes)`` runs ahead of frame
    ``k``, the trailer's (``nbytes`` 0) included: a throttle, a fault
    point."""
    k = 0
    for frame in frames:
        if before is not None:
            before(k, len(frame))
        write_frame(sock, frame)
        k += 1
    if before is not None:
        before(k, 0)
    write_stride(sock, [], [], last=True)


def read_frames(sock: socket.socket, size: int, span: str | None = None,
                read_on: bool = False) -> tuple[np.ndarray, int, int]:
    """What ``write_frames`` sends, landed in ONE buffer of the ``size``
    bytes its sender stated: ``(buf, frames, segments)``, the frames and
    segments that carried bytes.  Each frame's segments are checked
    against their CRC32Cs (one native call a frame) before the next frame
    is read; given ``span``, a frame's read and check are one span of that
    name.  A frame that fails its check raises ValueError (the bytes are
    wrong, as in ``verify_stride``): at once, or with ``read_on`` after
    the rest of the stream is read, so that an answer leaves on a
    connection still in step; so does a stream that ends short of
    ``size``.  A frame that runs past ``size`` is ``read_stride``'s
    IOError: the stream cannot go on.  Either way no byte is handed
    on."""
    buf = np.empty(size, np.uint8)
    got = k = frames = segments = 0
    bad: ValueError | None = None
    last = False
    while not last:
        with profiler.phase(span) if span else contextlib.nullcontext():
            part, lens, crcs, last = read_stride(sock, buf[got:])
            if part.size and bad is None:
                try:
                    verify_stride(part, lens, crcs)
                except ValueError as e:
                    bad = ValueError(f"frame {k}: {e}")
                    if not read_on:
                        raise bad from e
        if part.size:
            got += part.size
            frames += 1
            segments += len(lens)
        k += 1
    if bad is not None:
        raise bad
    if got != size:
        raise ValueError(f"stated {size} bytes, streamed {got}")
    return buf, frames, segments


# -------------------------------------------------------------- raw replies


def send_with_payloads(sock: socket.socket, header: Any,
                       payloads: list) -> None:
    """A msgpack frame and, behind it, ``payloads`` (bytes-likes whose
    lengths the header states) as they are: one ``sendmsg``, nothing packed
    or joined."""
    _sendmsg_all(sock, [pack_frame(header), *payloads])


def recv_payload(sock: socket.socket, n: int) -> bytearray:
    """``n`` raw bytes into one buffer, handed on as it is (``recv_exact``
    would copy it to ``bytes``)."""
    buf = bytearray(n)
    _recv_all_into(sock, buf)
    return buf
