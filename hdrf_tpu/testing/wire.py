"""Test helpers for the data-transfer packet wire (proto/datatransfer.py):
packets framed as ``write_packet`` frames them, and the receive side of a
socket that hands a byte stream over in chosen pieces — what ``recv_into``
sees of a TCP stream, without a peer or a thread.  The framing is the
reference's packet header (DataTransferProtocol.java:42; a receiver reads
it as BlockReceiver.java:877-897 does, from whatever pieces arrive)."""

from __future__ import annotations

import itertools
import socket
from typing import Iterable

from hdrf_tpu import native
from hdrf_tpu.proto import datatransfer as dt


def frame_packets(packets: Iterable[tuple[int, bytes, int]]) -> bytes:
    """``(seqno, payload, flags)`` packets as they go on the wire."""
    return b"".join(
        dt.PKT_HDR.pack(len(d), seq, fl, native.crc32c(d)) + bytes(d)
        for seq, d, fl in packets)


class PiecedSocket:
    """Answers ``recv_into`` with ``wire`` in pieces of ``sizes`` (cycled),
    then 0 as a closed peer does; ``calls`` counts the receives."""

    def __init__(self, wire, sizes: Iterable[int]):
        self._wire, self._off = memoryview(wire), 0
        self._sizes = itertools.cycle(sizes)
        self.calls = 0

    def recv_into(self, view, n: int = 0) -> int:
        take = min(next(self._sizes), n or len(view),
                   len(self._wire) - self._off)
        view[:take] = self._wire[self._off:self._off + take]
        self._off += take
        self.calls += 1
        return take


def open_write_block(mc, path: str, tenant: str | None = "raw",
                     encrypted: bool = False) -> tuple[socket.socket, int]:
    """A client's ``dedup_lz4`` WRITE_BLOCK op on a raw socket to a
    MiniCluster's first DataNode, the file created and its block allocated
    at the NameNode first: ``(socket, block id)``.  ``tenant`` is the op's
    ``_client`` field; ``None`` sends none, as an internal relay does."""
    nn = mc.namenode
    nn.rpc_create(path, client="raw", scheme="dedup_lz4")
    alloc = nn.rpc_add_block(path, client="raw")
    s = socket.create_connection(mc.datanodes[0].addr, timeout=20)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    s = dt.secure_socket(s, alloc.get("token"), encrypted)
    fields = {} if tenant is None else {"_client": tenant}
    dt.send_op(s, dt.WRITE_BLOCK, block_id=alloc["block_id"],
               gen_stamp=alloc["gen_stamp"], scheme="dedup_lz4",
               token=alloc.get("token"), targets=[], **fields)
    return s, alloc["block_id"]
