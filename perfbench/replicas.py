"""What the NameNode says of a client's files after the window, and reading a
range back from each replica, one location at a time (the drivers' ``check``
uses both, in the client processes).

``short_blocks`` counts written blocks with fewer finalized locations than
the configuration's replication (a location reaches the NameNode by the
DataNode's incremental report, so it waits up to ``wait_s`` for a late one:
late is not wrong).  ``read_each`` reads ``[off, off + ln)`` through the
client library's own block read (``HdrfClient._read_block``), handed one
DataNode's location of each block at a time, so a replica that differs
cannot hide behind another that does not.
"""

from __future__ import annotations

import time


def short_blocks(client, paths: list, want: int,
                 wait_s: float = 10.0) -> tuple[int, dict]:
    """(blocks with fewer than ``want`` locations, ``{path: locations}``)."""
    deadline = time.time() + wait_s
    while True:
        locs = {p: client._call("get_block_locations", path=p) for p in paths}
        short = sum(len(b["locations"]) < want
                    for loc in locs.values() for b in loc["blocks"])
        if not short or time.time() >= deadline:
            return short, locs
        time.sleep(0.5)


def read_each(client, loc: dict, off: int, ln: int) -> dict:
    """``{dn_id: bytes, or the exception that read raised}`` for every
    DataNode that the NameNode names for a block of the range."""
    spans, pos = [], 0
    for b in loc["blocks"]:
        start, pos = pos, pos + b["length"]
        if pos <= off or start >= off + ln:
            continue
        lo = max(off, start) - start
        spans.append((b, lo, min(off + ln, pos) - start - lo))
    out = {}
    for dn_id in sorted({x["dn_id"] for b, _, _ in spans
                         for x in b["locations"]}):
        try:
            parts = []
            for b, lo, n in spans:
                mine = [x for x in b["locations"] if x["dn_id"] == dn_id]
                if not mine:
                    raise IOError(f"block {b['block_id']} has no replica "
                                  f"on {dn_id}")
                parts.append(client._read_block(dict(b, locations=mine),
                                                lo, n))
            out[dn_id] = b"".join(parts)
        except Exception as e:  # noqa: BLE001 — a bad replica, reported
            out[dn_id] = e
    return out
