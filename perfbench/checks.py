"""The comparison that decides ``correct``: what the timed path stored and
returned against the plain reference (``perfbench/reference/chunking.py``,
run in the client processes on the very bytes they sent).

Every number compared is exact, so every limit is 0 (the list is in
``perfbench/README.md``).  ``compare`` returns ``{name: [value, limit]}``; a
run is correct when every value is within its limit.
"""

from __future__ import annotations

import struct

import reference.chunking as ref

# A sealed container as the store writes it (storage/container_store.py,
# utils/codec.py; the on-disk format is part of what is under test): magic,
# uncompressed size, codec id, then the payload.
_SEAL_HDR = struct.Struct("<IQI")
_SEAL_MAGIC = 0x48435452
_CODEC_NONE, _CODEC_LZ4 = 0, 1


def decode_sealed(dn) -> dict:
    """Every sealed container through the reference LZ4 block decoder."""
    sealed = lz4n = nbytes = file_bytes = failures = 0
    errors = []
    for cid in dn.containers.container_ids():
        blob = dn.containers.sealed_file_bytes(cid)
        if blob is None:
            continue
        sealed += 1
        file_bytes += len(blob)
        try:
            magic, usize, codec_id = _SEAL_HDR.unpack_from(blob)
            if magic != _SEAL_MAGIC:
                raise ValueError("bad seal magic")
            payload = bytes(blob[_SEAL_HDR.size:])
            if codec_id == _CODEC_LZ4:
                out = ref.lz4_block_decode(payload, usize)
                if len(out) != usize:
                    raise ValueError("short decode")
                lz4n += 1
            elif codec_id != _CODEC_NONE or len(payload) != usize:
                raise ValueError(f"unexpected codec {codec_id}")
            nbytes += usize
        except Exception as e:  # noqa: BLE001 — a container that fails
            failures += 1
            errors.append(f"container {cid}: {type(e).__name__}: {e}"[:200])
    return {"sealed": sealed, "lz4_coded": lz4n, "decoded_bytes": nbytes,
            "file_bytes": file_bytes,
            "failures": failures, "errors": errors[:5]}


def compare(dn, client_checks: list, ops: list, before: dict, after: dict,
            client_counters: list, worker, fault: str,
            parent_backends: list, block_size: int,
            physical_bytes: int) -> tuple[dict, dict]:
    """Returns (checks, notes).  ``before`` is the snapshot taken when the
    cluster came up (a fresh store), ``after`` the one after the window;
    ``physical_bytes`` is ``stored_pct``'s numerator, held here to the bytes
    of the sealed files the reference decoder was given."""
    table: dict[bytes, int] = {}
    ref_chunks = 0
    for c in client_checks:
        ref_chunks += c["chunks"]
        for d, ln in c["table"].items():
            table.setdefault(d, ln)
    idx = after["index"]
    logical = sum(c["logical_bytes"] for c in client_checks)
    missing = 0
    digests = list(table)
    for i in range(0, len(digests), 8192):
        part = digests[i:i + 8192]
        for d, loc in dn.index.lookup_chunks(part).items():
            if loc is None or loc.length != table[d]:
                missing += 1
    sealed = decode_sealed(dn)
    # bytes a writer appended for a chunk that a concurrent writer's commit
    # won: in the containers, owned by no index entry, counted by the index
    orphans = sum(dn.index.orphan_bytes().values())
    gw0, gw1 = before["give_way"], after["give_way"]
    blocks = sum(-(-op["bytes"] // block_size) for op in ops
                 if op["kind"] == "write" and op["ok"])
    # a block the client had to send twice is a failed first attempt
    retries = sum(c.get("block_write_retries", 0)
                  + c.get("write_sheds_seen", 0) for c in client_counters)
    ref_unique_bytes = sum(table.values())
    checks = {
        "ops_failed": [sum(1 for op in ops if not op["ok"]), 0],
        "readback_bad": [sum(c["readback_bad"] for c in client_checks), 0],
        "logical_bytes_gap": [abs(idx["logical_bytes"] - logical), 0],
        "unique_chunks_gap": [abs(idx["chunks"] - len(table)), 0],
        "unique_bytes_gap": [abs(idx["unique_chunk_bytes"]
                                 - ref_unique_bytes), 0],
        "digests_missing": [missing, 0],
        "sealed_decode_failures": [sealed["failures"], 0],
        "sealed_bytes_gap": [abs(sealed["decoded_bytes"] - orphans
                                 - idx["unique_chunk_bytes"]), 0],
        "stored_bytes_gap": [abs(physical_bytes - sealed["file_bytes"]), 0],
        "worker_fallbacks": [gw1["worker_fallbacks"], 0],
        "degraded_writes": [gw1["degraded_writes"], 0],
        "breaker_open_total": [gw1["breaker_open_total"], 0],
        "reduction_degraded": [gw1["reduction_degraded"], 0],
        "client_block_retries": [retries, 0],
        "blocks_not_on_worker": [max(blocks - (gw1["worker_reduces"]
                                               - gw0["worker_reduces"]), 0),
                                 0],
        "parent_jax_backends": [len(parent_backends), 0],
        "device_not_tpu": [int(worker.backend != "tpu"
                               or worker.device.get("platform") != "tpu"), 0],
        "no_device_dispatch": [int(worker.backend == "tpu"
                                   and after["dispatch_total"] <= 0), 0],
        "fault_planted": [int(bool(fault)), 0],
    }
    notes = {
        "reference": {"chunks": ref_chunks, "unique_chunks": len(table),
                      "unique_bytes": ref_unique_bytes,
                      "logical_bytes": logical,
                      "seconds": max((c["reference_s"] for c in client_checks),
                                     default=0.0)},
        "index": idx, "sealed": sealed, "orphan_bytes": orphans,
        "readback": {"reads": sum(c["readback_reads"] for c in client_checks),
                     "bytes": sum(c["readback_bytes"] for c in client_checks),
                     "seconds": max((c["readback_s"] for c in client_checks),
                                    default=0.0),
                     "errors": [e for c in client_checks
                                for e in c["errors"]][:5]},
        "made_in_window": sum(c["made_in_window"] for c in client_checks),
    }
    return checks, notes


def verdict(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())
