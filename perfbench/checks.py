"""The comparison that decides ``correct``: what the timed path stored and
returned against the plain reference (``perfbench/reference/chunking.py``,
run in the client processes on the very bytes they sent).

Every number compared is exact, so every limit is 0 (the list is in
``perfbench/README.md``).  ``compare`` returns ``{name: [value, limit]}``; a
run is correct when every value is within its limit.  With N DataNodes each
DataNode's comparisons are taken on it and summed.
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


def compare(nodes: list, client_checks: list, ops: list,
            client_counters: list, workers: list, fault: str,
            parent_backends: list, block_size: int) -> tuple[dict, dict]:
    """Returns (checks, notes).  ``nodes``: one dict a DataNode with its
    ``dn_id``, ``before`` (the snapshot taken when the cluster came up: a
    fresh store) and ``after`` (the one after the window), ``missing`` (the
    reference's digests absent from its index), ``sealed``
    (``decode_sealed``), ``orphans``, ``mirror``, ``backends`` (its process's,
    None where that is the harness's) and ``physical_bytes`` (its part of
    ``stored_pct``'s numerator, held here to the bytes of the sealed files
    the reference decoder was given).  Every comparison of a DataNode is
    taken on each and summed; ``notes["per_datanode"]`` keeps each."""
    table: dict[bytes, int] = {}
    ref_chunks = 0
    for c in client_checks:
        ref_chunks += c["chunks"]
        for d, ln in c["table"].items():
            table.setdefault(d, ln)
    logical = sum(c["logical_bytes"] for c in client_checks)
    ref_unique_bytes = sum(table.values())
    per_dn = {}
    for n in nodes:
        idx, sealed = n["after"]["index"], n["sealed"]
        gw = n["after"]["give_way"]
        per_dn[n["dn_id"]] = {
            "readback_bad": sum(c["readback_bad_by_dn"].get(n["dn_id"], 0)
                                for c in client_checks),
            "logical_bytes_gap": abs(idx["logical_bytes"] - logical),
            "unique_chunks_gap": abs(idx["chunks"] - len(table)),
            "unique_bytes_gap": abs(idx["unique_chunk_bytes"]
                                    - ref_unique_bytes),
            "digests_missing": n["missing"],
            "sealed_decode_failures": sealed["failures"],
            # bytes a writer appended for a chunk that a concurrent writer's
            # commit won: in the containers, owned by no index entry,
            # counted by the index
            "sealed_bytes_gap": abs(sealed["decoded_bytes"] - n["orphans"]
                                    - idx["unique_chunk_bytes"]),
            "stored_bytes_gap": abs(n["physical_bytes"]
                                    - sealed["file_bytes"]),
            "worker_fallbacks": gw["worker_fallbacks"],
            "degraded_writes": gw["degraded_writes"],
            "breaker_open_total": gw["breaker_open_total"],
            "reduction_degraded": gw["reduction_degraded"],
            "mirror_failures": (n["mirror"]["failed_legs"]
                                + n["mirror"]["partial_replicas"]),
            "no_device_dispatch": int(n["backend"] == "tpu"
                                      and n["after"]["dispatch_total"] <= 0),
            "parent_jax_backends": len(n["backends"] or ()),
        }

    def total(name: str) -> int:
        return sum(v[name] for v in per_dn.values())

    blocks = sum(-(-op["bytes"] // block_size) for op in ops
                 if op["kind"] == "write" and op["ok"])
    # reduced mirroring: a block is reduced once, by its pipeline's first
    # DataNode, on that DataNode's worker
    reduced = sum(n["after"]["give_way"]["worker_reduces"]
                  - n["before"]["give_way"]["worker_reduces"] for n in nodes)
    # a block the client had to send twice is a failed first attempt
    retries = sum(c.get("block_write_retries", 0)
                  + c.get("write_sheds_seen", 0) for c in client_counters)
    checks = {
        "ops_failed": [sum(1 for op in ops if not op["ok"]), 0],
        "readback_bad": [sum(c["readback_bad"] for c in client_checks), 0],
        **{k: [total(k), 0] for k in (
            "logical_bytes_gap", "unique_chunks_gap", "unique_bytes_gap",
            "digests_missing", "sealed_decode_failures", "sealed_bytes_gap",
            "stored_bytes_gap", "worker_fallbacks", "degraded_writes",
            "breaker_open_total", "reduction_degraded")},
        "client_block_retries": [retries, 0],
        "blocks_not_on_worker": [max(blocks - reduced, 0), 0],
        "replicas_short": [sum(c["replicas_short"] for c in client_checks),
                           0],
        "mirror_failures": [total("mirror_failures"), 0],
        "parent_jax_backends": [len(parent_backends)
                                + total("parent_jax_backends"), 0],
        "device_not_tpu": [sum(int(w.backend != "tpu"
                                   or w.device.get("platform") != "tpu")
                               for w in workers), 0],
        "no_device_dispatch": [total("no_device_dispatch"), 0],
        "fault_planted": [int(bool(fault)), 0],
    }
    notes = {
        "reference": {"chunks": ref_chunks, "unique_chunks": len(table),
                      "unique_bytes": ref_unique_bytes,
                      "logical_bytes": logical,
                      "seconds": max((c["reference_s"] for c in client_checks),
                                     default=0.0)},
        "index": [n["after"]["index"] for n in nodes],
        "sealed": [n["sealed"] for n in nodes],
        "orphan_bytes": [n["orphans"] for n in nodes],
        "readback": {"reads": sum(c["readback_reads"] for c in client_checks),
                     "bytes": sum(c["readback_bytes"] for c in client_checks),
                     "seconds": max((c["readback_s"] for c in client_checks),
                                    default=0.0),
                     "errors": [e for c in client_checks
                                for e in c["errors"]][:5]},
        "made_in_window": sum(c["made_in_window"] for c in client_checks),
        "per_datanode": per_dn,
    }
    if len(nodes) == 1:     # the notes as the one-DataNode layout gave them
        for k in ("index", "sealed", "orphan_bytes"):
            notes[k] = notes[k][0]
    return checks, notes


def verdict(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())
