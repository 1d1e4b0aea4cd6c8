"""Closed-loop positional reader (TestDFSIO ``-read -random`` shape).

Set-up writes ``files`` files (one shared set, made from the seed by every
client so that each can check what it reads), file ``j`` by client
``j % clients``.  In the window each client issues ``read_bytes``-byte reads
at uniform random offsets over all files through
``HdrfClient.read(path, offset, length)``.  Every read is compared with the
generated bytes as soon as its clock has stopped: a read that differs is a
failed operation.  After the window its set-up files' blocks are held to the
configuration's replication (``replicas.short_blocks``).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference.chunking as ref
import replicas


def _path(j: int) -> str:
    return f"/perfbench/shared/f{j:05d}"


def prepare(ctx) -> None:
    src = ctx.generator.Source(ctx.params, ctx.seed, 0)   # the shared set
    with ThreadPoolExecutor(4) as pool:     # rows of one file need no other
        ctx.state["files"] = list(pool.map(
            lambda j: src.file(j).tobytes(), range(int(ctx.params["files"]))))
    ctx.state["written"] = []


def setup(ctx, client) -> list:
    ops = []
    scheme = ctx.config["cluster"]["scheme"]
    for j, data in enumerate(ctx.state["files"]):
        if j % ctx.clients != ctx.idx:
            continue
        rec = ctx.op("write", _path(j), len(data),
                     lambda: client.write(_path(j), data, scheme=scheme))
        if rec["ok"]:
            ctx.state["written"].append(j)
        ops.append(rec)
    return ops


def run(ctx, client, until: float) -> list:
    files = ctx.state["files"]
    n = int(ctx.params["read_bytes"])
    rng = np.random.default_rng([ctx.seed, ctx.idx, 6_000_000])
    ops = []
    while time.time() < until:
        j = int(rng.integers(0, len(files)))
        off = int(rng.integers(0, len(files[j]) - n + 1))
        rec = ctx.op("read", _path(j), n, lambda: client.read(
            _path(j), offset=off, length=n))
        if rec["ok"] and rec["out"] != files[j][off:off + n]:
            rec["ok"], rec["err"] = False, f"{_path(j)} [{off}, +{n}) differs"
        rec["out"] = None
        ops.append(rec)
    return ops


def check(ctx, client) -> dict:
    short, _ = replicas.short_blocks(
        client, [_path(j) for j in ctx.state["written"]],
        int(ctx.config["cluster"]["replication"]))
    t0 = time.time()
    table, chunks = ref.chunk_tables(
        (ctx.state["files"][j] for j in ctx.state["written"]),
        ctx.config["cdc"], ctx.config["cluster"]["block_size"])
    return {"table": table, "chunks": chunks,
            "files": len(ctx.state["written"]),
            "logical_bytes": sum(len(ctx.state["files"][j])
                                 for j in ctx.state["written"]),
            "reference_s": time.time() - t0, "readback_s": 0.0,
            "readback_reads": 0, "readback_bytes": 0, "readback_bad": 0,
            "readback_bad_by_dn": {},
            "replicas_short": short, "errors": [], "made_in_window": 0}
