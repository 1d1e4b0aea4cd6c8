"""Closed-loop writer: whole files through ``HdrfClient.write``, back to back.

Traffic parameters (``perfbench/traffic/<mix>.json`` → ``params``):

- ``file_blocks``: a file is this many of the configuration's blocks;
- ``setup_files`` / ``setup_clients`` (``"all"`` or ``"first"``): files each
  of those clients writes before the window (the warm block; the trees'
  generation 0).  The window goes on from the next file index;
- ``pregenerate``: window files each client makes before the barrier; past
  them it makes files in the window (``made_in_window`` says how many);
- ``readback_full`` / ``readback_ranges`` / ``readback_range_bytes``: after
  the window each client reads back that many whole files, drawn from the
  seed among those it wrote, and of each other file that many ranges of
  that many bytes at odd offsets — each range from every replica the
  NameNode names, one location at a time (``replicas.py``), after counting
  the blocks with fewer locations than the configuration's replication.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference.chunking as ref
import replicas


def _n_setup(ctx) -> int:
    p = ctx.params
    mine = p.get("setup_clients", "all") == "all" or ctx.idx == 0
    return int(p.get("setup_files", 0)) if mine else 0


def _path(ctx, k: int) -> str:
    return f"/perfbench/c{ctx.idx}/f{k:05d}"


def prepare(ctx) -> None:
    ctx.state["source"] = ctx.generator.Source(ctx.params, ctx.seed, ctx.idx)
    first = int(ctx.params.get("setup_files", 0))
    want = list(range(_n_setup(ctx)))
    want += list(range(first, first + int(ctx.params["pregenerate"])))
    src = ctx.state["source"]
    # bytes, as the client library takes them; kept for the comparison.  A
    # generator whose files do not depend on one another makes them on a few
    # threads (numpy drops the interpreter lock), so set-up does not wait
    threads = 4 if getattr(ctx.generator, "PARALLEL", False) else 1
    with ThreadPoolExecutor(threads) as pool:
        made = pool.map(lambda k: src.file(k).tobytes(), want)
        ctx.state["files"] = dict(zip(want, made))
    ctx.state["written"] = []
    ctx.state["made_in_window"] = 0


def _write(ctx, client, k: int):
    files = ctx.state["files"]
    if k not in files:
        files[k] = ctx.state["source"].file(k).tobytes()
        ctx.state["made_in_window"] += 1
    data = files[k]
    scheme = ctx.config["cluster"]["scheme"]
    rec = ctx.op("write", _path(ctx, k), len(data),
                 lambda: client.write(_path(ctx, k), data, scheme=scheme))
    if rec["ok"]:
        ctx.state["written"].append(k)
    else:
        files.pop(k, None)
    return rec


def setup(ctx, client) -> list:
    return [_write(ctx, client, k) for k in range(_n_setup(ctx))]


def run(ctx, client, until: float) -> list:
    ops, k = [], int(ctx.params.get("setup_files", 0))
    while time.time() < until:
        ops.append(_write(ctx, client, k))
        k += 1
    return ops


def _reference(ctx, out: dict) -> None:
    """The reference's chunk table of every file this client wrote."""
    files = ctx.state["files"]
    t0 = time.time()
    out["table"], out["chunks"] = ref.chunk_tables(
        (files[k] for k in ctx.state["written"]), ctx.config["cdc"],
        ctx.config["cluster"]["block_size"])
    out["seconds"] = time.time() - t0


def check(ctx, client) -> dict:
    files, written = ctx.state["files"], ctx.state["written"]
    # the reference computes (numpy and hashlib drop the interpreter lock)
    # while the read-back waits on the DataNodes
    refd: dict = {}
    worker = threading.Thread(target=_reference, args=(ctx, refd))
    worker.start()
    short, locs = replicas.short_blocks(
        client, [_path(ctx, k) for k in written],
        int(ctx.config["cluster"]["replication"]))
    rng = np.random.default_rng([ctx.seed, ctx.idx, 5_000_000])
    first = int(ctx.params.get("setup_files", 0))
    window = [k for k in written if k >= first] or list(written)
    n_full = min(int(ctx.params.get("readback_full", 1)), len(window))
    full = set(rng.choice(window, size=n_full, replace=False).tolist()) \
        if n_full else set()
    bad = compared = reads = 0
    bad_by_dn: dict = {}
    errors = []
    t0 = time.time()
    for k in written:
        data = files[k]
        if k in full:
            spans = [(0, len(data))]
        else:
            spans = []
            for _ in range(int(ctx.params.get("readback_ranges", 2))):
                ln = min(int(ctx.params.get("readback_range_bytes",
                                            1 << 20)), len(data)) | 1
                off = int(rng.integers(0, max(len(data) - ln, 1))) | 1
                spans.append((off, min(ln, len(data) - off)))
        for off, ln in spans:
            # every replica the NameNode names, one location at a time
            got = replicas.read_each(client, locs[_path(ctx, k)], off, ln)
            if not got:
                bad += 1
                errors.append(f"{_path(ctx, k)}: no location")
            for dn_id, out in got.items():
                reads += 1
                compared += ln
                if isinstance(out, Exception):
                    err = f"{type(out).__name__}: {out}"[:300]
                elif out != data[off:off + ln]:
                    err = f"{_path(ctx, k)} [{off}, +{ln}) differs"
                else:
                    continue
                bad += 1
                bad_by_dn[dn_id] = bad_by_dn.get(dn_id, 0) + 1
                errors.append(f"{dn_id}: {err}")
    t_read = time.time() - t0
    worker.join()
    return {"table": refd["table"], "chunks": refd["chunks"],
            "files": len(written),
            "logical_bytes": sum(len(files[k]) for k in written),
            "reference_s": refd["seconds"], "readback_s": t_read,
            "readback_reads": reads, "readback_bytes": compared,
            "readback_bad": bad, "readback_bad_by_dn": bad_by_dn,
            "replicas_short": short, "errors": errors[:5],
            "made_in_window": ctx.state["made_in_window"]}
