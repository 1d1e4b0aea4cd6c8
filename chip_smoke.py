#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

Default (one chip): the repo's north-star deployment through its normal
entry points — ``MiniCluster(n_datanodes=1, replication=1, tpu_worker=True,
worker_backend="tpu")`` with the config defaults of BASELINE config 1
(128 MiB blocks, 32 MiB containers, ``dedup_lz4``, r=1).
Client, NameNode and DataNode live in THIS process, which never initialises
a JAX backend; the reduction worker is the one child that owns the chip.
Phases: build the native library here (``make -B``), start the cluster,
warm every program shape, write a seeded corpus (one block of phrase-log
data, then TeraGen-shaped 100-byte rows with planted duplicate spans), read
it all back plus ranged reads at odd offsets, compare with the native oracle
(chunk counts, unique bytes, every sealed container decodes), and assert
that nothing gave way to a host fallback.

``--multichip`` (four chips, run by the builder): ``reduce_sharded`` on one
real-size block over a ('data'=1, 'seq'=4) mesh in this one process,
bit-identical to the native oracle, shards on four distinct devices — and
no other phase.

Any failed phase exits non-zero.  The last stdout line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}`` on success,
``{"ok": false, ...}`` otherwise.  There is no CPU success mode.
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
MIB = 1 << 20


def note(**kw) -> None:
    """One JSON note per line on stdout (notes, not metrics)."""
    print(json.dumps(kw, sort_keys=True), flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ corpus

def phrase_block(rng, n: int):
    """Log-like block: random picks from a 64 KiB dictionary of 64-512 byte
    phrases — chunks stay unique for dedup, containers compress about 2x
    (TeraGen rows alone would leave the seal only one kind of payload)."""
    import numpy as np

    lens = rng.integers(64, 513, size=256)
    book = [rng.integers(0, 256, size=int(k), dtype=np.uint8) for k in lens]
    picks = rng.integers(0, len(book), size=n // 200 + 16)
    out = np.concatenate([book[i] for i in picks])
    return out[:n]


def teragen_rows(rng, n: int):
    """TeraGen-shaped records: 10 random key bytes, 10 ASCII row-id digits,
    78 filler bytes of per-row shifting 10-letter blocks, CRLF."""
    import numpy as np

    rows = -(-n // 100)
    rec = np.empty((rows, 100), dtype=np.uint8)
    rec[:, :10] = rng.integers(0, 256, size=(rows, 10), dtype=np.uint8)
    ids = np.arange(rows, dtype=np.int64)
    for d in range(10):
        rec[:, 10 + d] = (ids // 10 ** (9 - d) % 10 + 48).astype(np.uint8)
    fill = (np.arange(78) // 10).astype(np.uint8)[None, :]
    rec[:, 20:98] = 65 + ((ids % 26).astype(np.uint8)[:, None] + fill) % 26
    rec[:, 98], rec[:, 99] = 13, 10
    return rec.reshape(-1)[:n]


def make_corpus(seed: int, n_blocks: int, block: int):
    """Block 0: phrase-log data.  Blocks 1..: TeraGen rows, with duplicate
    spans planted at odd byte offsets (cut points must resynchronise)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n = n_blocks * block
    a = np.empty(n, dtype=np.uint8)
    a[:block] = phrase_block(rng, block)
    a[block:] = teragen_rows(rng, n - block)
    span = block // 8
    for k in range(1, n_blocks - 1):
        src = k * block + 12_345 + 1_001 * k
        dst = (k + 1) * block + block // 3 + 7 * k + 1
        a[dst:dst + span] = a[src:src + span]
    return a


def oracle(a, block: int, cdc):
    """Host-only reference: per block native CDC cuts + SHA-256.  Returns
    (total chunks, unique chunks, unique chunk bytes)."""
    import numpy as np

    from hdrf_tpu import native
    from hdrf_tpu.ops.dispatch import gear_mask

    mask = gear_mask(cdc)
    seen: dict[bytes, int] = {}
    total = 0
    for off in range(0, a.size, block):
        buf = a[off:off + block]
        cuts = native.cdc_chunk(buf, mask, cdc.min_chunk, cdc.max_chunk)
        starts = np.concatenate([[0], cuts[:-1]]).astype(np.uint64)
        lens = (cuts - starts).astype(np.uint64)
        digs = native.sha256_batch(buf, starts, lens)
        total += len(cuts)
        for d, ln in zip(digs, lens):
            seen.setdefault(d.tobytes(), int(ln))
    return total, len(seen), sum(seen.values())


# ---------------------------------------------------------------- one chip

_SEAL_HDR = struct.Struct("<IQI")   # storage/container_store.py: magic,
_SEAL_MAGIC = 0x48435452            # usize, codec id


def parent_backends() -> list[str]:
    """JAX backends this process has initialised (none, if it is to leave
    the chip to the worker).  Importing jax is not initialising it."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return [] if xb is None else sorted(getattr(xb, "_backends", {}))


def give_way_counters(dn) -> dict:
    from hdrf_tpu.utils import metrics

    c = {r: metrics.registry(r).snapshot()["counters"]
         for r in ("block_receiver", "datanode", "dedup", "resilience",
                   "client")}
    return {
        # a block the client had to send twice is a failed first attempt
        "client_block_retries": (c["client"].get("block_write_retries", 0)
                                 + c["client"].get("write_sheds_seen", 0)),
        "worker_fallbacks": (c["block_receiver"].get("worker_fallbacks", 0)
                             + c["datanode"].get("worker_fallbacks", 0)
                             + c["dedup"].get("worker_fallbacks", 0)),
        "degraded_writes": c["block_receiver"].get("degraded_writes", 0),
        "breaker_open_total": c["resilience"].get("breaker_open_total", 0),
        "reduction_degraded": int(bool(dn.reduction_degraded)),
    }


def decode_sealed(dn) -> tuple[int, int, int]:
    """Every sealed container decodes with the native LZ4 oracle.  Returns
    (sealed containers, lz4-coded ones, decoded bytes)."""
    import numpy as np

    from hdrf_tpu import native
    from hdrf_tpu.utils import codec as codecs

    sealed = lz4n = nbytes = 0
    for cid in dn.containers.container_ids():
        blob = dn.containers.sealed_file_bytes(cid)
        if blob is None:
            continue
        magic, usize, codec_id = _SEAL_HDR.unpack_from(blob)
        check(magic == _SEAL_MAGIC, f"container {cid}: bad seal magic")
        payload = np.frombuffer(blob, np.uint8, offset=_SEAL_HDR.size)
        if codec_id == codecs.CODEC_IDS["lz4"]:
            out = native.lz4_decompress(payload, usize)
            check(len(out) == usize, f"container {cid}: short decode")
            lz4n += 1
        else:
            check(codec_id == codecs.CODEC_IDS["none"]
                  and payload.size == usize,
                  f"container {cid}: unexpected codec {codec_id}")
        sealed += 1
        nbytes += usize
    return sealed, lz4n, nbytes


def run_served(args) -> dict:
    import numpy as np

    from hdrf_tpu.config import CdcConfig, NameNodeConfig, ReductionConfig
    from hdrf_tpu.testing.minicluster import MiniCluster

    red = ReductionConfig()
    block = args.block_mb * MIB if args.block_mb else NameNodeConfig().block_size
    container = (args.container_mb * MIB if args.container_mb
                 else red.container_size)
    cdc = CdcConfig()
    note(phase="config", block_bytes=block, container_bytes=container,
         scheme="dedup_lz4", replication=1,
         blocks=args.blocks, seed=args.seed, nproc=os.cpu_count(),
         worker_backend=args.worker_backend)

    t0 = time.perf_counter()
    corpus = make_corpus(args.seed, args.blocks, block)
    warm = phrase_block(np.random.default_rng(args.seed + 1), block)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    o_total, o_unique, o_bytes = oracle(corpus, block, cdc)
    note(phase="oracle", corpus_bytes=int(corpus.size), chunks=o_total,
         unique_chunks=o_unique, unique_bytes=o_bytes,
         gen_s=round(t_gen, 2), oracle_s=round(time.perf_counter() - t0, 2))

    with MiniCluster(n_datanodes=1, replication=1, block_size=block,
                     container_size=container, tpu_worker=True,
                     worker_backend=args.worker_backend,
                     backend="native") as mc:
        dn = mc.datanodes[0]
        ping = dn._worker.ping()
        note(phase="worker", backend=ping["backend"], device=ping["device"])
        with mc.client("chip-smoke") as c:
            # Warm-up, outside the asserted window: one block of its own
            # data compiles the reduce programs and (through its container
            # rollovers) the match scan.  Cold compiles run for tens of
            # seconds; the worker deadline must not read them as a hang.
            t0 = time.perf_counter()
            c.write("/smoke/warm", warm.tobytes(), scheme="dedup_lz4")
            dn.containers.drain_seals()
            t_warm = time.perf_counter() - t0
            rep0 = dn._worker.device_report()
            gw0 = give_way_counters(dn)
            idx0 = dn.index.stats()
            note(phase="warm", wall_s=round(t_warm, 2),
                 compile_s=rep0["compile_s"], cache_dir=rep0["cache_dir"],
                 give_way=gw0, lz4=rep0["lz4"])

            from hdrf_tpu.utils import profiler

            m0 = profiler.mark()
            t0 = time.perf_counter()
            c.write("/smoke/corpus", corpus.tobytes(), scheme="dedup_lz4")
            t_write = time.perf_counter() - t0
            dn.containers.drain_seals()
            t_seal = time.perf_counter() - t0
            prof = profiler.window_profile(m0, profiler.mark())
            t0 = time.perf_counter()
            back = c.read("/smoke/corpus")
            t_read = time.perf_counter() - t0
            check(len(back) == corpus.size, "read-back length differs")
            check(np.array_equal(np.frombuffer(back, np.uint8), corpus),
                  "read-back bytes differ from what was written")
            del back
            rng = np.random.default_rng(args.seed + 2)
            for _ in range(4):
                off = int(rng.integers(1, corpus.size - 3)) | 1
                ln = int(min(rng.integers(1, 3 * MIB) | 1, corpus.size - off))
                got = c.read("/smoke/corpus", offset=off, length=ln)
                check(got == corpus[off:off + ln].tobytes(),
                      f"ranged read at {off}+{ln} differs")
            check(c.read("/smoke/warm") == warm.tobytes(),
                  "warm-up file read-back differs")

        idx1 = dn.index.stats()
        got = {"unique_chunks": idx1["chunks"] - idx0["chunks"],
               "unique_bytes": (idx1["unique_chunk_bytes"]
                                - idx0["unique_chunk_bytes"]),
               "logical_bytes": idx1["logical_bytes"] - idx0["logical_bytes"]}
        from hdrf_tpu.utils import metrics
        ded = metrics.registry("dedup").snapshot()["counters"]
        note(phase="write_read", write_s=round(t_write, 2),
             write_sealed_s=round(t_seal, 2), read_s=round(t_read, 2),
             write_mb_s=round(corpus.size / MIB / t_write, 1),
             read_mb_s=round(corpus.size / MIB / t_read, 1), index=got,
             # this process's phase clock over the write (utils/profiler.py:
             # exclusive seconds; device_wait = waiting on the worker)
             write_phases_s={k: round(v, 2)
                             for k, v in prof["phases"].items()},
             write_classes_s={k: round(v, 2)
                              for k, v in prof["classes"].items()},
             dedup_counters={k: ded.get(k, 0) for k in
                             ("chunks_total", "chunks_new", "bytes_new")})
        check(got["logical_bytes"] == corpus.size, "index logical bytes")
        check(got["unique_chunks"] == o_unique,
              f"unique chunks {got['unique_chunks']} != oracle {o_unique}")
        check(got["unique_bytes"] == o_bytes,
              f"unique bytes {got['unique_bytes']} != oracle {o_bytes}")

        dn.containers.flush_open()      # the open tail seals too
        sealed, lz4n, dec = decode_sealed(dn)
        note(phase="containers", sealed=sealed, lz4_coded=lz4n,
             decoded_bytes=dec)
        check(sealed >= 2 and lz4n >= 1, "too few sealed containers")
        check(dec == idx1["unique_chunk_bytes"],
              "sealed containers do not hold the unique chunk bytes")

        wstats = dn._worker.stats()     # before the probe's device work
        rep = dn._worker.device_report(probe=True)
        gw = give_way_counters(dn)
        recv = metrics.registry("block_receiver").snapshot()["counters"]
        note(phase="device", ledger=rep["ledger"], ops=rep["ops"],
             lz4=rep["lz4"], compile_s=rep["compile_s"],
             cache_dir=rep["cache_dir"], box=rep.get("box"),
             sha_odd_rows_ok=rep.get("sha_odd_rows_ok"),
             worker_reduces=recv.get("worker_reduces", 0), give_way=gw,
             worker_stats={k: round(v, 2) if isinstance(v, float) else v
                           for k, v in wstats.items()})
        check(recv.get("worker_reduces", 0) >= args.blocks + 1,
              "not every block was reduced by the worker")
        for k in gw:
            check(gw[k] == gw0[k] == 0, f"{k}: {gw0[k]} warm, {gw[k]} after")
        # Two counters the corpus may legitimately move; say why if so.
        retries = rep["ops"].get("resident.prep_retry", {"n": 0})["n"]
        floods = rep["lz4"].get("native_fallbacks", 0)
        note(phase="legitimate", cdc_overflow_retries=retries,
             lz4_native_fallbacks=floods,
             lz4_bypassed_scans=rep["lz4"].get("bypassed_scans", 0),
             reason=("TeraGen rows carry ~9 short matches per 100-byte row: "
                     "past the match scan's record cap the container is "
                     "emitted by the native encoder (ops/lz4_tpu.py flood "
                     "fallback), and after two such the next 16 seals skip "
                     "the scan" if floods else None))
        check(retries == 0, f"{retries} CDC candidate-overflow retries")
    note(phase="parent", jax_backends=parent_backends())
    check(parent_backends() == [],
          f"the parent initialised JAX backends {parent_backends()}")
    # Last, what only a chip can satisfy: there is no CPU success mode.
    check(ping["backend"] == "tpu",
          f"worker backend is {ping['backend']!r}, not 'tpu'")
    check(rep["device"]["platform"] == "tpu",
          f"worker platform is {rep['device']['platform']!r}, not 'tpu'")
    check(rep["ledger"]["dispatch_total"] > 0, "no device dispatch")
    check(rep.get("sha_odd_rows_ok") is True,
          "Pallas SHA diverges from hashlib at odd lane-row counts")
    return rep["device"]


# --------------------------------------------------------------- four chips

def run_multichip(args) -> dict:
    import numpy as np

    from hdrf_tpu.utils import device_env

    cache = device_env.enable_compile_cache()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hdrf_tpu import native
    from hdrf_tpu.config import CdcConfig, NameNodeConfig
    from hdrf_tpu.ops.dispatch import gear_mask
    from hdrf_tpu.parallel import sharded

    dev = device_env.device_info()
    note(phase="devices", cache_dir=cache, nproc=os.cpu_count(), **dev)
    check(dev["platform"] == "tpu" and dev["count"] >= 4,
          f"--multichip needs four TPU devices, JAX reports {dev}")
    devices = jax.devices()[:4]
    mesh = sharded.make_mesh(n_data=1, n_seq=4, devices=devices)
    block = args.block_mb * MIB if args.block_mb else NameNodeConfig().block_size
    cdc = CdcConfig()
    data = teragen_rows(np.random.default_rng(args.seed), block).copy()
    data[block // 2 + 1:block // 2 + 1 + block // 8] = data[:block // 8]

    # Where the block lands: the same placement reduce_sharded makes.
    pad = (-data.size) % (512 * 4)
    img = sharded._put_global(np.concatenate([data, np.zeros(pad, np.uint8)]),
                              NamedSharding(mesh, P("seq")))
    where = sorted(str(s.device) for s in img.addressable_shards)
    sizes = {int(s.data.size) for s in img.addressable_shards}
    note(phase="placement", shard_devices=where, shard_bytes=sorted(sizes))
    check(len(set(where)) == 4, f"shards sit on {set(where)}, not 4 devices")
    check(sizes == {(data.size + pad) // 4}, f"uneven shards {sizes}")
    del img

    t0 = time.perf_counter()
    cuts, digs = sharded.reduce_sharded(data, cdc, mesh)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    cuts2, digs2 = sharded.reduce_sharded(data, cdc, mesh)
    t_warm = time.perf_counter() - t0
    want = native.cdc_chunk(data, gear_mask(cdc), cdc.min_chunk,
                            cdc.max_chunk)
    starts = np.concatenate([[0], want[:-1]]).astype(np.uint64)
    wd = native.sha256_batch(data, starts, (want - starts).astype(np.uint64))
    note(phase="reduce_sharded", block_bytes=int(data.size),
         chunks=int(len(want)), cold_s=round(t_cold, 2),
         warm_s=round(t_warm, 2), compile_s=device_env.compile_seconds())
    for c, d in ((cuts, digs), (cuts2, digs2)):
        check(len(c) == len(want) and (np.asarray(c) == want).all(),
              "sharded cuts diverge from the native oracle")
        check(np.array_equal(np.asarray(d), wd),
              "sharded digests diverge from the native oracle")
    return {"platform": dev["platform"], "kind": dev["kind"], "count": 4}


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=22)
    p.add_argument("--blocks", type=int, default=5,
                   help="blocks in the corpus (>= 4 at the default size)")
    p.add_argument("--multichip", action="store_true",
                   help="four chips: reduce_sharded vs the oracle, only")
    # Sizes for the CPU rehearsal among the tests; the defaults (0) are the
    # config defaults that define the deployment.
    p.add_argument("--block-mb", type=int, default=0)
    p.add_argument("--container-mb", type=int, default=0)
    p.add_argument("--worker-backend", default="tpu")
    args = p.parse_args(argv)
    t0 = time.perf_counter()
    try:
        sys.path.insert(0, REPO)
        # The library is -march=native and git-ignored: build it on THIS
        # machine, once, before any child could race to.
        subprocess.run(["make", "-B", "-s", "-C",
                        os.path.join(REPO, "hdrf_tpu", "native")], check=True)
        os.environ["PYTHONPATH"] = REPO + os.pathsep + os.environ.get(
            "PYTHONPATH", "")
        device = run_multichip(args) if args.multichip else run_served(args)
    except Exception as e:  # noqa: BLE001 — every failure is the verdict
        import traceback

        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    note(phase="done", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
