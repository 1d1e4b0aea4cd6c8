"""In-tree performance harnesses.

Analogs of the reference's test-tree benchmarks:

- ``nn``   — metadata-storm harness: concurrent wire clients hammer
             create/stat/getBlockLocations/listing against a started
             NameNode; ONE JSON line with rpc_p99_ms, lock_saturation
             and the per-method lock-share curve (what
             NNThroughputBenchmark.java:97 never measured — it calls
             handlers in-process, so lock contention and RPC service
             time are invisible by construction).
- ``dfs``  — DFS write/read MB/s through a MiniCluster per reduction scheme
             (BenchmarkThroughput.java).
- ``ec``   — RS encode/decode MB/s + striped write/read MB/s
             (ErasureCodeBenchmarkThroughput.java).
- ``reduction`` — the block-reduction pipeline (what bench.py at the repo
             root reports to the driver), selectable backend.
- ``churn`` — long-horizon delete/rewrite lifecycle over a MiniCluster:
             storage_ratio / garbage / cache / read-p95 curves over time
             (no reference analog; the trajectory axis ROADMAP item 1
             calls the honest production number).

Run: ``python -m hdrf_tpu.benchmarks <which> [options]``; each prints
one JSON object per metric.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def _rate(n: int, t0: float) -> float:
    return n / (time.perf_counter() - t0)


def _nn_observer_ab(args) -> None:
    """Paired observer A/B (ISSUE 20): the same metadata storm run twice
    per round — leg A against a lone active, leg B with ``--observers``
    observer NNs tailing it and the HA proxy routing reads observer-first
    (state-id protocol; one msync barrier per data op buys read-your-writes
    for the reads that follow).  Medians over ``--rounds`` rounds (the
    PERF_NOTES paired-pass discipline: the VM's write-burst throttling
    hits whichever leg draws it).  Prints ONE JSON line: per-leg read p99
    + the ACTIVE's lock share of the read methods (the PR 18 /contention
    decomposition — near-zero in leg B is the whole point), plus
    observer_reads / observer_share / msync_p99_ms / observer_lag_txids."""
    import dataclasses
    import tempfile
    import threading

    from hdrf_tpu.config import NameNodeConfig
    from hdrf_tpu.proto.rpc import HaRpcClient
    from hdrf_tpu.server.namenode import NameNode
    from hdrf_tpu.utils import metrics, retry

    read_methods = ("stat", "get_block_locations", "listing")

    def _counter(reg: str, key: str) -> int:
        return metrics.registry(reg).snapshot()["counters"].get(key, 0)

    def leg(observer: bool) -> dict:
        clients = max(1, args.clients)
        per = max(1, args.ops // clients)
        meta = max(0, args.meta_per_op)
        obs_reads0 = _counter("client.ha", "observer_reads")
        bounces0 = _counter("client.ha", "observer_bounces")
        with tempfile.TemporaryDirectory() as d:
            cfg = NameNodeConfig(
                meta_dir=d, replication=1, heartbeat_interval_s=30.0,
                dead_node_interval_s=600.0, tail_interval_s=0.02)
            nn = NameNode(cfg).start()
            obs = []
            try:
                nn.rpc_register_datanode("dn-bench", ["127.0.0.1", 1])
                if observer:
                    for _k in range(max(1, args.observers)):
                        ob = NameNode(dataclasses.replace(
                            cfg, role="observer", port=0)).start()
                        ob.rpc_register_datanode("dn-bench",
                                                 ["127.0.0.1", 1])
                        obs.append(ob)
                addrs = [nn.addr] + [o.addr for o in obs]
                read_ms = [[] for _ in range(clients)]
                msync_ms = [[] for _ in range(clients)]
                errors = [0] * clients
                calls = [0] * clients

                def storm(w: int) -> None:
                    ha = HaRpcClient(addrs, observer_reads=observer)
                    try:
                        for i in range(per):
                            p = f"/storm/c{w}/{i // args.files}/f{i}"
                            try:
                                ha.call("create", path=p, client=f"s{w}")
                                alloc = ha.call("add_block", path=p,
                                                client=f"s{w}")
                                ha.call("complete", path=p, client=f"s{w}",
                                        block_lengths={
                                            alloc["block_id"]: 1024})
                                calls[w] += 3
                                if observer:
                                    t = time.perf_counter()
                                    ha.msync(wait_s=1.0)
                                    msync_ms[w].append(
                                        (time.perf_counter() - t) * 1e3)
                                for j in range(meta):
                                    which = (i * meta + j) % 3
                                    t = time.perf_counter()
                                    if which == 0:
                                        ha.call("stat", path=p)
                                    elif which == 1:
                                        ha.call("get_block_locations",
                                                path=p)
                                    else:
                                        ha.call("listing",
                                                path=f"/storm/c{w}/"
                                                     f"{i // args.files}")
                                    read_ms[w].append(
                                        (time.perf_counter() - t) * 1e3)
                                    calls[w] += 1
                            except Exception:  # noqa: BLE001 — count on
                                errors[w] += 1
                    finally:
                        ha.close()

                t0 = time.perf_counter()
                ts = [threading.Thread(target=storm, args=(w,))
                      for w in range(clients)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                dt = time.perf_counter() - t0
                lock = nn.rpc_contention()["lock"]
                flat = [x for lat in read_ms for x in lat]
                msy = [x for lat in msync_ms for x in lat]
                lag_txids = max((nn._editlog.seq - o._editlog.seq
                                 for o in obs), default=0)
                obs_reads = _counter("client.ha",
                                     "observer_reads") - obs_reads0
                return {
                    "ops_per_s": round(sum(calls) / dt) if dt > 0 else 0,
                    "errors": sum(errors),
                    "read_p99_ms": round(float(
                        np.percentile(flat, 99)) if flat else 0.0, 3),
                    "active_read_lock_share": round(sum(
                        lock["by_method"].get(m, {}).get("hold_share", 0.0)
                        for m in read_methods), 4),
                    "observer_reads": obs_reads,
                    "observer_share": round(obs_reads / len(flat), 4)
                    if flat else 0.0,
                    "observer_bounces": _counter(
                        "client.ha", "observer_bounces") - bounces0,
                    "msync_p99_ms": round(float(
                        np.percentile(msy, 99)) if msy else 0.0, 3),
                    "observer_lag_txids": lag_txids,
                }
            finally:
                for o in obs:
                    o.stop()
                nn.stop()
                retry.reset_breakers()

    rounds = max(1, args.rounds)
    a_rounds = [leg(False) for _ in range(rounds)]
    b_rounds = [leg(True) for _ in range(rounds)]

    def med(rs: list[dict], key: str) -> float:
        return float(np.median([r[key] for r in rs]))

    a_p99, b_p99 = med(a_rounds, "read_p99_ms"), med(b_rounds, "read_p99_ms")
    print(json.dumps({
        "bench": "nn_observer_ab",
        "rounds": rounds,
        "clients": max(1, args.clients),
        "data_ops": max(1, args.ops // max(1, args.clients))
        * max(1, args.clients),
        "observers": max(1, args.observers),
        "a": {"read_p99_ms": round(a_p99, 3),
              "active_read_lock_share": round(
                  med(a_rounds, "active_read_lock_share"), 4),
              "ops_per_s": round(med(a_rounds, "ops_per_s"))},
        "b": {"read_p99_ms": round(b_p99, 3),
              "active_read_lock_share": round(
                  med(b_rounds, "active_read_lock_share"), 4),
              "ops_per_s": round(med(b_rounds, "ops_per_s"))},
        "read_p99_ratio": round(b_p99 / a_p99, 3) if a_p99 > 0 else 0.0,
        "observer_reads": round(med(b_rounds, "observer_reads")),
        "observer_share": round(med(b_rounds, "observer_share"), 4),
        "observer_bounces": round(med(b_rounds, "observer_bounces")),
        "msync_p99_ms": round(med(b_rounds, "msync_p99_ms"), 3),
        "observer_lag_txids": round(med(b_rounds, "observer_lag_txids")),
        "errors": sum(r["errors"] for r in a_rounds + b_rounds),
    }))


def _nn_kill_active(args) -> None:
    """Kill-active-mid-storm scenario (ISSUE 20): readers hammer a file
    through the HA proxy (observer-routed) while the active NN dies
    abruptly a third of the way in; a FailoverController promotes the
    standby while observers keep serving staleness-bounded reads.  Prints
    ONE JSON line: reads served, read errors, responses staler than the
    bound (must be 0 — bounced reads retry, they never lie), and the
    write-unavailability window (kill -> first post-promotion write)."""
    import threading

    from hdrf_tpu.server.failover import FailoverController
    from hdrf_tpu.testing.minicluster import MiniCluster
    from hdrf_tpu.utils import metrics

    def _counter(reg: str, key: str) -> int:
        return metrics.registry(reg).snapshot()["counters"].get(key, 0)

    payload = b"observer-kill-active" * 200
    dur = max(2.0, args.duration)
    readers = max(1, args.clients)
    obs_reads0 = _counter("client.ha", "observer_reads")
    bounces0 = _counter("client.ha", "observer_bounces")
    with MiniCluster(n_datanodes=1, replication=1, ha=True,
                     observers=max(1, args.observers)) as mc:
        with mc.client("seed") as c:
            c.write("/kill/f0", payload)
            c.msync(wait_s=2.0)
        fc = FailoverController(mc.nn_addrs(), probe_interval_s=0.2,
                                grace=2).start()
        stop = threading.Event()
        reads = [0] * readers
        read_errors = [0] * readers
        stale = [0] * readers

        def reader(w: int) -> None:
            with mc.client(f"reader-{w}") as c:
                while not stop.is_set():
                    try:
                        data = c.read("/kill/f0")
                    except Exception:  # noqa: BLE001 — the verdict counts
                        read_errors[w] += 1
                        time.sleep(0.05)
                        continue
                    reads[w] += 1
                    if data != payload:
                        stale[w] += 1

        ts = [threading.Thread(target=reader, args=(w,))
              for w in range(readers)]
        for t in ts:
            t.start()
        time.sleep(dur / 3)
        t_kill = time.perf_counter()
        mc.kill_namenode()
        # write probe: the moment a mutation lands again, promotion is done
        failover_s = None
        deadline = time.monotonic() + dur
        with mc.client("write-probe") as c:
            k = 0
            while time.monotonic() < deadline:
                try:
                    c.write(f"/kill/probe{k}", b"x")
                    failover_s = time.perf_counter() - t_kill
                    break
                except Exception:  # noqa: BLE001 — still failing over
                    k += 1
                    time.sleep(0.1)
        time.sleep(max(0.0, dur / 3))
        stop.set()
        for t in ts:
            t.join()
        fc.stop()
    print(json.dumps({
        "bench": "nn_kill_active",
        "duration_s": dur,
        "readers": readers,
        "reads": sum(reads),
        "read_errors": sum(read_errors),
        "stale_beyond_bound": sum(stale),
        "failover_s": round(failover_s, 3) if failover_s else None,
        "observer_reads": _counter("client.ha",
                                   "observer_reads") - obs_reads0,
        "observer_bounces": _counter("client.ha",
                                     "observer_bounces") - bounces0,
    }))


def bench_nn(args) -> None:
    """Metadata-storm harness (ISSUE 18; the NNThroughputBenchmark.java:97
    successor): ``--clients`` concurrent WIRE clients each run a data op
    (create + addBlock + complete — the edit-log group-commit load shape)
    followed by ``--meta-per-op`` read-plane calls (stat /
    getBlockLocations / listing, round-robin), against a started NameNode
    over real RPC connections so the per-method service-time
    decomposition, the lock books and the handler-pool gauges all
    populate.  Prints exactly ONE JSON line: throughput, rolling
    ``rpc_p99_ms``, ``lock_saturation``, the rolling lock-wait p99, the
    top lock-holding method and the per-method lock-share curve.

    ISSUE 20 modes: ``--observer-ab`` runs the paired observer A/B legs,
    ``--kill-active`` the kill-active-mid-storm failover scenario."""
    if getattr(args, "observer_ab", False):
        return _nn_observer_ab(args)
    if getattr(args, "kill_active", False):
        return _nn_kill_active(args)
    import tempfile
    import threading

    from hdrf_tpu.config import NameNodeConfig
    from hdrf_tpu.proto.rpc import RpcClient
    from hdrf_tpu.server.namenode import NameNode

    with tempfile.TemporaryDirectory() as d:
        nn = NameNode(NameNodeConfig(
            meta_dir=d, replication=1,
            heartbeat_interval_s=30.0, dead_node_interval_s=600.0)).start()
        try:
            nn.rpc_register_datanode("dn-bench", ["127.0.0.1", 1])
            clients = max(1, args.clients)
            per = max(1, args.ops // clients)
            meta = max(0, args.meta_per_op)
            errors = [0] * clients
            calls = [0] * clients

            def storm(w: int) -> None:
                with RpcClient(nn.addr) as c:
                    for i in range(per):
                        # rotate subdirs so listings stay <= --files wide
                        p = f"/storm/c{w}/{i // args.files}/f{i}"
                        try:
                            c.call("create", path=p, client=f"s{w}")
                            alloc = c.call("add_block", path=p,
                                           client=f"s{w}")
                            c.call("complete", path=p, client=f"s{w}",
                                   block_lengths={alloc["block_id"]: 1024})
                            calls[w] += 3
                            for j in range(meta):
                                which = (i * meta + j) % 3
                                if which == 0:
                                    c.call("stat", path=p)
                                elif which == 1:
                                    c.call("get_block_locations", path=p)
                                else:
                                    c.call("listing",
                                           path=f"/storm/c{w}/"
                                                f"{i // args.files}")
                                calls[w] += 1
                            if w == 0 and i % 50 == 0:
                                c.call("heartbeat", dn_id="dn-bench")
                                calls[w] += 1
                        except Exception:  # noqa: BLE001 — count, keep going
                            errors[w] += 1

            t0 = time.perf_counter()
            ts = [threading.Thread(target=storm, args=(w,))
                  for w in range(clients)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.perf_counter() - t0
            cont = nn.rpc_contention()
            lock = cont["lock"]
            shares = sorted(((m, r["hold_share"])
                             for m, r in lock["by_method"].items()),
                            key=lambda kv: kv[1], reverse=True)
            print(json.dumps({
                "bench": "nn_metadata_storm",
                "clients": clients,
                "data_ops": per * clients,
                "meta_per_op": meta,
                "rpc_calls": sum(calls),
                "errors": sum(errors),
                "ops_per_s": round(sum(calls) / dt) if dt > 0 else 0,
                "rpc_p99_ms": round(cont["rpc_p99_ms"], 3),
                "lock_saturation": round(lock["saturation"], 4),
                "lock_wait_p99_us": round(
                    lock["wait_us"].get("p99", 0.0), 1),
                "top_method": shares[0][0] if shares else None,
                "lock_share": {m: round(s, 4) for m, s in shares[:8]},
                "attributed_frac": round(cont["attributed_frac"], 4),
            }))
        finally:
            nn.stop()


def bench_dfs(args) -> None:
    from hdrf_tpu.testing.minicluster import MiniCluster

    rng = np.random.default_rng(42)
    n = args.mb << 20
    payload = rng.integers(0, 256, size=n, dtype=np.uint8)
    payload[: n // 2] = rng.integers(97, 123, size=n // 2, dtype=np.uint8)
    payload = payload.tobytes()
    with MiniCluster(n_datanodes=args.datanodes, replication=args.replication,
                     block_size=8 << 20) as mc:
        from hdrf_tpu.utils import device_ledger

        with mc.client("bench") as c:
            for scheme in args.schemes.split(","):
                led0 = device_ledger.stamp()
                t0 = time.perf_counter()
                c.write(f"/bench/{scheme}", payload, scheme=scheme)
                w = n / (time.perf_counter() - t0) / 2**20
                t0 = time.perf_counter()
                got = c.read(f"/bench/{scheme}")
                r = n / (time.perf_counter() - t0) / 2**20
                assert got == payload
                led = device_ledger.delta(led0)
                print(json.dumps({"scheme": scheme,
                                  "write_MBps": round(w, 1),
                                  "read_MBps": round(r, 1),
                                  "ledger": led,
                                  "stalls": led.get("stall_total", 0)}))


def bench_ec_repair_ab(args) -> None:
    """Paired repair A/B (ISSUE 16): classic full-gather decode vs the
    coded partial-sum exchange, over the same container, erasure pattern,
    and holder layout.  BEFORE timing, the coded fold is pinned
    bit-identical to the full-gather oracle
    (storage/stripe_store.py ``reconstruct_container``) on EVERY erasure
    pattern up to ``m`` losses — the acceptance bar is correctness first,
    wire ratio second.  The wire ledger mirrors the live path's
    accounting (server/coded_exchange.py ``book_repair_wire``): full
    gather ships k whole stripes to the repairing owner, the coded chain
    ships one (|missing|, stripe_len) fold, holder-local contributions
    are free, and the contributions additionally ride the smaller-of LZ4
    negotiation.  Slope method for the timings; prints exactly ONE JSON
    line."""
    import itertools

    import jax

    from hdrf_tpu.ops import rs
    from hdrf_tpu.server import coded_exchange
    from hdrf_tpu.storage import stripe_store

    k, m, _cell = rs.parse_policy(args.policy)
    rng = np.random.default_rng(7)
    n = args.mb << 20
    # half-compressible corpus: random tiles interleaved with repeated
    # text, the shape raw-codec container stripes actually have (sealed
    # lz4 containers stripe to incompressible bytes and ship raw — the
    # negotiation's enc flags report which regime this run measured)
    tile = rng.integers(0, 256, size=max(n // 2, 1), dtype=np.uint8)
    text = np.frombuffer(
        (b"the quick brown fox jumps over the lazy dog. " * 8192)
        [: max(n - tile.size, 1)], dtype=np.uint8)
    payload = np.concatenate([tile, text])[:n].tobytes()
    stripes, manifest = stripe_store.encode_container(payload, k, m)
    stripe_len = int(manifest["stripe_len"])
    arrs = {i: np.frombuffer(s, dtype=np.uint8)
            for i, s in enumerate(stripes)}
    dns = max(int(args.dns), 2)
    holder_of = {i: i % dns for i in range(k + m)}  # round-robin layout

    def coded_fold(missing: list[int], shards: dict[int, np.ndarray]):
        """The owner's view of one coded repair: per-holder partial sums
        (one bit-matmul each), XOR fold, plus the remote wire bytes."""
        have = sorted(shards)[:k]
        rows = rs.repair_rows(k, m, tuple(have), tuple(missing))
        col = {s: j for j, s in enumerate(have)}
        parts, remote = [], 0
        for h in range(dns):
            mine = [s for s in have if holder_of[s] == h]
            if not mine:
                continue
            st = np.stack([shards[s] for s in mine])
            parts.append(rs.partial_sums(
                st, rows[:, [col[s] for s in mine]]))
            if h != 0:  # holder 0 is the repairing owner: local = free
                remote = len(missing) * stripe_len  # ONE chained fold
        return rs.xor_fold(parts), remote

    # ---- oracle pin: every erasure pattern up to m losses, small corpus
    small, sman = stripe_store.encode_container(payload[: k * 256], k, m)
    sarrs = {i: np.frombuffer(s, dtype=np.uint8)
             for i, s in enumerate(small)}
    patterns = [list(c) for e in range(1, m + 1)
                for c in itertools.combinations(range(k + m), e)]
    oracle_ok = True
    for missing in patterns:
        shards = {i: a for i, a in sarrs.items() if i not in missing}
        want = stripe_store.reconstruct_container(
            dict(shards), sman, want=missing)
        fold, _ = coded_fold(missing, shards)
        for i, w in enumerate(missing):
            if fold[i].tobytes() != want[w]:
                oracle_ok = False

    # ---- paired timing on the full corpus; default is the common
    # single-loss repair (full gather pays k stripes of wire per ONE
    # rebuilt — the ratio the coded path collapses to ~1)
    e = max(1, min(int(args.erasures), m))
    missing = list(range(e))  # data stripes lost: decode-heavy for A
    survivors = {i: arrs[i] for i in range(k + m) if i not in missing}
    rebuilt = len(missing) * stripe_len

    def run_full():
        return stripe_store.reconstruct_container(
            dict(survivors), manifest, want=missing)

    def run_coded():
        return coded_fold(missing, survivors)

    def slope_mbps(fn) -> float:
        fn()  # warm: jit compile + page in
        t0 = time.perf_counter()
        fn()
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.inner):
            fn()
        tk = time.perf_counter() - t0
        per = ((tk - t1) / (args.inner - 1)) if args.inner > 1 else t1
        return rebuilt / max(per, 1e-9) / 2**20

    full_mbps = slope_mbps(run_full)
    coded_mbps = slope_mbps(run_coded)

    # ---- wire ledger (the live path's accounting, stamped in-registry)
    fold, remote_wire = run_coded()
    packed = coded_exchange.pack_many(
        [fold[i].tobytes() for i in range(len(missing))])
    coded_wire_packed = sum(len(p) for p, _ in packed)
    full_wire = sum(len(survivors[i]) for i in sorted(survivors)[:k])
    coded_exchange.book_repair_wire(remote_wire, rebuilt)
    print(json.dumps({
        "op": f"ec repair A/B [{args.policy}, slope]",
        "mb": args.mb, "backend": jax.default_backend(),
        "k": k, "m": m, "dns": dns, "inner": args.inner,
        "erasures": len(missing),
        "patterns_pinned": len(patterns),
        "parity_oracle_ok": bool(oracle_ok),
        "full_gather_MBps": round(full_mbps, 1),
        "coded_repair_MBps": round(coded_mbps, 1),
        "speedup": (round(coded_mbps / full_mbps, 3)
                    if full_mbps > 0 else None),
        "repair_wire_ratio_full": round(full_wire / rebuilt, 3),
        "repair_wire_ratio_coded": round(remote_wire / rebuilt, 3),
        "repair_wire_ratio_coded_lz4": round(
            coded_wire_packed / rebuilt, 3),
        "wire_saved_frac": round(1 - remote_wire / full_wire, 4),
    }))


def bench_ec(args) -> None:
    """EC cold-tier harness: paired encode / intact-reassembly /
    degraded-decode slopes over the container striping path
    (storage/stripe_store.py on top of ops/rs.py), slope method — one
    timed call vs ``--inner`` back-to-back calls, (t_k - t_1)/(k-1)
    dividing out the fixed dispatch constant (PERF_NOTES.md round 4's
    discipline).  The pair that matters is intact vs degraded: intact
    reassembly is pure CRC+concat (all k data stripes present), degraded
    drops the first m stripes (all-data erasures, the worst case) and
    decodes through parity on the device — their ratio is the cold
    tier's read penalty.  Parity is pinned against the GF log/antilog
    oracle (rs.encode_ref) before timing.  Prints exactly ONE JSON
    line."""
    if getattr(args, "repair_ab", False):
        return bench_ec_repair_ab(args)
    import jax

    from hdrf_tpu.ops import rs
    from hdrf_tpu.storage import stripe_store

    k, m, _cell = rs.parse_policy(args.policy)
    rng = np.random.default_rng(7)
    n = args.mb << 20
    payload = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
    stripes, manifest = stripe_store.encode_container(payload, k, m)

    # pin vs the numpy GF oracle before trusting any timing
    padded = np.zeros(k * manifest["stripe_len"], dtype=np.uint8)
    padded[:n] = np.frombuffer(payload, dtype=np.uint8)
    ref = rs.encode_ref(padded.reshape(k, -1), m)
    oracle_ok = all(bytes(ref[i]) == stripes[k + i] for i in range(m))

    intact = {i: stripes[i] for i in range(k)}
    degraded = {i: stripes[i] for i in range(m, k + m)}

    def slope_mbps(fn) -> float:
        fn()  # warm: jit compile + page in
        t0 = time.perf_counter()
        fn()
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.inner):
            fn()
        tk = time.perf_counter() - t0
        per = ((tk - t1) / (args.inner - 1)) if args.inner > 1 else t1
        return n / max(per, 1e-9) / 2**20

    enc = slope_mbps(lambda: stripe_store.encode_container(payload, k, m))
    rd_ok = slope_mbps(
        lambda: stripe_store.reconstruct_container(intact, manifest))
    rd_deg = slope_mbps(
        lambda: stripe_store.reconstruct_container(degraded, manifest))
    print(json.dumps({
        "op": f"ec cold tier [{args.policy}, slope]",
        "mb": args.mb, "backend": jax.default_backend(),
        "k": k, "m": m, "inner": args.inner,
        "parity_oracle_ok": bool(oracle_ok),
        "encode_MBps": round(enc, 1),
        "intact_read_MBps": round(rd_ok, 1),
        "degraded_read_MBps": round(rd_deg, 1),
        "degraded_penalty": (round(rd_ok / rd_deg, 3)
                             if rd_deg > 0 else None),
        # the tier's expansion: (k+m)*stripe_len over true length
        "storage_ratio": round(
            (k + m) * manifest["stripe_len"] / manifest["length"], 4),
    }))


def bench_reduction(args) -> None:
    from hdrf_tpu.config import CdcConfig
    from hdrf_tpu.ops import dispatch

    rng = np.random.default_rng(3)
    n = args.mb << 20
    data = rng.integers(0, 256, size=n, dtype=np.uint8)
    cdc = CdcConfig()
    backend = dispatch.resolve_backend(args.backend)
    dispatch.chunk_and_fingerprint(data[: 1 << 20], cdc, backend)  # warm
    from hdrf_tpu.utils import device_ledger

    led0 = device_ledger.stamp()
    t0 = time.perf_counter()
    cuts, digs = dispatch.chunk_and_fingerprint(data, cdc, backend)
    mbps = n / (time.perf_counter() - t0) / 2**20
    led = device_ledger.delta(led0)
    print(json.dumps({"op": f"reduction pipeline [{backend}]",
                      "MBps": round(mbps, 1), "chunks": int(cuts.size),
                      "ledger": led,
                      "stalls": led.get("stall_total", 0)}))


def bench_recon(args) -> None:
    """Read-side reconstruction MB/s: host path vs device gather path
    (DataConstructor.java:360-567 vs ops/reconstruct.py).  Builds a dedup
    store once, then reconstructs blocks repeatedly — the device path's
    HBM-resident container images make repeat reads gather-only."""
    import dataclasses
    import tempfile

    from hdrf_tpu.config import ReductionConfig
    from hdrf_tpu.index.chunk_index import ChunkIndex
    from hdrf_tpu.ops.reconstruct import DeviceReconstructor
    from hdrf_tpu.reduction import scheme as schemes
    from hdrf_tpu.reduction.scheme import ReductionContext
    from hdrf_tpu.storage.container_store import ContainerStore

    rng = np.random.default_rng(5)
    n = args.mb << 20
    blocks = {}
    with tempfile.TemporaryDirectory() as d:
        cfg = ReductionConfig()
        if args.chunk_kb:
            # bigger lanes (the verdict's 64 KiB-lane case): per-lane
            # dispatch overhead amortizes with lane size on both gather
            # formulations
            import math

            from hdrf_tpu.config import CdcConfig

            kb = args.chunk_kb
            cfg = dataclasses.replace(cfg, cdc=CdcConfig(
                mask_bits=int(math.log2(kb)) + 10,
                min_chunk=(kb << 10) // 4, max_chunk=(kb << 10) * 4))
        ctx = ReductionContext(
            config=cfg,
            containers=ContainerStore(d + "/containers", codec="lz4"),
            index=ChunkIndex(d + "/index"), backend="native")
        s = schemes.get("dedup_lz4")
        per = 8 << 20
        for bid in range(n // per):
            data = rng.integers(0, 256, size=per, dtype=np.uint8)
            data[: per // 3] = rng.integers(97, 123, size=per // 3,
                                            dtype=np.uint8)
            blocks[bid] = data.tobytes()
            s.reduce(bid, blocks[bid], ctx)
        for label, rctx in (
                ("host", ctx),
                ("device", dataclasses.replace(
                    ctx, recon=DeviceReconstructor()))):
            for bid, data in blocks.items():  # warm (stage images/compile)
                assert s.reconstruct(bid, b"", len(data), rctx) == data
            t0 = time.perf_counter()
            total = 0
            for _ in range(args.repeats):
                for bid, data in blocks.items():
                    out = s.reconstruct(bid, b"", len(data), rctx)
                    total += len(out)
            mbps = total / (time.perf_counter() - t0) / 2**20
            print(json.dumps({"op": f"reconstruction [{label}]",
                              "MBps": round(mbps, 1)}))

        # Device GATHER service rate: the kernel's own throughput once
        # images are HBM-resident, with a tiny dependent readback (the
        # same framing bench.py uses — on the earlier shared dev box every
        # reconstructed byte pays the ~25 MB/s D2H link, which measures
        # the WAN, not the gather; on PCIe-attached chips the D2H is
        # noise and THIS rate bounds the read path).
        import jax
        import jax.numpy as jnp

        if jax.default_backend() != "cpu":
            from hdrf_tpu.ops.reconstruct import _bucket_of

            recon = DeviceReconstructor()
            s2 = dataclasses.replace(ctx, recon=recon)
            for bid, data in blocks.items():   # stage images
                assert s.reconstruct(bid, b"", len(data), s2) == data
            # group every block's chunks like DeviceReconstructor.gather
            jobs = []
            for bid in blocks:
                entry = ctx.index.get_block(bid)
                locmap = ctx.index.lookup_chunks(list(set(entry.hashes)))
                groups: dict = {}
                for h in entry.hashes:
                    loc = locmap[h]
                    b = _bucket_of(-(-loc.length // 64) + 1)
                    groups.setdefault((loc.container_id, b),
                                      []).append(loc)
                for (cid, b), locs in groups.items():
                    L = -(-len(locs) // 128) * 128
                    ol = np.zeros((2, L), np.int32)
                    for j, loc in enumerate(locs):
                        ol[0, j], ol[1, j] = loc.offset, loc.length
                    img = recon._image(
                        cid, lambda c=cid: ctx.containers.read_container(c))
                    jobs.append((img, jax.device_put(ol), b,
                                 sum(loc.length for loc in locs)))
            from hdrf_tpu.ops.gather_pallas import gather_pad_messages

            buckets = tuple(b for _, _, b, _ in jobs)
            imgs = [j[0] for j in jobs]
            ols = [j[1] for j in jobs]

            INNER = 8

            @jax.jit
            def fused(imgs, ols):
                # ONE device program per pass (per-group dispatches would
                # measure the transport's per-dispatch cost, not the
                # gather), with INNER salted iterations inside so the
                # ~100 ms awaited-readback RTT amortizes (the slope
                # method, PERF_NOTES.md; the +i byte offset defeats CSE
                # while staying inside the images' zero headroom)
                tot = jnp.uint64(0)
                for i in range(INNER):
                    for img, ol, b in zip(imgs, ols, buckets):
                        o = gather_pad_messages(img, ol.at[0].add(i), b)
                        tot += jnp.sum(o[:, :1].astype(jnp.uint64))
                return tot

            def one_pass():
                return float(fused(imgs, ols))  # dependent readback

            one_pass()  # compile
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                one_pass()
            dt = time.perf_counter() - t0
            gathered = args.repeats * INNER * sum(j[3] for j in jobs)
            print(json.dumps({"op": "reconstruction [device gather kernel]",
                              "MBps": round(gathered / dt / 2**20, 1)}))
        ctx.index.close()


def bench_sort(args) -> None:
    """Match-scan sort engine A/B: the Pallas fused bitonic network
    (ops/sort_pallas.py) vs the ``jax.lax.sort`` reference, slope method —
    k salted iterations inside ONE dispatch with a dependent readback, so
    (T(k) - T(1)) / (k - 1) divides out the ~100 ms per-dispatch transport
    constant (PERF_NOTES.md round 4).  On the CPU mesh only the XLA path
    runs (Mosaic needs a real chip); ``--interpret`` forces the kernel
    through the Pallas interpreter for correctness spot-checks, not
    timing."""
    import jax
    import jax.numpy as jnp

    from hdrf_tpu.ops import sort_pallas

    rng = np.random.default_rng(13)
    t, e = args.tiles, args.entries
    stride, pos_bits = 2, int(e - 1).bit_length()
    vals = jnp.asarray(rng.integers(0, 2**32, size=(t, e), dtype=np.uint32))
    half = e // 2
    idx = np.arange(e)
    posn = jnp.asarray(np.where(idx < half, 2 * idx,
                                2 * (idx - half) + 1)
                       .astype(np.uint32))[None].repeat(t, axis=0)

    impls = ["xla"]
    if sort_pallas.use_pallas() or args.interpret:
        impls.append("pallas")

    def measure(build):
        def timed(k):
            f = jax.jit(build(k))
            float(f(vals))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                float(f(vals))  # dependent readback acks real completion
            return (time.perf_counter() - t0) / args.repeats
        t1, tk = timed(1), timed(args.inner)
        return (tk - t1) / (args.inner - 1)

    for impl in impls:
        interp = args.interpret and impl == "pallas"

        def build(k, impl=impl, interp=interp):
            def f(v):
                acc = jnp.uint32(0)
                for i in range(k):
                    # the salt defeats CSE between iterations
                    d = sort_pallas.match_deltas(v ^ jnp.uint32(i), posn,
                                                 stride, pos_bits,
                                                 impl=impl,
                                                 interpret=interp)
                    acc += d[0, 0] + jnp.sum(d[:, -1])
                return acc
            return f

        per = measure(build)
        print(json.dumps({
            "op": f"match_deltas [{impl}{'/interp' if interp else ''}]",
            "entries": t * e, "ms_per_scan": round(per * 1e3, 3),
            "MBps": round(t * e * stride / per / 2**20, 1)}))

    for impl in impls:
        interp = args.interpret and impl == "pallas"

        def build(k, impl=impl, interp=interp):
            def f(v):
                acc = jnp.uint32(0)
                for i in range(k):
                    _, sv = sort_pallas.sort_rows(v ^ jnp.uint32(i), v,
                                                  impl=impl,
                                                  interpret=interp)
                    acc += sv[0, 0] + jnp.sum(sv[:, -1])
                return acc
            return f

        per = measure(build)
        print(json.dumps({
            "op": f"sort_rows [{impl}{'/interp' if interp else ''}]",
            "entries": t * e, "ms_per_sort": round(per * 1e3, 3),
            "Mkeys_per_s": round(t * e / per / 1e6, 1)}))

    # Readback-size ledger: the packed record layout vs the full one at the
    # production L3 width (deterministic; no device needed).
    from hdrf_tpu.ops.lz4_tpu import _packed_len

    p3 = 1 << 17
    full, packed = 1 + 2 * p3, _packed_len(p3)
    print(json.dumps({"op": "record readback", "p3": p3,
                      "full_words": full, "packed_words": packed,
                      "reduction_pct": round(100 * (1 - packed / full), 1)}))


def bench_cdc(args) -> None:
    """Fused Pallas CDC front end, geometry-sweepable A/B (ISSUE 15): the
    skip-ahead + sequence-select kernel vs the PR 4 fused scan vs the XLA
    ``_prep`` pipeline stage (ops/resident.py), slope method — k salted
    iterations in ONE dispatch with a dependent readback divides out the
    ~100 ms transport constant (PERF_NOTES.md round 4).  ``--mask-bits`` /
    ``--min-size`` sweep the geometry; ``--no-skip-ahead`` pins the PR 4
    scan alone.  Prints exactly ONE JSON line carrying the paired A/B, the
    per-leg micro-profile (gear = scan-only kernel slope, scan = fused
    minus gear, image = be_word_image slope, pad = sha_pad_messages
    slope — the round-17 PERF_NOTES table from one command), the kernel's
    H_SURV/H_CANDS telemetry, and the per-block readback byte ledger.
    Cuts are pinned bit-identical to native.cdc_chunk for every variant
    BEFORE any timing.  Without a chip the kernels run in the Pallas
    interpreter — a correctness-grade timing, flagged in the line (the
    round-6 precedent)."""
    import jax
    import jax.numpy as jnp

    from hdrf_tpu import native
    from hdrf_tpu.config import CdcConfig
    from hdrf_tpu.ops import cdc_pallas, resident

    cdc = CdcConfig(mask_bits=args.mask_bits, min_chunk=args.min_size)
    r = resident.ResidentReducer(cdc, fused_mode="off")
    n = args.mb << 20
    rng = np.random.default_rng(17)
    a = rng.integers(0, 256, n, dtype=np.uint8)
    a[: n // 4] = rng.integers(97, 123, size=n // 4, dtype=np.uint8)

    # The kernel under A/B runs through Mosaic on a chip (where it does not
    # lower yet, cdc_pallas_mode) and through the interpreter elsewhere —
    # never interpreted on a chip.
    import jax

    interpret = jax.default_backend() != "tpu"
    plans = {}
    if args.skip_ahead:
        plans["skip"] = cdc_pallas.plan_for(
            n, r.mask, cdc.mask_bits, cdc.min_chunk, cdc.max_chunk,
            r._b_small, r._b_big, skip_ahead=True)
    plans["walk"] = cdc_pallas.plan_for(
        n, r.mask, cdc.mask_bits, cdc.min_chunk, cdc.max_chunk,
        r._b_small, r._b_big, skip_ahead=False)
    n_pad = max(p.n_pad for p in plans.values())
    buf = np.zeros(n_pad, dtype=np.uint8)
    buf[:n] = a
    w2d = jax.device_put(buf.view(np.uint32).reshape(-1, 128))
    pad512 = n + (-n) % 512
    blk = jax.device_put(np.concatenate([a, np.zeros(pad512 - n,
                                                     np.uint8)]))
    cap_x = max(1, min(pad512 // 32,
                       max(1024, (n >> max(cdc.mask_bits - 1, 0)) + 1024)))

    # -- correctness pin BEFORE timing: every variant's cuts must equal
    # the native oracle (overflow => the variant reports it and equality
    # is vacuous: callers take the oracle path).
    want = native.cdc_chunk(a.tobytes(), r.mask, cdc.min_chunk,
                            cdc.max_chunk)
    surv = cands = 0
    overflowed = False
    for name, p in plans.items():
        _, table, _, _ = jax.jit(
            lambda w, p=p: cdc_pallas.fused_block(w, p, interpret))(w2d)
        tb = np.asarray(table)[0]
        if int(tb[cdc_pallas.H_OVERFLOW]):
            overflowed = True
            continue
        nc = int(tb[cdc_pallas.H_COUNT])
        got = tb[cdc_pallas.TABLE_HDR:cdc_pallas.TABLE_HDR + nc].astype(
            np.uint64)
        assert np.array_equal(got, np.asarray(want, np.uint64)), \
            f"{name} kernel cuts diverge from native.cdc_chunk"
        if name == "skip":
            surv = int(tb[cdc_pallas.H_SURV])
            cands = int(tb[cdc_pallas.H_CANDS])

    def measure(build, inp):
        def timed(k):
            f = jax.jit(build(k))
            int(f(inp))                        # compile + warm
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                int(f(inp))
            return (time.perf_counter() - t0) / args.repeats
        t1, tk = timed(1), timed(args.inner)
        return (tk - t1) / (args.inner - 1)

    def build_fused(p):
        def build(k):
            def f(w):
                acc = jnp.int32(0)
                for i in range(k):
                    _, table, _, _ = cdc_pallas.fused_block(
                        w ^ jnp.uint32(i), p, interpret)  # salt kills CSE
                    acc += table[0, cdc_pallas.H_COUNT]
                return acc
            return f
        return build

    def build_scan_only(k):
        # gear leg: the scan-only kernel shares the gear-map + window-hash
        # core but does NO cut selection — fused minus this is the select
        # leg the sequence-based scan targets.
        R_s = plans["walk"].R
        T = w2d.shape[0] // R_s
        pos0 = jnp.zeros((1, 1), jnp.int32)
        m32 = jnp.full((1, 1), r.mask, jnp.uint32)

        def f(w):
            acc = jnp.int32(0)
            for i in range(k):
                nib = cdc_pallas._scan_call(T, R_s, n, interpret)(
                    pos0, m32, w ^ jnp.uint32(i))
                acc += jnp.sum(nib)
            return acc
        return f

    def build_image(k):
        def f(b):
            acc = jnp.uint32(0)
            for i in range(k):
                acc += jnp.max(resident.be_word_image(b ^ jnp.uint8(i)))
            return acc
        return f

    L_pad = 1024
    ol_np = np.zeros((2, L_pad), dtype=np.int32)
    ol_np[0] = (np.arange(L_pad) * cdc.min_chunk) % max(n // 2, 1)
    ol_np[1] = min(cdc.min_chunk, r._b_small * 64 - 9)
    ol_dev = jax.device_put(ol_np)

    def build_pad(k):
        def f(w):
            acc = jnp.uint32(0)
            for i in range(k):
                out, _ = resident.sha_pad_messages(
                    w.reshape(-1) ^ jnp.uint32(i), ol_dev, r._b_small)
                acc += jnp.max(out)
            return acc
        return f

    def build_xla(k):
        def f(b):
            acc = jnp.uint32(0)
            for i in range(k):
                words, cand = resident._prep_impl(b ^ jnp.uint8(i & 0xFF),
                                                  jnp.uint32(b.shape[0]),
                                                  r.mask, cap_x,
                                                  r.pad_words)
                acc += jnp.max(words) + cand[0].astype(jnp.uint32)
            return acc
        return f

    fused_ms = {name: measure(build_fused(p), w2d) * 1e3
                for name, p in plans.items()}
    gear_ms = measure(build_scan_only, w2d) * 1e3
    image_ms = measure(build_image, blk) * 1e3
    pad_ms = measure(build_pad, w2d) * 1e3
    xla_ms = measure(build_xla, blk) * 1e3
    best = fused_ms.get("skip", fused_ms["walk"])
    plan = plans.get("skip", plans["walk"])
    print(json.dumps({
        "op": "cdc_prep [skip-ahead vs pr4 fused vs xla prep, slope A/B]",
        "mb": args.mb, "backend": jax.default_backend(),
        "interpret": interpret,
        "mask_bits": cdc.mask_bits, "min_size": cdc.min_chunk,
        "skip_ahead": bool(args.skip_ahead),
        "cuts_verified": not overflowed, "overflowed": overflowed,
        "fused_ms_per_block": round(best, 3),
        "fused_noskip_ms_per_block": round(fused_ms["walk"], 3),
        "skip_ahead_speedup": (round(fused_ms["walk"] / best, 3)
                               if "skip" in fused_ms and best > 0 else None),
        "xla_ms_per_block": round(xla_ms, 3),
        "speedup": round(xla_ms / best, 3) if best > 0 else None,
        # Per-leg micro-profile (the PERF_NOTES round-17 table): scan =
        # what cut selection costs on top of the shared gear/hash core.
        "micro_profile_ms": {"gear": round(max(gear_ms, 0.0), 3),
                             "scan": round(max(best - gear_ms, 0.0), 3),
                             "image": round(max(image_ms, 0.0), 3),
                             "pad": round(max(pad_ms, 0.0), 3)},
        "scan_slab_survivors": surv, "scan_candidates": cands,
        # Per-block readback ledger: what each shape must await before SHA
        # can be PLACED (XLA: packed candidates -> host select -> offsets
        # re-upload; fused: nothing — the cut table D2H overlaps SHA).
        "cand_d2h_bytes_per_block_xla": (1 + 2 * cap_x) * 4,
        "cut_table_d2h_bytes_per_block_fused":
            (cdc_pallas.TABLE_HDR + plan.cap) * 4,
        "serial_awaited_boundaries": {"xla": 2, "fused": 1},
    }))


def bench_multichip(args) -> None:
    """Mesh-plane service-rate curve (ISSUE 9 acceptance): the same
    small-block corpus through parallel/sharded.MeshReducer on sub-meshes
    of 1/2/4/8 devices.  Each coalesced group runs CDC cut selection,
    SHA-256 fingerprinting, and the sharded dedup-bucket probe as ONE
    ledger-visible dispatch ("sharded.step"), so widening the mesh
    multiplies blocks-per-dispatch while the per-step fixed cost (python
    dispatch, transfer setup, readback sync) stays put — per-dispatch
    overhead amortization, the same constant every prior PERF_NOTES round
    measured, and the lever that holds on the emulated CPU mesh too
    (1 vCPU: shard COMPUTE serializes, fixed costs do not — so the
    emulated ratio is capped at d*(F+c)/(F+d*c) for the published
    step_fixed_ms F and step_per_device_ms c; PERF_NOTES round 13 carries
    the decomposition and the real-mesh projection).  Cuts+digests
    are pinned against the native oracle before any timing, and the timed
    full-width pass carries device-ledger evidence that one mesh step ==
    one dispatch.  Prints exactly ONE JSON line."""
    import jax

    from hdrf_tpu import native
    from hdrf_tpu.config import CdcConfig
    from hdrf_tpu.ops.dispatch import gear_mask
    from hdrf_tpu.parallel.sharded import MeshReducer, make_mesh
    from hdrf_tpu.utils import device_ledger

    cdc = CdcConfig(mask_bits=args.mask_bits, min_chunk=args.min_chunk,
                    max_chunk=args.max_chunk)
    mask = gear_mask(cdc)
    devs = jax.devices()
    widths = [d for d in (1, 2, 4, 8) if d <= len(devs)]
    bs = args.block_kb << 10
    rng = np.random.default_rng(23)
    blocks = []
    for _ in range(args.blocks):
        a = rng.integers(0, 256, size=bs, dtype=np.uint8)
        a[: bs // 2] = rng.integers(97, 123, size=bs // 2, dtype=np.uint8)
        blocks.append(a)

    def reducer(d: int) -> MeshReducer:
        mesh = make_mesh(n_data=d, n_seq=1, devices=devs[:d])
        return MeshReducer(cdc, mesh=mesh, lanes_per_device=args.lanes)

    # pin vs the native oracle on the full-width mesh before any timing
    r_full = reducer(widths[-1])
    got = r_full.reduce_many(blocks[: r_full.max_group()])
    oracle_ok = True
    for a, (cuts, digs, _probe) in zip(blocks, got):
        ref_cuts = native.cdc_chunk(a, mask, cdc.min_chunk, cdc.max_chunk)
        starts = np.concatenate([[0], ref_cuts[:-1]]).astype(np.uint64)
        ref_digs = native.sha256_batch(
            a, starts, (ref_cuts - starts).astype(np.uint64))
        oracle_ok &= bool(np.array_equal(cuts, ref_cuts)
                          and np.array_equal(digs, ref_digs))

    def timed(r: MeshReducer):
        g = r.max_group()
        groups = [blocks[at:at + g] for at in range(0, len(blocks), g)]
        for grp in groups:        # warm: jit compile + page in
            r.finish_many(r.submit_many(grp))
        evs = device_ledger.events_snapshot()
        id0 = evs[-1]["id"] if evs else 0
        steps = 0
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            inflight = None
            for grp in groups:    # depth-2 pipelining, write-path style
                nxt = r.submit_many(grp)
                steps += 1
                if inflight is not None:
                    r.finish_many(inflight)
                inflight = nxt
            r.finish_many(inflight)
        dt = time.perf_counter() - t0
        enq = [e for e in device_ledger.events_snapshot()
               if e["id"] > id0 and e["kind"] == "enqueue"]
        disp = sum(1 for e in enq if e["op"] == "sharded.step")
        foreign = sum(1 for e in enq
                      if e["op"] not in ("sharded.step",
                                         "sharded.bucket_refresh"))
        rate = args.repeats * len(blocks) * bs / dt / 2**20
        return rate, dt / steps * 1e3, steps, disp, foreign

    rates: dict[int, float] = {}
    step_ms: dict[int, float] = {}
    steps_full = disp_full = foreign_full = 0
    for d in widths:
        r = r_full if d == widths[-1] else reducer(d)
        rate, per_step, steps, disp, foreign = timed(r)
        rates[d] = rate
        step_ms[d] = per_step
        if d == widths[-1]:
            steps_full, disp_full, foreign_full = steps, disp, foreign
    # Two-point fit of step_time(d) = fixed + d * per_device: on the
    # emulated mesh shard compute serializes onto the one vCPU, so the
    # curve's ceiling is d*(F+c)/(F+d*c) — publishing F and c makes the
    # ratio reproducible and shows what a real mesh (per-device compute
    # parallel, F ~ the awaited-dispatch cost) unlocks.
    dmax = widths[-1]
    c_fit = ((step_ms[dmax] - step_ms[1]) / (dmax - 1)
             if dmax > 1 else 0.0)
    print(json.dumps({
        "op": "multichip mesh reduction plane [service-rate curve]",
        "backend": jax.default_backend(),
        "devices": dmax, "blocks": args.blocks,
        "block_kb": args.block_kb, "lanes_per_device": args.lanes,
        "oracle_ok": oracle_ok,
        "MBps": {str(d): round(v, 2) for d, v in rates.items()},
        "ratio_8v1": round(rates[dmax] / rates[1], 2),
        "step_ms": {str(d): round(v, 3) for d, v in step_ms.items()},
        "step_fixed_ms": round(step_ms[1] - c_fit, 3),
        "step_per_device_ms": round(c_fit, 3),
        "steps": steps_full, "step_dispatches": disp_full,
        "one_dispatch_per_step": bool(steps_full == disp_full
                                      and foreign_full == 0),
    }))


def bench_churn(args) -> None:
    """Long-horizon churn scenario (ISSUE 17 tentpole d; ROADMAP item 1's
    "storage_ratio and read p95 over time is the honest production
    number").  Drives a delete-heavy / rewrite lifecycle over a 1-DN
    MiniCluster: every round writes a generation of dedup-friendly files
    (a shared tile plus a unique tail), deletes a fraction of the oldest
    generation, rewrites a fraction of the survivors, reads everything
    still live, runs one scrub census cycle, and takes one deterministic
    flight-recorder sample (utils/flight_recorder.py sample_once — the
    thread is cadence, never semantics).

    Deletes shrink the DN's LOGICAL footprint (replica block report)
    while the already-sealed containers keep their PHYSICAL bytes, so the
    storage_ratio curve (physical/logical, server/datanode.py
    _flight_sample) degrades UPWARD round over round and the scrub census
    counts the dead chunks as garbage_bytes — the trend report
    (tools/slo_report.py trend) must flag it REGRESS_UP.  Prints exactly
    ONE JSON line: the per-metric first/last/slope curve summary plus the
    trend verdict."""
    import random

    from hdrf_tpu.testing.minicluster import MiniCluster
    from hdrf_tpu.tools import slo_report

    rng = random.Random(0x17)
    kb = args.kb
    shared = bytes(rng.getrandbits(8) for _ in range(kb << 10))

    def payload() -> bytes:
        return shared + bytes(rng.getrandbits(8) for _ in range(kb << 10))

    samples: list[dict] = []
    live: list[str] = []
    gen = 0
    with MiniCluster(n_datanodes=1, replication=1) as mc:
        dn = mc.datanodes[0]
        with mc.client("churn") as c:
            for _ in range(args.rounds):
                for i in range(args.files):
                    path = f"/churn/g{gen}/f{i}"
                    c.write(path, payload(), scheme="dedup_lz4")
                    live.append(path)
                gen += 1
                ndel = int(len(live) * args.delete_frac)
                for path in live[:ndel]:
                    c.delete(path)
                live = live[ndel:]
                nrw = int(len(live) * args.rewrite_frac)
                for path in live[:nrw]:
                    c.delete(path)
                    c.write(path, payload(), scheme="dedup_lz4")
                # deletes reach the DN as invalidate commands riding
                # heartbeats (~0.2 s in MiniCluster): wait for the
                # replica count to settle so the logical census is honest
                deadline = time.monotonic() + 5.0
                while (len(dn.replicas.block_ids()) > len(live)
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                for path in live:
                    c.read(path)
                dn.scrubber.run_cycle()
                samples.append(dn.flight.sample_once())
    curves = {}
    for metric in ("storage_ratio", "garbage_bytes",
                   "chunk_cache_hit_ratio", "read_p95_ms"):
        vals = [float(s.get(metric, 0.0)) for s in samples]
        curves[metric] = {"first": vals[0], "last": vals[-1],
                          "slope": slo_report.slope(vals),
                          "series": vals}
    tr = slo_report.trend(samples)
    print(json.dumps({
        "op": "churn [delete/rewrite lifecycle, flight-sampled]",
        "rounds": args.rounds,
        "files_per_round": args.files,
        "kb": kb,
        "delete_frac": args.delete_frac,
        "rewrite_frac": args.rewrite_frac,
        "samples": len(samples),
        "curves": curves,
        "regressions": tr["regressions"],
        "verdict": tr["verdict"],
    }))


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="hdrf-bench")
    sub = p.add_subparsers(dest="which", required=True)
    d = sub.add_parser("nn")
    d.add_argument("--ops", type=int, default=2000,
                   help="total data ops (create+addBlock+complete chains)")
    d.add_argument("--clients", type=int, default=8,
                   help="concurrent wire clients")
    d.add_argument("--meta-per-op", type=int, default=3,
                   help="stat/getBlockLocations/listing calls per data op")
    d.add_argument("--files", type=int, default=100,
                   help="files per listing directory (rotation width)")
    d.add_argument("--observer-ab", action="store_true",
                   help="paired A/B: storm with vs without observer reads")
    d.add_argument("--kill-active", action="store_true",
                   help="kill the active mid-storm; observers keep serving")
    d.add_argument("--observers", type=int, default=1,
                   help="observer NNs in --observer-ab/--kill-active modes")
    d.add_argument("--rounds", type=int, default=5,
                   help="paired rounds to median over (--observer-ab)")
    d.add_argument("--duration", type=float, default=6.0,
                   help="storm duration in seconds (--kill-active)")
    d.set_defaults(fn=bench_nn)
    d = sub.add_parser("dfs")
    d.add_argument("--mb", type=int, default=64)
    d.add_argument("--datanodes", type=int, default=3)
    d.add_argument("--replication", type=int, default=2)
    d.add_argument("--schemes", default="direct,lz4,dedup_lz4")
    d.set_defaults(fn=bench_dfs)
    d = sub.add_parser("ec")
    d.add_argument("--mb", type=int, default=48)
    d.add_argument("--policy", default="rs-6-3-64k")
    d.add_argument("--inner", type=int, default=4,
                   help="k for the slope method's long pass")
    d.add_argument("--repair-ab", action="store_true",
                   help="paired repair A/B: full-gather decode vs coded "
                        "partial-sum exchange, oracle-pinned on every "
                        "erasure pattern; one JSON line")
    d.add_argument("--dns", type=int, default=5,
                   help="simulated holder count for --repair-ab")
    d.add_argument("--erasures", type=int, default=1,
                   help="stripes lost in the --repair-ab timed pattern")
    d.set_defaults(fn=bench_ec)
    d = sub.add_parser("reduction")
    d.add_argument("--mb", type=int, default=64)
    d.add_argument("--backend", default="auto")
    d.set_defaults(fn=bench_reduction)
    d = sub.add_parser("sort")
    d.add_argument("--tiles", type=int, default=8)
    d.add_argument("--entries", type=int, default=1 << 15)
    d.add_argument("--inner", type=int, default=8,
                   help="k for the slope method's long pass")
    d.add_argument("--repeats", type=int, default=5)
    d.add_argument("--interpret", action="store_true",
                   help="run the Pallas kernel through the interpreter "
                        "(correctness spot-check on the CPU mesh)")
    d.set_defaults(fn=bench_sort)
    d = sub.add_parser("cdc")
    d.add_argument("--mb", type=int, default=16)
    d.add_argument("--inner", type=int, default=4,
                   help="k for the slope method's long pass")
    d.add_argument("--repeats", type=int, default=3)
    d.add_argument("--mask-bits", type=int, default=13,
                   help="geometry sweep: expected chunk size 2^mask_bits")
    d.add_argument("--min-size", type=int, default=2048,
                   help="geometry sweep: CDC min chunk size (bytes)")
    d.add_argument("--no-skip-ahead", dest="skip_ahead",
                   action="store_false",
                   help="pin the PR 4 fused scan alone (drops the "
                        "skip-ahead leg of the A/B)")
    d.set_defaults(fn=bench_cdc)
    d = sub.add_parser("multichip")
    d.add_argument("--blocks", type=int, default=64)
    # Defaults are the dispatch-bound geometry (2 KiB blocks, single-SHA
    # -leg chunks): per-device compute is as thin as the kernels allow,
    # so the curve isolates what widening the mesh buys per step.  Bigger
    # blocks push every width into the 1-vCPU compute wall and flatten
    # the curve without telling you anything new (PERF_NOTES round 13).
    d.add_argument("--block-kb", type=int, default=2)
    d.add_argument("--lanes", type=int, default=1,
                   help="per-device lane capacity (blocks per device "
                        "per mesh step)")
    d.add_argument("--repeats", type=int, default=3)
    d.add_argument("--mask-bits", type=int, default=6)
    d.add_argument("--min-chunk", type=int, default=32)
    d.add_argument("--max-chunk", type=int, default=112)
    d.set_defaults(fn=bench_multichip)
    d = sub.add_parser("recon")
    d.add_argument("--mb", type=int, default=64)
    d.add_argument("--repeats", type=int, default=3)
    d.add_argument("--chunk-kb", type=int, default=0,
                   help="target avg chunk KiB (0 = config default ~8)")
    d.set_defaults(fn=bench_recon)
    d = sub.add_parser("churn")
    d.add_argument("--rounds", type=int, default=6,
                   help="churn generations (one flight sample each)")
    d.add_argument("--files", type=int, default=6,
                   help="files written per generation")
    d.add_argument("--kb", type=int, default=64,
                   help="shared-tile and unique-tail size per file (KiB)")
    d.add_argument("--delete-frac", type=float, default=0.4,
                   help="fraction of the oldest live files deleted per "
                        "round")
    d.add_argument("--rewrite-frac", type=float, default=0.2,
                   help="fraction of survivors rewritten per round")
    d.set_defaults(fn=bench_churn)
    args = p.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        # this process may come to own a device: place the compile cache
        from hdrf_tpu.utils import device_env

        device_env.enable_compile_cache()
    args.fn(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
