"""Cluster health intelligence: heartbeat telemetry, median+MAD outlier
detection (slow peers / slow volumes), and reduction-effectiveness
accounting.

Covers the re-expressed SlowPeerTracker.java:56 / SlowDiskTracker /
OutlierDetector.java:61-103 stack (utils/rollwin.py, utils/outlier.py,
server/namenode.py's _health_report + slow_nodes_report RPC) and the
reduction accounting registry (reduction/accounting.py,
index/chunk_index.py:319 accounting) riding DN heartbeats — including the
acceptance pins: a delayed DN flags within two heartbeat intervals, the
dfsadmin -report cluster dedup ratio equals the chunk-index recompute
EXACTLY, and none of it adds device dispatches."""

import io
import json
import time
import urllib.request
from contextlib import redirect_stdout

import numpy as np
import pytest

from hdrf_tpu.reduction import accounting
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.tools import cli
from hdrf_tpu.utils import device_ledger, fault_injection, outlier, rollwin


def run_cli(argv) -> tuple[int, str]:
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(argv)
    return rc, buf.getvalue()


# ------------------------------------------------------------ rolling windows


class TestRollingWindow:
    def test_decay_and_summary(self):
        t = [0.0]
        w = rollwin.RollingWindow(window_s=10.0, clock=lambda: t[0])
        w.add(1.0)
        w.add(3.0)
        t[0] = 5.0
        s = w.summary()
        assert s == {"median": 2.0, "mean": 2.0, "max": 3.0, "p95": 3.0,
                     "count": 2}
        t[0] = 11.0  # both samples older than the window
        assert w.summary() is None

    def test_partial_decay_keeps_fresh_samples(self):
        t = [0.0]
        w = rollwin.RollingWindow(window_s=10.0, clock=lambda: t[0])
        w.add(1.0)
        t[0] = 8.0
        w.add(9.0)
        t[0] = 12.0  # first sample decayed, second still in window
        s = w.summary()
        assert s is not None and s["count"] == 1 and s["median"] == 9.0

    def test_maxlen_bounds_memory(self):
        w = rollwin.RollingWindow(window_s=1e9, maxlen=4, clock=lambda: 0.0)
        for v in range(10):
            w.add(float(v))
        s = w.summary()
        assert s["count"] == 4 and s["max"] == 9.0

    def test_window_map_drops_decayed_keys(self):
        t = [0.0]
        m = rollwin.WindowMap(window_s=10.0, clock=lambda: t[0])
        m.note("a", 1.0)
        t[0] = 5.0
        m.note("b", 2.0)
        t[0] = 12.0  # "a" fully decayed; "b" survives
        s = m.summaries()
        assert set(s) == {"b"} and s["b"]["median"] == 2.0


# ---------------------------------------------------------- outlier detector


class TestOutlierDetector:
    def test_planted_straggler_flags_on_degenerate_window(self):
        """MAD == 0 (every healthy value identical): the threshold
        collapses to median * min_ratio and the straggler still flags."""
        flags = outlier.detect({"a": 1.0, "b": 1.0, "c": 1.0, "d": 9.0})
        assert set(flags) == {"d"}
        assert flags["d"]["rule"] == "mad" and flags["d"]["mad"] == 0.0

    def test_uniform_population_never_flags(self):
        assert outlier.detect({"a": 2.0, "b": 2.0, "c": 2.0, "d": 2.0}) == {}

    def test_min_points_guards_tiny_population(self):
        # two resources cannot support a MAD verdict...
        assert outlier.detect({"a": 1.0, "b": 9.0}) == {}
        # ...but the absolute rule still catches pathological values
        flags = outlier.detect({"a": 1.0, "b": 9.0}, abs_floor=5.0)
        assert set(flags) == {"b"} and flags["b"]["rule"] == "absolute"

    def test_floor_suppresses_subthreshold_outliers(self):
        # 4x the median, but everything is sub-millisecond: not actionable
        vals = {"a": 0.0001, "b": 0.0001, "c": 0.0001, "d": 0.0004}
        assert outlier.detect(vals, floor=0.001) == {}

    def test_mad_spread_tolerated(self):
        # wide but consistent spread: within median + 3 * scaled MAD
        vals = {"a": 10.0, "b": 12.0, "c": 14.0, "d": 16.0, "e": 18.0}
        assert outlier.detect(vals) == {}

    def test_tracker_expires_healed_flags(self):
        t = [0.0]
        tr = outlier.OutlierTracker(expiry_s=100.0, clock=lambda: t[0])
        flagged = tr.observe({"a": 1.0, "b": 1.0, "c": 1.0, "d": 9.0})
        assert set(flagged) == {"d"} and flagged["d"]["since"] == 0.0
        t[0] = 50.0  # healed: subsequent observations are uniform
        assert set(tr.observe({"a": 1.0, "b": 1.0, "c": 1.0,
                               "d": 1.0})) == {"d"}  # not yet expired
        t[0] = 101.0
        assert tr.report() == {}  # flag expired without a re-flag

    def test_tracker_keeps_since_across_reflag(self):
        t = [0.0]
        tr = outlier.OutlierTracker(expiry_s=100.0, clock=lambda: t[0])
        tr.observe({"a": 1.0, "b": 1.0, "c": 1.0, "d": 9.0})
        t[0] = 40.0
        rep = tr.observe({"a": 1.0, "b": 1.0, "c": 1.0, "d": 9.0})
        assert rep["d"]["since"] == 0.0 and rep["d"]["last"] == 40.0


# ------------------------------------------------------- heartbeat telemetry


class TestHeartbeatTelemetry:
    def test_stats_round_trip_to_namenode(self):
        """DN heartbeat stats carry the volume, reduction and stall
        summaries; the NN stores them per DN (DatanodeInfo.stats)."""
        rng = np.random.default_rng(81)
        # ``stall_total`` lives in the process-wide ``datanode`` registry:
        # a stall that an earlier test file of this worker provoked is not
        # this cluster's
        from hdrf_tpu.utils import metrics

        stalls_before = metrics.registry("datanode").counter("stall_total")
        with MiniCluster(n_datanodes=2, replication=2) as mc:
            with mc.client("ht") as c:
                c.write("/ht/f", rng.integers(0, 256, size=150_000,
                                              dtype=np.uint8).tobytes(),
                        scheme="dedup_lz4")
            deadline = time.time() + 8
            stats = {}
            while time.time() < deadline:
                report = mc.namenode.rpc_datanode_report()
                stats = {d["dn_id"]: d["stats"] for d in report}
                if stats and all(
                        ("volumes" in s and "reduction" in s
                         and "stalls" in s) for s in stats.values()):
                    break
                time.sleep(0.2)
            for dn_id, s in stats.items():
                assert "volumes" in s, f"{dn_id} missing volume telemetry"
                for v in s["volumes"].values():
                    assert {"storage_type", "failed", "used_bytes",
                            "probe_median_s", "probe_count"} <= set(v)
                red = s["reduction"]
                assert {"logical_bytes", "unique_chunk_bytes", "dedup_ratio",
                        "refcount_hist", "container_util_hist",
                        "counters"} <= set(red)
                assert red["dedup_ratio"] >= 1.0
                assert s["stalls"] == stalls_before

    def test_slow_volume_flags_from_probe_latency(self):
        """A volume whose health probes run past the absolute floor is
        flagged by the NN detector (SlowDiskTracker analog) within the
        heartbeat cadence, and surfaces on the /prom gauge."""
        with MiniCluster(n_datanodes=2, replication=2) as mc:
            dn = mc.datanodes[0]
            for _ in range(4):
                dn.note_volume_latency(0, 5.0)  # 5 s probes: sick disk
            deadline = time.time() + 6
            rep = {}
            while time.time() < deadline:
                rep = mc.namenode.rpc_slow_nodes_report()
                if rep["slow_volumes"]:
                    break
                time.sleep(0.1)
            key = f"{dn.dn_id}:vol-0"
            assert key in rep["slow_volumes"], rep
            assert rep["slow_volumes"][key]["rule"] == "absolute"
            from hdrf_tpu.utils import metrics
            gauges = metrics.registry("namenode").snapshot()["gauges"]
            assert gauges.get("slow_volume_count", 0) >= 1


# --------------------------------------------------------- slow-peer e2e


class TestSlowPeerEndToEnd:
    def test_delayed_datanode_flagged_within_two_heartbeats(self):
        """Acceptance pin: one DN's packet path is artificially delayed
        (block_receiver.packet fault point, filtered by dn_id since every
        MiniCluster DN shares the process); its upstream pipeline peers
        observe the slow mirror leg organically, and the NN outlier
        detector flags it — visible through slow_nodes_report, the /prom
        gauge, and dfsadmin -slowPeers — within two heartbeat intervals
        of the telemetry landing."""
        rng = np.random.default_rng(82)
        hb = 0.2
        with MiniCluster(n_datanodes=3, replication=3, heartbeat_s=hb,
                         block_size=1 << 20) as mc:
            victim = mc.datanodes[2]

            def delay(**kw):
                if kw.get("dn_id") == victim.dn_id:
                    time.sleep(0.25)

            def observed() -> bool:
                # some upstream peer sampled the slow mirror leg
                return any(victim.dn_id in dn._peer_report()
                           for dn in mc.datanodes if dn is not victim)

            fault_injection.install("block_receiver.packet", delay)
            try:
                with mc.client("slow") as c:
                    # the victim only registers on peers when it is a
                    # MIRROR (not pipeline head); keep writing until some
                    # peer has sampled it
                    for i in range(8):
                        c.write(f"/slow/f{i}",
                                rng.integers(0, 256, size=150_000,
                                             dtype=np.uint8).tobytes())
                        if i >= 2 and observed():
                            break
            finally:
                fault_injection.remove("block_receiver.packet")
            assert observed(), "no peer recorded latency about the slow DN"
            # ... and the NN must flag it within two heartbeat intervals
            # (plus scheduling slack for a loaded CI host)
            deadline = time.time() + 2 * hb + 3.0
            rep = {}
            while time.time() < deadline:
                rep = mc.namenode.rpc_slow_nodes_report()
                if victim.dn_id in rep["slow_peers"]:
                    break
                time.sleep(hb / 2)
            assert victim.dn_id in rep["slow_peers"], rep
            assert rep["slow_peers"][victim.dn_id]["value"] > 1.0

            # /prom gauge via the gateway exposition
            from hdrf_tpu.server.http_gateway import HttpGateway
            gw = HttpGateway(mc.namenode.addr).start()
            try:
                with urllib.request.urlopen(
                        f"http://{gw.addr[0]}:{gw.addr[1]}/prom",
                        timeout=10) as r:
                    text = r.read().decode()
                line = next(ln for ln in text.splitlines()
                            if ln.startswith("hdrf_slow_peer_count"))
                assert float(line.rsplit(" ", 1)[1]) >= 1
                # /health JSON carries the same verdict
                with urllib.request.urlopen(
                        f"http://{gw.addr[0]}:{gw.addr[1]}/health",
                        timeout=10) as r:
                    health = json.loads(r.read())
                assert health["status"] == "degraded"
                assert victim.dn_id in health["slow_peers"]
            finally:
                gw.stop()

            # operator surface: dfsadmin -slowPeers prints the flag
            nn = f"{mc.namenode.addr[0]}:{mc.namenode.addr[1]}"
            rc, out = run_cli(["dfsadmin", "--namenode", nn, "-slowPeers"])
            assert rc == 0
            assert victim.dn_id in json.loads(out)["slow_peers"]


# ------------------------------------------------- reduction accounting e2e


class TestReductionAccounting:
    def test_report_dedup_ratio_exactly_matches_index(self):
        """Acceptance pin: the cluster dedup ratio printed by dfsadmin
        -report equals the ground truth recomputed from the chunk index
        tables EXACTLY (same ints, same float division — repr round-trip
        through the CLI)."""
        rng = np.random.default_rng(83)
        base = rng.integers(0, 256, size=120_000, dtype=np.uint8).tobytes()
        with MiniCluster(n_datanodes=1, replication=1) as mc:
            with mc.client("acct") as c:
                c.write("/acct/a", base, scheme="dedup_lz4")
                c.write("/acct/b", base, scheme="dedup_lz4")  # full dedup
                c.write("/acct/c", base[:40_000], scheme="dedup_lz4")
            # ground truth from the live chunk index tables
            logical = unique = 0
            for dn in mc.datanodes:
                acc = dn.index.accounting()
                logical += acc["logical_bytes"]
                unique += acc["unique_chunk_bytes"]
            truth = accounting.dedup_ratio(logical, unique)
            assert truth > 1.5  # the corpus really deduped
            nn = f"{mc.namenode.addr[0]}:{mc.namenode.addr[1]}"
            deadline = time.time() + 8
            reported = None
            while time.time() < deadline:
                cs = mc.namenode.rpc_cluster_status()
                if (cs["dedup_logical_bytes"] == logical
                        and cs["dedup_unique_bytes"] == unique):
                    reported = cs["dedup_ratio"]
                    break
                time.sleep(0.2)
            assert reported is not None, "heartbeat stats never converged"
            assert reported == truth  # exact: identical ints, same division
            rc, out = run_cli(["dfsadmin", "--namenode", nn, "-report"])
            assert rc == 0
            line = next(ln for ln in out.splitlines()
                        if "dedup_ratio=" in ln)
            printed = float(line.split("dedup_ratio=")[1].split()[0])
            assert printed == truth  # repr round-trips exactly

    def test_accounting_counters_stamped_on_write_path(self):
        """Per-scheme logical/physical bytes and dedup hit/miss chunks
        land in the reduction_accounting registry from the product write
        path (DataDeduplicator.java:338-367's checkChunk points)."""
        rng = np.random.default_rng(84)
        base = rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
        before = accounting.snapshot()["counters"]
        with MiniCluster(n_datanodes=1, replication=1) as mc:
            with mc.client("ctr") as c:
                c.write("/ctr/a", base, scheme="dedup_lz4")
                c.write("/ctr/b", base, scheme="dedup_lz4")
                c.write("/ctr/z", base, scheme="lz4")

        def delta(key: str) -> int:
            after = accounting.snapshot()["counters"]
            return after.get(key, 0) - before.get(key, 0)

        assert delta("logical_bytes__dedup_lz4") == 2 * len(base)
        assert delta("logical_bytes__lz4") >= len(base)
        assert delta("physical_bytes__lz4") > 0
        # second identical write: all chunks hit, none missed
        assert delta("dedup_chunks_hit") > 0
        assert delta("dedup_chunks_miss") > 0
        # hits == misses here: write 1 misses every chunk, write 2 hits
        # every one of the same chunks
        assert delta("dedup_chunks_hit") == delta("dedup_chunks_miss")

    def test_utilization_hist_buckets(self):
        live = {1: 50, 2: 100, 3: 0}
        sizes = {1: 100, 2: 100, 3: 100, 4: 0}
        h = accounting.utilization_hist(live, sizes)
        # cid1 -> 50% (bucket 5), cid2 -> 100% (bucket 10), cid3+cid4 -> 0
        assert h == {5: 1, 10: 1, 0: 2}

    def test_telemetry_adds_zero_device_dispatches(self):
        """Acceptance pin: assembling heartbeat telemetry and running the
        detector are pure host work — the dispatch ledger must not move."""
        with MiniCluster(n_datanodes=1, replication=1) as mc:
            dn = mc.datanodes[0]
            with mc.client("zd") as c:
                c.write("/zd/f", b"x" * 50_000, scheme="dedup_lz4")
            led0 = device_ledger.stamp()
            for _ in range(3):
                dn._stats()
                mc.namenode.rpc_slow_nodes_report()
                mc.namenode.rpc_cluster_status()
                accounting.snapshot()
            led = device_ledger.delta(led0)
            assert led.get("dispatch_total", 0) == 0, led
            assert led.get("readback_total", 0) == 0, led
