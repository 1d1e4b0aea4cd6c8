"""Read-path & per-tenant SLO observability plane.

Covers the serving-path twin of the write profiler: read timelines and
their exclusive-class partition (utils/profiler.py:272-312 read_timeline,
server/block_sender.py:66-108 serve_read), read-amplification accounting
(reduction/accounting.py:96-163), per-tenant attribution
(utils/tenants.py:40-99; the reference counts ops per daemon only,
DataNodeMetrics.java:553-560), the time-series flight recorder and its
``/timeseries`` surfaces (utils/flight_recorder.py:33-98,
server/status_http.py:84-87), the slo_report renderer
(tools/slo_report.py:94-146), the decoded-container LRU on the EC
degraded path (storage/container_store.py:455-515), and the rollwin
quantile extensions (utils/rollwin.py:79-168)."""

import json
import os
import random
import time
import urllib.request

import pytest

from hdrf_tpu.server.http_gateway import HttpGateway
from hdrf_tpu.server.status_http import StatusHttpServer
from hdrf_tpu.storage import container_store
from hdrf_tpu.storage.container_store import ContainerStore
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.reduction import accounting
from hdrf_tpu.tools import slo_report
from hdrf_tpu.utils import metrics, profiler, rollwin, tenants
from hdrf_tpu.utils.flight_recorder import FlightRecorder
from hdrf_tpu.utils.profiler import BlockTimeline, phase_class


def blob(seed: int, n: int) -> bytes:
    return random.Random(seed).randbytes(n)


def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=10) as r:
        assert r.status == 200
        return r.read()


def _await(cond, timeout: float = 5.0) -> bool:
    """Poll a cross-thread condition: the serving thread books its tenant
    note a hair after the client has its bytes (serve_read's latency covers
    the full packet run), so counter asserts must tolerate that window."""
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return cond()
        time.sleep(0.01)
    return True


# ------------------------------------------------------ timeline partition


class TestReadPhasePartition:
    def test_read_phase_classes(self):
        """The read phases join the exclusive-class map: index/cache/decode
        burn the single vCPU, stripe gathers and the packet run are
        transport waits the host could hide."""
        for p in ("index_lookup", "cache_probe", "container_decode"):
            assert phase_class(p) == profiler.HOST
        for p in ("ec_gather", "net_send"):
            assert phase_class(p) == profiler.TRANSPORT
        assert phase_class("device_wait") == profiler.DEVICE

    def test_serial_partition_sums_exactly(self):
        """Injected clocks: a serial read decomposes into host + transport
        with zero idle, attributed_frac == 1.0, and the class partition
        summing exactly to the wall clock."""
        tl = BlockTimeline(1, nbytes=1000, t0=0.0)
        tl.add_span("index_lookup", 0.0, 0.1)
        tl.add_span("cache_probe", 0.1, 0.15)
        tl.add_span("container_decode", 0.15, 0.5)
        tl.add_span("ec_gather", 0.5, 0.8)
        tl.add_span("net_send", 0.8, 1.0)
        tl.finish(t1=1.0)
        prof = tl.profile()
        assert prof["wall_s"] == pytest.approx(1.0)
        assert prof["classes"]["host_busy"] == pytest.approx(0.5)
        assert prof["classes"]["transport_wait"] == pytest.approx(0.5)
        assert prof["classes"]["idle"] == pytest.approx(0.0, abs=1e-12)
        assert sum(prof["classes"].values()) == pytest.approx(prof["wall_s"])
        assert prof["attributed_frac"] == pytest.approx(1.0)
        assert prof["phases"]["index_lookup"] == pytest.approx(0.1)
        assert prof["phases"]["cache_probe"] == pytest.approx(0.05)
        assert prof["phases"]["container_decode"] == pytest.approx(0.35)
        assert prof["phases"]["ec_gather"] == pytest.approx(0.3)
        assert prof["phases"]["net_send"] == pytest.approx(0.2)

    def test_hidden_transport_wait_under_decode(self):
        """A net_send window overlapped by host decode counts host_busy
        (the wait is HIDDEN — the desirable state); overlap_efficiency is
        hidden / hideable."""
        tl = BlockTimeline(2, t0=0.0)
        tl.add_span("container_decode", 0.0, 0.6)
        tl.add_span("net_send", 0.2, 1.0)
        tl.finish(t1=1.0)
        prof = tl.profile()
        assert prof["classes"]["host_busy"] == pytest.approx(0.6)
        assert prof["classes"]["transport_wait"] == pytest.approx(0.4)
        assert prof["hideable_wait_s"] == pytest.approx(0.8)
        assert prof["hidden_wait_s"] == pytest.approx(0.4)
        assert prof["overlap_efficiency"] == pytest.approx(0.5)

    def test_nested_lookup_attributes_innermost(self):
        """index_lookup nested inside a container_decode window attributes
        to the innermost phase (PHASE_ORDER lists it first)."""
        tl = BlockTimeline(3, t0=0.0)
        tl.add_span("container_decode", 0.0, 1.0)
        tl.add_span("index_lookup", 0.2, 0.4)
        tl.finish(t1=1.0)
        prof = tl.profile()
        assert prof["phases"]["index_lookup"] == pytest.approx(0.2)
        assert prof["phases"]["container_decode"] == pytest.approx(0.8)

    def test_read_timeline_observes_read_registry(self):
        """Finished read timelines ring separately from write ones and
        observe into the read_profiler registry."""
        profiler.reset()
        reg = metrics.registry("read_profiler")
        before = reg.counter("reads_profiled")
        with profiler.read_timeline(77, nbytes=4096):
            with profiler.phase("index_lookup"):
                pass
        assert reg.counter("reads_profiled") == before + 1
        snaps = profiler.read_timelines_snapshot()
        assert snaps and snaps[-1]["block_id"] == 77
        assert snaps[-1]["nbytes"] == 4096
        assert "profile" in snaps[-1]
        # the read ring is not the write ring
        assert all(t["block_id"] != 77
                   for t in profiler.timelines_snapshot())
        with reg._lock:
            h = reg._histograms.get("read_wall_us")
        assert h is not None and h.snapshot()["count"] >= 1


# --------------------------------------------------- read amplification


class TestReadAmplification:
    def test_exact_synthetic_corpus(self):
        """Hand-computed corpus: 4096 logical bytes served, 10240 physical
        bytes decoded, 2048 stripe bytes gathered -> amplification 2.5 /
        stripe amplification 0.5, exactly."""
        accounting.record_read_logical("t_ro_synth", 4096)
        with accounting.read_scope("t_ro_synth"):
            accounting.record_container_decode(10240)
            accounting.record_stripe_gather(2048)
        rep = accounting.read_amplification_report()["t_ro_synth"]
        assert rep["logical_bytes"] == 4096
        assert rep["physical_bytes"] == 10240
        assert rep["stripe_bytes"] == 2048
        assert rep["read_amplification"] == pytest.approx(2.5)
        assert rep["stripe_amplification"] == pytest.approx(0.5)
        # the derived ratio also lands as a /prom gauge
        snap = metrics.registry("reduction_accounting").snapshot()
        assert snap["gauges"]["read_amplification__t_ro_synth"] == \
            pytest.approx(2.5)

    def test_decode_outside_scope_books_raw(self):
        """Decodes outside any read scope (compaction, EC repair) book
        under the ``raw`` pseudo-scheme."""
        reg = metrics.registry("reduction_accounting")
        before = reg.counter("read_physical_bytes__raw")
        accounting.record_container_decode(777)
        assert reg.counter("read_physical_bytes__raw") == before + 777

    def test_container_store_decode_attribution(self, tmp_path):
        """A sealed-container decode inside read_scope books its physical
        bytes under the ambient scheme; the LRU hit on the second read
        decodes (and books) nothing — the compounding win."""
        cs = ContainerStore(str(tmp_path), container_size=1 << 20,
                            lanes=1, codec="lz4")
        locs = cs.append_chunks([blob(41, 8 * 1024)])
        cid = locs[0][0]
        cs.flush_open()
        reg = metrics.registry("reduction_accounting")
        before = reg.counter("read_physical_bytes__t_ro_cs")
        with accounting.read_scope("t_ro_cs"):
            data = cs.read_container(cid)
        assert reg.counter("read_physical_bytes__t_ro_cs") - before \
            == len(data)
        with accounting.read_scope("t_ro_cs"):
            assert cs.read_container(cid) == data  # LRU hit
        assert reg.counter("read_physical_bytes__t_ro_cs") - before \
            == len(data), "cache hit must not book decoded bytes"


class TestEcDegradedCacheHit:
    def test_lru_hit_after_stripe_fallback(self, tmp_path):
        """A container demoted to stripes (sealed file gone) decodes via
        the EC fallback ONCE; the decoded image lands in the LRU so the
        second read is a cache hit that never touches the stripes."""
        cs = ContainerStore(str(tmp_path), container_size=1 << 20,
                            lanes=1, codec="lz4")
        locs = cs.append_chunks([blob(42, 16 * 1024)])
        cid = locs[0][0]
        cs.flush_open()
        sealed = cs.sealed_file_bytes(cid)
        assert sealed is not None
        os.remove(os.path.join(str(tmp_path), f"{cid}.sealed"))
        calls = []

        def fallback(c):
            calls.append(c)
            return sealed
        cs._stripe_fallback = fallback
        reg = metrics.registry("container_store")
        hits0 = reg.counter("cache_hit")
        data = cs.read_container(cid)
        assert calls == [cid], "first read must reassemble from stripes"
        assert cs.read_container(cid) == data
        assert calls == [cid], "second read must be served by the LRU"
        assert reg.counter("cache_hit") == hits0 + 1
        assert container_store.cache_hit_ratio() > 0.0
        # the ratio also rides /prom as a gauge
        assert reg.snapshot()["gauges"]["cache_hit_ratio"] == \
            pytest.approx(container_store.cache_hit_ratio())


# ------------------------------------------------------- tenant tracking


class TestTenantTracker:
    def test_counters_and_rolling_gauges(self):
        """Fresh tracker on an injected clock: per-(tenant, op) counters
        are exact, rolling p50/p95/p99 gauges refresh on latency notes,
        and an absent tenant id books under ``anon``."""
        trk = tenants.TenantTracker(window_s=300.0, clock=lambda: 0.0)
        trk.note_op("t-ro-u1", "read", 100, latency_s=0.010, now=1.0)
        trk.note_op("t-ro-u1", "read", 200, latency_s=0.030, now=2.0)
        trk.note_op("t-ro-u2", "read", 50, latency_s=0.020, now=2.0)
        trk.note_op(None, "read", 1, now=2.0)
        assert trk.tenant_count() == 3  # u1, u2, anon
        reg = metrics.registry("tenants")
        assert reg.counter("tenant_ops|tenant=t-ro-u1,op=read") == 2
        assert reg.counter("tenant_bytes|tenant=t-ro-u1,op=read") == 300
        assert reg.counter("tenant_ops|tenant=t-ro-u2,op=read") == 1
        assert reg.counter("tenant_ops|tenant=anon,op=read") >= 1
        s = trk.summaries(now=2.0)
        assert set(s["t-ro-u1/read"]) == {"p50", "p95", "p99"}
        assert s["t-ro-u1/read"]["p95"] == pytest.approx(30.0)  # ms
        g = reg.snapshot()["gauges"]
        assert g["tenant_p95_ms|tenant=t-ro-u1,op=read"] == \
            pytest.approx(30.0)

    def test_reset_isolates_windows_not_counters(self):
        trk = tenants.TenantTracker(clock=lambda: 0.0)
        trk.note_op("t-ro-reset", "read", latency_s=0.001, now=1.0)
        assert trk.tenant_count() == 1
        trk.reset()
        assert trk.tenant_count() == 0
        assert trk.summaries(now=1.0) == {}


# ------------------------------------------------------ rollwin quantiles


class TestRollwinQuantiles:
    def test_quantiles_agree_with_summary_p95(self):
        """quantiles((95,)) equals summary()['p95'] by construction (same
        nearest-rank rule), and summary() keeps its exact key set."""
        w = rollwin.RollingWindow(window_s=100.0, clock=lambda: 0.0)
        for i, v in enumerate([5.0, 1.0, 9.0, 3.0, 7.0]):
            w.add(v, now=float(i))
        s = w.summary(now=5.0)
        assert set(s) == {"median", "mean", "max", "p95", "count"}
        assert w.quantiles((95,), now=5.0) == {"p95": s["p95"]}
        q = w.quantiles(now=5.0)
        assert q == {"p50": 5.0, "p95": 9.0, "p99": 9.0}

    def test_quantiles_decay_deterministically(self):
        w = rollwin.RollingWindow(window_s=10.0, clock=lambda: 0.0)
        w.add(100.0, now=0.0)
        w.add(1.0, now=9.0)
        assert w.quantiles(now=9.0) == {"p50": 1.0, "p95": 100.0,
                                        "p99": 100.0}
        # the old sample ages out; the window survives on the fresh one
        assert w.quantiles(now=11.0) == {"p50": 1.0, "p95": 1.0, "p99": 1.0}
        assert w.quantiles(now=99.0) is None

    def test_p2_exact_below_five_samples(self):
        est = rollwin.P2Quantile(0.5)
        assert est.value() == 0.0
        for v in (9.0, 1.0, 5.0):
            est.add(v)
        assert est.value() == 5.0  # nearest-rank median of {1,5,9}
        assert est.count == 3

    def test_p2_bounded_memory_and_accuracy(self):
        """P² keeps five markers regardless of stream length and lands
        near the true quantile on a deterministic uniform stream."""
        rng = random.Random(0x52)
        est = rollwin.P2Quantile(0.95)
        vals = [rng.uniform(0.0, 1000.0) for _ in range(5000)]
        for v in vals:
            est.add(v)
        assert len(est._h) == 5  # O(1) state, not O(n)
        assert est.count == 5000
        true_p95 = sorted(vals)[int(0.95 * 5000) - 1]
        assert abs(est.value() - true_p95) / true_p95 < 0.05

    def test_p2_rejects_degenerate_quantile(self):
        with pytest.raises(ValueError):
            rollwin.P2Quantile(0.0)
        with pytest.raises(ValueError):
            rollwin.P2Quantile(1.0)


# ------------------------------------------------------- flight recorder


class TestFlightRecorder:
    def test_ring_bounds_and_injected_clocks(self):
        """The ring holds exactly ``capacity`` samples (oldest dropped)
        and stamps the injected clocks — fully deterministic."""
        ticks = iter(range(100))
        n = [0]

        def sample():
            n[0] += 1
            return {"v": float(n[0])}
        fr = FlightRecorder("t-ro", sample, interval_s=1.0, capacity=4,
                            clock=lambda: float(next(ticks)),
                            wall=lambda: 1000.0)
        for _ in range(10):
            fr.sample_once()
        snap = fr.snapshot()
        assert snap["daemon"] == "t-ro"
        assert snap["interval_s"] == 1.0 and snap["capacity"] == 4
        assert len(snap["samples"]) == 4
        assert [s["v"] for s in snap["samples"]] == [7.0, 8.0, 9.0, 10.0]
        assert [s["mono"] for s in snap["samples"]] == [6.0, 7.0, 8.0, 9.0]
        assert all(s["t"] == 1000.0 for s in snap["samples"])
        json.dumps(snap)  # the /timeseries body must be JSON-plain

    def test_sample_errors_counted_never_raised(self):
        reg = metrics.registry("flight_recorder")
        before = reg.counter("sample_errors")

        def bad():
            raise RuntimeError("gauge bug")
        fr = FlightRecorder("t-ro-err", bad, capacity=2,
                            clock=lambda: 0.0, wall=lambda: 0.0)
        s = fr.sample_once()  # must not raise
        assert reg.counter("sample_errors") == before + 1
        assert set(s) == {"t", "mono"}  # clock stamps survive the error
        assert len(fr.snapshot()["samples"]) == 1

    def test_status_http_timeseries_roundtrip(self):
        """/timeseries on a daemon status server serves the recorder's
        ring; a recorder-less daemon serves the empty shell, not a 404."""
        fr = FlightRecorder("t-ro-http", lambda: {"g": 1.0}, capacity=8,
                            clock=lambda: 0.0, wall=lambda: 0.0)
        fr.sample_once()
        srv = StatusHttpServer("t-ro-http", port=0, recorder=fr).start()
        try:
            host, port = srv.addr
            doc = json.loads(_get(f"http://{host}:{port}/timeseries"))
        finally:
            srv.stop()
        assert doc["daemon"] == "t-ro-http"
        assert [s["g"] for s in doc["samples"]] == [1.0]
        bare = StatusHttpServer("t-ro-bare", port=0).start()
        try:
            host, port = bare.addr
            doc = json.loads(_get(f"http://{host}:{port}/timeseries"))
        finally:
            bare.stop()
        assert doc["samples"] == [] and doc["capacity"] == 0


# ----------------------------------------------------------- slo report


class TestSloReport:
    SAMPLES = [
        {"t": 1.0, "mono": 1.0, "read_p95_ms": 10.0, "cache_hit_ratio": 0.8},
        {"t": 2.0, "mono": 2.0, "read_p95_ms": 10.0, "cache_hit_ratio": 0.8},
        {"t": 3.0, "mono": 3.0, "read_p95_ms": 20.0, "cache_hit_ratio": 0.8},
        {"t": 4.0, "mono": 4.0, "read_p95_ms": 20.0, "cache_hit_ratio": 0.8},
    ]

    def test_direction_aware_regression_flags(self):
        agg = slo_report.aggregate(self.SAMPLES, baseline_frac=0.5)
        rows = {r["gauge"]: r for r in agg["gauges"]}
        assert "t" not in rows and "mono" not in rows
        assert rows["read_p95_ms"]["regressed"] is True
        assert rows["read_p95_ms"]["rel_change"] == pytest.approx(1.0)
        assert rows["cache_hit_ratio"]["regressed"] is False
        assert agg["regressions"] == ["read_p95_ms"]
        assert agg["verdict"] == "REGRESSED"

    def test_down_direction_and_unknown_gauges(self):
        samples = [{"cache_hit_ratio": 0.9, "mystery": 1.0},
                   {"cache_hit_ratio": 0.9, "mystery": 1.0},
                   {"cache_hit_ratio": 0.3, "mystery": 100.0},
                   {"cache_hit_ratio": 0.3, "mystery": 100.0}]
        agg = slo_report.aggregate(samples, baseline_frac=0.5)
        rows = {r["gauge"]: r for r in agg["gauges"]}
        assert rows["cache_hit_ratio"]["regressed"] is True  # ratio fell
        assert rows["mystery"]["direction"] == "none"
        assert rows["mystery"]["regressed"] is False  # unknown: never flags
        assert agg["regressions"] == ["cache_hit_ratio"]

    def test_jitter_floor_does_not_flag(self):
        samples = [{"read_p95_ms": 10.0}, {"read_p95_ms": 10.0},
                   {"read_p95_ms": 11.0}, {"read_p95_ms": 11.0}]
        agg = slo_report.aggregate(samples, baseline_frac=0.5)
        assert agg["verdict"] == "OK"  # +10% sits under the 25% floor

    def test_format_table_golden(self):
        agg = slo_report.aggregate(self.SAMPLES, baseline_frac=0.5)
        golden = (
            "slo report: 4 samples, baseline window = first/last 50%\n"
            "verdict: REGRESSED (read_p95_ms)\n"
            "\n"
            "gauge                          baseline    current"
            "    drift  flag\n"
            "cache_hit_ratio                   0.800      0.800"
            "     0.0%     -\n"
            "read_p95_ms                      10.000     20.000"
            "   100.0%  REGR")
        assert slo_report.format_table(agg) == golden

    def test_load_samples_shapes(self):
        assert slo_report._load_samples([{"a": 1}]) == [{"a": 1}]
        assert slo_report._load_samples(
            {"daemon": "dn", "samples": [{"a": 1}]}) == [{"a": 1}]
        assert slo_report._load_samples(
            {"value": 9.0, "read": {"read_p95_ms": 3.0}}) == \
            [{"read_p95_ms": 3.0}]
        assert slo_report._load_samples({"b": 2}) == [{"b": 2}]
        with pytest.raises(ValueError):
            slo_report._load_samples("nope")

    def test_accepts_bench_json_via_input(self, tmp_path, capsys):
        """bench.py's one JSON line feeds straight into slo_report
        --input (the 'read' block becomes a one-sample series)."""
        doc = {"value": 12.5, "unit": "MB/s",
               "read": {"read_amplification": 0.2, "cache_hit_ratio": 0.8,
                        "read_p95_ms": 4.0, "tenant_count": 1}}
        path = tmp_path / "bench.json"
        path.write_text(json.dumps(doc))
        rc = slo_report.main(["--input", str(path), "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["samples"] == 1 and out["verdict"] == "OK"
        gauges = {r["gauge"] for r in out["gauges"]}
        assert {"read_amplification", "cache_hit_ratio",
                "read_p95_ms"} <= gauges


class TestSloTrend:
    # injected step regression in read_p95_ms; cache_hit_ratio flat
    SAMPLES = [{"t": float(i + 1), "mono": float(i + 1),
                "read_p95_ms": 10.0 if i < 4 else 20.0,
                "cache_hit_ratio": 0.8} for i in range(8)]

    def test_trend_flags_injected_regression_deterministically(self):
        tr = slo_report.trend(self.SAMPLES)
        assert tr["regressions"] == ["read_p95_ms"]
        assert tr["verdict"] == "REGRESSED"
        rows = {r["metric"]: r for r in tr["metrics"]}
        r = rows["read_p95_ms"]
        assert r["slope"] == pytest.approx(80.0 / 42.0)
        assert r["changepoint"]["index"] == 4
        assert r["changepoint"]["before"] == pytest.approx(10.0)
        assert r["changepoint"]["after"] == pytest.approx(20.0)
        assert rows["cache_hit_ratio"]["regressed"] is False

    def test_flat_series_never_flags(self):
        flat = [{"read_p95_ms": 10.0, "cache_hit_ratio": 0.8}
                for _ in range(8)]
        tr = slo_report.trend(flat)
        assert tr["regressions"] == [] and tr["verdict"] == "OK"

    def test_slow_ramp_caught_by_slope_not_changepoint(self):
        """A creep with no step still regresses: the fitted total drift
        clears the jitter floor even though no single shift does."""
        ramp = [{"write_p95_ms": 10.0 + i} for i in range(10)]
        tr = slo_report.trend(ramp)
        assert tr["regressions"] == ["write_p95_ms"]

    def test_down_direction_metric(self):
        falling = [{"cache_hit_ratio": 0.8 if i < 4 else 0.2}
                   for i in range(8)]
        tr = slo_report.trend(falling)
        assert tr["regressions"] == ["cache_hit_ratio"]

    def test_format_trend_table_golden(self):
        golden = (
            "slo trend: 8 samples, jitter floor = 25%\n"
            "verdict: REGRESSED (read_p95_ms)\n"
            "\n"
            "metric                            first       last"
            "      slope   cp  flag\n"
            # a constant series has no changepoint (the old golden's "4"
            # was float noise in the SSE of 0.8s on another machine)
            "cache_hit_ratio                   0.800      0.800"
            "     0.0000    -     -\n"
            "read_p95_ms                      10.000     20.000"
            "     1.9048    4  REGR")
        assert slo_report.format_trend_table(
            slo_report.trend(self.SAMPLES)) == golden

    def test_trend_from_archive_directory(self, tmp_path, capsys):
        """Satellite: --input accepts a flight-archive DIRECTORY and the
        trend verdict survives a restart — the samples come back off
        disk, torn tail and all."""
        from hdrf_tpu.utils.flight_archive import FlightArchive
        d = str(tmp_path / "arch")
        arch = FlightArchive(d)
        for s in self.SAMPLES:
            arch.append(s)
        arch.close()
        seg = sorted(os.listdir(d))[-1]
        with open(os.path.join(d, seg), "ab") as f:
            f.write(b'{"torn": ')           # crash mid-append
        rc = slo_report.main(["--input", d, "--trend", "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["samples"] == 8
        assert out["regressions"] == ["read_p95_ms"]
        # flat archived series stays unflagged through the same path
        d2 = str(tmp_path / "flat")
        arch2 = FlightArchive(d2)
        for _ in range(8):
            arch2.append({"read_p95_ms": 10.0})
        arch2.close()
        rc = slo_report.main(["--input", d2, "--trend", "--json"])
        assert rc == 0
        assert json.loads(capsys.readouterr().out)["verdict"] == "OK"

    def test_guard_direction_aware_with_blast_radius(self):
        base = [{"read_p95_ms": 10.0, "dedup_ratio": 2.0, "noise": 1.0}
                for _ in range(4)]
        worse = [{"read_p95_ms": 20.0, "dedup_ratio": 2.0, "noise": 9.0}
                 for _ in range(4)]
        g = slo_report.guard(base, worse)
        assert g["regressed"] is True
        rows = {r["metric"]: r for r in g["rows"]}
        assert rows["read_p95_ms"]["regressed"] is True
        assert rows["noise"]["regressed"] is False  # unknown direction
        # narrowing to the change's blast radius vetoes unrelated gauges
        g = slo_report.guard(base, worse, gauges=("dedup_ratio",))
        assert g["regressed"] is False
        assert [r["metric"] for r in g["rows"]] == ["dedup_ratio"]

    def test_guard_improvement_never_rolls_back(self):
        base = [{"read_p95_ms": 20.0} for _ in range(4)]
        better = [{"read_p95_ms": 10.0} for _ in range(4)]
        assert slo_report.guard(base, better)["regressed"] is False


# ------------------------------------------------------------ cluster e2e


@pytest.fixture(scope="class")
def ro_cluster():
    with MiniCluster(n_datanodes=1, replication=1, block_size=256 * 1024,
                     dn_config_overrides={"status_port": 0}) as mc:
        gw = HttpGateway(mc.namenode.addr).start()
        try:
            yield mc, gw
        finally:
            gw.stop()


class TestClusterReadObservability:
    def test_two_tenant_isolation(self, ro_cluster):
        """Two clients reading the same blocks stay apart on the tenants
        registry: ops/bytes/latency gauges key by the _client identity the
        RPC-kwarg and DT-header channels carry."""
        mc, _ = ro_cluster
        data = blob(11, 96 * 1024)
        with mc.client("t-ro-writer") as c:
            c.write("/ro/iso", data, scheme="dedup")
        with mc.client("t-ro-alice") as a, mc.client("t-ro-bob") as b:
            for _ in range(3):
                assert a.read("/ro/iso") == data
            assert b.read("/ro/iso") == data
        reg = metrics.registry("tenants")
        assert _await(lambda:
                      reg.counter("tenant_ops|tenant=t-ro-alice,op=read")
                      == 3
                      and reg.counter("tenant_ops|tenant=t-ro-bob,op=read")
                      == 1)
        assert reg.counter("tenant_bytes|tenant=t-ro-alice,op=read") \
            == 3 * len(data)
        assert reg.counter("tenant_bytes|tenant=t-ro-bob,op=read") \
            == len(data)
        g = reg.snapshot()["gauges"]
        assert "tenant_p95_ms|tenant=t-ro-alice,op=read" in g
        # prom exposition renders the |k=v suffix as real labels
        host, port = mc.datanodes[0]._status.addr
        text = _get(f"http://{host}:{port}/prom").decode()
        assert 'tenant="t-ro-alice"' in text
        assert 'tenant="t-ro-bob"' in text

    def test_short_circuit_read_attributed(self, ro_cluster):
        """The AF_UNIX fd-grant path carries _client too (the client
        stamps it into the JSON request; the DN books read_sc ops)."""
        mc, _ = ro_cluster
        data = blob(12, 64 * 1024)
        with mc.client("t-ro-writer") as c:
            c.write("/ro/sc", data, scheme="direct")
        with mc.client("t-ro-scuser") as c:
            assert c.read("/ro/sc") == data
        reg = metrics.registry("tenants")
        assert _await(lambda: reg.counter(
            "tenant_ops|tenant=t-ro-scuser,op=read_sc") >= 1)

    def test_read_plane_rides_health_report(self, ro_cluster):
        """The DN stats payload (heartbeat /health surface) carries the
        serving-path aggregate: cache hit ratio, per-scheme read
        amplification, tenant summaries."""
        mc, _ = ro_cluster
        data = blob(13, 64 * 1024)
        with mc.client("t-ro-health") as c:
            c.write("/ro/health", data, scheme="dedup")
            assert c.read("/ro/health") == data
        rp = mc.datanodes[0]._stats()["read_plane"]
        assert 0.0 <= rp["container_cache_hit_ratio"] <= 1.0
        assert "dedup" in rp["read_amplification"]
        amp = rp["read_amplification"]["dedup"]
        assert amp["logical_bytes"] > 0
        assert any(k.startswith("t-ro-") for k in rp["tenants"])

    def test_dn_and_gateway_timeseries(self, ro_cluster):
        """/timeseries round-trips on both surfaces: the DN's own status
        server and the gateway (which pulls the NN ring over the
        flight_timeseries RPC)."""
        mc, gw = ro_cluster
        dn = mc.datanodes[0]
        dn.flight.sample_once()
        host, port = dn._status.addr
        doc = json.loads(_get(f"http://{host}:{port}/timeseries"))
        assert doc["daemon"] == dn.dn_id
        assert doc["samples"]
        last = doc["samples"][-1]
        for key in ("storage_ratio", "container_cache_hit_ratio",
                    "read_p95_ms", "write_p95_ms", "tenant_count",
                    "breakers_open", "t", "mono"):
            assert key in last, f"DN flight sample missing {key}"
        mc.namenode.flight.sample_once()
        doc = json.loads(
            _get(f"http://{gw.addr[0]}:{gw.addr[1]}/timeseries"))
        assert doc["daemon"] == "namenode"
        assert doc["samples"]
        last = doc["samples"][-1]
        for key in ("blocks", "datanodes", "datanodes_live",
                    "under_replicated", "safemode", "tenant_count"):
            assert key in last, f"NN flight sample missing {key}"
        assert last["datanodes_live"] >= 1

    def test_timeseries_metric_filter_strictly_smaller(self, ro_cluster):
        """Satellite bar: a ?metric= filtered pull is strictly smaller
        than the unfiltered one, on the DN status server and the gateway
        alike (the filter runs server-side, not in the client)."""
        mc, gw = ro_cluster
        dn = mc.datanodes[0]
        dn.flight.sample_once()
        mc.namenode.flight.sample_once()
        host, port = dn._status.addr
        full = _get(f"http://{host}:{port}/timeseries")
        slim = _get(f"http://{host}:{port}/timeseries"
                    f"?metric=storage_ratio")
        assert len(slim) < len(full)
        doc = json.loads(slim)
        assert doc["samples"]
        assert set(doc["samples"][-1]) == {"t", "mono", "storage_ratio"}
        gfull = _get(f"http://{gw.addr[0]}:{gw.addr[1]}/timeseries")
        gslim = _get(f"http://{gw.addr[0]}:{gw.addr[1]}/timeseries"
                     f"?metric=blocks")
        assert len(gslim) < len(gfull)
        # ?since= far in the future empties the series but keeps the shell
        doc = json.loads(_get(f"http://{host}:{port}/timeseries"
                              f"?since=9e18"))
        assert doc["samples"] == [] and doc["daemon"] == dn.dn_id

    def test_gateway_cluster_scope_merges_all_daemons(self, ro_cluster):
        """?scope=cluster fans out to every live DN over the
        flight_timeseries DT op, merges with the NN series, and a &step=
        rollup bounds the response."""
        mc, gw = ro_cluster
        mc.datanodes[0].flight.sample_once()
        mc.namenode.flight.sample_once()
        doc = json.loads(_get(f"http://{gw.addr[0]}:{gw.addr[1]}"
                              f"/timeseries?scope=cluster"))
        assert doc["scope"] == "cluster"
        assert "namenode" in doc["daemons"]
        assert any(d != "namenode" for d in doc["daemons"])
        assert doc["samples"]
        merged = doc["samples"][-1]
        assert merged["nodes"] >= 1 and "t" in merged
        # DN gauges and NN gauges land in one merged series
        names = set().union(*(set(s) for s in doc["samples"]))
        assert "storage_ratio" in names and "datanodes_live" in names
        rolled = json.loads(_get(f"http://{gw.addr[0]}:{gw.addr[1]}"
                                 f"/timeseries?scope=cluster&step=60"))
        assert rolled["rollup"]
        row = rolled["rollup"][-1]
        assert {"min", "max", "mean", "last"} <= set(
            next(iter(row["gauges"].values())))

    def test_nn_rpc_latency_histogram_and_p99_gauge(self, ro_cluster):
        """Satellite: every NN RPC books nn_rpc_us|method=<name> and the
        NN flight sample carries the rolling p99 gauge."""
        mc, _ = ro_cluster
        with mc.client("t-ro-rpc") as c:
            c.ls("/")
        hists = metrics.registry("rpc.namenode").snapshot()["histograms"]
        assert hists["nn_rpc_us|method=listing"]["count"] >= 1
        sample = mc.namenode.flight.sample_once()
        assert "nn_rpc_p99_ms" in sample
        assert sample["nn_rpc_p99_ms"] > 0.0

    def test_read_smoke_mostly_attributed(self, ro_cluster):
        """Acceptance bar: >= 95% of the read smoke's serve wall clock is
        attributed to named phases (aggregated over the data-bearing read
        timelines, weighted by wall)."""
        mc, _ = ro_cluster
        profiler.reset()
        data = blob(14, 240 * 1024)
        with mc.client("t-ro-smoke") as c:
            c.write("/ro/smoke", data, scheme="dedup")
            for _ in range(5):
                assert c.read("/ro/smoke") == data
        snaps = [t for t in profiler.read_timelines_snapshot()
                 if t["nbytes"] > 0]
        assert snaps, "no data-bearing read timeline recorded"
        wall = sum(t["profile"]["wall_s"] for t in snaps)
        attributed = sum(t["profile"]["wall_s"]
                         * t["profile"]["attributed_frac"] for t in snaps)
        assert wall > 0
        assert attributed / wall >= 0.95, \
            f"only {attributed / wall:.1%} of read wall attributed"
