"""Observability spine: dispatch ledger, Prometheus exposition, trace
propagation/assembly, the stall watchdog, and the bench JSON contract.

Covers the reference's metrics2 -> PrometheusMetricsSink text rendering,
the HTrace span resume over op headers (Receiver.java:94-98
``continueTraceSpan``), and HttpServer2's /stacks servlet — in their
re-expressed forms (utils/prom.py, utils/tracing.py, utils/watchdog.py,
server/status_http.py, the gateway's /prom /traces /stacks routes)."""

import json
import os
import random
import re
import subprocess
import sys
import time
import urllib.request

import pytest

from hdrf_tpu.server.http_gateway import HttpGateway
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.utils import device_ledger, fault_injection, metrics, prom, tracing
from hdrf_tpu.utils.metrics import Histogram
from hdrf_tpu.utils.watchdog import StallWatchdog, thread_stacks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def blob(seed: int, n: int) -> bytes:
    return random.Random(seed).randbytes(n)


def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=10) as r:
        assert r.status == 200
        return r.read()


# ------------------------------------------------------------- prom parsing

_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)\{([^}]*)\} (-?[0-9.eE+]+|NaN)$')
_TYPE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|histogram|summary|untyped)$")


def parse_prom(text: str):
    """Strict exposition-format parser: every line must be a valid # TYPE
    comment or a ``name{labels} value`` sample; TYPE names must be unique.
    Returns ({family: type}, [(name, labels, value)])."""
    types: dict[str, str] = {}
    samples: list[tuple[str, dict, float]] = []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = _TYPE.match(line)
            assert m, f"malformed comment line: {line!r}"
            assert m.group(1) not in types, f"duplicate TYPE {m.group(1)}"
            types[m.group(1)] = m.group(2)
            continue
        m = _SAMPLE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, raw, val = m.groups()
        labels = dict(re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"', raw))
        samples.append((name, labels, float(val)))
    return types, samples


def check_prom(text: str):
    """Cross-checks beyond line syntax: every sample belongs to a typed
    family, counters end in _total, histogram buckets are cumulative and
    their +Inf bucket equals _count."""
    types, samples = parse_prom(text)
    hist_series: dict[tuple, list] = {}
    for name, labels, val in samples:
        fam = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and types.get(base) == "histogram":
                fam = base
        assert fam in types, f"sample {name} has no # TYPE"
        if types[fam] == "counter":
            assert name.endswith("_total"), f"counter {name} missing _total"
        if types[fam] == "histogram" and name.endswith("_bucket"):
            # key on the FULL label set minus le — per-op/per-phase series
            # (wait_us{op=...}, phase_us{phase=...}) are distinct histograms
            # sharing one family
            key = (fam, tuple(sorted((k, v) for k, v in labels.items()
                                     if k != "le")))
            hist_series.setdefault(key, []).append(
                (float("inf") if labels["le"] == "+Inf" else float(labels["le"]),
                 val))
    for (fam, lab_key), rows in hist_series.items():
        rows.sort()
        cums = [v for _, v in rows]
        assert cums == sorted(cums), f"{fam}{{{lab_key}}} buckets not cumulative"
        count = next(v for n, lab, v in samples
                     if n == f"{fam}_count"
                     and tuple(sorted(lab.items())) == lab_key)
        assert rows[-1][0] == float("inf") and rows[-1][1] == count, \
            f"{fam}{{{lab_key}}} +Inf bucket != _count"
    return types, samples


# ----------------------------------------------------------------- units


class TestHistogramBuckets:
    def test_cumulative_snapshot(self):
        h = Histogram()
        for v in (1, 3, 3, 100):
            h.update(v)
        snap = h.snapshot()
        assert snap["count"] == 4 and snap["sum"] == 107
        bounds = [b for b, _ in snap["buckets"]]
        cums = [c for _, c in snap["buckets"]]
        assert bounds == sorted(bounds)
        assert cums == sorted(cums), "bucket counts must be cumulative"
        assert cums[-1] == snap["count"], "all samples below 2**32 bound"
        # every emitted bound's cumulative count really is #observations <= it
        assert dict(snap["buckets"])[1.0] == 1
        assert dict(snap["buckets"])[4.0] == 3

    def test_empty(self):
        snap = Histogram().snapshot()
        assert snap["count"] == 0 and snap["buckets"] == []


class TestPromRender:
    def test_render_is_valid_exposition(self):
        reg = metrics.registry("obs_prom_unit")
        reg.incr("widgets")            # gains _total
        reg.incr("frobs_total", 5)     # keeps single _total
        reg.gauge("depth", 3.5)
        for v in (10, 20, 20_000):
            reg.observe("lat_us", v)
        types, samples = check_prom(prom.render(metrics.all_snapshots()))
        names = {n for n, _, _ in samples}
        assert "hdrf_widgets_total" in names
        assert "hdrf_frobs_total" in names and "hdrf_frobs_total_total" not in names
        assert "hdrf_depth" in names and types["hdrf_depth"] == "gauge"
        assert types["hdrf_lat_us"] == "histogram"
        assert any(lab.get("registry") == "obs_prom_unit"
                   for _, lab, _ in samples)

    def test_same_family_across_registries(self):
        a = metrics.registry("obs_prom_a")
        b = metrics.registry("obs_prom_b")
        a.incr("shared_ops")
        b.incr("shared_ops", 2)
        text = prom.render(metrics.all_snapshots())
        assert text.count("# TYPE hdrf_shared_ops_total counter") == 1
        check_prom(text)

    def test_label_suffix_keys_render_as_labels(self):
        """``name|k=v`` keys (per-op wait_us, per-phase phase_us) render as
        extra labels on the BASE family — one # TYPE, distinct series."""
        reg = metrics.registry("obs_prom_lbl")
        for v in (10, 20):
            reg.observe("io_us", v)
            reg.observe("io_us|op=cdc", v)
            reg.observe("io_us|op=sha", 2 * v)
        reg.incr("ops|op=cdc")
        text = prom.render(metrics.all_snapshots())
        types, samples = check_prom(text)
        assert text.count("# TYPE hdrf_io_us histogram") == 1
        ops = {lab.get("op") for n, lab, _ in samples
               if n == "hdrf_io_us_count"
               and lab.get("registry") == "obs_prom_lbl"}
        assert ops == {None, "cdc", "sha"}
        assert any(n == "hdrf_ops_total" and lab.get("op") == "cdc"
                   for n, lab, _ in samples)


class TestLedger:
    def test_dispatch_readback_counts_and_stamp(self):
        before = device_ledger.stamp()
        tok = device_ledger.dispatch("obs.unit", batch=4, h2d_bytes=1024,
                                     key=("obs-shape", 4))
        device_ledger.readback(tok, d2h_bytes=64)
        device_ledger.readback(None)           # None-safe (skipped dispatch)
        device_ledger.transfer("d2h", "obs.copy", 32)
        d = device_ledger.delta(before)
        assert d["dispatch_total"] == 1 and d["readback_total"] == 1
        assert d["h2d_bytes_total"] == 1024 and d["d2h_bytes_total"] == 96
        assert d["compiles_total"] >= 1      # first sighting of the key
        # the same shape key must not count a second compile
        before2 = device_ledger.stamp()
        device_ledger.readback(
            device_ledger.dispatch("obs.unit", key=("obs-shape", 4)))
        assert device_ledger.delta(before2)["compiles_total"] == 0

    def test_events_carry_trace_context(self):
        tr = tracing.tracer("obs_ledger_unit")
        with tr.span("ledger_linkage") as sp:
            device_ledger.readback(device_ledger.dispatch("obs.linked"))
        evs = [e for e in device_ledger.events_snapshot()
               if e["op"] == "obs.linked"]
        assert evs, "dispatch event missing from the ring"
        assert evs[-1]["trace_id"] == f"{sp.trace_id:016x}"
        assert evs[-1]["span_id"] == f"{sp.span_id:016x}"
        # events are msgpack/JSON-plain
        json.dumps(evs[-1])

    def test_chrome_trace_includes_ledger_rows(self):
        tr = tracing.tracer("obs_chrome_unit")
        with tr.span("chrome_root") as sp:
            device_ledger.readback(device_ledger.dispatch("obs.chrome"))
        tid = f"{sp.trace_id:016x}"
        doc = tracing.chrome_trace(tracing.all_span_snapshots(),
                                   device_ledger.events_snapshot(),
                                   trace_id=tid)
        evs = doc["traceEvents"]
        assert any(e.get("cat") == "span" and e["name"] == "chrome_root"
                   for e in evs)
        assert any(e.get("cat") == "device_ledger"
                   and e["args"]["trace_id"] == tid for e in evs)
        assert all(e["ph"] in ("M", "X") for e in evs)


class TestWatchdog:
    def test_scan_flags_once_per_budget(self):
        events = []
        wd = StallWatchdog("obs-unit", budget_s=10.0, tick_s=999)
        base = wd.stall_count()
        with fault_injection.inject("watchdog.stall",
                                    lambda **kw: events.append(kw)):
            with wd.track("slow_op"):
                t0 = time.monotonic()
                assert wd.scan(now=t0 + 1) == 0          # within budget
                assert wd.scan(now=t0 + 11) == 1         # over budget: flag
                assert wd.scan(now=t0 + 12) == 0         # already flagged
                assert wd.scan(now=t0 + 22) == 1         # a further budget
            assert wd.scan(now=t0 + 99) == 0             # op finished
        assert wd.stall_count() - base == 2
        assert [e["op"] for e in events] == ["slow_op", "slow_op"]
        recs = wd.stalls()
        assert recs and recs[-1]["op"] == "slow_op" and recs[-1]["stacks"]

    def test_inflight_and_stacks(self):
        wd = StallWatchdog("obs-unit2", budget_s=5.0, tick_s=999)
        with wd.track("visible"):
            ops = [e["op"] for e in wd.inflight()]
            assert "visible" in ops
        assert wd.inflight() == []
        stacks = thread_stacks()
        assert any("test_inflight_and_stacks" in "".join(frames)
                   for frames in stacks.values())


# ------------------------------------------------------------- cluster e2e


@pytest.fixture(scope="class")
def obs_cluster():
    with MiniCluster(n_datanodes=1, replication=1, block_size=256 * 1024,
                     dn_config_overrides={"status_port": 0}) as mc:
        gw = HttpGateway(mc.namenode.addr).start()
        try:
            yield mc, gw
        finally:
            gw.stop()


class TestEndpoints:
    def test_prom_from_gateway_and_datanode(self, obs_cluster):
        mc, gw = obs_cluster
        with mc.client() as c:
            c.write("/obs/prom", blob(1, 64 * 1024), scheme="dedup_lz4")
        # daemon status endpoint (DN opted in via status_port=0)
        dn = mc.datanodes[0]
        host, port = dn._status.addr
        types, samples = check_prom(
            _get(f"http://{host}:{port}/prom").decode())
        regs = {lab.get("registry") for _, lab, _ in samples}
        assert "datanode" in regs
        # gateway endpoint merges its own + the NameNode's registries
        types, samples = check_prom(
            _get(f"http://{gw.addr[0]}:{gw.addr[1]}/prom").decode())
        regs = {lab.get("registry") for _, lab, _ in samples}
        assert "namenode" in regs

    def test_status_metrics_and_stacks(self, obs_cluster):
        mc, gw = obs_cluster
        host, port = mc.datanodes[0]._status.addr
        snaps = json.loads(_get(f"http://{host}:{port}/metrics"))
        assert "datanode" in snaps and "counters" in snaps["datanode"]
        stacks = json.loads(_get(f"http://{host}:{port}/stacks"))
        assert stacks["threads"] and "inflight" in stacks
        gstacks = json.loads(_get(f"http://{gw.addr[0]}:{gw.addr[1]}/stacks"))
        assert gstacks["threads"]

    def test_rpc_trace_roundtrip(self, obs_cluster):
        mc, _ = obs_cluster
        tr = tracing.tracer("obs_rpc_client")
        with tr.span("client.ls") as sp:
            with mc.client() as c:
                c.ls("/")
        tid, sid = f"{sp.trace_id:016x}", f"{sp.span_id:016x}"
        server = [s for s in tracing.all_span_snapshots()
                  if s["tracer"] == "rpc.namenode" and s["trace_id"] == tid]
        assert server, "NameNode RPC span did not resume the client trace"
        assert any(s["parent_id"] == sid for s in server), \
            "server span's parent is not the client span"

    def test_datatransfer_trace_roundtrip(self, obs_cluster):
        mc, _ = obs_cluster
        tr = tracing.tracer("obs_dt_client")
        data = blob(2, 96 * 1024)
        with tr.span("client.write") as sp:
            with mc.client() as c:
                c.write("/obs/dt", data, scheme="lz4")
        tid = f"{sp.trace_id:016x}"
        spans = [s for s in tracing.all_span_snapshots()
                 if s["trace_id"] == tid]
        xceiver = [s for s in spans if s["name"].startswith("xceiver.")]
        assert xceiver, "DN xceiver span did not resume the wire trace"
        # the receiver's reduce_block span nests under the xceiver span
        reduce = [s for s in spans if s["name"] == "reduce_block"]
        assert reduce
        xc_ids = {s["span_id"] for s in xceiver}
        assert all(s["parent_id"] in xc_ids for s in reduce)

    def test_watchdog_flags_delayed_op(self, obs_cluster):
        """An op that outlives its budget gets flagged WHILE in flight.
        The injected packet handler drives a deterministic watchdog pass
        with a synthetic clock from inside the stalled xceiver op itself
        (the background thread does the same every tick_s; the manual
        scan keeps the test free of real 30 s waits)."""
        mc, _ = obs_cluster
        dn = mc.datanodes[0]
        base = dn.watchdog.stall_count()
        fired = []
        hit = []

        def slow_packet(**kw):
            if not hit:                      # one packet is enough
                hit.append(1)
                assert any(e["op"].startswith("xceiver.")
                           for e in dn.watchdog.inflight())
                dn.watchdog.scan(now=time.monotonic() + 60.0)
        with fault_injection.inject("block_receiver.packet", slow_packet), \
                fault_injection.inject("watchdog.stall",
                                       lambda **kw: fired.append(kw)):
            with mc.client() as c:
                c.write("/obs/slow", blob(3, 64 * 1024), scheme="direct")
        assert dn.watchdog.stall_count() > base, "stall never flagged"
        assert any(e["op"].startswith("xceiver.") for e in fired)
        recs = dn.watchdog.stalls()
        assert recs and recs[-1]["stacks"], "stall record missing stacks"
        # the stall surfaces on the /stacks endpoint too
        host, port = dn._status.addr
        body = json.loads(_get(f"http://{host}:{port}/stacks"))
        assert body.get("stalls")


class TestTraceAssembly:
    def test_e2e_chrome_trace_with_worker(self):
        """The acceptance-criteria trace: one write through a real worker
        subprocess (device backend on the virtual mesh) shows up at the
        gateway's /traces?format=chrome as one trace with the client ->
        NN rpc -> DN xceiver -> worker chain AND >= 1 linked device-ledger
        event (the worker's resident-pipeline dispatches)."""
        base = blob(7, 32 * 1024)
        data = base * 3 + blob(8, 32 * 1024)   # dedup-friendly, 128 KiB
        # ``--backend tpu`` refuses to start without a chip, so the worker
        # that runs the device programs on the virtual mesh is started
        # from here, around main()'s check — steering stays in the test.
        code = ("import sys, time; "
                "from hdrf_tpu.server.reduction_worker import "
                "ReductionWorker; "
                "w = ReductionWorker(backend='tpu').start(); "
                "print('listening on %s:%d' % w.addr, flush=True); "
                "time.sleep(600)")
        proc = subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                                stdout=subprocess.PIPE, text=True)
        try:
            m = re.search(r"listening on ([\d.]+):(\d+)",
                          proc.stdout.readline())
            assert m, "device-path worker failed to start"
            doc, root = self._trace_one_write(
                data, [m.group(1), int(m.group(2))])
        finally:
            proc.kill()
            proc.wait()
        self._check_trace(doc, root)

    @staticmethod
    def _trace_one_write(data, worker_addr):
        with MiniCluster(n_datanodes=1, replication=1,
                         block_size=256 * 1024,
                         reduction_overrides={"worker_addr": worker_addr}
                         ) as mc:
            gw = HttpGateway(mc.namenode.addr).start()
            try:
                tr = tracing.tracer("obs_e2e_client")
                with tr.span("client.write") as root:
                    with mc.client() as c:
                        c.write("/obs/e2e", data, scheme="dedup_lz4")
                with mc.client() as c:
                    assert c.read("/obs/e2e") == data
                tid = f"{root.trace_id:016x}"
                body = _get(f"http://{gw.addr[0]}:{gw.addr[1]}"
                            f"/traces?format=chrome&trace_id={tid}")
                doc = json.loads(body)
            finally:
                gw.stop()
        return doc, root

    @staticmethod
    def _check_trace(doc, root):
        tid = f"{root.trace_id:016x}"
        evs = doc["traceEvents"]
        spans = [e for e in evs if e.get("cat") == "span"]
        names = {e["name"] for e in spans}
        assert "client.write" in names
        assert any(n.startswith("xceiver.") for n in names)
        assert any(n.startswith("worker.") for n in names), \
            f"worker span missing from {sorted(names)}"
        assert any(s["args"]["parent_id"] == f"{root.span_id:016x}"
                   for s in spans), "nothing chained to the client root"
        # every non-root span's ancestry resolves back to the client span
        by_id = {e["args"]["span_id"]: e for e in spans}
        root_sid = f"{root.span_id:016x}"
        worker = next(e for e in spans if e["name"].startswith("worker."))
        sid, hops = worker["args"]["parent_id"], 0
        while sid != root_sid:
            assert sid in by_id, f"broken parent chain at {sid}"
            sid = by_id[sid]["args"]["parent_id"]
            hops += 1
            assert hops < 32
        led = [e for e in evs if e.get("cat") == "device_ledger"]
        assert led, "no device-ledger event linked into the trace"
        assert all(e["args"]["trace_id"] == tid for e in led)
        # at least three daemons contributed rows (client, DN, worker, ...)
        assert len({e["pid"] for e in spans}) >= 3

    def test_gateway_traces_json_merge(self, ):
        with MiniCluster(n_datanodes=1, replication=1) as mc:
            gw = HttpGateway(mc.namenode.addr).start()
            try:
                with mc.client() as c:
                    c.write("/obs/merge", blob(9, 32 * 1024), scheme="lz4")
                doc = json.loads(
                    _get(f"http://{gw.addr[0]}:{gw.addr[1]}/traces"))
            finally:
                gw.stop()
        tracers = {s["tracer"] for s in doc["spans"]}
        assert "rpc.namenode" in tracers, tracers
        assert "datanode" in tracers, tracers
        # merged view dedupes: span ids unique
        sids = [s["span_id"] for s in doc["spans"]]
        assert len(sids) == len(set(sids))


# ------------------------------------------------------- bench contract


class TestBenchContract:
    def test_bench_emits_one_json_line_with_ledger(self):
        """bench.py's stdout contract (CLAUDE.md: exactly ONE JSON line)
        now including the dispatch-ledger delta and stall count."""
        from hdrf_tpu.utils.cleanenv import clean_cpu_env
        env = clean_cpu_env(8, keep_existing_count=True)
        env["HDRF_BENCH_SMOKE"] = "1"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
        assert len(lines) == 1, f"stdout must be ONE line, got {lines!r}"
        doc = json.loads(lines[0])
        assert doc["unit"] == "MB/s" and "value" in doc
        assert "stalls" in doc
        for key in ("dispatch_total", "readback_total", "compiles_total",
                    "stall_total", "h2d_bytes_total", "d2h_bytes_total"):
            assert key in doc["ledger"], f"ledger missing {key}"
        # reduction-effectiveness + health-intelligence fields: the dedup
        # ratio recomputed from the pass's chunk index (>= 1.0 by
        # definition) and the outlier detector's slow-peer verdict (0 —
        # the bench runs no cluster)
        assert float(doc["dedup_ratio"]) >= 1.0
        assert int(doc["slow_peer_count"]) == 0
        # degraded-mode health of the run: no breaker tripped, no write
        # fell back mid-bench (either would taint the throughput verdict)
        assert int(doc["resilience"]["breaker_open_total"]) == 0
        assert int(doc["resilience"]["degraded_writes"]) == 0
        # write-path phase profile: the e2e window decomposed into the
        # profiler's exclusive classes (sums to wall within rounding) with
        # the overlap ratios alongside
        pp = doc["phase_profile"]
        assert set(pp["classes"]) == {"host_busy", "device_busy",
                                      "transport_wait", "idle"}
        assert pp["wall_s"] > 0
        assert abs(sum(pp["classes"].values()) - pp["wall_s"]) < 0.005
        assert 0.0 <= pp["overlap_efficiency"] <= 1.0
        assert 0.0 <= pp["attributed_frac"] <= 1.0
        # the smoke e2e pass runs real CDC+SHA + WAL commits: both phases
        # must have been attributed some exclusive time
        assert pp["phases"].get("reduce_compute", 0) > 0
        assert pp["phases"].get("wal_commit", 0) > 0
        # write-overlap stamp (same shape in the no-TPU and TPU prints):
        # WAL group-commit batches, overlap efficiency
        pl = doc["pipeline"]
        assert int(pl["group_commit_batches"]) >= 0
        assert 0.0 <= float(pl["overlap_efficiency"]) <= 1.0
        # EC cold-tier stamp: the in-bench RS(6,3) exercise encodes one
        # container (9 stripes) and reads it back degraded (all-data
        # erasures -> decode through parity), so both counters are live;
        # the tier's expansion sits at ~(k+m)/k = 1.5
        ec = doc["ec"]
        assert int(ec["stripes_encoded"]) >= 9
        assert int(ec["degraded_reads"]) >= 1
        assert int(ec["repair_bytes"]) >= 0
        assert 1.49 <= float(ec["storage_ratio"]) <= 1.51

    def test_benchmarks_ec_one_json_line(self):
        """python -m hdrf_tpu.benchmarks ec: the paired encode / intact /
        degraded-read slope harness prints exactly ONE JSON line, with the
        parity pinned against the GF log/antilog oracle before timing."""
        from hdrf_tpu.utils.cleanenv import clean_cpu_env
        env = clean_cpu_env(8, keep_existing_count=True)
        out = subprocess.run(
            [sys.executable, "-m", "hdrf_tpu.benchmarks", "ec",
             "--mb", "2", "--inner", "2"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
        assert len(lines) == 1, f"stdout must be ONE line, got {lines!r}"
        doc = json.loads(lines[0])
        assert doc["parity_oracle_ok"] is True
        assert doc["k"] == 6 and doc["m"] == 3
        for key in ("encode_MBps", "intact_read_MBps",
                    "degraded_read_MBps"):
            assert float(doc[key]) > 0, key
        assert 1.49 <= float(doc["storage_ratio"]) <= 1.51
