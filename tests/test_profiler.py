"""Write-path critical-path profiler (utils/profiler.py) + gap-attribution
report (tools/gap_report.py): the decomposition the reference never had
(DataNodeMetrics.java:553-560 stops at per-op rate counters).

Partition math on injected integer clocks (exact sums — the idle remainder
makes the class partition total the wall clock by construction), timeline
assembly through the ambient contextvar, device-ledger linkage
(dispatch/readback ids landing on the open timeline), the watchdog's
cross-thread phase attribution, the gap_report golden table, and the
MiniCluster end-to-end acceptance bar (>= 95% of write wall attributed)."""

import json
import threading

import pytest

from hdrf_tpu.tools import gap_report
from hdrf_tpu.utils import device_ledger, fault_injection, profiler, tracing

W = profiler.profile_spans


def approx(a, b, tol=1e-9):
    return abs(a - b) < tol


# ------------------------------------------------------- overlap accountant


class TestPartition:
    def test_empty_window_is_all_idle(self):
        p = W([], 0.0, 10.0)
        assert p["wall_s"] == 10.0
        assert p["classes"] == {"host_busy": 0.0, "device_busy": 0.0,
                                "transport_wait": 0.0, "idle": 10.0}
        assert p["attributed_frac"] == 0.0
        assert p["overlap_efficiency"] == 1.0  # nothing to hide

    def test_serial_phases_sum_exactly(self):
        spans = [("recv", 0, 3), ("wal_commit", 3, 5), ("device_wait", 5, 9)]
        p = W(spans, 0, 10)
        assert p["classes"]["transport_wait"] == 3
        assert p["classes"]["host_busy"] == 2
        assert p["classes"]["device_busy"] == 4
        assert p["classes"]["idle"] == 1
        assert sum(p["classes"].values()) == p["wall_s"] == 10
        assert p["phases"] == {"recv": 3, "wal_commit": 2, "device_wait": 4}
        assert approx(p["attributed_frac"], 0.9)

    def test_hidden_wait_and_efficiency(self):
        # recv [0,4), device [2,8), wal [6,10): the canonical overlap case
        spans = [("recv", 0, 4), ("device_wait", 2, 8), ("wal_commit", 6, 10)]
        p = W(spans, 0, 12)
        assert p["classes"] == {"host_busy": 4.0, "device_busy": 4.0,
                                "transport_wait": 2.0, "idle": 2.0}
        # hideable = any device/transport active = [0,8) = 8;
        # hidden = host concurrently busy = [6,8) = 2
        assert p["hideable_wait_s"] == 8 and p["hidden_wait_s"] == 2
        assert approx(p["overlap_efficiency"], 0.25)
        assert p["phases"] == {"recv": 2.0, "device_wait": 4.0,
                               "wal_commit": 4.0}
        assert sum(p["classes"].values()) == 12.0

    def test_class_priority_host_over_device_over_transport(self):
        spans = [("recv", 0, 6), ("device_wait", 0, 4), ("checksum", 0, 2)]
        p = W(spans, 0, 6)
        # [0,2) host wins; [2,4) device wins; [4,6) transport remains
        assert p["classes"]["host_busy"] == 2
        assert p["classes"]["device_busy"] == 2
        assert p["classes"]["transport_wait"] == 2
        assert p["phases"] == {"checksum": 2.0, "device_wait": 2.0,
                               "recv": 2.0}
        # full overlap of waits by time, but only [0,2) of the 6 hideable
        # seconds sat under host work
        assert p["hideable_wait_s"] == 6 and p["hidden_wait_s"] == 2

    def test_unknown_phase_defaults_to_host(self):
        assert profiler.phase_class("weird_new_phase") == profiler.HOST
        p = W([("weird_new_phase", 0, 2)], 0, 2)
        assert p["classes"]["host_busy"] == 2
        assert p["phases"] == {"weird_new_phase": 2.0}

    def test_spans_clamped_to_window(self):
        p = W([("recv", -5, 3), ("wal_commit", 8, 20)], 0, 10)
        assert p["classes"]["transport_wait"] == 3
        assert p["classes"]["host_busy"] == 2
        assert p["phases"] == {"recv": 3.0, "wal_commit": 2.0}
        assert sum(p["classes"].values()) == 10.0

    def test_bytes_rate(self):
        p = W([("recv", 0, 1)], 0, 2, nbytes=4 << 20)
        assert p["bytes"] == 4 << 20 and approx(p["mb_per_s"], 2.0)


# -------------------------------------------------------- timeline assembly


class _Clock:
    """Settable wall clock injected over profiler._now."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class TestTimelineAssembly:
    def test_phases_land_on_ambient_timeline(self, monkeypatch):
        profiler.reset()
        clk = _Clock()
        monkeypatch.setattr(profiler, "_now", clk)
        assert profiler.current_timeline() is None
        with profiler.block_timeline(7, nbytes=123) as tl:
            assert profiler.current_timeline() is tl
            with profiler.phase("wal_commit"):
                clk.t += 2
            clk.t += 1
            with profiler.phase("recv"):
                clk.t += 3
        assert profiler.current_timeline() is None
        assert tl.t0 == 100.0 and tl.t1 == 106.0
        assert tl.spans == [("wal_commit", 100.0, 102.0, tl.spans[0][3]),
                            ("recv", 103.0, 106.0, tl.spans[1][3])]
        prof = tl.profile()
        assert prof["classes"] == {"host_busy": 2.0, "transport_wait": 3.0,
                                   "device_busy": 0.0, "idle": 1.0}
        assert approx(prof["attributed_frac"], 5.0 / 6.0)
        snap = profiler.timelines_snapshot()[-1]
        assert snap["block_id"] == 7 and snap["nbytes"] == 123
        assert snap["spans"] == [["wal_commit", 100.0, 102.0],
                                 ["recv", 103.0, 106.0]]
        assert snap["profile"]["wall_s"] == 6.0

    def test_finished_timeline_observes_registry(self, monkeypatch):
        profiler.reset()
        clk = _Clock()
        monkeypatch.setattr(profiler, "_now", clk)
        from hdrf_tpu.utils import metrics
        reg = metrics.registry("write_profiler")
        before = reg.counter("blocks_profiled")
        with profiler.block_timeline(1):
            with profiler.phase("container_io"):
                clk.t += 1
        assert reg.counter("blocks_profiled") == before + 1
        snap = reg.snapshot()
        assert snap["gauges"]["attributed_frac"] == 1.0
        assert "phase_us|phase=container_io" in snap["histograms"]

    def test_timed_iter_records_per_item_spans(self, monkeypatch):
        profiler.reset()
        clk = _Clock()
        monkeypatch.setattr(profiler, "_now", clk)

        def slow_src():
            for i in range(3):
                clk.t += 2  # the wait happens inside next()
                yield i

        with profiler.block_timeline(2) as tl:
            items = list(profiler.timed_iter("recv", slow_src()))
        assert items == [0, 1, 2]
        recv = [s for s in tl.spans if s[0] == "recv"]
        assert len(recv) == 3
        assert all(s[2] - s[1] == 2.0 for s in recv)
        assert tl.profile()["classes"]["transport_wait"] == 6.0

    def test_window_profile_sees_other_threads(self, monkeypatch):
        profiler.reset()
        t0 = profiler.mark()

        def worker():
            with profiler.phase("wal_commit"):
                pass

        th = threading.Thread(target=worker)
        th.start()
        th.join()
        prof = profiler.window_profile(t0, profiler.mark())
        assert "wal_commit" in prof["phases"]


# ------------------------------------------- inclusive table, CPU, covering


COVERS = [("seal", 1.0, 9.0, 7, 0.5), ("seal_queue", 0.0, 1.0, 7),
          ("seal_index", 8.0, 9.0, 7), ("seal_drain", 3.0, 11.5, 1),
          ("dn_block", -2.0, 6.25, 3, 1.5), ("dn_read", 2.5, 2.75, 4, 0.1)]
SPAN_SETS = {
    "empty": [],
    "serial": [("recv", 0, 3), ("wal_commit", 3, 5), ("device_wait", 5, 9)],
    "overlap": [("recv", 0, 4), ("device_wait", 2, 8),
                ("wal_commit", 6, 10)],
    "four_streams": [("container_io", 0.1 * k, 0.1 * k + 0.7, k)
                     for k in range(4)]
    + [("packet_verify", 0.05 + 0.3 * k, 0.3 * k + 0.33, k)
       for k in range(12)]
    + [("seal_wait", 0.0, 7.3, 9), ("heartbeat_stats", 2.1, 2.6, 8, 0.2)],
    "nested_read": [("read_serve", 0.0, 1.0), ("container_load", 0.25, 0.75),
                    ("index_lookup", 0.0, 0.125), ("net_send", 1.0, 4.0)],
    "thirds": [("recv", k / 3.0, (k + 1) / 3.0 + 0.01, k % 3)
               for k in range(30)]
    + [("dedup_lookup", k / 7.0, k / 7.0 + 0.1, 5) for k in range(60)],
}


class TestCoveringSpans:
    @pytest.mark.parametrize("which", sorted(SPAN_SETS))
    def test_they_leave_the_partition_equal_to_the_last_digit(self, which):
        """A covering span is in the inclusive table and nowhere else: no
        class, no phase, no hidden or hideable second moves by a digit
        (``==`` on floats: the sweep must not even see its boundaries)."""
        spans = SPAN_SETS[which]
        plain = W(spans, 0.0, 10.0)
        mixed = W(COVERS[:3] + spans + COVERS[3:], 0.0, 10.0)
        for key in ("classes", "phases", "wall_s", "hidden_wait_s",
                    "hideable_wait_s", "overlap_efficiency",
                    "attributed_frac"):
            assert mixed[key] == plain[key], key
        assert set(mixed["inclusive"]) == {sp[0] for sp in spans} | {
            "seal", "seal_queue", "seal_index", "seal_drain", "dn_block",
            "dn_read"}

    def test_alone_they_leave_the_window_idle(self):
        p = W(COVERS, 0.0, 10.0)
        assert p["classes"]["idle"] == 10.0 and p["phases"] == {}
        assert p["attributed_frac"] == 0.0

    @pytest.mark.parametrize("name", ["seal_queue", "seal", "seal_index",
                                      "seal_drain", "dn_block", "dn_read"])
    def test_every_name_of_the_class(self, name):
        assert profiler.phase_class(name) == profiler.COVER
        assert name not in profiler.PHASE_ORDER


class TestInclusiveTable:
    def test_counts_walls_and_cpu_over_a_hand_made_set(self):
        spans = [("seal", 1.0, 3.0, 7, 0.5), ("seal", 4.0, 4.5, 7, 0.25),
                 ("container_io", 0.0, 2.0, 1), ("container_io", 1.0, 4.0, 2),
                 ("container_io", 1.5, 2.0, 3), ("recv", 0.0, 0.5)]
        prof = W(spans, 0.0, 10.0)
        assert prof["inclusive"] == {
            "seal": {"count": 2, "wall_s": 2.5, "wall_max_s": 2.0,
                     "cpu_s": 0.75},
            "container_io": {"count": 3, "wall_s": 5.5, "wall_max_s": 3.0},
            "recv": {"count": 1, "wall_s": 0.5, "wall_max_s": 0.5}}
        # three threads inside one phase: 5.5 s of their own time, 4 s of
        # the window (the partition gives an instant to one phase once)
        assert prof["phases"]["container_io"] == 4.0

    def test_a_span_cut_by_the_window_keeps_its_share_of_cpu(self):
        spans = [("seal", -2.0, 2.0, 7, 1.0),      # half inside
                 ("seal", 9.0, 13.0, 7, 2.0),      # a quarter inside
                 ("seal", 4.0, 5.0, 7, 0.125),     # whole
                 ("seal", 20.0, 21.0, 7, 9.0)]     # outside: not counted
        row = W(spans, 0.0, 10.0)["inclusive"]["seal"]
        assert row == {"count": 3, "wall_s": 4.0, "wall_max_s": 2.0,
                       "cpu_s": 0.5 + 0.5 + 0.125}

    def test_a_span_of_no_length_inside_the_window_still_counts(self):
        """``drain_seals()`` on an empty queue can end inside the clock's
        last digit: counted with no seconds, and no boundary for the sweep
        (the partition beside it equal to the last digit)."""
        spans = SPAN_SETS["thirds"]
        plain = W(spans, 0.0, 10.0)
        mixed = W(spans + [("seal_drain", 4.0, 4.0, 1), ("recv", 1.7, 1.7),
                           ("seal_drain", 10.0, 10.0, 1),
                           ("seal_drain", 12.0, 12.0, 1),    # outside
                           ("seal", 10.0, 12.0, 7, 1.0)],    # touches only
                  0.0, 10.0)
        assert mixed["phases"] == plain["phases"]
        assert mixed["classes"] == plain["classes"]
        assert mixed["inclusive"]["seal_drain"] == {
            "count": 2, "wall_s": 0.0, "wall_max_s": 0.0}
        assert "seal" not in mixed["inclusive"]
        assert mixed["inclusive"]["recv"]["count"] == \
            plain["inclusive"]["recv"]["count"] + 1

    def test_the_timeline_sweep_may_leave_it_out(self):
        assert "inclusive" not in W([("recv", 0, 1)], 0, 2, inclusive=False)
        assert W([("recv", 0, 1)], 0, 2, inclusive=False)["phases"] == \
            W([("recv", 0, 1)], 0, 2)["phases"]

    def test_it_is_json_safe(self):
        p = W(COVERS + SPAN_SETS["four_streams"], 0.0, 10.0)
        assert json.loads(json.dumps(p))["inclusive"] == p["inclusive"]


class TestThreadCpu:
    def _clocks(self, monkeypatch):
        profiler.reset()
        clk, cpu = _Clock(), _Clock(50.0)   # wall, thread CPU
        monkeypatch.setattr(profiler, "_now", clk)
        monkeypatch.setattr(profiler, "_thread_cpu", cpu)
        return clk, cpu

    def test_cpu_phase_records_a_fifth_field_and_a_plain_phase_none(
            self, monkeypatch):
        clk, cpu = self._clocks(monkeypatch)
        with profiler.cpu_phase("heartbeat_stats"):
            clk.t += 4
            cpu.t += 1.5
        with profiler.phase("container_io"):
            clk.t += 1
            cpu.t += 1
        a, b = profiler.window_spans(0.0, float("inf"))
        assert a == ("heartbeat_stats", 100.0, 104.0, a[3], 1.5)
        assert b == ("container_io", 104.0, 105.0, b[3])
        inc = profiler.window_profile(100.0, 105.0)["inclusive"]
        assert inc["heartbeat_stats"] == {"count": 1, "wall_s": 4.0,
                                          "wall_max_s": 4.0, "cpu_s": 1.5}
        assert "cpu_s" not in inc["container_io"]

    def test_a_plain_phase_never_reads_the_cpu_clock(self, monkeypatch):
        profiler.reset()

        def boom():
            raise AssertionError("a plain span took thread_time()")

        monkeypatch.setattr(profiler, "_thread_cpu", boom)
        with profiler.phase("recv"):
            pass
        profiler.lap("packet_verify", profiler.mark())
        profiler.flush_laps()
        profiler.record_span("seal_queue", 1.0, 2.0)
        assert list(profiler.timed_iter("recv", [1, 2])) == [1, 2]

    def test_window_spans_cuts_cpu_with_the_wall(self, monkeypatch):
        clk, cpu = self._clocks(monkeypatch)
        with profiler.cpu_phase("seal"):
            clk.t += 8
            cpu.t += 2
        (sp,) = profiler.window_spans(102.0, 104.0)
        assert sp[:3] == ("seal", 102.0, 104.0) and sp[4] == 0.5
        (whole,) = profiler.window_spans(0.0, float("inf"))
        assert whole[4] == 2.0
        # the benchmark's sampler: cut at the release, kept by
        # (name, end, thread), partitioned once to the window's end
        (kept,) = profiler.window_spans(106.0, float("inf"))
        row = W([kept], 106.0, 107.0)["inclusive"]["seal"]
        assert row == {"count": 1, "wall_s": 1.0, "wall_max_s": 1.0,
                       "cpu_s": 0.25}

    @pytest.mark.parametrize("opener,name", [
        (profiler.block_timeline, "dn_block"),
        (profiler.read_timeline, "dn_read")])
    def test_a_timeline_leaves_one_covering_span_as_long_as_itself(
            self, monkeypatch, opener, name):
        clk, cpu = self._clocks(monkeypatch)
        with opener(9, nbytes=5) as tl:
            clk.t += 1
            with profiler.phase("container_io"):
                clk.t += 2
                cpu.t += 0.75
            clk.t += 0.5
            cpu.t += 0.25
        covers = [sp for sp in profiler.window_spans(0.0, float("inf"))
                  if sp[0] == name]
        assert covers == [(name, tl.t0, tl.t1, threading.get_ident(), 1.0)]
        assert (tl.t0, tl.t1) == (100.0, 103.5)
        # in the ring alone: the timeline's own spans and profile (what
        # the histograms observe) are what they were
        assert [sp[0] for sp in tl.spans] == ["container_io"]
        prof = profiler.window_profile(100.0, 103.5)
        assert prof["classes"]["idle"] == 1.5
        assert prof["inclusive"][name] == {
            "count": 1, "wall_s": 3.5, "wall_max_s": 3.5, "cpu_s": 1.0}

    def test_a_timeline_snapshot_keeps_three_fields_a_span(self,
                                                           monkeypatch):
        clk, cpu = self._clocks(monkeypatch)
        with profiler.block_timeline(3):
            with profiler.cpu_phase("seal"):      # an inline seal
                with profiler.phase("seal_write"):
                    clk.t += 1
                clk.t += 1
        snap = profiler.timelines_snapshot()[-1]
        assert snap["spans"] == [["seal_write", 100.0, 101.0],
                                 ["seal", 100.0, 102.0]]
        assert snap["profile"]["phases"] == {"seal_write": 1.0}
        assert snap["profile"]["classes"]["idle"] == 1.0
        assert snap["profile"]["inclusive"]["seal"]["wall_s"] == 2.0
        json.dumps(snap)
        assert gap_report.aggregate([snap])["blocks"] == 1


# ------------------------------------------------------- device-ledger link


class TestLedgerLinkage:
    def test_dispatch_readback_lands_on_timeline(self):
        profiler.reset()
        with profiler.block_timeline(11) as tl:
            tok = device_ledger.dispatch("prof.unit", batch=2,
                                         h2d_bytes=64, key=("prof-unit", 2))
            device_ledger.readback(tok, d2h_bytes=16)
        assert len(tl.ledger_ids) == 1
        evs = {e["id"]: e for e in device_ledger.events_snapshot()}
        ev = evs[tl.ledger_ids[0]]
        assert ev["op"] == "prof.unit" and ev["kind"] == "dispatch"
        waits = [s for s in tl.spans if s[0] == "device_wait"]
        assert len(waits) == 1
        assert tl.profile()["classes"]["device_busy"] >= 0.0

    def test_outstanding_dispatches_track_balances(self):
        profiler.reset()
        tok = device_ledger.dispatch("prof.track", batch=1)
        names = {(s["name"], s["value"])
                 for s in profiler.counters_snapshot()}
        assert ("outstanding_dispatches", 1.0) in names
        device_ledger.readback(tok)
        last = [s for s in profiler.counters_snapshot()
                if s["name"] == "outstanding_dispatches"][-1]
        assert last["value"] == 0.0
        # aggregate (pending) tokens must NOT decrement below zero
        device_ledger.readback(device_ledger.pending("prof.track"))
        last = [s for s in profiler.counters_snapshot()
                if s["name"] == "outstanding_dispatches"][-1]
        assert last["value"] == 0.0

    def test_counter_samples_render_as_chrome_counter_events(self):
        profiler.reset()
        profiler.counter_set("wal_queue_depth", 3)
        doc = tracing.chrome_trace([], counters=profiler.counters_snapshot())
        cevs = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
        assert any(e["name"] == "wal_queue_depth"
                   and e["args"]["value"] == 3 for e in cevs)


# ------------------------------------------- watchdog phase/trace attribution


class TestWatchdogAttribution:
    def test_stall_record_carries_phase_and_trace(self):
        from hdrf_tpu.utils.watchdog import StallWatchdog
        wd = StallWatchdog("prof_wd", budget_s=5.0, tick_s=999.0)
        seen = {}

        def on_stall(**kw):
            seen.update(kw)

        import time as _time
        tr = tracing.tracer("prof_wd_client")
        with tr.span("client.write") as root:
            with wd.track("xceiver.write"):
                with profiler.phase("container_io"):
                    with fault_injection.inject("watchdog.stall", on_stall):
                        n = wd.scan(now=_time.monotonic() + 100.0)
        assert n == 1
        tid = f"{root.trace_id:016x}"
        rec = wd.stalls()[-1]
        assert rec["phase"] == "container_io"
        assert rec["trace_id"] == tid
        assert seen["phase"] == "container_io" and seen["trace_id"] == tid
        # synthetic stall span joined the watchdog tracer under the same
        # trace id (visible next to the block's spans in a chrome export)
        spans = tracing.tracer("watchdog").snapshot()
        mine = [s for s in spans if s["trace_id"] == tid]
        assert mine and mine[-1]["name"] == "stall:xceiver.write"
        assert mine[-1]["annotations"]["phase"] == "container_io"

    def test_thread_phase_probe(self):
        assert profiler.thread_phase() is None
        with profiler.phase("checksum"):
            with profiler.phase("container_io"):
                assert profiler.thread_phase() == "container_io"
            assert profiler.thread_phase() == "checksum"
        assert profiler.thread_phase() is None


# ------------------------------------------------------- gap_report goldens


def _golden_timelines():
    spans = [["recv", 0.0, 4.0], ["device_wait", 2.0, 8.0],
             ["wal_commit", 6.0, 10.0]]
    tl = {"block_id": 1, "nbytes": 8 << 20, "t0": 0.0, "t1": 12.0,
          "spans": spans, "ledger_ids": [],
          "profile": profiler.profile_spans(
              [tuple(s) for s in spans], 0.0, 12.0, nbytes=8 << 20)}
    return [tl]


class TestGapReport:
    def test_aggregate_golden(self):
        agg = gap_report.aggregate(_golden_timelines())
        assert agg["blocks"] == 1 and agg["bytes"] == 8 << 20
        assert agg["wall_s"] == 12.0
        assert approx(agg["attributed_frac"], 10.0 / 12.0)
        assert approx(agg["overlap_efficiency"], 0.25)
        rows = {r["phase"]: r for r in agg["phases"]}
        assert rows["device_wait"]["exclusive_s"] == 4.0
        # removing wal_commit's 4 exclusive seconds: 8 MiB / 8 s vs /12 s
        assert approx(rows["wal_commit"]["lost_mb_per_s"],
                      8.0 / 8.0 - 8.0 / 12.0)

    def test_format_table_golden(self):
        text = gap_report.format_table(gap_report.aggregate(
            _golden_timelines()))
        assert text == "\n".join([
            "write path: 1 blocks, 8.00 MiB in 12.000 s = 0.7 MB/s",
            "attributed: 83.3% of wall clock in named phase/overlap classes",
            "overlap efficiency: 25.0% (2.000 s of 8.000 s wait hidden "
            "under host work)",
            "",
            "class              seconds   share",
            "host_busy            4.000   33.3%",
            "device_busy          4.000   33.3%",
            "transport_wait       2.000   16.7%",
            "idle                 2.000   16.7%",
            "",
            "phase               excl s   share  lost MB/s",
            "device_wait          4.000   33.3%        0.3",
            "wal_commit           4.000   33.3%        0.3",
            "recv                 2.000   16.7%        0.1",
        ])

    def test_main_json_over_input_file(self, tmp_path):
        f = tmp_path / "tls.json"
        f.write_text(json.dumps(_golden_timelines()))
        import io
        from contextlib import redirect_stdout
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = gap_report.main(["--input", str(f), "--json"])
        assert rc == 0
        agg = json.loads(buf.getvalue())
        assert agg["blocks"] == 1 and approx(agg["overlap_efficiency"], 0.25)

    def test_main_accepts_bench_json_line(self, tmp_path):
        """--input takes bench.py's single JSON line directly: the
        ``phase_profile`` object is lifted out and reported as one
        pseudo-timeline (and a bare profile object works the same)."""
        prof = _golden_timelines()[0]["profile"]
        bench_line = {"metric": "x", "value": 1.0, "unit": "MB/s",
                      "phase_profile": prof,
                      "pipeline": {"group_commit_batches": 2,
                                   "overlap_efficiency":
                                       prof["overlap_efficiency"]}}
        import io
        from contextlib import redirect_stdout
        for doc in (bench_line, prof):
            f = tmp_path / "in.json"
            f.write_text(json.dumps(doc))
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = gap_report.main(["--input", str(f), "--json"])
            assert rc == 0
            agg = json.loads(buf.getvalue())
            assert approx(agg["overlap_efficiency"],
                          prof["overlap_efficiency"])
            assert approx(agg["wall_s"], prof["wall_s"])
            phases = {r["phase"] for r in agg["phases"]}
            assert phases == set(prof["phases"])


# ----------------------------------------------------------- end to end


class TestE2E:
    def test_minicluster_smoke_attribution_bar(self):
        """The ISSUE acceptance gate: the gap_report smoke partitions
        >= 95% of MiniCluster write wall clock into named classes."""
        agg = gap_report.aggregate(gap_report.run_smoke())
        assert agg["blocks"] == gap_report.SMOKE_BLOCKS
        assert agg["attributed_frac"] >= 0.95, agg
        # partition exactness survives aggregation
        assert approx(sum(agg["classes"].values()), agg["wall_s"], tol=1e-6)
        # the dedup write path must show its signature phases
        rows = {r["phase"] for r in agg["phases"]}
        assert {"recv", "wal_commit", "container_io",
                "dedup_lookup"} <= rows

    def test_smoke_shows_hidden_overlap(self):
        """ISSUE 7 acceptance: with the pipeline on (default depth > 1) the
        smoke corpus shows overlap_efficiency > 0 — the ack/CRC pump hides
        host work under the client-stream transport waits even for
        sequential single-stream writes."""
        agg = gap_report.aggregate(gap_report.run_smoke())
        assert agg["overlap_efficiency"] > 0.0, agg
        assert agg["hidden_wait_s"] > 0.0

    def test_next_block_reduces_under_a_parked_container_append(self):
        """Overlap contract on the served route, pinned deterministically:
        while block K is parked inside its container append (the
        ``dedup.container_append`` fault point), block K+1's write — its
        hop to the reduction worker, its commit, its last ack — runs to
        completion on its own connection thread, so its
        ``worker_reduces`` is counted BEFORE K's container_io finishes."""
        import random
        import threading

        from hdrf_tpu.server.reduction_worker import ReductionWorker
        from hdrf_tpu.testing.minicluster import MiniCluster
        from hdrf_tpu.utils import fault_injection, metrics

        br = metrics.registry("block_receiver")
        parked = threading.Event()
        release = threading.Event()
        seen: dict = {}
        lock = threading.Lock()

        def park(block_id=None, **kw):
            with lock:
                if "first" in seen:
                    return  # only block K parks; K+1 sails through
                seen["first"] = block_id
                seen["reduces_before"] = br.counter("worker_reduces")
            parked.set()
            release.wait(30)
            # still inside K's container_io phase: K+1 has come and gone
            seen["reduces_during"] = br.counter("worker_reduces")
            seen["k1_done"] = k1_done.is_set()

        k1_done = threading.Event()
        pay_k = random.Random(11).randbytes(1 << 20)
        pay_k1 = random.Random(12).randbytes(1 << 20)
        w = ReductionWorker(backend="native").start()
        try:
            with MiniCluster(n_datanodes=1, replication=1,
                             block_size=1 << 20,
                             reduction_overrides={
                                 "worker_addr": list(w.addr)}) as mc:
                def write_k():
                    with mc.client("k") as c:
                        c.write("/ov/k", pay_k, scheme="dedup")

                with fault_injection.inject("dedup.container_append", park):
                    t = threading.Thread(target=write_k)
                    t.start()
                    assert parked.wait(30), "block K never reached its append"
                    with mc.client("k1") as c2:   # runs while K is parked
                        c2.write("/ov/k1", pay_k1, scheme="dedup")
                    k1_done.set()
                    release.set()
                    t.join(30)
                    assert not t.is_alive()
                with mc.client("rd") as c3:
                    assert c3.read("/ov/k") == pay_k
                    assert c3.read("/ov/k1") == pay_k1
        finally:
            w.stop()
        # K's own reduce was counted before it parked; K+1's landed while
        # K was still inside its append
        assert seen["k1_done"], seen
        assert seen["reduces_during"] == seen["reduces_before"] + 1, seen

    def test_minicluster_tpu_backend_links_ledger(self):
        """A write through the jax reduction path (virtual-device mesh)
        produces a timeline whose device_wait spans carry the ledger event
        ids of the dispatches it waited on."""
        from hdrf_tpu.testing.minicluster import MiniCluster
        profiler.reset()
        import random
        payload = random.Random(5).randbytes(1 << 20)
        with MiniCluster(n_datanodes=1, replication=1,
                         block_size=1 << 20, backend="tpu") as mc:
            with mc.client("prof-e2e") as c:
                c.write("/prof/blk", payload, scheme="dedup")
        tls = profiler.timelines_snapshot()
        assert tls, "no timeline recorded for the write"
        tl = tls[-1]
        assert tl["ledger_ids"], "jax write produced no ledger links"
        evs = {e["id"] for e in device_ledger.events_snapshot()}
        assert set(tl["ledger_ids"]) <= evs
        assert tl["profile"]["phases"].get("device_wait", 0) > 0
