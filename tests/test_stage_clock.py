"""One clock down the served write path (PR 25): the phase clock's new
DataNode phases, the per-stride laps of the per-packet verify, the span a
stride frame of the hop (PR 26) and the worker's stage clock.

Reference seam: DataNodeMetrics.java:553-560 counts write ops and packet
round trips, never where a block's time went; BlockReceiver.java:877-897 is
the receive loop the phases decompose.
"""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from hdrf_tpu.config import CdcConfig
from hdrf_tpu.proto import datatransfer as dt
from hdrf_tpu.server.reduction_worker import (_STRIDE, ReductionWorker,
                                              WorkerClient)
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.testing.wire import PiecedSocket, frame_packets
from hdrf_tpu.utils import profiler

BLOCK = 2 << 20
DN_PHASES = ("packet_verify", "worker_send", "seal_send", "seal_wait",
             "seal_write", "nn_rpc", "heartbeat_stats", "block_scan")


def _payload(n: int, seed: int = 7) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def read_layer(perfbench_file):
    """``read_layer(metric, stats)``: what the benchmark's per-layer metric
    reads from a ``stats`` delta — ``perfbench/layers/<metric>.json``
    through the reader it names."""
    def read(metric: str, stats: dict):
        with open(os.path.join(perfbench_file.root, "layers",
                               metric + ".json")) as f:
            layer = json.load(f)
        assert layer["metric"] == metric
        reader = perfbench_file(f"readers/{layer['reader']}.py")
        return reader.read({"window": {"stats": stats}}, layer["params"])

    return read


def _monotone(before: dict, after: dict, keys) -> bool:
    return all(after[k] >= before.get(k, 0.0) for k in keys)


@pytest.fixture(scope="module")
def served():
    """One DataNode beside a native reduction worker PROCESS (the served
    layout of chip_smoke.py and perfbench), two blocks written through it;
    heartbeat and scanner fast enough to tick inside the test."""
    from hdrf_tpu.utils import metrics

    profiler.reset()
    t0 = profiler.mark()
    reg = metrics.registry("block_receiver")
    with MiniCluster(n_datanodes=1, replication=1, block_size=BLOCK,
                     container_size=1 << 20, tpu_worker=True,
                     worker_backend="native",
                     dn_config_overrides={"scan_interval_s": 0.2}) as mc:
        dn = mc.datanodes[0]
        first = dn._worker.stats()
        recv = (reg.counter("recv_packets"), reg.counter("recv_runs"))
        with mc.client("stage-clock") as c:
            c.write("/a", _payload(BLOCK, 1), scheme="dedup_lz4")
            mid = dn._worker.stats()
            c.write("/b", _payload(BLOCK, 2), scheme="dedup_lz4")
            recv = (reg.counter("recv_packets") - recv[0],
                    reg.counter("recv_runs") - recv[1])
            dn.containers.drain_seals()
            deadline = time.time() + 10
            while time.time() < deadline:          # one tick of each loop
                names = {sp[0] for sp in profiler.window_spans(
                    t0, float("inf"))}
                if {"heartbeat_stats", "block_scan"} <= names:
                    break
                time.sleep(0.1)
            assert c.read("/a") == _payload(BLOCK, 1)
        last = dn._worker.stats()
        yield {"t0": t0, "t1": profiler.mark(), "first": first, "mid": mid,
               "last": last, "recv": recv,
               "timelines": profiler.timelines_snapshot()}


class TestDataNodePhases:
    @pytest.mark.parametrize("name", DN_PHASES)
    def test_a_served_block_leaves_the_phase_in_the_window(self, served,
                                                           name):
        spans = profiler.window_spans(served["t0"], served["t1"])
        assert any(sp[0] == name and sp[2] > sp[1] for sp in spans)
        prof = profiler.window_profile(served["t0"], served["t1"])
        if name in ("packet_verify", "worker_send"):
            # on the block's own path.  A background wait (seal_*) or a
            # millisecond tick owns only the seconds nothing ahead of it in
            # the order claims, which in a busy test process may be none
            assert prof["phases"].get(name, 0.0) > 0.0, sorted(prof["phases"])

    def test_the_existing_phases_keep_their_names(self, served):
        prof = profiler.window_profile(served["t0"], served["t1"])
        assert {"recv", "ack", "device_wait", "dedup_lookup",
                "container_io"} <= set(prof["phases"])
        # the block lands once, where it is read from: no join to time
        assert "buffer_assemble" not in prof["phases"]

    def test_a_block_is_attributed(self, served):
        tls = [t for t in served["timelines"] if t["nbytes"] == BLOCK]
        assert len(tls) == 2
        for tl in tls:
            assert tl["profile"]["attributed_frac"] >= 0.9, tl["profile"]
            names = {s[0] for s in tl["spans"]}
            assert {"recv", "packet_verify", "worker_send",
                    "device_wait"} <= names

    def test_per_packet_phases_land_as_a_span_a_stride(self, served):
        """32 packets of 64 KiB a block: one ``recv`` span a run (at most
        one a packet), and far fewer ``packet_verify`` spans (a lap a run)
        and ``worker_send`` spans (one a stride frame: this block's only
        frame is its last)."""
        for tl in [t for t in served["timelines"] if t["nbytes"] == BLOCK]:
            n = {name: sum(1 for s in tl["spans"] if s[0] == name)
                 for name in ("recv", "ack", "packet_verify", "worker_send")}
            assert 1 <= n["recv"] <= BLOCK // (64 << 10) + 1
            # one write of acks a run, the NameNode's notice, the last ack
            assert n["ack"] <= n["recv"] + 2
            assert 1 <= n["packet_verify"] <= 3
            assert 1 <= n["worker_send"] <= 3


class TestUnitsOfWork:
    """PR 35: the covering spans of a block, a container, a read and a tick
    on the served path, and the benchmark's reader of them."""

    def _spans(self, served, name):
        return [sp for sp in profiler.window_spans(served["t0"],
                                                   served["t1"])
                if sp[0] == name]

    def test_a_block_leaves_one_dn_block_as_long_as_its_timeline(self,
                                                                 served):
        blocks = self._spans(served, "dn_block")
        walls = sorted((sp[1], sp[2]) for sp in blocks)
        assert walls == sorted((t["t0"], t["t1"])
                               for t in served["timelines"])
        assert len(blocks) >= 2
        for sp in blocks:
            assert len(sp) == 5 and 0.0 < sp[4] <= sp[2] - sp[1] + 1e-4

    def test_the_seal_count_is_the_workers_compress_jobs(self, served):
        seals = self._spans(served, "seal")
        jobs = served["last"]["compress_jobs"] - \
            served["first"]["compress_jobs"]
        assert len(seals) == jobs >= 2
        assert len(self._spans(served, "seal_queue")) == jobs
        assert len(self._spans(served, "seal_index")) == jobs
        assert len({sp[3] for sp in seals}) == 1    # the one seal thread
        assert self._spans(served, "seal_drain")    # the fixture's own

    def test_a_read_leaves_one_dn_read(self, served):
        """The block read back: one ``dn_read`` for its ``serve_read``,
        none for the short-circuit fd grant the local client asked first
        (a read timeline of its own, in the ring of read timelines)."""
        (rd,) = self._spans(served, "dn_read")
        assert len(rd) == 5 and rd[2] > rd[1]
        serves = self._spans(served, "read_serve")
        assert len(serves) == 1
        assert rd[1] <= serves[0][1] and serves[0][2] <= rd[2]
        assert len(profiler.read_timelines_snapshot()) >= 2

    @pytest.mark.parametrize("name", ["heartbeat_stats", "block_scan"])
    def test_a_tick_carries_its_threads_cpu(self, served, name):
        ticks = self._spans(served, name)
        assert ticks and all(len(sp) == 5 and sp[4] >= 0.0 for sp in ticks)

    @pytest.mark.parametrize("metric,per", [
        ("seal.wall_ms_per_container", "seal"),
        ("seal.cpu_ms_per_container", "seal"),
        ("seal.queue_wait_ms_per_container", "seal_queue"),
        ("seal.index_ms_per_container", "seal_index"),
        ("dn.block_wall_ms", "dn_block"), ("dn.block_cpu_ms", "dn_block"),
        ("dn.receive_ms_per_block", "dn_block"),
        ("dn.commit_ms_per_block", "dn_block"),
        ("dn.heartbeat_ms_per_tick", "heartbeat_stats"),
        ("dn.heartbeat_cpu_ms_per_tick", "heartbeat_stats"),
        ("dn.read_wall_ms", "dn_read"), ("dn.read_cpu_ms", "dn_read"),
        ("seal.thread_busy_pct", "window"),
        ("seal.drain_tail_pct", "window"), ("dn.host_busy_pct", "window")])
    def test_the_benchmark_reads_it_from_the_served_window(
            self, served, perfbench_file, metric, per):
        with open(os.path.join(perfbench_file.root, "layers",
                               metric + ".json")) as f:
            layer = json.load(f)
        prof = profiler.window_profile(served["t0"], served["t1"])
        src = {"phases": prof, "window_s": prof["wall_s"]}
        value = perfbench_file(f"readers/{layer['reader']}.py").read(
            src, layer["params"])
        assert value is not None and value >= 0.0
        if per == "window":
            assert value <= 100.0 + 1e-6
        else:
            assert layer["params"]["per"] == per
            # thread-wall a unit, or the CPU under it
            wall = sum(prof["inclusive"][n]["wall_s"]
                       for n in layer["params"]["spans"])
            assert value <= 1000.0 * wall / prof["inclusive"][per]["count"] \
                + 1e-6


class TestStrideSpans:
    def test_a_two_stride_block_sends_two_or_three_frames(self):
        """``worker_send`` is one span a frame: two full strides, and the
        last frame that says so (empty when the block ends on a stride)."""
        block = 2 * _STRIDE
        w = ReductionWorker(backend="native").start()
        try:
            with MiniCluster(n_datanodes=1, replication=1, block_size=block,
                             reduction_overrides={
                                 "worker_addr": list(w.addr)}) as mc:
                a = w.stats()
                with mc.client("two-strides") as c:
                    c.write("/two", _payload(block, 3), scheme="dedup_lz4")
                b = w.stats()
        finally:
            w.stop()
        tl = [t for t in profiler.timelines_snapshot()
              if t["nbytes"] == block][-1]
        sends = [s for s in tl["spans"] if s[0] == "worker_send"]
        assert 2 <= len(sends) <= 3 and all(s[2] > s[1] for s in sends)
        assert (b["hop_frames"] - a["hop_frames"],
                b["hop_packets"] - a["hop_packets"]) == (2, 128)

    def test_the_worker_verifies_once_a_stride(self):
        """``_strides``: one ``ingest_wait`` span a frame read, one
        ``packet_verify`` span a frame that carried bytes."""
        from hdrf_tpu import native

        parts = [_payload(4096, i) for i in range(3)]  # fits the socketpair
        crcs = [native.crc32c(p) for p in parts]
        profiler.reset()
        t0 = profiler.mark()
        a, b = socket.socketpair()
        try:
            dt.write_stride(a, parts, crcs)
            dt.write_stride(a, parts[:2] + [parts[2][:100]],
                            crcs[:2] + [native.crc32c(parts[2][:100])])
            dt.write_stride(a, [], [], last=True)
            w = ReductionWorker(backend="native")       # never started
            got = [bytes(s) for s in w._strides(b)]
        finally:
            a.close()
            b.close()
            w._server.server_close()
        assert got == [b"".join(parts), parts[0] + parts[1] + parts[2][:100]]
        assert (w._stats["hop_frames"], w._stats["hop_packets"]) == (2, 6)
        names = [s[0] for s in profiler.window_spans(t0, float("inf"))]
        assert names.count("ingest_wait") == 3
        assert names.count("packet_verify") == 2


class TestHopCounter:
    """``hop_frames`` / ``hop_packets`` and the per-layer metric that reads
    them (``perfbench/layers/hop.packets_per_frame.json``: data only)."""

    METRIC = "hop.packets_per_frame"

    def test_monotone_and_a_frame_a_block_under_one_stride(self, served):
        first, mid, last = served["first"], served["mid"], served["last"]
        packets = BLOCK // (64 << 10)
        assert (mid["hop_frames"] - first["hop_frames"],
                mid["hop_packets"] - first["hop_packets"]) == (1, packets)
        assert (last["hop_frames"] - mid["hop_frames"],
                last["hop_packets"] - mid["hop_packets"]) == (1, packets)

    def test_the_metric_reads_packets_per_frame(self, served, read_layer):
        delta = {k: served["last"][k] - served["first"].get(k, 0)
                 for k in served["last"]}
        assert read_layer(self.METRIC, delta) == \
            pytest.approx(BLOCK // (64 << 10))

    def test_the_metric_reads_nothing_on_a_program_without_the_counter(
            self, read_layer):
        # the parent's stats, and a window in which no frame arrived
        assert read_layer(self.METRIC, {"blocks_reduced": 3,
                                        "ingest_wait_s": 0.6}) is None
        assert read_layer(self.METRIC, {"hop_frames": 0,
                                        "hop_packets": 0}) is None

    def test_the_manifest_lists_the_metric(self):
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
        (entry,) = [m for m in bench["per_layer"]
                    if m["name"] == "hop.packets_per_frame"]
        assert entry == {
            "name": "hop.packets_per_frame", "unit": "packets",
            "better": "higher", "source": "program_counter",
            "layer": "DN to worker hop", "moves": "write_mb_s",
            "workloads": ["teragen-1dn.ingest", "teragen-1dn.ingest-1w",
                          "versions-dedup.ingest", "small-files.create",
                          "teragen-1dn.pread-ingest", "teragen-3dn.ingest",
                          "versions-3dn.ingest"]}


class TestSealWireMetrics:
    """``seal_ingest_s`` and ``seal_frames`` / ``seal_segments`` of a worker
    snapshot, and the two per-layer metrics that read them (data files for
    the ``stage_ratio`` reader, PR 30)."""

    ENTRIES = {
        "seal.ingest_ms_per_job": {
            "unit": "ms", "better": "lower", "layer": "worker"},
        "seal.segments_per_frame": {
            "unit": "segments", "better": "higher", "layer": "DN commit"},
    }

    def test_the_served_seals_left_the_stage_and_the_counters(self, served):
        """1 MiB containers: every seal is one frame of one segment, and
        its read + verify has seconds of its own."""
        first, last = served["first"], served["last"]
        jobs = last["compress_jobs"] - first["compress_jobs"]
        assert jobs >= 2
        assert last["seal_frames"] - first["seal_frames"] == jobs
        assert last["seal_segments"] - first["seal_segments"] == jobs
        assert last["seal_ingest_s"] > first.get("seal_ingest_s", 0.0)

    def test_the_metrics_read_the_snapshot(self, served, read_layer):
        delta = {k: served["last"][k] - served["first"].get(k, 0)
                 for k in served["last"]}
        assert read_layer("seal.ingest_ms_per_job", delta) == pytest.approx(
            1000.0 * delta["seal_ingest_s"] / delta["compress_jobs"])
        assert read_layer("seal.segments_per_frame", delta) == \
            pytest.approx(delta["seal_segments"] / delta["seal_frames"])
        assert read_layer("seal.segments_per_frame",
                          {"seal_frames": 8, "seal_segments": 32}) == 4.0

    @pytest.mark.parametrize("metric", sorted(ENTRIES))
    def test_a_snapshot_without_the_keys_reads_absent_not_zero(
            self, read_layer, metric):
        # the parent's stats (its seal keeps the packet wire), and a window
        # in which nothing was sealed
        assert read_layer(metric, {"compress_jobs": 5, "emit_s": 0.1,
                                   "packet_verify_s": 0.2}) is None
        assert read_layer(metric, {"compress_jobs": 0, "seal_frames": 0,
                                   "seal_segments": 0}) is None

    @pytest.mark.parametrize("metric", sorted(ENTRIES))
    def test_the_manifest_lists_the_metric(self, metric):
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
        (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert entry == {
            "name": metric, "source": "program_counter",
            "moves": "write_mb_s",
            "workloads": ["teragen-1dn.ingest", "teragen-1dn.ingest-1w",
                          "small-files.create", "teragen-1dn.pread-ingest",
                          "teragen-3dn.ingest"],
            **self.ENTRIES[metric]}


class TestRecvCounter:
    """``recv_packets`` / ``recv_runs`` of the ``block_receiver`` registry:
    packets a run, how often the run reader engaged (PR 28).  No per-layer
    metric reads them yet (``perfbench`` takes no DataNode counter)."""

    @staticmethod
    def _block(nbytes: int, packet: int = 64 << 10):
        """(wire, packet lengths on it) of a client's block."""
        data = memoryview(_payload(nbytes, 28))
        lens = [min(packet, nbytes - o) for o in range(0, nbytes, packet)]
        lens.append(0)
        offs = [0] + list(np.cumsum(lens[:-1]))
        return frame_packets(
            (seq, data[o:o + ln], dt.FLAG_LAST * (not ln))
            for seq, (o, ln) in enumerate(zip(offs, lens))), lens

    def _count(self, wire, pieces, capacity):
        """Drain ``wire`` through the reader and ``_admit_runs``; returns
        the counters' (packets, runs) and the acks that would be written."""
        import types

        from hdrf_tpu.server.block_receiver import BlockReceiver
        from hdrf_tpu.utils import metrics

        reg = metrics.registry("block_receiver")
        before = (reg.counter("recv_packets"), reg.counter("recv_runs"))
        out = dt.BlockBuffer(capacity)
        rcv = BlockReceiver(types.SimpleNamespace(dn_id="dn-count"))
        acks = b"".join(a for _, a in rcv._admit_runs(
            dt.iter_packet_runs(PiecedSocket(wire, pieces), out), 7, [0]))
        return (reg.counter("recv_packets") - before[0],
                reg.counter("recv_runs") - before[1]), acks, out

    def test_a_128_mib_block_counts_2049_packets_once_each(self):
        nbytes = 128 << 20
        wire, lens = self._block(nbytes)
        rng = np.random.default_rng(5)
        pieces = (int(p) for p in rng.integers(1, 2 << 20, 1 << 16))
        (packets, runs), acks, out = self._count(wire, pieces, nbytes)
        assert packets == len(lens) == 2049
        assert 1 <= runs <= packets
        assert out.size == nbytes and out.arr.size == nbytes   # never grown
        # one ack a packet but the last, same bytes and order as send_ack's
        assert acks == b"".join(dt.ACK.pack(q, dt.ACK_SUCCESS)
                                for q in range(2048))

    def test_a_packet_at_a_time_reads_exactly_one_packet_a_run(self):
        wire, lens = self._block(2 << 20)
        pieces = [dt.PKT_HDR.size + ln for ln in lens]
        (packets, runs), _, _ = self._count(wire, pieces, 2 << 20)
        assert packets == runs == len(lens) == 33
        assert packets / runs == 1.0

    def test_a_served_block_counts_its_packets(self, served):
        """Through a live DataNode (the fixture's two 2 MiB blocks and
        nothing else in this module's window before it)."""
        assert served["recv"][0] == 2 * (BLOCK // (64 << 10) + 1)
        assert 2 <= served["recv"][1] <= served["recv"][0]


class TestWorkerStageClock:
    NATIVE = ("ingest_wait_s", "packet_verify_s", "reduce_compute_s",
              "block_s")

    def test_stats_carry_the_stages_the_backend_runs(self, served):
        st = served["last"]
        for key in self.NATIVE + ("emit_s", "cpu_s", "wall_s", "ingest_s",
                                  "reduce_s", "compress_s"):
            assert key in st, sorted(st)
        # a stage this backend does not run is left out, not zero; and no
        # stage that no metric and none of the three sums reads
        for key in ("stage_h2d_s", "prep_wait_s", "select_s", "sha_wait_s",
                    "scan_wait_s", "idle_s", "reply_s", "seal_recv_s",
                    "seal_reply_s"):
            assert key not in st
        assert all(isinstance(v, (int, float)) for v in st.values())

    def test_every_stage_is_monotone(self, served):
        keys = [k for k in served["last"] if k.endswith("_s")]
        assert _monotone(served["first"], served["mid"],
                         [k for k in keys if k in served["mid"]])
        assert _monotone(served["mid"], served["last"], keys)
        assert served["last"]["blocks_reduced"] == \
            served["first"]["blocks_reduced"] + 2

    def test_the_stages_close_on_the_legacy_sums(self, served):
        st = served["last"]
        legs = st["ingest_s"] + st["reduce_s"]
        # every packet_verify of this process: the reduces' and the seals'
        stages = (st["ingest_wait_s"] + st["packet_verify_s"]
                  + st["reduce_compute_s"])
        assert legs > 0 and abs(stages - legs) <= 0.1 * legs
        # what no stage explains of a reduce op (the covering span's self
        # seconds, the reply among them) is small beside the op — or, for
        # two blocks as small as these, small
        assert 0.0 < st["block_s"] <= max(0.1 * legs, 0.05)
        assert abs(st["compress_s"] - st["emit_s"]) <= 0.1 * st["compress_s"]

    def test_a_block_counted_has_its_seconds(self):
        """The sums go in with ``blocks_reduced``, before the reply: the
        caller's next ``stats`` cannot see a block without its seconds."""
        w = ReductionWorker(backend="native").start()
        try:
            c = WorkerClient(w.addr)
            a = c.stats()
            c.reduce(_payload(200_000), CdcConfig())
            b = c.stats()
            c.close()
        finally:
            w.stop()
        assert b["blocks_reduced"] == a["blocks_reduced"] + 1
        assert b["ingest_s"] > a["ingest_s"] and b["reduce_s"] > a["reduce_s"]

    def test_cpu_and_wall_make_a_busy_share(self, served):
        a, b = served["first"], served["last"]
        busy = (b["cpu_s"] - a["cpu_s"]) / (b["wall_s"] - a["wall_s"])
        assert 0.0 < busy < os.cpu_count() + 1


class TestDeviceStages:
    def test_the_device_path_reports_its_own_stages(self):
        """backend="tpu" in-process on the CPU mesh: the stages of the
        streamed reduce, and their closure on ingest_s + reduce_s."""
        w = ReductionWorker(backend="tpu").start()
        try:
            c = WorkerClient(w.addr)
            a = c.stats()
            c.reduce(_payload(300_000), CdcConfig())
            b = c.stats()
            d = {k: b[k] - a.get(k, 0.0) for k in b if k.endswith("_s")}
            for key in ("ingest_wait_s", "packet_verify_s", "stage_h2d_s",
                        "prep_wait_s", "select_s", "sha_wait_s"):
                assert d[key] > 0.0, (key, d)
            legs = d["ingest_s"] + d["reduce_s"]
            stages = sum(d[k] for k in (
                "ingest_wait_s", "packet_verify_s", "stage_h2d_s",
                "prep_wait_s", "select_s", "sha_wait_s"))
            assert abs(stages - legs) <= 0.1 * legs
            # one part under a stride: one frame of one segment
            assert (b["hop_frames"] - a["hop_frames"],
                    b["hop_packets"] - a["hop_packets"]) == (1, 1)
            c.close()
        finally:
            w.stop()

    def test_a_zero_dense_block_retries_once_and_the_rung_sticks(
            self, read_layer):
        """``prep_retries`` and the gauge ``prep_cap_words`` in ``stats``
        (PR 27), and ``worker.prep_retries_per_block`` that reads them: a
        tar-like block overflows ``_prep``'s first rung once; the next is
        dispatched at the rung that held it."""
        rng = np.random.default_rng(27)
        blocks = []
        for _ in range(2):
            a = rng.integers(0, 256, 600_000, dtype=np.uint8)
            a[50_000:450_000] = 0
            blocks.append(a.tobytes())
        from hdrf_tpu.ops.resident import block_rung

        # the capacity follows the rung the block lands at (PR 31)
        first_shot = (block_rung(600_000) >> 12) + 1024
        w = ReductionWorker(backend="tpu").start()
        try:
            c = WorkerClient(w.addr)
            c.reduce(_payload(600_000), CdcConfig())
            s0 = c.stats()
            c.reduce(blocks[0], CdcConfig())
            s1 = c.stats()
            c.reduce(blocks[1], CdcConfig())
            s2 = c.stats()
            c.close()
        finally:
            w.stop()
        assert s0["prep_cap_words"] == first_shot
        assert s1["prep_retries"] == s0["prep_retries"] + 1
        assert s2["prep_retries"] == s1["prep_retries"]
        assert s2["prep_cap_words"] == s1["prep_cap_words"] == 16 * first_shot
        delta = {k: s2[k] - s0.get(k, 0) for k in s2}
        assert read_layer("worker.prep_retries_per_block", delta) == 0.5
        # a program without the counter (the parent's), an empty window
        assert read_layer("worker.prep_retries_per_block",
                           {"blocks_reduced": 2}) is None
        assert read_layer("worker.prep_retries_per_block",
                           {"prep_retries": 0, "blocks_reduced": 0}) is None

    def test_stats_count_the_candidate_words_cut_selection_walked(self):
        """``select_words`` in ``stats`` beside ``prep_retries``: a
        reduce op moves it by its block's non-zero candidate words, which a
        zero run makes dense."""
        from hdrf_tpu import native
        from hdrf_tpu.ops.dispatch import gear_mask

        a = np.random.default_rng(43).integers(0, 256, 600_000,
                                               dtype=np.uint8)
        a[50_000:450_000] = 0
        pos = native.gear_candidates(a, gear_mask(CdcConfig()))
        words = int(np.unique((pos - 1) >> 5).size)
        w = ReductionWorker(backend="tpu").start()
        try:
            c = WorkerClient(w.addr)
            c.reduce(_payload(600_000), CdcConfig())
            s0 = c.stats()
            c.reduce(a.tobytes(), CdcConfig())
            s1 = c.stats()
            c.close()
        finally:
            w.stop()
        assert words > 12_000      # 400 000 zero bytes: 12 500 full words
        assert s1["select_words"] - s0["select_words"] == words

    def test_the_ladder_metrics_read_the_worker_counters(self, read_layer):
        """``worker.pad_pct`` and ``worker.prep_shapes_per_block`` (data
        files for ``stage_ratio``, PR 31) from a device worker's ``stats``:
        a block at a rung pads nothing; two lengths under one rung are one
        shape."""
        from hdrf_tpu.ops.resident import block_rung

        sizes = (1 << 20, 1_400_000, 1_300_000)   # a rung; two at 1.5 MiB
        w = ReductionWorker(backend="tpu").start()
        try:
            c = WorkerClient(w.addr)
            snaps = [c.stats()]
            for n in sizes:
                c.reduce(_payload(n), CdcConfig())
                snaps.append(c.stats())
            c.close()
        finally:
            w.stop()
        deltas = [{k: b[k] - a.get(k, 0) for k in b}
                  for a, b in zip(snaps, snaps[1:])]
        assert read_layer("worker.pad_pct", deltas[0]) == 0.0
        assert read_layer("worker.pad_pct", deltas[1]) == pytest.approx(
            100.0 * (block_rung(1_400_000) - 1_400_000) / 1_400_000)
        assert [read_layer("worker.prep_shapes_per_block", d)
                for d in deltas] == [1.0, 1.0, 0.0]
        # a program without the counters (the parent's), an empty window
        for metric in ("worker.pad_pct", "worker.prep_shapes_per_block"):
            assert read_layer(metric, {"blocks_reduced": 3,
                                       "bytes_reduced": 9}) is None
            assert read_layer(metric, {"bytes_padded": 0, "prep_shapes": 0,
                                       "blocks_reduced": 0,
                                       "bytes_reduced": 0}) is None

    @pytest.mark.parametrize("metric, unit", [
        ("worker.pad_pct", "%"), ("worker.prep_shapes_per_block", "shapes")])
    def test_the_manifest_lists_the_ladder_metrics(self, metric, unit):
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
        (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert entry == {
            "name": metric, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "device programs",
            "moves": "write_mb_s",
            "workloads": [w["name"] for w in bench["workloads"]]}

    def test_a_device_compress_records_scan_wait_inside_emit(self):
        from hdrf_tpu.ops.lz4_tpu import TpuLz4

        before = profiler.thread_cumulative()
        text = (b"the quick brown fox jumps over the lazy dog " * 8000)
        with profiler.phase("emit"):
            out = TpuLz4(min_device=1 << 16).compress(text)
        from hdrf_tpu import native

        assert bytes(native.lz4_decompress(out, len(text))) == text
        after = profiler.thread_cumulative()
        scan = after.get("scan_wait", 0.0) - before.get("scan_wait", 0.0)
        emit = after.get("emit", 0.0) - before.get("emit", 0.0)
        assert scan > 0.0 and emit > 0.0   # emit keeps only its own seconds


class TestLaps:
    def test_laps_gather_into_one_span_a_stride(self, monkeypatch):
        profiler.reset()
        clk = [10.0]
        monkeypatch.setattr(profiler, "_now", lambda: clk[0])
        before = profiler.thread_cumulative()
        with profiler.phase("ingest_wait"):
            for _ in range(profiler._LAP_EVERY):
                t0 = profiler.mark()
                clk[0] += 0.25                  # the lap
                profiler.lap("packet_verify", t0)
                clk[0] += 0.75                  # the wait beside it
        spans = profiler.window_spans(0.0, float("inf"))
        n = profiler._LAP_EVERY
        assert [s[:3] for s in spans] == [
            ("packet_verify", 10.0 + n - 0.75 - 0.25 * n, 10.0 + n - 0.75),
            ("ingest_wait", 10.0, 10.0 + n)]
        after = profiler.thread_cumulative()
        d = {k: after[k] - before.get(k, 0.0) for k in after}
        assert d["packet_verify"] == pytest.approx(0.25 * n)
        assert d["ingest_wait"] == pytest.approx(0.75 * n)   # self seconds
        prof = profiler.profile_spans(spans, 10.0, 10.0 + n)
        assert prof["phases"] == pytest.approx(
            {"packet_verify": 0.25 * n, "ingest_wait": 0.75 * n})

    def test_two_names_are_laid_end_to_end_and_flushed_together(
            self, monkeypatch):
        """The DataNode's receive thread laps a verify and a forward per
        packet: one flush lands both, side by side, so the exclusive
        partition books each what it measured."""
        profiler.reset()
        clk = [0.0]
        monkeypatch.setattr(profiler, "_now", lambda: clk[0])
        for _ in range(10):
            with profiler.phase("recv"):
                clk[0] += 1.0
                t0 = profiler.mark()
                clk[0] += 0.5
                profiler.lap("packet_verify", t0)
            t0 = profiler.mark()
            clk[0] += 2.0
            profiler.lap("worker_send", t0)
        profiler.flush_laps()
        profiler.flush_laps()               # nothing gathered: no span
        spans = profiler.window_spans(-1.0, float("inf"))
        laid = {s[0]: (s[1], s[2]) for s in spans if s[0] != "recv"}
        assert laid == {"packet_verify": (30.0, 35.0),
                        "worker_send": (10.0, 30.0)}
        assert sum(1 for s in spans if s[0] == "recv") == 10
        prof = profiler.profile_spans(spans, 0.0, 35.0)
        assert prof["phases"]["packet_verify"] == pytest.approx(5.0)
        assert prof["phases"]["worker_send"] == pytest.approx(20.0)
        # recv keeps the three spans ahead of the laid ones: [0, 1.5],
        # [3.5, 5], [7, 8.5]; the gaps between them are in no phase
        assert prof["phases"]["recv"] == pytest.approx(4.5)
        assert prof["classes"]["idle"] == pytest.approx(5.5)

    def test_the_last_packet_flushes_the_reader(self):
        """``read_packet_ex`` laps every packet and lands them at the last
        one, so an op's verify seconds are on the clock when it ends."""
        import socket

        from hdrf_tpu.proto import datatransfer as dt

        profiler.reset()
        t0 = profiler.mark()
        a, b = socket.socketpair()
        try:
            for seq in range(5):
                dt.write_packet(a, seq, _payload(4096, seq))
            dt.write_packet(a, 5, b"", last=True)
            got = b"".join(d for _, d, _ in dt.iter_packets(b))
        finally:
            a.close()
            b.close()
        assert len(got) == 5 * 4096
        spans = [s for s in profiler.window_spans(t0, float("inf"))
                 if s[0] == "packet_verify"]
        assert len(spans) == 1 and spans[0][2] > spans[0][1]


class TestPartitionStillExact:
    SPANS3 = [("recv", 0.0, 4.0), ("wal_commit", 1.0, 2.0),
              ("device_wait", 3.0, 6.0)]

    @pytest.mark.parametrize("fields", [3, 4, 5, "mixed"])
    def test_classes_sum_to_the_wall(self, fields):
        def widen(i, sp):
            n = fields if fields != "mixed" else 3 + i % 3
            return sp + ((7,) if n >= 4 else ()) + ((0.25,) if n == 5
                                                    else ())

        spans = [widen(i, sp) for i, sp in enumerate(self.SPANS3)]
        prof = profiler.profile_spans(spans, 0.0, 8.0)
        assert sum(prof["classes"].values()) == pytest.approx(8.0, abs=1e-12)
        assert prof["classes"] == {"host_busy": 1.0, "device_busy": 3.0,
                                   "transport_wait": 2.0, "idle": 2.0}
        assert prof["phases"] == {"recv": 2.0, "wal_commit": 1.0,
                                  "device_wait": 3.0}
        assert "host_stall_s" not in prof       # step 2 of ISSUE 25: cut

    def test_a_clipped_span_counts_what_the_window_holds(self):
        prof = profiler.profile_spans([("checksum", 0.0, 4.0, 1)], 2.0, 4.0)
        assert prof["classes"]["host_busy"] == 2.0
        assert prof["phases"] == {"checksum": 2.0}

    def test_new_phases_have_the_classes_the_partition_needs(self):
        host = ("packet_verify", "seal_write", "nn_rpc", "heartbeat_stats",
                "block_scan")
        waits = ("worker_send", "seal_send", "seal_wait")
        assert all(profiler.phase_class(p) == profiler.HOST for p in host)
        assert all(profiler.phase_class(p) == profiler.TRANSPORT
                   for p in waits)
        order = profiler.PHASE_ORDER
        assert order.index("worker_send") < order.index("recv")
        assert order.index("ack") < order.index("seal_send")
        # a host phase takes its seconds from recv; a background wait
        # claims only what no foreground phase does
        prof = profiler.profile_spans(
            [("recv", 0.0, 4.0), ("packet_verify", 1.0, 2.0),
             ("worker_send", 3.0, 5.0), ("seal_wait", 0.0, 6.0)], 0.0, 8.0)
        assert prof["phases"] == {"recv": 2.0, "packet_verify": 1.0,
                                  "worker_send": 2.0, "seal_wait": 1.0}


class TestRecorder:
    def test_cumulative_self_seconds_close_on_the_covering_span(
            self, monkeypatch):
        clk = [50.0]
        monkeypatch.setattr(profiler, "_now", lambda: clk[0])
        before = profiler.thread_cumulative()
        with profiler.phase("block"):
            clk[0] += 1
            with profiler.phase("ingest_wait"):
                clk[0] += 2
                with profiler.phase("stage_h2d"):
                    clk[0] += 0.5
            clk[0] += 0.25                  # the reply: no stage of its own
        after = profiler.thread_cumulative()
        d = {k: after[k] - before.get(k, 0.0) for k in after}
        assert d["block"] == pytest.approx(1.25)
        assert d["ingest_wait"] == pytest.approx(2.0)
        assert d["stage_h2d"] == pytest.approx(0.5)
        assert sum(d.values()) == pytest.approx(3.75)

    def test_cumulative_sums_over_threads_that_have_ended(self):
        base = profiler.cumulative().get("emit", 0.0)

        def work():
            with profiler.phase("emit"):
                time.sleep(0.01)

        for _ in range(profiler._FOLD_AT + 8):
            th = threading.Thread(target=work)
            th.start()
            th.join()
        total = profiler.cumulative()["emit"] - base
        assert total >= 0.01 * (profiler._FOLD_AT + 8)
        assert len(profiler._threads) <= profiler._FOLD_AT + 8

    def test_spans_carry_no_lock_and_survive_concurrent_readers(self):
        profiler.reset()
        t0 = profiler.mark()
        stop = threading.Event()

        def write():
            while not stop.is_set():
                with profiler.phase("ack"):
                    pass

        ths = [threading.Thread(target=write) for _ in range(4)]
        for th in ths:
            th.start()
        try:
            for _ in range(20):
                profiler.window_profile(t0, profiler.mark())
        finally:
            stop.set()
            for th in ths:
                th.join()

    def test_a_trace_annotation_opens_only_under_a_live_session(self):
        import jax  # noqa: F401 — the test process holds JAX already

        with profiler.phase("select") as p:
            assert p._ann is None         # no profiler session: a flag test
        assert profiler._trace_me is jax.profiler.TraceAnnotation

    def test_under_a_session_the_span_is_on_the_trace(self, tmp_path):
        import glob

        import jax

        jax.profiler.start_trace(str(tmp_path))
        try:
            with profiler.phase("block") as cover:
                assert cover._ann is not None
                with profiler.phase("ingest_wait"):
                    time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
        pb = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                           "*.xplane.pb"))[0]
        names = set()
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    names.update(e.name for e in line.events)
        assert any(n.startswith("hdrf.ingest_wait") for n in names), \
            sorted(n for n in names if "hdrf" in n)
        assert any(n.startswith("hdrf.block") for n in names)
