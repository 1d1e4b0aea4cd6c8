"""Three DataNodes at replication 3 with reduced block mirroring: the
deployment of the benchmark's ``teragen-3dn.ingest`` cell at a small size —
blocks of 1 MiB in 256 KiB containers, TeraGen rows from the benchmark's
generator — held to the benchmark's plain reference on every replica, and
the mirror leg's own spans on the phase clock.

A block is received and reduced once, by its pipeline's first DataNode; its
reduced form (hash list, then the chunks the next DataNode lacks) is pushed
to the second, which relays it to the third.  Push side: ``mirror_read`` (the
needed chunks out of the pusher's index and store), ``mirror_stream`` (frames
and packets written), ``mirror_wait`` (the chain below answering), under one
covering ``mirror_push``.  Relay side: ``mirror_recv`` a frame read of the
delta stream (its lengths, each stride frame of the chunks' bytes, the
trailer), under one covering ``mirror_ingest`` a relayed block.

Everything here stops by counts (files written), never by seconds.
"""

import json
import os

import numpy as np
import pytest

from hdrf_tpu.proto import datatransfer as dt
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.utils import metrics, profiler

BLOCK = 1 << 20
CONTAINER = 256 << 10
FILES = 4
SEED = 2**31 + 38
CDC = {"mask_bits": 13, "min_chunk": 2048, "max_chunk": 65536}
PUSH = ("mirror_read", "mirror_stream", "mirror_wait")
STORE_READS = ("container_load", "container_decode", "chunk_copy")
NEW_METRICS = ("dn.mirror_pct", "mirror.push_ms_per_block",
               "mirror.push_cpu_ms_per_block", "mirror.read_ms_per_push",
               "mirror.wait_ms_per_push", "mirror.ingest_ms_per_block",
               "mirror.packets_per_block")
CELL = "teragen-3dn.ingest"


def _rows(perfbench_file, k: int) -> bytes:
    teragen = perfbench_file("generators/teragen.py")
    return teragen.Source({"file_bytes": BLOCK}, SEED, 0).file(k).tobytes()


def _counters() -> dict:
    return dict(metrics.registry("block_receiver").snapshot()["counters"])


def _delta(after: dict, before: dict, name: str) -> int:
    return after.get(name, 0) - before.get(name, 0)


@pytest.fixture(scope="module")
def written(perfbench_file):
    """Four one-block files written at r=3 through one native worker; the
    phase clock's spans of the writes, the counters moved, each replica read
    back and each DataNode's store and index as the writes left them."""
    ref = perfbench_file("reference/chunking.py")
    replicas = perfbench_file("replicas.py")
    with pytest.MonkeyPatch.context() as mp:
        # checks.py imports its neighbour ``reference.chunking``
        mp.syspath_prepend(perfbench_file.root)
        checks = perfbench_file("checks.py")
    files = [_rows(perfbench_file, k) for k in range(FILES)]
    before = _counters()
    with MiniCluster(n_datanodes=3, replication=3, block_size=BLOCK,
                     container_size=CONTAINER, tpu_worker=True,
                     worker_backend="native") as mc:
        t0 = profiler.mark()
        with mc.client("writer") as c:
            for k, data in enumerate(files):
                c.write(f"/tg/f{k}", data, scheme="dedup_lz4")
        for dn in mc.datanodes:
            dn.containers.drain_seals()
        t1 = profiler.mark()
        after = _counters()
        spans = profiler.window_spans(t0, t1)
        ranges = [(k, off, n) for k in range(FILES)
                  for off, n in ((0, BLOCK), (12_345, 300_001))]
        readback = {}
        with mc.client("readback") as c:
            _, locs = replicas.short_blocks(
                c, [f"/tg/f{k}" for k in range(FILES)], 3)
            for k, off, n in ranges:
                readback[(k, off, n)] = replicas.read_each(
                    c, locs[f"/tg/f{k}"], off, n)
        nodes = []
        for dn in mc.datanodes:
            dn.containers.flush_open()
            dn.containers.drain_seals()
            nodes.append({"dn_id": dn.dn_id, "index": dn.index,
                          "stats": dn.index.stats(),
                          "sealed": checks.decode_sealed(dn),
                          "orphans": sum(dn.index.orphan_bytes().values()),
                          "partial": dn.mirror.report()["partial_blocks"]})
        table, _ = ref.chunk_tables(files, CDC, BLOCK)
        found = [n["index"].lookup_chunks(list(table)) for n in nodes]
    yield {"files": files, "spans": spans, "t0": t0, "t1": t1,
           "counters": (before, after), "readback": readback,
           "locs": locs, "nodes": nodes, "table": table, "found": found}


# ------------------------------------------- (a) every replica, to the reference


class TestEveryReplica:
    def test_each_range_reads_back_from_each_of_three_locations(self,
                                                                written):
        for (k, off, n), got in written["readback"].items():
            assert sorted(got) == ["dn-0", "dn-1", "dn-2"], got
            for dn_id, data in got.items():
                assert data == written["files"][k][off:off + n], (k, dn_id)

    def test_every_block_has_three_finalized_locations(self, written):
        for loc in written["locs"].values():
            assert [len(b["locations"]) for b in loc["blocks"]] == [3]

    @pytest.mark.parametrize("i", range(3))
    def test_each_index_is_the_references_chunk_table(self, written, i):
        table, node = written["table"], written["nodes"][i]
        assert all(loc is not None and loc.length == table[d]
                   for d, loc in written["found"][i].items())
        assert node["stats"]["chunks"] == len(table)
        assert node["stats"]["unique_chunk_bytes"] == sum(table.values())
        assert node["stats"]["logical_bytes"] == FILES * BLOCK

    @pytest.mark.parametrize("i", range(3))
    def test_every_sealed_container_decodes_through_the_reference(
            self, written, i):
        node = written["nodes"][i]
        sealed = node["sealed"]
        assert sealed["failures"] == 0, sealed["errors"]
        assert sealed["sealed"] > 0 and sealed["lz4_coded"] > 0
        assert sealed["decoded_bytes"] - node["orphans"] == \
            node["stats"]["unique_chunk_bytes"]
        assert node["partial"] == 0

    def test_each_block_is_reduced_once_and_relayed_twice(self, written):
        before, after = written["counters"]
        assert _delta(after, before, "worker_reduces") == FILES
        assert _delta(after, before, "blocks_received_reduced") == FILES
        assert _delta(after, before, "blocks_ingested_reduced") == 2 * FILES
        assert _delta(after, before, "reduced_mirror_pushes") == 2 * FILES
        for name in ("worker_fallbacks", "degraded_writes",
                     "mirror_failures"):
            assert _delta(after, before, name) == 0, name


# ------------------------------------------------ (b) the leg on the phase clock


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


class TestMirrorSpans:
    @pytest.mark.parametrize("name", ["mirror_push", "mirror_ingest"])
    def test_one_covering_span_a_leg_end_with_its_cpu(self, written, name):
        got = _named(written["spans"], name)
        assert len(got) == 2 * FILES        # two hops a block
        assert all(len(sp) == 5 and 0.0 <= sp[4] for sp in got)

    def test_a_relay_leaves_a_span_a_frame_it_reads(self, written):
        """The lengths frame, ceil(delta / ``dt.STRIDE``) stride frames and
        the trailer a relayed block, never a span a chunk: rows never
        repeat, so each relay lacks every chunk of the block (1 MiB, one
        frame)."""
        spans = written["spans"]
        recv = _named(spans, "mirror_recv")
        ingests = _named(spans, "mirror_ingest")
        assert len(ingests) == 2 * FILES
        for _, a, b, tid, _ in ingests:
            assert sum(1 for sp in recv if sp[3] == tid and a <= sp[1]
                       and sp[2] <= b) == -(-BLOCK // dt.STRIDE) + 2
        assert len(recv) == 2 * FILES * 3 < len(written["table"])

    def test_the_push_records_none_of_the_commits_phases(self, written):
        spans = written["spans"]
        inside = set()
        for _, a, b, tid, _ in _named(spans, "mirror_push"):
            inside |= {sp[0] for sp in spans
                       if sp[3] == tid and a <= sp[1] and sp[2] <= b
                       and sp[0] != "mirror_push"}
        assert set(PUSH) <= inside
        assert inside <= set(PUSH) | set(STORE_READS)
        assert not inside & {"dedup_lookup", "container_io", "recv"}

    def test_every_push_reads_what_it_sends_out_of_its_own_store(
            self, written):
        """The head's push and the middle DataNode's (inside its
        ``mirror_ingest``) alike take the needed chunks out of their own
        index and store: the relay reads back containers its append has
        just rolled over."""
        spans = written["spans"]
        ingests = _named(spans, "mirror_ingest")

        def inside(sp, outer):
            return sp[3] == outer[3] and outer[1] <= sp[1] and sp[2] <= outer[2]

        pushes = _named(spans, "mirror_push")
        relayed = [p for p in pushes if any(inside(p, i) for i in ingests)]
        assert len(relayed) == FILES and len(pushes) == 2 * FILES
        for p in pushes:
            assert {sp[0] for sp in spans if inside(sp, p)} & set(STORE_READS)

    def test_the_relays_check_of_a_frame_is_on_the_mirror_legs_clock(
            self, written):
        """A frame's CRC check runs inside its ``mirror_recv`` span, as the
        packet's did: nothing on a relay's thread under ``mirror_ingest``
        is the client stream's ``packet_verify``."""
        spans = written["spans"]
        for _, a, b, tid, _ in _named(spans, "mirror_ingest"):
            assert not [sp for sp in _named(spans, "packet_verify")
                        if sp[3] == tid and a <= sp[1] and sp[2] <= b]

    @pytest.mark.parametrize("name", PUSH)
    def test_each_push_phase_is_a_few_spans_a_push(self, written, name):
        n = len(_named(written["spans"], name))
        assert 2 * FILES <= n <= 2 * 2 * FILES, n

    def test_the_window_partition_still_sums_to_the_window(self, written):
        prof = profiler.profile_spans(written["spans"], written["t0"],
                                      written["t1"])
        wall = prof["wall_s"]
        assert sum(prof["classes"].values()) == pytest.approx(wall)
        assert sum(prof["phases"].values()) == pytest.approx(
            wall - prof["classes"]["idle"])
        # the store's reads of a push nest in its mirror_read, which takes
        # their instants: none of them owns any of the window
        assert not set(STORE_READS) & set(prof["phases"])
        assert "mirror_recv" in prof["inclusive"]
        assert "mirror_push" not in prof["phases"]


def _random(n: int) -> bytes:
    return np.random.default_rng(SEED).integers(0, 256, n, np.uint8).tobytes()


def _block_of(c, path: str) -> dict:
    return c._nn.call("get_block_locations", path=path)["blocks"][0]


def _holders(mc, block_id: int) -> list:
    return [dn for dn in mc.datanodes
            if dn.replicas.get_meta(block_id) is not None]


class TestTheDeltaWire:
    """The leg end to end at two DataNodes, no worker: what a relay keeps
    when a frame is bad, when it needs nothing, and what a throttled push
    meters.  Blocks of 16 MiB, so a delta is several 4 MiB frames."""

    BIG = 16 << 20

    @pytest.mark.parametrize("k", [0, 2])
    def test_a_flipped_byte_in_frame_k_leaves_the_relay_nothing(
            self, k, monkeypatch):
        """The relay raises at frame ``k``'s check, before any append: no
        replica, no index entry, no container byte there; the push side
        attributes the relay; the head's replica serves the file."""
        data = _random(2 * dt.STRIDE + 300_000)     # three data frames
        real, frames = dt.write_stride, {}

        def flipping(sock, segs, crcs, last=False):
            j = frames[sock] = frames.get(sock, -1) + 1
            if j == k and segs:
                seg = bytearray(segs[0])
                seg[len(seg) // 2] ^= 0x40
                segs = [seg, *segs[1:]]
            real(sock, segs, crcs, last)

        with MiniCluster(n_datanodes=2, replication=2,
                         block_size=self.BIG) as mc:
            monkeypatch.setattr(dt, "write_stride", flipping)
            with mc.client("flip") as c:
                c.write("/flip/f", data, scheme="dedup_lz4")
                bid = _block_of(c, "/flip/f")["block_id"]
                (head,) = _holders(mc, bid)
                (relay,) = [dn for dn in mc.datanodes if dn is not head]
                assert relay.index.get_block(bid) is None
                assert relay.index.stats()["chunks"] == 0
                assert relay.containers.physical_bytes() == 0
                assert {p for dn in mc.datanodes
                        for p in dn._mirror_fail} == {relay.dn_id}
                assert c.read("/flip/f") == data

    def test_an_empty_delta_commits_with_nothing_appended(self):
        """A block whose chunks the relay holds already: the lengths frame
        is empty and the trailer follows, two frame reads; the relay
        commits the block and appends no chunk."""
        data = _random(3 << 20)
        with MiniCluster(n_datanodes=2, replication=2,
                         block_size=self.BIG) as mc:
            with mc.client("empty") as c:
                c.write("/empty/a", data, scheme="dedup_lz4")
                appended = metrics.registry("container_store").counter(
                    "chunks_appended")
                t0 = profiler.mark()
                c.write("/empty/b", data, scheme="dedup_lz4")
                spans = profiler.window_spans(t0, profiler.mark())
                bid = _block_of(c, "/empty/b")["block_id"]
                assert metrics.registry("container_store").counter(
                    "chunks_appended") == appended
                assert len(_holders(mc, bid)) == 2
                assert all(dn.index.get_block(bid) is not None
                           for dn in mc.datanodes)
                assert len(_named(spans, "mirror_ingest")) == 1
                assert len(_named(spans, "mirror_recv")) == 2
                assert c.read("/empty/b") == data

    def test_a_throttled_push_meters_the_delta_once_a_frame(
            self, monkeypatch):
        """Re-replication (the balancer's throttle): ``throttle(n)`` once a
        stride frame, the ``n`` summing to the delta the relay lacked."""
        data = _random(2 * dt.STRIDE + 12_345)
        with MiniCluster(n_datanodes=2, replication=1,
                         block_size=self.BIG) as mc:
            with mc.client("thr") as c:
                c.write("/thr/f", data, scheme="dedup_lz4")
                bid = _block_of(c, "/thr/f")["block_id"]
                (holder,) = _holders(mc, bid)
                metered: list[int] = []
                real = holder.balance_throttler.throttle
                monkeypatch.setattr(
                    holder.balance_throttler, "throttle",
                    lambda n: (metered.append(n), real(n))[1])
                c.set_replication("/thr/f", 2)
                mc.wait_for_replication("/thr/f", 2)
                hashes = holder.index.get_block(bid).hashes
                delta = sum(loc.length for loc in holder.index.lookup_chunks(
                    list(dict.fromkeys(hashes))).values())
                assert metered == [min(dt.STRIDE, delta - o)
                                   for o in range(0, delta, dt.STRIDE)]
                (relay,) = [dn for dn in mc.datanodes if dn is not holder]
                assert relay.index.stats()["unique_chunk_bytes"] == delta

    def test_a_block_without_a_container_codec_crosses_in_frames(self):
        """The whole-block branch (``lz4``: the stored bytes as they are,
        their length in the op) lands the same bytes on the relay."""
        data = _random(dt.STRIDE) + bytes(dt.STRIDE)   # 8 MiB, half zeros
        with MiniCluster(n_datanodes=2, replication=2,
                         block_size=self.BIG) as mc:
            with mc.client("lz4") as c:
                t0 = profiler.mark()
                c.write("/lz4/f", data, scheme="lz4")
                spans = profiler.window_spans(t0, profiler.mark())
                bid = _block_of(c, "/lz4/f")["block_id"]
                a, b = (dn.replicas.read_data(bid)
                        for dn in _holders(mc, bid))
                assert a == b and 0 < len(a) < len(data)
                assert len(_named(spans, "mirror_recv")) == \
                    -(-len(a) // dt.STRIDE) + 1
                assert c.read("/lz4/f") == data

    @pytest.mark.parametrize("stored_len", [(1 << 20) + (1 << 14) + 1024,
                                            (1 << 20) + (1 << 14) + 1025,
                                            1 << 40, -1])
    def test_a_stored_length_past_any_codecs_bound_is_refused(
            self, stored_len):
        """The whole-block branch allocates the length the op states: one
        past a codec's worst case over the block (1 MiB + 1/64 + 1 KiB) is
        refused before a frame goes back, and the relay keeps nothing; the
        bound itself is answered with the empty need frame."""
        import socket

        from hdrf_tpu.proto.rpc import recv_frame

        with MiniCluster(n_datanodes=1, replication=1,
                         block_size=self.BIG) as mc:
            (dn,) = mc.datanodes
            with socket.create_connection(tuple(dn.addr), timeout=10) as s:
                dt.send_op(s, "write_reduced", block_id=77, gen_stamp=1,
                           scheme="lz4", logical_len=1 << 20,
                           checksums=[], checksum_chunk=dn.checksum_chunk,
                           token=dn.tokens.mint(77, "w"), hashes=None,
                           stored_len=stored_len, targets=[])
                if stored_len == (1 << 20) + (1 << 14) + 1024:
                    assert recv_frame(s) == {"need": []}
                else:
                    with pytest.raises((ConnectionError, OSError)):
                        recv_frame(s)
            assert dn.replicas.get_meta(77) is None


def test_one_datanode_records_no_mirror_phase(perfbench_file):
    with MiniCluster(n_datanodes=1, replication=1, block_size=BLOCK,
                     container_size=CONTAINER, tpu_worker=True,
                     worker_backend="native") as mc:
        t0 = profiler.mark()
        with mc.client("writer") as c:
            c.write("/one/f0", _rows(perfbench_file, 0), scheme="dedup_lz4")
        mc.datanodes[0].containers.drain_seals()
        names = {sp[0] for sp in profiler.window_spans(t0, profiler.mark())}
    assert "dn_block" in names and "recv" in names
    assert not {n for n in names if n.startswith("mirror_")}


class TestPhaseTables:
    @pytest.mark.parametrize("name,cls", [
        ("mirror_read", profiler.HOST), ("mirror_stream", profiler.TRANSPORT),
        ("mirror_wait", profiler.TRANSPORT),
        ("mirror_recv", profiler.TRANSPORT), ("mirror_push", profiler.COVER),
        ("mirror_ingest", profiler.COVER)])
    def test_every_name_has_a_class_and_a_rank(self, name, cls):
        assert profiler.phase_class(name) == cls
        assert (name in profiler.PHASE_ORDER) == (cls != profiler.COVER)

    @pytest.mark.parametrize("inner", STORE_READS)
    def test_mirror_read_takes_the_instants_of_the_reads_inside_it(self,
                                                                  inner):
        prof = profiler.profile_spans(
            [("mirror_read", 0.0, 1.0), (inner, 0.25, 0.75)], 0.0, 1.0)
        assert prof["phases"] == {"mirror_read": 1.0}
        # the commit on another thread still outranks it
        prof = profiler.profile_spans(
            [("mirror_read", 0.0, 1.0, 1), (inner, 0.25, 0.75, 1),
             ("container_io", 0.5, 1.0, 2)], 0.0, 1.0)
        assert prof["phases"] == pytest.approx(
            {"mirror_read": 0.5, "container_io": 0.5})

    def test_the_relays_packets_are_not_the_client_streams(self):
        order = profiler.PHASE_ORDER
        assert order.index("recv") < order.index("mirror_recv") \
            < order.index("mirror_stream") < order.index("mirror_wait") \
            < order.index("ack")
        prof = profiler.profile_spans(
            [("mirror_recv", 0.0, 1.0), ("mirror_wait", 0.5, 2.0),
             ("recv", 1.5, 3.0)], 0.0, 3.0)
        assert prof["phases"] == pytest.approx(
            {"mirror_recv": 1.0, "mirror_wait": 0.5, "recv": 1.5})
        assert prof["classes"]["transport_wait"] == pytest.approx(3.0)


# ------------------------------------------- (c) the cell and its configuration


def _bench(perfbench_file) -> dict:
    with open(os.path.join(os.path.dirname(perfbench_file.root),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def _config(perfbench_file, name: str) -> dict:
    with open(os.path.join(perfbench_file.root, "configs",
                           name + ".json")) as f:
        return json.load(f)


class TestTheCellsConfiguration:
    """``teragen-3dn`` is ``teragen-1dn``'s deployment at HDFS's default
    replication: one CDC geometry, one data source, one cluster but for
    the DataNodes, their workers, the replication and the chips."""

    @pytest.mark.parametrize("group", ["cdc", "data"])
    def test_it_keeps_teragen_1dns_group(self, perfbench_file, group):
        assert _config(perfbench_file, "teragen-3dn")[group] == \
            _config(perfbench_file, "teragen-1dn")[group]

    def test_its_cluster_differs_in_four_keys(self, perfbench_file):
        mine = _config(perfbench_file, "teragen-3dn")["cluster"]
        base = _config(perfbench_file, "teragen-1dn")["cluster"]
        assert set(mine) == set(base)
        assert {k: mine[k] for k in mine if mine[k] != base[k]} == \
            {"datanodes": 3, "workers": 3, "replication": 3, "chips": 4}

    def test_the_cell_runs_it_on_four_chips(self, perfbench_file):
        bench = _bench(perfbench_file)
        (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            ("teragen-3dn", "ingest", 4)
        (cfg,) = [c for c in bench["configs"] if c["name"] == "teragen-3dn"]
        mine = _config(perfbench_file, "teragen-3dn")
        assert cfg["file"] == "perfbench/configs/teragen-3dn.json"
        assert (cfg["source"], sorted(cfg["reduced"])) == \
            (mine["source"], sorted(mine["reduced"]))
        assert len(cfg["source"]) <= 200 and len(cell["why"]) <= 200

    def test_every_write_metric_lists_it(self, perfbench_file):
        bench = _bench(perfbench_file)
        for m in bench["end_to_end"]:
            if m["name"] in ("write_mb_s", "stored_pct"):
                assert CELL in m["workloads"]
        for m in bench["per_layer"]:
            if m["name"] in NEW_METRICS:
                assert m["workloads"] == [CELL]
                assert (m["layer"], m["moves"], m["source"]) == \
                    ("DN mirror leg", "write_mb_s", "program_span")
            else:
                assert (CELL in m["workloads"]) == \
                    ("teragen-1dn.ingest" in m["workloads"]), m["name"]


class TestTheMirrorLegsReaders:
    """Each new layer file, through the reader it names, on a synthetic
    phase clock of three DataNodes (exclusive seconds averaged, the
    inclusive table summed)."""

    SRC = {"window_s": 10.0, "phases": {
        "datanodes": 3,
        "phases": {"mirror_read": 0.5, "mirror_stream": 1.0,
                   "mirror_wait": 1.5, "mirror_recv": 2.0, "recv": 3.0},
        "classes": {"host_busy": 4.0, "device_busy": 0.0,
                    "transport_wait": 4.0, "idle": 2.0},
        "inclusive": {
            "mirror_push": {"count": 8, "wall_s": 40.0, "wall_max_s": 9.0,
                            "cpu_s": 4.0},
            "mirror_ingest": {"count": 8, "wall_s": 48.0, "wall_max_s": 9.5,
                              "cpu_s": 6.0},
            "mirror_read": {"count": 8, "wall_s": 2.0, "wall_max_s": 0.5},
            "mirror_wait": {"count": 24, "wall_s": 20.0, "wall_max_s": 5.0},
            "mirror_recv": {"count": 272, "wall_s": 30.0,
                            "wall_max_s": 0.1}}}}
    WANT = {"dn.mirror_pct": 100.0 * 5.0 / 10.0,
            "mirror.push_ms_per_block": 5000.0,
            "mirror.push_cpu_ms_per_block": 500.0,
            "mirror.read_ms_per_push": 250.0,
            "mirror.wait_ms_per_push": 2500.0,
            "mirror.ingest_ms_per_block": 6000.0,
            "mirror.packets_per_block": 34.0}

    def _read(self, perfbench_file, metric: str, src: dict):
        with open(os.path.join(perfbench_file.root, "layers",
                               metric + ".json")) as f:
            layer = json.load(f)
        assert layer["metric"] == metric
        return perfbench_file(f"readers/{layer['reader']}.py").read(
            src, layer["params"])

    @pytest.mark.parametrize("metric", NEW_METRICS)
    def test_it_is_the_layer_files_arithmetic(self, perfbench_file, metric):
        assert self._read(perfbench_file, metric, self.SRC) == \
            pytest.approx(self.WANT[metric])

    @pytest.mark.parametrize("metric", NEW_METRICS[1:])
    def test_a_clock_without_the_leg_is_nothing_to_read(self, perfbench_file,
                                                        metric):
        """A one-DataNode cell, or a program older than these spans."""
        prof = dict(self.SRC["phases"], inclusive={
            "recv": {"count": 9, "wall_s": 3.0, "wall_max_s": 1.0}})
        assert self._read(perfbench_file, metric,
                          dict(self.SRC, phases=prof)) is None
