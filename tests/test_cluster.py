"""End-to-end MiniCluster tests: the §3.1/§3.2 flagship paths, failure
handling, and reduced block mirroring."""

import os
import random
import time

import pytest

from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.utils import codec


@pytest.fixture(scope="module")
def cluster():
    with MiniCluster(n_datanodes=3, replication=2, block_size=256 * 1024) as c:
        yield c


def blob(seed: int, n: int) -> bytes:
    return random.Random(seed).randbytes(n)


class TestEndToEnd:
    def test_write_read_direct(self, cluster):
        data = blob(1, 700_000)  # spans 3 blocks
        with cluster.client() as c:
            c.write("/e2e/direct", data, scheme="direct")
            assert c.read("/e2e/direct") == data
            st = c.stat("/e2e/direct")
            assert st["length"] == len(data) and st["blocks"] == 3

    @pytest.mark.parametrize("scheme", [
        "lz4",
        pytest.param("zstd", marks=pytest.mark.skipif(
            not codec.available("zstd"),
            reason="zstandard module not installed")),
        "dedup_lz4"])
    def test_write_read_reduced(self, cluster, scheme):
        base = blob(2, 200_000)
        data = base * 3 + blob(3, 100_000)  # dedup-friendly
        with cluster.client() as c:
            c.write(f"/e2e/{scheme}", data, scheme=scheme)
            assert c.read(f"/e2e/{scheme}") == data

    def test_range_reads(self, cluster):
        data = blob(4, 600_000)
        with cluster.client() as c:
            c.write("/e2e/range", data, scheme="dedup_lz4")
            for off, ln in [(0, 100), (255_000, 3000), (599_990, 10),
                            (100_000, 400_000)]:
                assert c.read("/e2e/range", off, ln) == data[off:off + ln]

    def test_namespace_ops(self, cluster):
        with cluster.client() as c:
            c.mkdir("/ns/a")
            c.write("/ns/a/f", b"hello", scheme="direct")
            assert {e["name"] for e in c.ls("/ns/a")} == {"f"}
            c.rename("/ns/a/f", "/ns/b/g")
            assert c.read("/ns/b/g") == b"hello"
            assert c.delete("/ns/b/g")
            assert not c.exists("/ns/b/g")

    def test_empty_file(self, cluster):
        with cluster.client() as c:
            c.write("/e2e/empty", b"", scheme="direct")
            assert c.read("/e2e/empty") == b""

    def test_dedup_across_files_saves_space(self):
        # Dedicated 1-DN cluster: both files land on the same node, so the
        # second file's chunks must all dedup against the first's.
        with MiniCluster(n_datanodes=1, replication=1,
                         block_size=256 * 1024) as cluster:
            data = blob(5, 400_000)
            with cluster.client() as c:
                c.write("/dedup/one", data, scheme="dedup_lz4")
                c.write("/dedup/two", data, scheme="dedup_lz4")
                assert c.read("/dedup/two") == data
            st = cluster.datanodes[0].index.stats()
            assert st["logical_bytes"] == 2 * len(data)
            assert st["unique_chunk_bytes"] <= len(data) + 70_000  # ~one copy


class TestReducedMirroring:
    def test_mirror_has_reduced_form_not_rerun(self, cluster):
        """Replicas of a dedup'd block exist on 2 DNs with consistent logical
        bytes served from both."""
        data = blob(6, 300_000)
        with cluster.client() as c:
            c.write("/mirror/f", data, scheme="dedup_lz4", replication=2)
            cluster.wait_for_replication("/mirror/f", 2)
            loc = c._nn.call("get_block_locations", path="/mirror/f")
            for b in loc["blocks"]:
                assert len(b["locations"]) == 2
                # read from EACH location directly
                for l in b["locations"]:
                    got = c._read_from(tuple(l["addr"]), b["block_id"], 0, -1)
                    assert len(got) == b["length"]


class TestFailure:
    def test_read_failover_after_dn_death(self):
        with MiniCluster(n_datanodes=3, replication=2,
                         block_size=128 * 1024) as cluster:
            data = blob(7, 300_000)
            with cluster.client() as c:
                c.write("/fail/f", data, scheme="lz4")
                cluster.wait_for_replication("/fail/f", 2)
                cluster.kill_datanode(0)
                assert c.read("/fail/f") == data  # failover to live replica

    def test_rereplication_after_dn_death(self):
        with MiniCluster(n_datanodes=3, replication=2, block_size=128 * 1024,
                         heartbeat_s=0.1, dead_node_s=0.5) as cluster:
            data = blob(8, 200_000)
            with cluster.client() as c:
                c.write("/rerep/f", data, scheme="dedup_lz4")
                cluster.wait_for_replication("/rerep/f", 2)
                cluster.kill_datanode(0)
                # monitor notices death, schedules re-replication to dn 2
                cluster.wait_for_replication("/rerep/f", 2, timeout=20)
                assert c.read("/rerep/f") == data

    def test_datanode_restart_recovers_state(self):
        with MiniCluster(n_datanodes=1, replication=1,
                         block_size=128 * 1024) as cluster:
            data = blob(9, 250_000)
            with cluster.client() as c:
                c.write("/restart/f", data, scheme="dedup_lz4")
                cluster.stop_datanode(0)
                cluster.restart_datanode(0)
                cluster.wait_for_datanodes(1)
                assert c.read("/restart/f") == data

    def test_namenode_restart_recovers_namespace(self):
        with MiniCluster(n_datanodes=1, replication=1,
                         block_size=128 * 1024) as cluster:
            data = blob(10, 150_000)
            with cluster.client() as c:
                c.write("/nnrestart/f", data, scheme="lz4")
            cluster.restart_namenode()
            # DN re-registers on next heartbeat (reregister flag)
            cluster.wait_for_datanodes(1)
            deadline = time.monotonic() + 10
            with cluster.client() as c:
                while time.monotonic() < deadline:
                    try:
                        assert c.read("/nnrestart/f") == data
                        break
                    except IOError:
                        time.sleep(0.2)
                else:
                    pytest.fail("file unreadable after NN restart")


class TestHeartbeatSurvives:
    def test_one_failing_stats_tick_does_not_end_the_heartbeat(self):
        """A raising _stats() used to kill the heartbeat thread: the NN then
        declared a DN dead that was still serving."""
        with MiniCluster(n_datanodes=1, replication=1) as mc:
            dn = mc.datanodes[0]
            real, calls = dn._stats, {"n": 0}

            def flaky():
                calls["n"] += 1
                if calls["n"] == 1:
                    raise FileNotFoundError("containers/4.raw")
                return real()

            dn._stats = flaky
            deadline = time.monotonic() + 5.0
            while calls["n"] < 3 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert calls["n"] >= 3, "heartbeat thread died on the first error"
            time.sleep(2.0)     # past dead_node_s: still heartbeating
            with mc.client() as c:
                assert [d["alive"] for d in c.datanode_report()] == [True]


class TestPlacementAndTrash:
    def test_rack_aware_placement(self, tmp_path):
        from hdrf_tpu.config import DataNodeConfig, NameNodeConfig
        from hdrf_tpu.server.datanode import DataNode
        from hdrf_tpu.server.namenode import NameNode
        from hdrf_tpu.client.filesystem import HdrfClient
        import os

        nn = NameNode(NameNodeConfig(meta_dir=str(tmp_path / "nn"),
                                     replication=2,
                                     block_size=64 * 1024)).start()
        dns = []
        try:
            for i in range(4):
                cfg = DataNodeConfig(
                    data_dir=str(tmp_path / f"dn{i}"),
                    rack=f"/rack{i % 2}", heartbeat_interval_s=0.2)
                dns.append(DataNode(cfg, nn.addr, dn_id=f"dn-{i}").start())
            with HdrfClient(nn.addr, name="rack") as c:
                import time
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    if sum(d["alive"] for d in c.datanode_report()) == 4:
                        break
                    time.sleep(0.05)
                for i in range(6):
                    c.write(f"/r/f{i}", b"z" * 10_000)
                    # complete() returns once ONE replica reported; wait for
                    # the second IBR before asserting rack spread
                    deadline = time.monotonic() + 10
                    while time.monotonic() < deadline:
                        loc = c._nn.call("get_block_locations",
                                         path=f"/r/f{i}")
                        if len(loc["blocks"][0]["locations"]) >= 2:
                            break
                        time.sleep(0.05)
                    racks = {nn._datanodes[ld["dn_id"]].rack
                             for ld in loc["blocks"][0]["locations"]}
                    assert len(racks) == 2, f"replicas on one rack: {racks}"
        finally:
            for dn in dns:
                dn.stop()
            nn.stop()

    def test_trash_and_expunge(self, cluster):
        with cluster.client("trash") as c:
            root = c._trash_root()
            c.write("/t/doomed", b"bytes" * 1000)
            c.delete("/t/doomed", skip_trash=False)
            assert not c.exists("/t/doomed")
            # same-second re-delete of a recreated path disambiguates
            c.write("/t/doomed", b"again")
            c.delete("/t/doomed", skip_trash=False)
            trash = c.ls(root)
            assert len(trash) == 2
            names = sorted(e["name"] for e in trash)
            restored = c.read(f"{root}/{names[0]}")
            assert restored == b"bytes" * 1000
            # -rm of a trash entry is a permanent delete, not a re-trash
            assert c.delete(f"{root}/{names[1]}", skip_trash=False)
            assert len(c.ls(root)) == 1
            assert c.expunge() == 1
            assert c.ls(root) == []
