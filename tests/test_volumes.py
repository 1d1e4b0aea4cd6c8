"""Multi-volume DataNodes (FsVolumeImpl/FsVolumeList analog,
storage/volumes.py): placement across volumes, per-volume storage types,
volume-failure ejection (DN survives), and the DiskBalancer-lite planner."""

import os
import time

import numpy as np
import pytest

from hdrf_tpu.storage.volumes import CID_SHIFT, VolumeSet
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.utils import metrics


def _payload(seed: int, n: int = 300_000) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, np.uint8).tobytes()


class TestVolumeSet:
    def test_blocks_spread_across_volumes(self, tmp_path):
        vs = VolumeSet(str(tmp_path), ["DISK", "DISK"], container_kw={})
        for bid in range(8):
            w = vs.create_rbw(bid)
            w.write(b"x" * 10_000)
            w.finalize(10_000, "direct", [1], 64 * 1024)
        homes = {vs._where[b] for b in range(8)}
        assert homes == {0, 1}, "placement never used the second volume"
        assert sorted(vs.block_ids()) == list(range(8))
        # report carries each replica's volume type
        assert {t[3] for t in vs.block_report()} == {"DISK"}

    def test_type_hint_routes_to_matching_volume(self, tmp_path):
        vs = VolumeSet(str(tmp_path), ["DISK", "SSD"], container_kw={})
        for bid, want in enumerate(["SSD", "DISK", "SSD"]):
            w = vs.create_rbw(bid, storage_type=want)
            w.write(b"y" * 1000)
            w.finalize(1000, "direct", [1], 64 * 1024)
            vol = vs.volumes[vs._where[bid]]
            assert vol.storage_type == want

    def test_container_cids_route_by_namespace(self, tmp_path):
        vs = VolumeSet(str(tmp_path), ["DISK", "DISK"], container_kw={})
        chunks = [b"c" * 5000, b"d" * 5000]
        locs = vs.containers.append_chunks(chunks, on_seal=lambda c: None)
        for (cid, off, ln), orig in zip(locs, chunks):
            assert vs.volumes[cid >> CID_SHIFT] is vs.volume_of_cid(cid)
        back = vs.containers.read_chunks(locs)
        assert [bytes(b) for b in back] == chunks

    def test_eject_drops_blocks_and_survivors_serve(self, tmp_path):
        vs = VolumeSet(str(tmp_path), ["DISK", "DISK"], container_kw={})
        for bid in range(6):
            w = vs.create_rbw(bid)
            w.write(b"z" * 2000)
            w.finalize(2000, "direct", [1], 64 * 1024)
        lost = vs.eject(0)
        assert lost and set(lost).isdisjoint(vs.block_ids())
        assert vs.alive_count() == 1
        for bid in vs.block_ids():
            assert vs.read_data(bid) == b"z" * 2000
        with pytest.raises(IOError):
            vs.read_data(lost[0])

    def test_disk_balancer_evens_a_skewed_set(self, tmp_path):
        vs = VolumeSet(str(tmp_path), ["DISK", "DISK"], container_kw={})
        # skew everything onto vol-0 by hand
        for bid in range(10):
            w = vs.volumes[0].replicas.create_rbw(bid)
            w.write(b"b" * 100_000)
            w.finalize(100_000, "direct", [1], 64 * 1024)
            vs._where[bid] = 0
        assert vs.volumes[1].used_bytes() == 0
        plan = vs.plan_moves(threshold=0.10)
        assert plan, "planner found nothing to move on a fully skewed DN"
        moved = vs.execute_moves(plan)
        assert moved == len(plan)
        u0, u1 = (vs.volumes[i].used_bytes() for i in (0, 1))
        assert abs(u0 - u1) <= 0.25 * max(u0, u1)
        # moved replicas still serve, routed to their new volume
        for bid in range(10):
            assert vs.read_data(bid) == b"b" * 100_000


class TestChoosingAVolume:
    """``_choose_volume`` spreads by usage where there is a choice and asks
    nothing where there is none (registry ``volumes``: ``volume_choices``
    counts its calls, ``volume_estimates`` those of ``free_estimate``)."""

    @staticmethod
    def _counts() -> tuple[int, int]:
        m = metrics.registry("volumes")
        return m.counter("volume_choices"), m.counter("volume_estimates")

    @staticmethod
    def _no_statvfs(monkeypatch):
        def refused(path):
            raise AssertionError(f"statvfs({path}) to choose among one")

        monkeypatch.setattr(os, "statvfs", refused)

    def test_one_volume_is_chosen_without_an_estimate(self, tmp_path,
                                                      monkeypatch):
        vs = VolumeSet(str(tmp_path), ["DISK"], container_kw={"lanes": 1})
        self._no_statvfs(monkeypatch)
        choices, estimates = self._counts()
        for bid in range(3):        # a block's two calls: append, create_rbw
            vs.containers.append_ranges(b"n" * 2000, [0, 1000], [1000, 1000])
            vs.containers.append_chunks([b"m" * 500])
            w = vs.create_rbw(bid, storage_type="SSD")   # no SSD: the one
            w.write(b"x" * 100)
            w.finalize(100, "direct", [1], 64 * 1024)
        assert self._counts() == (choices + 9, estimates)
        assert {vs._where[b] for b in range(3)} == {0}
        assert vs.volumes[0].used_bytes() == 300 + 3 * 2500 + 16

    def test_two_volumes_still_follow_usage(self, tmp_path):
        vs = VolumeSet(str(tmp_path), ["DISK", "DISK"], container_kw={})
        choices, estimates = self._counts()
        # container bytes count as usage: whichever volume took the first
        # append, the next three choices go to the other one
        (cid, _, _), = vs.containers.append_chunks([b"c" * 50_000])
        full = cid >> CID_SHIFT
        for bid in range(3):
            w = vs.create_rbw(bid)
            w.write(b"r" * 10_000)
            w.finalize(10_000, "direct", [1], 64 * 1024)
            assert vs._where[bid] == 1 - full
        assert self._counts() == (choices + 4, estimates + 8)
        # a type hint that one volume answers leaves nothing to estimate
        vs = VolumeSet(str(tmp_path / "typed"), ["DISK", "SSD"],
                       container_kw={})
        choices, estimates = self._counts()
        vs.create_rbw(7, storage_type="SSD")
        assert vs._where[7] == 1
        assert self._counts() == (choices + 1, estimates)

    @pytest.mark.parametrize("types", [["RAM_DISK"], ["RAM_DISK", "DISK"]],
                             ids=["ram-alone-refuses", "ram-and-disk"])
    def test_shared_containers_never_land_in_ram(self, tmp_path, monkeypatch,
                                                 types):
        # keep the RAM volume under tmp_path (no /dev/shm segment to reclaim)
        monkeypatch.setattr(os, "access", lambda *a, **kw: False)
        vs = VolumeSet(str(tmp_path), types, container_kw={})
        choices, estimates = self._counts()
        if types == ["RAM_DISK"]:
            with pytest.raises(IOError, match="no non-RAM volume"):
                vs.containers.append_chunks([b"s" * 100])
            with pytest.raises(IOError, match="no non-RAM volume"):
                vs.containers.append_ranges(b"s" * 100, [0], [100])
            assert self._counts() == (choices + 2, estimates)
            return
        self._no_statvfs(monkeypatch)   # one candidate once RAM is excluded
        (cid, _, _), = vs.containers.append_chunks([b"s" * 100])
        assert vs.volume_of_cid(cid).storage_type == "DISK"
        assert self._counts() == (choices + 1, estimates)


class TestMultiVolumeCluster:
    def test_volume_failure_ejects_volume_not_dn(self):
        """VERDICT r3 #7 'done' criterion: a volume dies -> its blocks
        re-replicate from peers, the DataNode itself survives and keeps
        serving its other volume."""
        data = {f"/mv/f{i}": _payload(i) for i in range(6)}
        with MiniCluster(n_datanodes=2, replication=2,
                         volume_types=["DISK", "DISK"],
                         block_size=1 << 20) as mc:
            with mc.client("mv") as c:
                for p, d in data.items():
                    c.write(p, d)
            dn0 = mc.datanodes[0]
            victim = next(v.vol_id for v in dn0.volumes.volumes
                          if v.replicas.block_ids())
            before = set(dn0.volumes.block_ids())
            dn0.eject_volume(victim)
            # DN is alive and still registered; reads keep working (the
            # healthy peer covers the ejected volume's blocks)
            assert dn0.volumes.alive_count() == 1
            with mc.client("mv2") as c:
                for p, d in data.items():
                    assert c.read(p) == d
            # the NN re-replicates the lost replicas back onto dn0's
            # surviving volume or keeps them safe on dn1
            deadline = time.time() + 10
            lost = before - set(dn0.volumes.block_ids())
            while time.time() < deadline:
                rep = mc.namenode.rpc_cluster_status()
                if rep["under_replicated"] == 0 and all(
                        len(mc.namenode._blocks[b].locations) >= 2
                        for b in lost if b in mc.namenode._blocks):
                    break
                time.sleep(0.4)
            for b in lost:
                info = mc.namenode._blocks.get(b)
                if info is not None:
                    assert len(info.locations) >= 2, \
                        f"block {b} not re-replicated: {info.locations}"

    def test_one_ssd_policy_lands_on_ssd_volume(self):
        """Policy placement reaches INTO a mixed DN: with one_ssd, the
        first replica must land on a volume of type SSD (the NN's slot
        hint rides the write op; the DN routes by it)."""
        with MiniCluster(n_datanodes=2, replication=2,
                         volume_types=["DISK", "SSD"],
                         block_size=1 << 20) as mc:
            with mc.client("pol") as c:
                c.mkdir("/ssd")
                c._call("set_storage_policy", path="/ssd", policy="one_ssd")
                c.write("/ssd/f", _payload(9))
            types = set()
            for dn in mc.datanodes:
                for v in dn.volumes.volumes:
                    for bid in v.replicas.block_ids():
                        types.add(v.storage_type)
            assert "SSD" in types, f"no replica landed on an SSD volume"
            # NN learned per-replica types from the 4-tuple block report
            info = next(iter(mc.namenode._blocks.values()))
            deadline = time.time() + 6
            while time.time() < deadline and not info.storage_of:
                time.sleep(0.3)
            assert set(info.storage_of.values()) & {"SSD", "DISK"}

    def test_diskbalancer_op_over_the_wire(self):
        import socket

        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.proto.rpc import recv_frame

        with MiniCluster(n_datanodes=1, replication=1,
                         volume_types=["DISK", "DISK"],
                         block_size=1 << 20) as mc:
            with mc.client("db") as c:
                for i in range(4):
                    c.write(f"/db/f{i}", _payload(20 + i))
            dn = mc.datanodes[0]
            with socket.create_connection(dn.addr, timeout=30) as s:
                dt.send_op(s, "disk_balance", threshold=0.05)
                r = recv_frame(s)
            assert {v["vol"] for v in r["volumes"]} == {0, 1}
            assert r["moved"] == r["planned"]
