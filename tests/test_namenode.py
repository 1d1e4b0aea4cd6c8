"""NameNode unit tests: namespace ops, leases, persistence, block management."""

import pytest

from hdrf_tpu.config import NameNodeConfig
from hdrf_tpu.server.namenode import NameNode


@pytest.fixture
def nn(tmp_path):
    cfg = NameNodeConfig(meta_dir=str(tmp_path / "name"), replication=2,
                         block_size=1024, dead_node_interval_s=60.0)
    n = NameNode(cfg)
    # no .start(): RPC/monitor not needed for direct-call unit tests
    yield n
    n._editlog.close()


def register(nn, n=3):
    for i in range(n):
        nn.rpc_register_datanode(f"dn-{i}", [f"h{i}", 1000 + i])


def complete(nn, path, lengths, client="c1"):
    """Report each block from dn-0 (the async-IBR contract: complete waits
    for minimal replication), then complete."""
    for bid, ln in lengths.items():
        nn.rpc_block_received("dn-0", bid, ln)
    assert nn.rpc_complete(path, client=client, block_lengths=lengths)


class TestNamespace:
    def test_mkdir_listing_stat(self, nn):
        nn.rpc_mkdir("/a/b/c")
        (ent,) = nn.rpc_listing("/a")
        assert (ent["name"], ent["type"], ent["children"]) == ("b", "dir", 1)
        st = nn.rpc_stat("/a/b/c")
        assert (st["name"], st["type"], st["children"]) == ("c", "dir", 0)
        assert st["mode"] == 0o755 and st["owner"]  # inode attributes exist

    def test_create_write_flow(self, nn):
        register(nn)
        info = nn.rpc_create("/f", client="c1", scheme="dedup_lz4")
        assert info["block_size"] == 1024 and info["scheme"] == "dedup_lz4"
        alloc = nn.rpc_add_block("/f", client="c1")
        assert len(alloc["targets"]) == 2  # replication
        assert alloc["scheme"] == "dedup_lz4"
        complete(nn, "/f", {alloc["block_id"]: 500})
        st = nn.rpc_stat("/f")
        assert st["length"] == 500 and st["complete"]

    def test_lease_enforcement(self, nn):
        register(nn)
        nn.rpc_create("/f", client="c1")
        with pytest.raises(PermissionError):
            nn.rpc_add_block("/f", client="c2")
        with pytest.raises(PermissionError):
            nn.rpc_create("/f", client="c2")  # lease held by c1

    def test_lease_expiry_recovers_file(self, nn):
        register(nn)
        nn.rpc_create("/f", client="c1")
        a = nn.rpc_add_block("/f", client="c1")
        bid = a["block_id"]
        # ALL expected pipeline DNs report the same length: the consistent
        # fast path completes without a recovery round trip
        for t in a["targets"]:
            nn.rpc_block_received(t["dn_id"], bid, 42)
        nn._leases.expiry_s = -1  # force expiry
        nn._leases.renew_all("c1")
        nn._recover_leases()
        st = nn.rpc_stat("/f")
        assert st["complete"] and st["length"] == 42  # recovered w/ reported len
        with pytest.raises(FileExistsError):
            nn.rpc_create("/f", client="c2")  # complete files aren't overwritten

    def test_delete_and_rename(self, nn):
        register(nn)
        nn.rpc_create("/d/f", client="c1")
        a = nn.rpc_add_block("/d/f", client="c1")
        complete(nn, "/d/f", {a["block_id"]: 10})
        nn.rpc_rename("/d/f", "/d2/g")
        assert nn.rpc_stat("/d2/g")["length"] == 10
        assert nn._blocks[a["block_id"]].path == "/d2/g"
        assert nn.rpc_delete("/d2/g")
        assert a["block_id"] not in nn._blocks
        assert not nn.rpc_delete("/d2/g")  # already gone

    def test_rename_into_own_subtree_rejected(self, nn):
        nn.rpc_mkdir("/a/b")
        with pytest.raises(ValueError):
            nn.rpc_rename("/a", "/a/b/c")
        with pytest.raises(ValueError):
            nn.rpc_rename("/a", "/a")
        assert nn.rpc_stat("/a/b")["type"] == "dir"  # tree intact

    def test_create_over_incomplete_invalidates_old_blocks(self, nn):
        register(nn)
        nn.rpc_create("/f", client="c1")
        a = nn.rpc_add_block("/f", client="c1")
        old_bid = a["block_id"]
        nn.rpc_block_received(a["targets"][0]["dn_id"], old_bid, 10)
        # c1 abandons; lease expires; c2 recreates the (incomplete) file
        nn._leases.drop("/f")
        nn.rpc_create("/f", client="c2")
        assert old_bid not in nn._blocks  # no leak in the block map
        cmds = nn.rpc_heartbeat(a["targets"][0]["dn_id"])["commands"]
        assert {"cmd": "invalidate", "block_ids": [old_bid]} in cmds

    def test_delete_queues_invalidation(self, nn):
        register(nn)
        nn.rpc_create("/f", client="c1")
        a = nn.rpc_add_block("/f", client="c1")
        bid = a["block_id"]
        complete(nn, "/f", {bid: 10})
        dn0 = a["targets"][0]["dn_id"]
        nn.rpc_block_received(dn0, bid, 10)
        nn.rpc_delete("/f")
        cmds = nn.rpc_heartbeat(dn0)["commands"]
        assert {"cmd": "invalidate", "block_ids": [bid]} in cmds


class TestPersistence:
    def _flow(self, nn):
        register(nn)
        nn.rpc_mkdir("/dir")
        nn.rpc_create("/dir/f", client="c1", scheme="lz4")
        a = nn.rpc_add_block("/dir/f", client="c1")
        complete(nn, "/dir/f", {a["block_id"]: 77})
        return a["block_id"]

    def test_wal_replay(self, nn, tmp_path):
        bid = self._flow(nn)
        nn._editlog.close()
        nn2 = NameNode(nn.config)
        st = nn2.rpc_stat("/dir/f")
        assert st["length"] == 77 and st["scheme"] == "lz4" and st["complete"]
        assert nn2._blocks[bid].length == 77
        assert nn2._next_block_id > bid
        nn2._editlog.close()

    def test_image_plus_wal(self, nn):
        self._flow(nn)
        nn.rpc_save_namespace()  # checkpoint
        register(nn)
        nn.rpc_create("/post", client="c1")
        a2 = nn.rpc_add_block("/post", client="c1")
        complete(nn, "/post", {a2["block_id"]: 5})
        nn._editlog.close()
        nn2 = NameNode(nn.config)
        assert nn2.rpc_stat("/dir/f")["length"] == 77
        assert nn2.rpc_stat("/post")["length"] == 5
        nn2._editlog.close()


class TestBlockManagement:
    def test_block_report_reconciles(self, nn):
        register(nn, 1)
        nn.rpc_create("/f", client="c1")
        a = nn.rpc_add_block("/f", client="c1")
        bid = a["block_id"]
        complete(nn, "/f", {bid: 9})
        nn.rpc_block_report("dn-0", [[bid, a["gen_stamp"], 9]])
        assert "dn-0" in nn._blocks[bid].locations
        # stale replica of a deleted file -> invalidate command
        nn.rpc_block_report("dn-0", [[bid, a["gen_stamp"], 9], [999, 1, 5]])
        cmds = nn.rpc_heartbeat("dn-0")["commands"]
        assert {"cmd": "invalidate", "block_ids": [999]} in cmds
        # replica disappears from next report -> location removed
        nn.rpc_block_report("dn-0", [])
        assert "dn-0" not in nn._blocks[bid].locations

    def test_replication_monitor_schedules(self, nn):
        register(nn, 3)
        nn.rpc_create("/f", client="c1", replication=3)
        a = nn.rpc_add_block("/f", client="c1")
        bid = a["block_id"]
        complete(nn, "/f", {bid: 9})
        nn.rpc_block_received("dn-0", bid, 9)  # only 1 of 3 replicas
        nn._check_replication()
        cmds = nn.rpc_heartbeat("dn-0")["commands"]
        rep = [c for c in cmds if c["cmd"] == "replicate"]
        assert len(rep) == 1 and rep[0]["block_id"] == bid
        assert len(rep[0]["targets"]) == 2
        assert all(t["dn_id"] != "dn-0" for t in rep[0]["targets"])

    def test_dead_node_detection(self, nn):
        register(nn, 2)
        nn.rpc_create("/f", client="c1")
        a = nn.rpc_add_block("/f", client="c1")
        bid = a["block_id"]
        complete(nn, "/f", {bid: 9})
        nn.rpc_block_received("dn-0", bid, 9)
        nn.config.dead_node_interval_s = -1  # everything is dead
        nn._check_dead_nodes()
        assert nn._datanodes == {}
        assert nn._blocks[bid].locations == set()

    def test_heartbeat_unknown_dn_asks_reregister(self, nn):
        assert nn.rpc_heartbeat("ghost")["reregister"]

    def test_add_block_no_datanodes(self, nn):
        nn.rpc_create("/f", client="c1")
        with pytest.raises(IOError):
            nn.rpc_add_block("/f", client="c1")


class TestPipelineLead:
    """A write pipeline's first DataNode reduces the block: the NameNode
    gives the lead to the target leading the fewest blocks under
    construction, of equals to the one that led least recently."""

    @pytest.mark.parametrize("in_flight", [True, False],
                             ids=["writers-abreast", "one-after-another"])
    def test_four_writers_over_three_datanodes_lead_on_each(self, nn,
                                                             in_flight):
        register(nn, 3)
        leads = []
        for k in range(7):
            nn.rpc_create(f"/f{k}", client=f"c{k}", replication=3)
            a = nn.rpc_add_block(f"/f{k}", client=f"c{k}")
            assert sorted(t["dn_id"] for t in a["targets"]) == [
                "dn-0", "dn-1", "dn-2"]
            leads.append(a["targets"][0]["dn_id"])
            if not in_flight:
                complete(nn, f"/f{k}", {a["block_id"]: 9}, client=f"c{k}")
            elif k >= 3:     # four abreast: the oldest block of four ends
                old = k - 3
                bid = nn._file(f"/f{old}").blocks[-1]
                complete(nn, f"/f{old}", {bid: 9}, client=f"c{old}")
        assert set(leads[:3]) == {"dn-0", "dn-1", "dn-2"}
        # either way the lead goes round: abreast, the fourth joins the
        # earliest lead and each later block goes where the one that just
        # ended was led; one after another, the least recent lead is next
        assert leads[3:] == leads[:4]

    def test_a_reported_block_stops_leading_and_slots_stay_aligned(
            self, nn):
        nn.rpc_register_datanode("dn-0", ["h0", 1000], storage_type="SSD")
        nn.rpc_register_datanode("dn-1", ["h1", 1001], storage_type="DISK")
        nn.rpc_set_storage_policy("/", "one_ssd")
        allocs = {}
        for p in ("/a", "/b", "/c"):
            nn.rpc_create(p, client=p, replication=2)
            allocs[p] = nn.rpc_add_block(p, client=p)
            if p == "/b":    # /a still leads on dn-0; /b's replica is in
                nn.rpc_block_received("dn-1", allocs[p]["block_id"], 9)
        leads = [allocs[p]["targets"][0]["dn_id"] for p in allocs]
        # /b: dn-0 leads /a, so dn-1; /c: /b reported, so dn-1 leads none
        # (were /b still counted, dn-0 would lead: it led longer ago)
        assert leads == ["dn-0", "dn-1", "dn-1"]
        for a in allocs.values():   # each target keeps its own slot
            assert [t["storage_type"] for t in a["targets"]] == [
                nn._datanodes[t["dn_id"]].storage_type for t in a["targets"]]


class TestWalIntegrity:
    def test_rejected_op_does_not_poison_wal(self, nn, tmp_path):
        """mkdir over an existing file must fail *without* leaving a WAL
        record that would crash every future NameNode start (apply-before-
        append in NameNode._log)."""
        register(nn)
        nn.rpc_create("/a", client="c1")
        nn.rpc_complete("/a", client="c1", block_lengths={})
        with pytest.raises(FileExistsError):
            nn.rpc_mkdir("/a/b")
        with pytest.raises(FileExistsError):
            nn.rpc_create("/x", client="c1") and nn.rpc_complete(
                "/x", client="c1", block_lengths={}) and nn.rpc_rename("/x", "/a")
        nn._editlog.close()
        # restart over the same meta dir must succeed and keep the namespace
        nn2 = NameNode(NameNodeConfig(meta_dir=str(tmp_path / "name")))
        assert nn2.rpc_stat("/a")["type"] == "file"
        nn2._editlog.close()

    def test_delete_dir_releases_child_leases(self, nn):
        register(nn)
        nn.rpc_create("/d/f", client="c1")   # lease held, file incomplete
        nn.rpc_delete("/d")
        # the path must be immediately re-creatable by another client
        nn.rpc_create("/d/f", client="c2")

    def test_replication_not_requeued_every_tick(self, nn):
        register(nn, n=3)
        nn.rpc_create("/f", client="c1")
        alloc = nn.rpc_add_block("/f", client="c1")
        bid = alloc["block_id"]
        complete(nn, "/f", {bid: 10})
        # one replica reported on dn-0 only; replication=2 -> deficit 1
        nn.rpc_block_received("dn-0", bid, 10)
        nn._check_replication()
        nn._check_replication()  # second tick while transfer "in flight"
        cmds = [c for d in nn._datanodes.values() for c in d.commands
                if c["cmd"] == "replicate"]
        assert len(cmds) == 1


class TestSafemodeAndDecommission:
    def test_startup_safemode_until_reports(self, tmp_path):
        cfg = NameNodeConfig(meta_dir=str(tmp_path / "name"), replication=1)
        nn = NameNode(cfg)
        register(nn, 1)
        nn.rpc_create("/f", client="c1")
        a = nn.rpc_add_block("/f", client="c1")
        nn.rpc_block_received("dn-0", a["block_id"], 7)
        assert nn.rpc_complete("/f", client="c1",
                               block_lengths={a["block_id"]: 7})
        nn._editlog.close()
        # restart over the same meta dir: non-empty namespace => safemode
        nn2 = NameNode(NameNodeConfig(meta_dir=str(tmp_path / "name"),
                                      replication=1))
        assert nn2.rpc_safemode("get") is True
        with pytest.raises(OSError, match="safe mode"):
            nn2.rpc_mkdir("/blocked")
        # a block report satisfies the threshold and safemode lifts
        nn2.rpc_register_datanode("dn-0", ["h0", 1000])
        nn2.rpc_block_report("dn-0", [[a["block_id"], a["gen_stamp"], 7]])
        assert nn2.rpc_safemode("get") is False
        nn2.rpc_mkdir("/unblocked")
        # manual enter/leave
        nn2.rpc_safemode("enter")
        with pytest.raises(OSError, match="safe mode"):
            nn2.rpc_delete("/unblocked")
        nn2.rpc_safemode("leave")
        assert nn2.rpc_delete("/unblocked")
        nn2._editlog.close()

    def test_decommission_drains_and_completes(self, nn):
        register(nn, 3)
        nn.rpc_create("/f", client="c1")
        a = nn.rpc_add_block("/f", client="c1")
        bid = a["block_id"]
        nn.rpc_block_received("dn-0", bid, 10)
        nn.rpc_block_received("dn-1", bid, 10)
        assert nn.rpc_complete("/f", client="c1", block_lengths={bid: 10})
        assert nn.rpc_decommission("dn-0")
        st = nn.rpc_decommission_status("dn-0")
        assert st["state"] == "decommissioning" and st["remaining"] == 1
        # decommissioning nodes are excluded from new placements
        targets = nn._choose_targets(3, exclude=set())
        assert all(t.dn_id != "dn-0" for t in targets)
        # the monitor schedules a replacement copy (replication=2, one
        # counted replica left on dn-1)
        nn._check_replication()
        cmds = [c for d in nn._datanodes.values() for c in d.commands
                if c["cmd"] == "replicate"]
        assert cmds and cmds[0]["block_id"] == bid
        # replica lands on dn-2 -> dn-0 is safe to stop
        nn.rpc_block_received("dn-2", bid, 10)
        assert nn.rpc_decommission_status("dn-0")["state"] == "decommissioned"

    def test_ec_shard_drain_and_recommission(self, nn, tmp_path):
        register(nn, 5)
        nn.rpc_create("/e", client="c1", ec="rs-3-2-4k")
        alloc = nn.rpc_add_block_group("/e", client="c1")
        gid = alloc["group_id"]
        for i, blk in enumerate(alloc["blocks"]):
            nn.rpc_block_received(f"dn-{i % 5}", blk["block_id"], 4096)
        assert nn.rpc_complete("/e", client="c1", block_lengths={gid: 12288})
        assert nn.rpc_decommission("dn-0")
        # the monitor schedules a plain copy of the EC shard off dn-0
        nn._check_replication()
        cmds = [c for d in nn._datanodes.values() for c in d.commands
                if c["cmd"] == "replicate"]
        shard_on_dn0 = next(b["block_id"] for i, b in
                            enumerate(alloc["blocks"]) if i % 5 == 0)
        assert any(c["block_id"] == shard_on_dn0 for c in cmds)
        # replica lands elsewhere -> drain completes
        nn.rpc_block_received("dn-3", shard_on_dn0, 4096)
        st = nn.rpc_decommission_status("dn-0")
        assert st["state"] == "decommissioned", st
        # recommission returns the node to placement
        assert nn.rpc_recommission("dn-0")
        assert nn.rpc_decommission_status("dn-0")["state"] == "normal"
        # the exclude set survives a restart over the same meta dir
        nn.rpc_decommission("dn-1")
        nn._editlog.close()
        nn2 = NameNode(NameNodeConfig(meta_dir=str(tmp_path / "name")))
        assert "dn-1" in nn2._decommissioning
        nn2._editlog.close()

    def test_recover_lease_rpc(self, nn):
        register(nn)
        nn.rpc_create("/rl", client="c1")
        a = nn.rpc_add_block("/rl", client="c1")
        bid = a["block_id"]
        # only ONE of the expected pipeline DNs has reported: recovery must
        # NOT complete from a partial peer set — it dispatches a length-sync
        # to the primary and waits for commitBlockSynchronization
        nn.rpc_block_received(a["targets"][0]["dn_id"], bid, 42)
        assert nn.rpc_recover_lease("/rl") is False
        all_cmds = [c for d in nn._datanodes.values() for c in d.commands]
        assert any(c["cmd"] == "recover_block" for c in all_cmds)
        # the primary reports the synced min length
        assert nn.rpc_commit_block_sync(
            "/rl", bid, 42, [a["targets"][0]["dn_id"]],
            gen_stamp=nn._blocks[bid].gen_stamp)
        assert nn.rpc_recover_lease("/rl") is True
        st = nn.rpc_stat("/rl")
        assert st["complete"] and st["length"] == 42
        # path is writable by a new client afterwards
        nn.rpc_create("/rl2", client="c2")


def completed_block(nn):
    """Register 3 DNs, create /f, complete one block with replicas on
    dn-0 and dn-1."""
    register(nn)
    nn.rpc_create("/f", client="c1")
    a = nn.rpc_add_block("/f", client="c1")
    bid = a["block_id"]
    nn.rpc_block_received("dn-0", bid, 500)
    nn.rpc_block_received("dn-1", bid, 500)
    assert nn.rpc_complete("/f", client="c1", block_lengths={bid: 500})
    return bid


class TestBalancerMoveSafety:
    """A balancer move must never reduce redundancy: the source replica is
    dropped only after the REQUESTED target reports its copy (not when any
    other replica happens to exist), and a move whose target never arrives
    is abandoned with the source untouched."""

    def test_source_kept_until_target_reports(self, nn):
        bid = completed_block(nn)
        assert nn.rpc_move_block(bid, "dn-0", "dn-2")
        nn._settle_moves()  # dn-1 replica exists, but dn-2 hasn't reported
        assert "dn-0" in nn._blocks[bid].locations
        assert bid in nn._pending_moves
        nn.rpc_block_received("dn-2", bid, 500)
        nn._settle_moves()
        locs = nn._blocks[bid].locations
        assert "dn-2" in locs and "dn-0" not in locs
        assert bid not in nn._pending_moves

    def test_move_abandoned_after_deadline(self, nn):
        bid = completed_block(nn)
        assert nn.rpc_move_block(bid, "dn-0", "dn-2")
        nn._pending_moves[bid]["deadline"] = 0.0  # force expiry
        nn._settle_moves()
        assert bid not in nn._pending_moves
        assert "dn-0" in nn._blocks[bid].locations  # replica untouched


class TestStandbyLeaseHygiene:
    def test_standby_create_leaves_no_lease(self, tmp_path):
        """A create rejected by the role check must not leave a lease behind:
        leases acquired on a standby are never recovered (lease recovery only
        runs on the active) and would block creates after promotion."""
        from hdrf_tpu.server.namenode import StandbyError

        cfg = NameNodeConfig(meta_dir=str(tmp_path / "sb"), role="standby")
        sb = NameNode(cfg)
        try:
            with pytest.raises(StandbyError):
                sb.rpc_create("/f", client="c1")
            assert "/f" not in sb._leases._leases
        finally:
            sb._editlog.close()


class TestExcessReplicas:
    def test_excess_replicas_pruned(self, nn):
        """Over-replication (re-replication racing a node's return, or an
        abandoned move whose target reported late) is pruned back to the
        target count — processExtraRedundancy analog."""
        bid = completed_block(nn)
        nn.rpc_block_received("dn-2", bid, 500)  # third copy, want=2
        assert len(nn._blocks[bid].locations) == 3
        nn._check_replication()
        locs = nn._blocks[bid].locations
        assert len(locs) == 2
        victim = ({"dn-0", "dn-1", "dn-2"} - locs).pop()
        cmds = nn._datanodes[victim].commands
        assert any(c["cmd"] == "invalidate" and bid in c["block_ids"]
                   for c in cmds)

    def test_excess_prune_preserves_rack_diversity(self, nn):
        """chooseReplicaToDelete semantics: never prune the last replica on
        a rack while another rack holds two — one rack failure must not be
        able to take out the block."""
        nn.rpc_register_datanode("dn-0", ["h0", 1000], rack="/rackA")
        nn.rpc_register_datanode("dn-1", ["h1", 1001], rack="/rackA")
        nn.rpc_register_datanode("dn-2", ["h2", 1002], rack="/rackB")
        nn.rpc_create("/f", client="c1")
        a = nn.rpc_add_block("/f", client="c1")
        bid = a["block_id"]
        nn.rpc_block_received("dn-0", bid, 500)
        nn.rpc_block_received("dn-1", bid, 500)
        assert nn.rpc_complete("/f", client="c1", block_lengths={bid: 500})
        nn.rpc_block_received("dn-2", bid, 500)  # 3rd copy, want=2
        # make the rackB node the fullest so naive selection would pick it
        nn._datanodes["dn-2"].blocks.update({991, 992, 993})
        nn._check_replication()
        locs = nn._blocks[bid].locations
        assert len(locs) == 2
        assert "dn-2" in locs  # rackB's only copy survived
