"""BASELINE config 3 at replication 3: successive generations of versioned
trees written to three DataNodes through reduced block mirroring — the
deployment of the benchmark's ``versions-3dn.ingest`` cell at a small size
(blocks of 1 MiB in 256 KiB containers, the cell's own generator) — held to
the benchmark's plain reference on every replica.

A generation repeats most of the chunks its tree's earlier generations
stored, so the need list a relay answers names only the rest, and the leg's
work is the relay's: ``mirror_need`` (the offered hashes probed in its
index, to the need frame sent) and ``mirror_commit`` (the delta's append,
the index commit, the replica's ``create_rbw`` + ``finalize``), both inside
the relayed block's ``mirror_ingest``.  The ``block_receiver`` counters
``mirror_chunks_offered`` / ``mirror_chunks_needed`` / ``mirror_delta_bytes``
are held to what the reference predicts, block by block; on the push side
``mirror_chunks_held`` / ``mirror_chunks_reread`` split each push's needed
chunks by where it sent them from (the bytes the pusher already holds, or
its store).

Everything here stops by counts (generations written), never by seconds.
"""

import json
import os
import time

import numpy as np
import pytest

from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.utils import metrics, profiler

BLOCK = 1 << 20
CONTAINER = 256 << 10
CLIENTS = 2
GENERATIONS = 4
SEED = 2**31 + 40
CDC = {"mask_bits": 13, "min_chunk": 2048, "max_chunk": 65536}
COUNTERS = ("mirror_chunks_offered", "mirror_chunks_needed",
            "mirror_delta_bytes", "blocks_ingested_reduced")
PUSHED = ("mirror_chunks_held", "mirror_chunks_reread")
RELAY = ("mirror_need", "mirror_commit")
CELL = "versions-3dn.ingest"
SIBLINGS = ("versions-dedup.ingest", "teragen-3dn.ingest")
NEW_METRICS = ("mirror.need_ms_per_block", "mirror.commit_ms_per_block")


def _config(perfbench_file, name: str) -> dict:
    with open(os.path.join(perfbench_file.root, "configs",
                           name + ".json")) as f:
        return json.load(f)


def _bench(perfbench_file) -> dict:
    with open(os.path.join(os.path.dirname(perfbench_file.root),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def _counters() -> dict:
    got = metrics.registry("block_receiver").snapshot()["counters"]
    return {name: got.get(name, 0) for name in COUNTERS + PUSHED}


def _settled(before: dict, relayed: int, wait_s: float = 30.0) -> dict:
    """The counters once ``relayed`` more blocks are counted as ingested: a
    relay counts its block after its ack has gone upstream, so the write
    that sent it may return first."""
    deadline = time.monotonic() + wait_s
    while True:
        now = _counters()
        if now["blocks_ingested_reduced"] - before["blocks_ingested_reduced"] \
                >= relayed or time.monotonic() > deadline:
            return now
        time.sleep(0.01)


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


def _inside(sp, outer) -> bool:
    return sp[3] == outer[3] and outer[1] <= sp[1] and sp[2] <= outer[2]


@pytest.fixture(scope="module")
def trees(perfbench_file):
    """Generations 0-3 of two clients' trees, as the cell's generator makes
    them (``versions-dedup``'s data group, one 1 MiB block a generation)."""
    data = {k: v for k, v in _config(perfbench_file,
                                     "versions-dedup")["data"].items()
            if k != "generator"}
    source = perfbench_file("generators/versions.py").Source
    out = []
    for client in range(CLIENTS):
        src = source(dict(data, file_bytes=BLOCK), SEED, client)
        out.append([src.file(g).tobytes() for g in range(GENERATIONS)])
    return out


@pytest.fixture(scope="module")
def predicted(perfbench_file, trees):
    """By (client, generation), in the order the writes go: the reference's
    unique chunks of that generation, and those of them absent from every
    block written before it — every earlier generation of the same tree,
    and the one chunk the trees share (below)."""
    ref = perfbench_file("reference/chunking.py")
    out = {}
    earlier: set = set()
    for g in range(GENERATIONS):
        for client in range(CLIENTS):
            table, _ = ref.chunk_table(
                np.frombuffer(trees[client][g], np.uint8), CDC, BLOCK)
            new = {d: n for d, n in table.items() if d not in earlier}
            out[(client, g)] = (table, new)
            earlier |= set(table)
    return out


@pytest.fixture(scope="module")
def written(perfbench_file, trees):
    """Every generation written in order at r=3 through one native worker:
    the counters each write moved, the phase clock's spans, each replica
    read back and each DataNode's store and index as the writes left them."""
    ref = perfbench_file("reference/chunking.py")
    replicas = perfbench_file("replicas.py")
    with pytest.MonkeyPatch.context() as mp:
        # checks.py imports its neighbour ``reference.chunking``
        mp.syspath_prepend(perfbench_file.root)
        checks = perfbench_file("checks.py")
    paths = {(k, g): f"/v{k}/g{g}.tar" for k in range(CLIENTS)
             for g in range(GENERATIONS)}
    moved, pushed = {}, {}
    with MiniCluster(n_datanodes=3, replication=3, block_size=BLOCK,
                     container_size=CONTAINER, tpu_worker=True,
                     worker_backend="native") as mc:
        t0 = profiler.mark()
        with mc.client("writer") as c:
            for g in range(GENERATIONS):
                for k in range(CLIENTS):
                    before = _counters()
                    c.write(paths[(k, g)], trees[k][g], scheme="dedup_lz4")
                    after = _settled(before, 2)
                    moved[(k, g)] = {n: after[n] - before[n]
                                     for n in COUNTERS}
                    pushed[(k, g)] = {n: after[n] - before[n]
                                      for n in PUSHED}
        for dn in mc.datanodes:
            dn.containers.drain_seals()
        t1 = profiler.mark()
        spans = profiler.window_spans(t0, t1)
        readback = {}
        with mc.client("readback") as c:
            _, locs = replicas.short_blocks(c, sorted(paths.values()), 3)
            for key, path in paths.items():
                for off, n in ((0, BLOCK), (12_345, 300_001)):
                    readback[(key, off, n)] = replicas.read_each(
                        c, locs[path], off, n)
        nodes = []
        for dn in mc.datanodes:
            dn.containers.flush_open()
            dn.containers.drain_seals()
            nodes.append({"stats": dn.index.stats(),
                          "sealed": checks.decode_sealed(dn),
                          "orphans": sum(dn.index.orphan_bytes().values())})
        table, _ = ref.chunk_tables([d for gens in trees for d in gens], CDC,
                                    BLOCK)
        found = [dn.index.lookup_chunks(list(table)) for dn in mc.datanodes]
    yield {"moved": moved, "pushed": pushed, "spans": spans, "t0": t0, "t1": t1,
           "readback": readback, "locs": locs, "nodes": nodes,
           "table": table, "found": found}


# ------------------------------------------- (a) every replica, to the reference


class TestEveryReplica:
    def test_each_range_reads_back_from_each_of_three_locations(
            self, written, trees):
        for ((k, g), off, n), got in written["readback"].items():
            assert sorted(got) == ["dn-0", "dn-1", "dn-2"], got
            for dn_id, data in got.items():
                assert data == trees[k][g][off:off + n], (k, g, dn_id)

    def test_every_block_has_three_finalized_locations(self, written):
        for loc in written["locs"].values():
            assert [len(b["locations"]) for b in loc["blocks"]] == [3]

    @pytest.mark.parametrize("i", range(3))
    def test_each_index_is_the_references_chunk_table(self, written, i):
        table, stats = written["table"], written["nodes"][i]["stats"]
        assert all(loc is not None and loc.length == table[d]
                   for d, loc in written["found"][i].items())
        assert stats["chunks"] == len(table)
        assert stats["unique_chunk_bytes"] == sum(table.values())
        assert stats["logical_bytes"] == CLIENTS * GENERATIONS * BLOCK

    @pytest.mark.parametrize("i", range(3))
    def test_every_sealed_container_decodes_through_the_reference(
            self, written, i):
        node = written["nodes"][i]
        sealed = node["sealed"]
        assert sealed["failures"] == 0, sealed["errors"]
        assert sealed["sealed"] > 0 and sealed["lz4_coded"] > 0
        assert sealed["decoded_bytes"] - node["orphans"] == \
            node["stats"]["unique_chunk_bytes"]


# ----------------------------------- (b) the need lists, against the reference


class TestTheLegsCounters:
    """A write is one block, relayed twice (second and third DataNode); each
    relay holds every block written before, so each needs exactly the chunks
    of this generation that no earlier generation of its tree had (and, for
    a tree's generation 0, not the closing zeros another tree stored)."""

    @pytest.mark.parametrize("k,g", [(k, g) for g in range(GENERATIONS)
                                     for k in range(CLIENTS)])
    def test_each_relayed_block_moves_what_the_reference_predicts(
            self, written, predicted, k, g):
        table, new = predicted[(k, g)]
        assert written["moved"][(k, g)] == {
            "blocks_ingested_reduced": 2,
            "mirror_chunks_offered": 2 * len(table),
            "mirror_chunks_needed": 2 * len(new),
            "mirror_delta_bytes": 2 * sum(new.values())}

    @pytest.mark.parametrize("k,g", [(k, g) for g in range(GENERATIONS)
                                     for k in range(CLIENTS)])
    def test_each_push_sends_what_it_holds_and_reads_nothing_again(
            self, written, predicted, k, g):
        """Both relays hold every earlier block, so the third DataNode
        needs exactly what the second needed: the head sends it out of the
        block it received, the middle relay out of the delta it landed,
        and neither reads a chunk back out of its store."""
        _, new = predicted[(k, g)]
        assert written["pushed"][(k, g)] == {
            "mirror_chunks_held": 2 * len(new), "mirror_chunks_reread": 0}

    def test_a_later_generation_needs_few_of_the_chunks_it_offers(
            self, predicted):
        """The cell's point: after generation 0 the need list is a small
        part of the block (all of it in ``teragen-3dn.ingest``)."""
        for (k, g), (table, new) in predicted.items():
            if g == 0:
                assert len(table) - len(new) == min(k, 1), k
            else:
                assert 0 < len(new) < len(table) // 4, (k, g)

    def test_the_trees_share_only_the_archives_closing_zeros(self, trees,
                                                              predicted):
        """Each tree is its own client's; the one chunk common to all is
        the run of zeros that ends every ustar stream (the gear hash of a
        zero window cuts in it), so a tree's first generation needs every
        chunk but that one once another tree has written."""
        firsts = [set(predicted[(k, 0)][0]) for k in range(CLIENTS)]
        (shared,) = set.intersection(*firsts)
        n = predicted[(0, 0)][0][shared]
        assert 0 < n <= 1024
        assert all(data.endswith(bytes(n)) for gens in trees
                   for data in gens)


# ------------------------------------------------ (c) the relay on the clock


class TestTheRelaysSpans:
    def test_one_need_step_and_one_commit_inside_each_relayed_block(
            self, written):
        spans = written["spans"]
        ingests = _named(spans, "mirror_ingest")
        assert len(ingests) == 2 * CLIENTS * GENERATIONS
        for name in RELAY:
            got = _named(spans, name)
            assert len(got) == len(ingests)
            assert all(len(sp) == 5 and 0.0 <= sp[4] for sp in got)
            for outer in ingests:
                assert sum(_inside(sp, outer) for sp in got) == 1, name

    @pytest.mark.parametrize("name,inner,outer", [
        ("mirror_need", {"dedup_lookup"}, {"mirror_recv", "container_io",
                                           "mirror_push"}),
        ("mirror_commit", {"container_io", "wal_commit"},
         {"dedup_lookup", "mirror_recv", "mirror_push", "mirror_read"})])
    def test_what_each_cover_holds(self, written, name, inner, outer):
        """The probe inside the need step; the append, the index commit and
        the replica inside the commit; the delta's frames and a middle
        relay's onward push in neither."""
        spans = written["spans"]
        seen = set()
        for cover in _named(spans, name):
            seen |= {sp[0] for sp in spans
                     if sp is not cover and _inside(sp, cover)}
        assert inner <= seen
        assert not seen & outer

    def test_the_covers_move_no_share_of_the_partition(self, written):
        spans, t0, t1 = written["spans"], written["t0"], written["t1"]
        prof = profiler.profile_spans(spans, t0, t1)
        plain = profiler.profile_spans(
            [sp for sp in spans if sp[0] not in RELAY], t0, t1)
        for key in ("classes", "phases", "attributed_frac",
                    "overlap_efficiency"):
            assert prof[key] == plain[key], key
        assert not set(RELAY) & set(prof["phases"])
        assert set(RELAY) <= set(prof["inclusive"])

    @pytest.mark.parametrize("metric,span", zip(NEW_METRICS, RELAY))
    def test_the_metric_reads_the_covers_per_relayed_block(
            self, written, perfbench_file, metric, span):
        prof = profiler.profile_spans(written["spans"], written["t0"],
                                      written["t1"])
        got = _read_layer(perfbench_file, metric,
                          {"window_s": prof["wall_s"], "phases": prof})
        table = prof["inclusive"]
        assert got == pytest.approx(1000.0 * table[span]["wall_s"]
                                    / table["mirror_ingest"]["count"])
        assert 0.0 < got < 1000.0 * table["mirror_ingest"]["wall_s"] \
            / table["mirror_ingest"]["count"]


# ------------------------------------------- (d) the cell and its configuration


def _read_layer(perfbench_file, metric: str, src: dict):
    with open(os.path.join(perfbench_file.root, "layers",
                           metric + ".json")) as f:
        layer = json.load(f)
    assert layer["metric"] == metric
    return perfbench_file(f"readers/{layer['reader']}.py").read(
        src, layer["params"])


class TestTheCellsConfiguration:
    """``versions-3dn`` is ``versions-dedup``'s data under ``teragen-3dn``'s
    cluster: one corpus and CDC geometry, one layout."""

    @pytest.mark.parametrize("group,base", [("cdc", "versions-dedup"),
                                            ("data", "versions-dedup"),
                                            ("cluster", "teragen-3dn")])
    def test_it_keeps_each_group_whole(self, perfbench_file, group, base):
        assert _config(perfbench_file, "versions-3dn")[group] == \
            _config(perfbench_file, base)[group]

    def test_its_cuts_and_guarantees(self, perfbench_file):
        mine = _config(perfbench_file, "versions-3dn")
        assert sorted(mine["reduced"]) == ["data_bytes", "index_entries"]
        assert len(mine["guarantees"]) == 5
        assert any("every DataNode" in g and "dedup is exact" in g
                   for g in mine["guarantees"])

    def test_the_cell_runs_it_on_four_chips(self, perfbench_file):
        bench = _bench(perfbench_file)
        (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            ("versions-3dn", "ingest", 4)
        (cfg,) = [c for c in bench["configs"] if c["name"] == "versions-3dn"]
        mine = _config(perfbench_file, "versions-3dn")
        assert cfg["file"] == "perfbench/configs/versions-3dn.json"
        assert (cfg["source"], sorted(cfg["reduced"])) == \
            (mine["source"], sorted(mine["reduced"]))
        assert len(cfg["source"]) <= 200 and len(cell["why"]) <= 200
        assert sum(w["chips"] == 4 for w in bench["workloads"]) \
            <= len(bench["workloads"]) // 2

    def test_every_metric_of_both_siblings_lists_it(self, perfbench_file):
        bench = _bench(perfbench_file)
        for m in bench["end_to_end"]:
            if m["name"] in ("write_mb_s", "stored_pct"):
                assert m["workloads"][-1] == CELL
        for m in bench["per_layer"]:
            both = all(s in m["workloads"] for s in SIBLINGS)
            leg = m["layer"] == "DN mirror leg"
            assert (CELL in m["workloads"]) == (both or leg), m["name"]
            if CELL in m["workloads"]:
                assert m["workloads"][-1] == CELL, m["name"]

    @pytest.mark.parametrize("metric", NEW_METRICS)
    def test_the_new_metric_is_the_legs_last(self, perfbench_file, metric):
        bench = _bench(perfbench_file)
        assert [m["name"] for m in bench["per_layer"][-2:]] == \
            list(NEW_METRICS)
        (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert entry == {
            "name": metric, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "DN mirror leg",
            "moves": "write_mb_s",
            "workloads": ["teragen-3dn.ingest", CELL]}
