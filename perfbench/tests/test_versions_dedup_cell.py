"""``versions-dedup.ingest`` as ``BENCHMARK.json`` lists it since PR 27: the
cell rehearsed from the real manifest on a native worker, and the three
per-layer metrics that came with it (layer files only; the readers were
there) — the split of ``dn.commit_pct`` that tells this cell from TeraGen's,
and the overflow retries of ``_prep`` per block."""

import json
import os

import pytest

from common import BENCH, REPO, add_shelved_cells, failing, rehearse

CELL = "versions-dedup.ingest"
NEW = {"worker.prep_retries_per_block": ("stage_ratio", "device programs"),
       "dn.dedup_lookup_pct": ("phase_share", "DN commit"),
       "dn.container_io_pct": ("phase_share", "DN commit")}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _reader(name):
    import sys

    sys.path.insert(0, BENCH)
    import manifest

    return manifest.plugin("readers", name)


def test_the_manifest_lists_the_cell_and_its_metrics():
    bench = _bench()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("versions-dedup", "ingest", 1)
    (cfg,) = [c for c in bench["configs"] if c["name"] == "versions-dedup"]
    with open(os.path.join(REPO, cfg["file"])) as f:
        stated = json.load(f)
    assert cfg["source"] == stated["source"]
    assert cfg["reduced"] == sorted(stated["reduced"])
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert ends == {"write_mb_s", "stored_pct", "setup_s"}
    layers = {m["name"]: m for m in bench["per_layer"]}
    for name, (reader, layer) in NEW.items():
        m = layers[name]
        assert (m["layer"], m["moves"], m["better"]) == \
            (layer, "write_mb_s", "lower")
        assert {"teragen-1dn.ingest", "teragen-1dn.ingest-1w", CELL} <= \
            set(m["workloads"])
        with open(os.path.join(BENCH, "layers", name + ".json")) as f:
            spec = json.load(f)
        assert (spec["metric"], spec["reader"]) == (name, reader)
    # every share of the write window is read here too, but those that need
    # a compress job in every traced window (a generation appends 4 MiB, so
    # a slow window — the parent's program: 25 blocks — seals none) and
    # those of the read path, which list the read cell alone
    mine = {n for n, m in layers.items() if CELL in m["workloads"]}
    reads = {n for n, m in layers.items() if m["layer"] == "DN read"
             or n.startswith("client.read_")}
    assert {n for n, m in layers.items()
            if m["moves"] == "write_mb_s"} - mine - reads == \
        {"seal.device_emitted_pct", "seal.scan_wait_ms_per_job",
         "seal.emit_ms_per_job", "seal.ingest_ms_per_job",
         "seal.segments_per_frame"}


def test_the_rehearsal_helper_lists_no_cell_twice():
    """``common.add_shelved_cells`` adds the shelved read cell alone: this
    cell, its configuration and every metric stay listed once."""
    import sys

    sys.path.insert(0, BENCH)
    import manifest

    bench = _bench()
    add_shelved_cells(bench)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[key]]
        assert len(names) == len(set(names)), key
    rows = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(rows) == 1 and rows[0]["why"] != "test"
    assert manifest.cell(CELL)["workload"] == rows[0]


def test_a_traced_rehearsal_reads_the_commit_split():
    out, rows = rehearse(CELL, trace=1, seed=2**31 + 27, seconds=3)
    last = rows[-1]
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    assert last["attempted"] > 4 and last["failed"] == 0
    got = last["metrics"]
    lookup, io = (got[n]["value"] for n in ("dn.dedup_lookup_pct",
                                            "dn.container_io_pct"))
    assert lookup > 0.0 and io > 0.0
    assert lookup + io <= got["dn.commit_pct"]["value"] + 1e-9
    # a native worker runs no _prep: nothing to read, never a 0
    assert "worker.prep_retries_per_block" not in got
    # the hit path did the work: most of what was written was stored already
    note = [r for r in rows if r.get("phase") == "checks"][0]["reference"]
    assert note["unique_bytes"] < 0.5 * note["logical_bytes"]


@pytest.mark.parametrize("stats, want", [
    ({"prep_retries": 0, "prep_cap_words": 0, "blocks_reduced": 48}, 0.0),
    ({"prep_retries": 4, "blocks_reduced": 4}, 1.0),
    ({"blocks_reduced": 48}, None),              # the parent's ``stats``
    ({"prep_retries": 0, "blocks_reduced": 0}, None)])
def test_retries_per_block_from_the_worker_counters(stats, want):
    with open(os.path.join(BENCH, "layers",
                           "worker.prep_retries_per_block.json")) as f:
        spec = json.load(f)
    got = _reader(spec["reader"]).read({"window": {"stats": stats}},
                                       spec["params"])
    assert got == want


def test_the_commit_split_from_the_phase_clock():
    src = {"window_s": 50.0, "phases": {"classes": {}, "phases": {
        "dedup_lookup": 2.0, "wal_commit": 1.0, "container_io": 0.5}}}
    shares = {}
    for name in ("dn.dedup_lookup_pct", "dn.container_io_pct",
                 "dn.commit_pct"):
        with open(os.path.join(BENCH, "layers", name + ".json")) as f:
            spec = json.load(f)
        shares[name] = _reader(spec["reader"]).read(src, spec["params"])
    assert shares == {"dn.dedup_lookup_pct": 4.0, "dn.container_io_pct": 1.0,
                      "dn.commit_pct": 7.0}
    assert _reader("phase_share").read(dict(src, phases=None),
                                       {"phases": ["dedup_lookup"]}) is None
