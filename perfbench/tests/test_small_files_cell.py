"""``small-files.create`` as ``BENCHMARK.json`` lists it since PR 31: SLive's
create-only mix (file sizes uniform in 4 KiB-4 MiB) rehearsed from the real
manifest on a native worker, its generator, and the two per-layer metrics
that came with it (layer files only, on the reader ``stage_ratio``)."""

import json
import os
import sys

import numpy as np
import pytest

from common import BENCH, REPO, failing, run

CELL = "small-files.create"
NEW = ("worker.pad_pct", "worker.prep_shapes_per_block")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(BENCH, "configs", "small-files.json")) as f:
        return json.load(f)


def _source(seed=7, client=0):
    sys.path.insert(0, BENCH)
    sys.path.insert(0, REPO)    # as a client has it: the generator looks
    # the program up
    import manifest

    cfg = _config()
    params = {k: v for k, v in cfg["data"].items() if k != "generator"}
    params["file_bytes"] = cfg["cluster"]["block_size"]
    return manifest.plugin("generators", cfg["data"]["generator"]).Source(
        params, seed, client)


def test_the_manifest_lists_the_cell_and_its_metrics():
    bench = _bench()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("small-files", "create", 1)
    (cfg,) = [c for c in bench["configs"] if c["name"] == "small-files"]
    stated = _config()
    assert cfg["source"] == stated["source"] and len(cfg["source"]) <= 200
    assert cfg["reduced"] == sorted(stated["reduced"])
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert ends == {"write_mb_s", "stored_pct", "setup_s"}
    # every share of the write window is read in this cell: a window seals
    # tens of containers, so the seal's metrics read too (the read path's
    # metrics list the read cell alone)
    for m in bench["per_layer"]:
        assert m["moves"] == "write_mb_s"
        assert (CELL in m["workloads"]) == \
            ("teragen-1dn.ingest" in m["workloads"]), m["name"]
    layers = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = layers[name]
        assert (m["layer"], m["better"], m["source"]) == \
            ("device programs", "lower", "program_counter")
        assert m["workloads"] == [w["name"] for w in bench["workloads"]]
        with open(os.path.join(BENCH, "layers", name + ".json")) as f:
            spec = json.load(f)
        assert (spec["metric"], spec["reader"]) == (name, "stage_ratio")


def test_the_configuration_is_teragens_cluster_with_slives_sizes():
    with open(os.path.join(BENCH, "configs", "teragen-1dn.json")) as f:
        tera = json.load(f)
    cfg = _config()
    for key in ("cluster", "cdc", "guarantees"):
        assert cfg[key] == tera[key]
    assert cfg["data"] == {"generator": "slive_sizes", "size_min": 4096,
                           "size_max": 4194304, "setup_files": 24,
                           "setup_clients": "first"}
    with open(os.path.join(BENCH, "traffic", "create.json")) as f:
        mix = json.load(f)
    assert (mix["driver"], mix["clients"]) == ("write_files", 4)
    assert mix["params"]["setup_files"] == cfg["data"]["setup_files"]
    assert mix["params"]["file_blocks"] == 1


def test_sizes_are_uniform_in_the_range_and_seeded():
    src = _source(seed=2**31 + 5)
    lo, hi, warm = src.lo, src.hi, src.warm
    sizes = np.array([src.size(k) for k in range(warm, warm + 4000)])
    assert sizes.min() >= lo and sizes.max() <= hi
    assert abs(sizes.mean() - (lo + hi) / 2) < 0.03 * hi
    quart = np.histogram(sizes, bins=4, range=(lo, hi))[0]
    assert quart.min() > 900                       # 1 000 a quarter
    again = _source(seed=2**31 + 5)
    assert [again.size(k) for k in range(warm, warm + 50)] == \
        sizes[:50].tolist()
    other = _source(seed=2**31 + 5, client=1)
    assert [other.size(k) for k in range(warm, warm + 50)] != \
        sizes[:50].tolist()
    f = src.file(warm + 3)
    assert f.dtype == np.uint8 and f.size == sizes[3]
    assert bytes(f[98:100]) == b"\r\n"             # TeraGen's rows


def test_the_warm_files_step_geometrically_over_the_range():
    src = _source()
    sizes = [src.size(k) for k in range(src.warm)]
    assert (sizes[0], sizes[-1], len(sizes)) == (4096, 4194304, 24)
    ratios = np.array(sizes[1:]) / np.array(sizes[:-1])
    assert np.allclose(ratios, 2 ** (10 / 23), rtol=1e-3)
    # they hold 16 MB, under one container: set-up seals nothing
    assert sum(sizes) < _config()["cluster"]["container_size"]
    # the same for every seed and client: set-up meets the same shapes
    assert sizes == [_source(seed=99, client=3).size(k) for k in range(24)]


def test_a_program_without_a_block_length_ladder_is_refused(tmp_path,
                                                           monkeypatch):
    """The parent of PR 31 compiles programs a file: the cell ends with an
    error before any file is made, so it is measured on a tree that has the
    ladder alone."""
    import types

    src = _source()                                 # this tree has one
    gen = sys.modules[type(src).__module__]
    bare = tmp_path / "resident.py"
    bare.write_text("def _cap(n):\n    return (n >> 12) + 1024\n")
    monkeypatch.setattr(gen.importlib.util, "find_spec",
                        lambda name: types.SimpleNamespace(origin=str(bare)))
    with pytest.raises(RuntimeError, match="block-length ladder"):
        _source()


@pytest.fixture(scope="module")
def rehearsal():
    """The cell at its real block size (no file reaches it) with 1 MiB
    containers, on a native worker, traced."""
    return run(["--workload", CELL, "--seed", str(2**31 + 31), "--seconds",
                "6", "--trace", "1", "--worker-backend", "native", "--set",
                "container_size=1048576"])


def test_a_rehearsal_ends_on_the_missing_chip_alone(rehearsal):
    out, rows = rehearsal
    last = rows[-1]
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    assert last["failed"] == 0 and last["attempted"] >= 100
    setup = [r for r in rows if r.get("phase") == "setup"][0]
    assert setup["setup_ops"] == 24
    note = [r for r in rows if r.get("phase") == "checks"][0]
    assert note["index"]["blocks"] == last["attempted"] + 24
    assert note["readback"]["reads"] == note["index"]["blocks"]
    # every chunk is new, as in teragen-1dn
    assert note["reference"]["unique_bytes"] == \
        note["reference"]["logical_bytes"]
    assert note["made_in_window"] == 0


def test_the_traced_rehearsal_reads_the_cells_witnesses(rehearsal):
    _, rows = rehearsal
    got = rows[-1]["metrics"]
    for name in ("nn.rpc_pct", "dn.recv_ack_pct", "dn.commit_pct",
                 "hop.device_wait_pct", "dn.unattributed_pct",
                 "hop.packets_per_frame", "seal.dn_pct"):
        assert name in got
    # a file is one short frame: fewer than the 64 packets of a full stride
    assert got["hop.packets_per_frame"]["value"] < 64
    # a native worker pads nothing and runs no _prep: nothing to read
    for name in NEW:
        assert name not in got


@pytest.mark.parametrize("metric, stats, want", [
    ("worker.pad_pct",
     {"bytes_padded": 0, "bytes_reduced": 1 << 27, "blocks_reduced": 1}, 0.0),
    ("worker.pad_pct",
     {"bytes_padded": 250, "bytes_reduced": 1000, "blocks_reduced": 1}, 25.0),
    ("worker.pad_pct", {"bytes_reduced": 1000, "blocks_reduced": 1}, None),
    ("worker.pad_pct", {"bytes_padded": 0, "bytes_reduced": 0}, None),
    ("worker.prep_shapes_per_block",
     {"prep_shapes": 0, "blocks_reduced": 900}, 0.0),
    ("worker.prep_shapes_per_block",
     {"prep_shapes": 12, "blocks_reduced": 12}, 1.0),
    ("worker.prep_shapes_per_block", {"blocks_reduced": 12}, None),
    ("worker.prep_shapes_per_block",
     {"prep_shapes": 0, "blocks_reduced": 0}, None)])
def test_the_ladder_metrics_from_the_worker_counters(metric, stats, want):
    sys.path.insert(0, BENCH)
    import manifest

    spec = manifest.layer(metric)
    got = manifest.plugin("readers", spec["reader"]).read(
        {"window": {"stats": stats}}, spec["params"])
    assert got == want
