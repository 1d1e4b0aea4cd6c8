"""PR 35's per-layer metrics read the phase clock's inclusive table through
one new reader, ``readers/span_stat.py``: its arithmetic over a hand-made
``sources``, every new layer file resolving to it (``dn.host_busy_pct`` to
``phase_share``), nothing to read on a program without the table, and one
traced rehearsal that prints them all.  (That no file the benchmark already
had is touched is the driver's check.)"""

import json
import os
import sys

import pytest

from common import BENCH, REPO, failing, rehearse

sys.path.insert(0, BENCH)
from readers import phase_share, span_stat  # noqa: E402

ALL = ["teragen-1dn.ingest", "teragen-1dn.ingest-1w", "versions-dedup.ingest",
       "small-files.create", "teragen-1dn.pread-ingest"]
READ = ["teragen-1dn.pread-ingest"]
SPAN_STAT = {
    "seal.thread_busy_pct": ALL, "seal.wall_ms_per_container": ALL,
    "seal.cpu_ms_per_container": ALL,
    "seal.queue_wait_ms_per_container": ALL,
    "seal.index_ms_per_container": ALL, "seal.drain_tail_pct": ALL,
    "dn.block_wall_ms": ALL, "dn.block_cpu_ms": ALL,
    "dn.receive_ms_per_block": ALL, "dn.commit_ms_per_block": ALL,
    "dn.heartbeat_ms_per_tick": ALL, "dn.heartbeat_cpu_ms_per_tick": ALL,
    "dn.read_wall_ms": READ, "dn.read_cpu_ms": READ}
NEW = dict(SPAN_STAT, **{"dn.host_busy_pct": ALL})

SRC = {
    "window_s": 50.0,
    "phases": {
        "classes": {"host_busy": 40.0, "device_busy": 1.0,
                    "transport_wait": 4.0, "idle": 5.0},
        "phases": {"container_io": 12.0},
        "inclusive": {
            "seal": {"count": 300, "wall_s": 48.0, "wall_max_s": 0.4,
                     "cpu_s": 15.0},
            "seal_queue": {"count": 300, "wall_s": 1800.0, "wall_max_s": 9.0},
            "seal_index": {"count": 300, "wall_s": 6.0, "wall_max_s": 0.1},
            "seal_drain": {"count": 1, "wall_s": 8.0, "wall_max_s": 8.0},
            "dn_block": {"count": 80, "wall_s": 232.0, "wall_max_s": 4.1,
                         "cpu_s": 60.0},
            "recv": {"count": 9000, "wall_s": 30.0, "wall_max_s": 0.2},
            "ack": {"count": 9000, "wall_s": 2.0, "wall_max_s": 0.01},
            "packet_verify": {"count": 2600, "wall_s": 16.0,
                              "wall_max_s": 0.1},
            "wal_commit": {"count": 80, "wall_s": 8.0, "wall_max_s": 0.3},
            "container_io": {"count": 240, "wall_s": 24.0, "wall_max_s": 1.0},
            "heartbeat_stats": {"count": 40, "wall_s": 14.0,
                                "wall_max_s": 0.6, "cpu_s": 9.0},
        }}}
EXPECT = {
    "seal.thread_busy_pct": 96.0, "seal.wall_ms_per_container": 160.0,
    "seal.cpu_ms_per_container": 50.0,
    "seal.queue_wait_ms_per_container": 6000.0,
    "seal.index_ms_per_container": 20.0, "seal.drain_tail_pct": 16.0,
    "dn.host_busy_pct": 80.0,
    "dn.block_wall_ms": 2900.0, "dn.block_cpu_ms": 750.0,
    "dn.receive_ms_per_block": 600.0,       # recv + ack + packet_verify
    "dn.commit_ms_per_block": 400.0,        # no dedup_lookup span: 0 of it
    "dn.heartbeat_ms_per_tick": 350.0, "dn.heartbeat_cpu_ms_per_tick": 225.0,
    "dn.read_wall_ms": None, "dn.read_cpu_ms": None}    # no read ended


def _layer(metric: str) -> dict:
    with open(os.path.join(BENCH, "layers", metric + ".json")) as f:
        return json.load(f)


def _read(metric: str, src: dict):
    layer = _layer(metric)
    reader = {"span_stat": span_stat, "phase_share": phase_share}[
        layer["reader"]]
    return reader.read(src, layer["params"])


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_layer_file_reads_the_hand_made_window(metric):
    got = _read(metric, SRC)
    if EXPECT[metric] is None:
        assert got is None
    else:
        assert got == pytest.approx(EXPECT[metric])


@pytest.mark.parametrize("metric", sorted(SPAN_STAT))
def test_every_new_layer_file_resolves_to_the_reader(metric):
    layer = _layer(metric)
    assert layer["metric"] == metric and layer["reader"] == "span_stat"
    assert set(layer["params"]) <= {"spans", "field", "per", "scale"}
    assert layer["params"].get("field", "wall_s") in ("wall_s", "cpu_s",
                                                      "count")


def test_host_busy_needs_no_reader_of_its_own():
    layer = _layer("dn.host_busy_pct")
    assert (layer["reader"], layer["params"]) == ("phase_share",
                                                  {"class": "host_busy"})


@pytest.mark.parametrize("metric", sorted(SPAN_STAT))
def test_nothing_to_read_without_the_table(metric):
    """The parent's ``profile_spans`` has no ``inclusive`` key, a run
    without ``--trace 1`` no phase clock at all: the metric is left out."""
    parent = {"window_s": 50.0, "phases": {
        k: v for k, v in SRC["phases"].items() if k != "inclusive"}}
    assert _read(metric, parent) is None
    assert _read(metric, {"window_s": 50.0, "phases": None}) is None


def test_the_arithmetic_of_the_reader():
    read = span_stat.read
    assert read(SRC, {"spans": ["seal"], "field": "count",
                      "per": "window"}) == 6.0
    assert read(SRC, {"spans": ["seal"], "field": "count",
                      "per": "dn_block"}) == 3.75
    # a field one of the named spans does not carry is nothing to read
    assert read(SRC, {"spans": ["seal", "recv"], "field": "cpu_s",
                      "per": "seal"}) is None
    # a divisor that counted nothing
    assert read(SRC, {"spans": ["seal"], "per": "dn_read"}) is None
    assert read(SRC, {"spans": ["nope"], "per": "window"}) is None


def test_the_manifest_lists_each_with_its_cells_last_and_unchanged_before():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-len(NEW):]
    assert {m["name"] for m in tail} == set(NEW)
    for m in tail:
        assert m["workloads"] == NEW[m["name"]]
        assert (m["source"], m["moves"], m["better"]) == (
            "program_span", "write_mb_s", "lower")
        assert m["unit"] == ("%" if m["name"].endswith("_pct") else "ms")
    assert len(bench["workloads"]) == 5 and len(bench["configs"]) == 4


def test_a_traced_rehearsal_prints_every_one():
    out, rows = rehearse("teragen-1dn.pread-ingest", trace=1,
                         seed=2**31 + 35, seconds=4,
                         extra=["--set", "block_size=4194304"])
    last = rows[-1]
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    got = last["metrics"]
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    assert got["dn.block_cpu_ms"]["value"] <= got["dn.block_wall_ms"]["value"]
    assert got["dn.read_cpu_ms"]["value"] <= got["dn.read_wall_ms"]["value"]
    assert got["seal.cpu_ms_per_container"]["value"] <= \
        got["seal.wall_ms_per_container"]["value"]
    assert got["seal.thread_busy_pct"]["value"] <= 100.0
